"""The train step names its work: every piece the benchmark times by scope
(``benchmarks/scope_reduce.py``) is an ``op_name`` path component of some
instruction of the compiled hybrid step, forward and, where there is one,
backward (``transpose(``). Compile-time metadata only: nothing runs."""

import functools
import re

import jax
import optax
import pytest

from dsml_tpu.models.gpt2 import GPT2, GPT2Config
from dsml_tpu.models.jamba import Jamba, JambaConfig
from dsml_tpu.models.mellum import Mellum, MellumConfig
from dsml_tpu.parallel.hybrid import make_hybrid_train_step
from dsml_tpu.parallel.mesh import MeshSpec, build_mesh


@functools.lru_cache(maxsize=None)
def _op_names(dp: int, dp_sync: str = "xla", family: str = "gpt2") -> list[list[str]]:
    """The op names of the compiled tiny step, each split into its components."""
    model = {"gpt2": lambda: GPT2(GPT2Config.tiny()), "jamba": lambda: Jamba(JambaConfig.tiny(remat=True)),
             "mellum": lambda: Mellum(MellumConfig.tiny(remat=True))}[family]()
    optimizer = optax.adamw(1e-3)
    mesh = build_mesh(MeshSpec(dp=dp), jax.devices()[:dp])
    step = make_hybrid_train_step(
        model, optimizer, mesh, attn_impl="flash", dp_sync=dp_sync)
    params = jax.eval_shape(lambda: model.init(0))
    batch = jax.ShapeDtypeStruct((2 * dp, 32), "int32")
    text = step.lower(params, jax.eval_shape(optimizer.init, params), batch, batch).compile().as_text()
    return [re.split(r"[/();]", name) for name in set(re.findall(r'op_name="([^"]*)"', text))]


def _has(names, scope: str, backward: bool) -> bool:
    return any(scope in tokens and ("transpose" in tokens[:tokens.index(scope)]) == backward
               for tokens in names)


@pytest.mark.parametrize("dp", [1, 2])
@pytest.mark.parametrize("scope,backward", [
    ("embed", False), ("embed", True), ("attn", False), ("attn", True),
    ("mlp", False), ("mlp", True), ("loss_head", False), ("loss_head", True),
    ("optimizer", False),
])
def test_compiled_step_names_its_work(dp, scope, backward):
    assert _has(_op_names(dp), scope, backward)


def test_optimizer_scope_on_the_explicit_sync_step():
    assert _has(_op_names(2, dp_sync="ring"), "optimizer", False)


@pytest.mark.parametrize("scope,backward", [
    # the Mamba mixer and, inside it, the scan kernels by their own names (interpreted here,
    # so each is the prefix of its body's ops); whole-block remat keeps the forward kernel's
    # outputs, so neither kernel runs in the other's pass
    ("ssm", False), ("ssm", True), ("ssm_scan_fwd", False), ("ssm_scan_fwd", None),
    ("ssm_scan_bwd", True),
    # what the family shares with the others keeps its names: Llama's blocks, GPT-2's head
    ("embed", False), ("attn", False), ("attn", True), ("mlp", False), ("mlp", True),
    # the attention backward is one kernel: dq rides flash_dkv, and no flash_dq is in the step
    ("flash_fwd", False), ("flash_dq", None), ("flash_dkv", True),
    ("loss_head", False), ("loss_head", True), ("optimizer", False),
])
def test_compiled_jamba_step_names_its_work(scope, backward):
    names = _op_names(1, family="jamba")
    if backward is None:  # absent from the backward
        assert not _has(names, scope, True)
        return
    assert _has(names, scope, backward)
    if scope == "ssm_scan_bwd":
        assert not _has(names, scope, False)


@pytest.mark.parametrize("scope,backward", [
    # the expert layer's four steps inside `mlp`, each forward and backward; the plan of `moe_route` is
    # integers, so its backward holds the router's alone
    ("moe_route", False), ("moe_route", True), ("moe_dispatch", False), ("moe_dispatch", True),
    ("experts", False), ("experts", True), ("moe_combine", False), ("moe_combine", True),
    # the grouped matmuls by their own names: the forward kernel also in the recomputed forward
    ("gmm_fwd", False), ("gmm_fwd", True), ("gmm_dx", True), ("gmm_dw", True),
    # the flash calls of the two layer types, told apart by a scope nested in `attn`
    ("attn_window", False), ("attn_window", True), ("attn_full", False), ("attn_full", True),
    ("flash_fwd", False), ("flash_dq", None), ("flash_dkv", True),
    ("embed", False), ("attn", False), ("attn", True), ("mlp", False), ("mlp", True),
    ("loss_head", False), ("loss_head", True), ("optimizer", False),
])
def test_compiled_mellum_step_names_its_work(scope, backward):
    names = _op_names(1, family="mellum")
    if backward is None:
        assert not _has(names, scope, True)
        return
    assert _has(names, scope, backward)
    if scope in ("gmm_dx", "gmm_dw"):
        assert not _has(names, scope, False)
    if scope.startswith(("moe_", "experts", "gmm_")):  # all of them inside `mlp`
        assert all("mlp" in tokens for tokens in names if scope in tokens)
    if scope.startswith("attn_"):
        assert all("attn" in tokens for tokens in names if scope in tokens)
