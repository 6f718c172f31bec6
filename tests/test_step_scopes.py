"""The train step names its work: every piece the benchmark times by scope
(``benchmarks/scope_reduce.py``) is an ``op_name`` path component of some
instruction of the compiled hybrid step, forward and, where there is one,
backward (``transpose(``). Compile-time metadata only: nothing runs."""

import dataclasses
import functools
import re

import jax
import jax.numpy as jnp
import optax
import pytest

from dsml_tpu.models.gpt2 import GPT2, GPT2Config
from dsml_tpu.models.jamba import Jamba, JambaConfig
from dsml_tpu.models.llama import Llama, LlamaConfig
from dsml_tpu.models.mellum import Mellum, MellumConfig
from dsml_tpu.parallel.hybrid import make_hybrid_train_step
from dsml_tpu.parallel.mesh import MeshSpec, build_mesh


@functools.lru_cache(maxsize=None)
def _op_names(dp: int, dp_sync: str = "xla", family: str = "gpt2") -> list[list[str]]:
    """The op names of the compiled tiny step, each split into its components."""
    model = {"gpt2": lambda: GPT2(GPT2Config.tiny()), "jamba": lambda: Jamba(JambaConfig.tiny(remat=True)),
             "llama": lambda: Llama(dataclasses.replace(LlamaConfig.tiny(), remat=True)),
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


# -- one level down (PR 37): the norms, the rotation, the key-value repeat, the convolution ------

REMAT = "rematted_computation"  # what `jax.checkpoint` writes round the forward it computes again
INNER = ("normalize", "rope", "kv_repeat", "ssm_conv")
_SETS = {  # family -> the inner names its step holds; the others it must not hold
    "gpt2": ("normalize",),                           # learned positions, as many key-value heads as heads
    "llama": ("normalize", "rope", "kv_repeat"),      # 8 query heads on 2
    "jamba": ("normalize", "kv_repeat", "ssm_conv"),  # `_rotate` returns its input and stays bare
    "mellum": ("normalize", "rope", "kv_repeat"),
}


def _direction(tokens, scope: str) -> str:
    """As ``benchmarks/name_reduce.py`` reads it."""
    if REMAT in tokens:
        return "remat"
    return "bwd" if "transpose" in tokens[:tokens.index(scope)] else "fwd"


@pytest.mark.parametrize("family,scope,direction", [
    (family, scope, direction) for family, scopes in _SETS.items() for scope in scopes
    for direction in (("fwd", "bwd") if family == "gpt2" else ("fwd", "remat", "bwd"))])  # tiny GPT-2: no remat
def test_compiled_step_names_its_work_one_level_down(family, scope, direction):
    names = [tokens for tokens in _op_names(1, family=family) if scope in tokens]
    assert any(_direction(tokens, scope) == direction for tokens in names)
    # each inside one of the scopes that were there, so every accepted reader still finds its own name
    outer = {"ssm_conv": {"ssm"}, "rope": {"attn"}, "kv_repeat": {"attn"},
             "normalize": {"attn", "mlp", "ssm", "loss_head"}}[scope]
    assert all(outer.intersection(tokens) for tokens in names)


@pytest.mark.parametrize("family,scope", [
    (family, scope) for family, scopes in _SETS.items() for scope in INNER if scope not in scopes])
def test_compiled_step_holds_no_name_for_work_it_does_not_do(family, scope):
    assert not any(scope in tokens for tokens in _op_names(1, family=family))


def test_checkpoint_writes_the_component_the_recomputed_forward_is_read_by():
    """``remat_ms`` (``benchmarks/layer_metrics``) reads the instructions whose op name holds
    ``rematted_computation``; a jax that renames it fails here and does not silently null the metric."""
    grad = jax.jit(jax.grad(jax.checkpoint(lambda x: jnp.sin(jnp.sin(x)).sum())))
    text = grad.lower(jnp.ones(8)).compile().as_text()
    names = [re.split(r"[/();]", name) for name in re.findall(r'op_name="([^"]*)"', text)]
    assert any(REMAT in tokens and "transpose" in tokens[:tokens.index(REMAT)] for tokens in names)


@pytest.mark.parametrize("kept", [True, False], ids=["kept", "not_kept"])
def test_the_flash_outputs_name_reaches_the_compiled_step(kept):
    """Whole-block remat keeps what ``ops/flash.py::FLASH_OUTPUTS`` names (``models/stack.py::KEPT``,
    ``models/jamba.py::_KEPT``): under a policy that names it, the compiled gradient holds the flash
    forward outside the recomputed forward alone; under one that names nothing, inside it too. A jax
    that loses the name on the way fails here and does not silently run the kernel twice a step."""
    from dsml_tpu.ops.flash import FLASH_OUTPUTS, flash_attention_packed

    policy = jax.checkpoint_policies.save_only_these_names(*([FLASH_OUTPUTS] if kept else []))
    block = jax.checkpoint(lambda x, w: flash_attention_packed(x @ w, 64)[0].sum(), policy=policy)
    shapes = [jax.ShapeDtypeStruct(shape, "float32") for shape in ((1, 64, 128), (128, 384))]
    text = jax.jit(jax.grad(block, argnums=(0, 1))).lower(*shapes).compile().as_text()
    names = [tokens for tokens in (re.split(r"[/();]", name) for name in re.findall(r'op_name="([^"]*)"', text))
             if "flash_fwd" in tokens]
    assert any(REMAT not in tokens for tokens in names)
    assert any(REMAT in tokens for tokens in names) == (not kept)


@pytest.mark.parametrize("family", ["jamba", "mellum"])
def test_the_remat_families_recompute_no_flash_forward(family):
    """The same in the two families' compiled steps: the flash forward is never in their recomputed forward."""
    names = [tokens for tokens in _op_names(1, family=family) if "flash_fwd" in tokens]
    assert names and not any(REMAT in tokens for tokens in names)


def test_the_inner_names_collide_with_nothing_jax_writes():
    """The same ``jnp`` calls the four scopes wrap, jitted here under no scope: a root-mean-square
    norm, ``jnp.repeat``, the rotation and a depthwise convolution by taps, forward and backward."""
    def bare(x, scale, cos, sin, taps):
        x32 = x.astype(jnp.float32)
        normed = (x32 * jax.lax.rsqrt(jnp.mean(x32 * x32, axis=-1, keepdims=True) + 1e-6)).astype(x.dtype) * scale
        repeated = jnp.repeat(normed.reshape(2, 16, 2, 8), 4, axis=2)
        x1, x2 = repeated[..., :4], repeated[..., 4:]
        rotated = jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos], axis=-1).reshape(2, 16, 64)
        padded = jnp.pad(rotated, ((0, 0), (3, 0), (0, 0)))
        conv = sum(padded[:, k:k + 16] * taps[k] for k in range(4))
        return jax.nn.silu(conv).sum()

    shapes = [jax.ShapeDtypeStruct(shape, "float32") for shape in ((2, 16, 16), (16,), (16, 1, 4), (16, 1, 4), (4, 64))]
    text = jax.jit(jax.grad(bare, argnums=(0, 1, 4))).lower(*shapes).compile().as_text()
    names = set(re.findall(r'op_name="([^"]*)"', text))
    assert len(names) > 20
    assert not [name for name in names if set(INNER).intersection(re.split(r"[/();]", name))]
