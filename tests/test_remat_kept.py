"""What whole-block remat keeps of an attention layer: the flash forward
kernel's ``out`` and ``lse`` (``ops/flash.py::FLASH_OUTPUTS``), so that in
the four families that recompute each block in the backward (Mellum,
DeepSeek-V3 and Ouro under ``models/stack.py::KEPT``, Jamba under its own
``_KEPT``) the kernel runs once per attention layer a step (in Ouro once
per layer and pass), not twice, and the gradients are the un-checkpointed
block's. Tiny sizes, the interpreter."""

import dataclasses
import functools
import sys
from pathlib import Path

import jax
import numpy as np
import pytest
from jax.sharding import PartitionSpec as P

sys.path.insert(0, str(Path(__file__).resolve().parents[1]))

from dsml_tpu.models import experts, jamba, stack  # noqa: E402
from dsml_tpu.models.deepseek_v3 import DeepseekV3, DeepseekV3Config  # noqa: E402
from dsml_tpu.models.jamba import Jamba, JambaConfig  # noqa: E402
from dsml_tpu.models.mellum import Mellum, MellumConfig  # noqa: E402
from dsml_tpu.models.ouro import Ouro, OuroConfig  # noqa: E402
from dsml_tpu.ops.selective_scan import SCAN_OUTPUTS  # noqa: E402
from dsml_tpu.parallel.hybrid import hybrid_loss_fn  # noqa: E402
from dsml_tpu.parallel.mesh import MeshSpec, build_mesh  # noqa: E402
from scripts.expert_layer_check import kernel_calls  # noqa: E402

SEQ = 64

# family -> (its model at a tiny size, whole-block remat on or off; its attention layers)
FAMILIES = {
    "mellum": (lambda remat: Mellum(dataclasses.replace(
        MellumConfig.tiny(remat=remat), n_layer=2, layer_types=("sliding_attention", "full_attention"))), 2),
    "deepseek_v3": (lambda remat: DeepseekV3(dataclasses.replace(DeepseekV3Config.tiny(remat=remat), n_layer=2)), 2),
    "jamba": (lambda remat: Jamba(JambaConfig.tiny(remat=remat)), 1),  # one attention layer among four
    "ouro": (lambda remat: Ouro(OuroConfig.tiny(remat=remat)), 2 * 4),  # two layers, four passes
}
# family -> (the module that holds its policy, the policy's name there, the policy without the flash outputs)
POLICIES = {
    "mellum": (stack, "KEPT", jax.checkpoint_policies.save_only_these_names(*experts.PLAN_NAMES)),
    "deepseek_v3": (stack, "KEPT", jax.checkpoint_policies.save_only_these_names(*experts.PLAN_NAMES)),
    "jamba": (jamba, "_KEPT", jax.checkpoint_policies.save_only_these_names(SCAN_OUTPUTS)),
    "ouro": (stack, "KEPT", jax.checkpoint_policies.save_only_these_names(*experts.PLAN_NAMES)),
}


def _batch(model, rows=2, seed=0):
    tokens = np.random.default_rng(seed).integers(0, model.config.vocab_size, (rows, SEQ + 1)).astype(np.int32)
    return tokens[:, :-1], tokens[:, 1:]


def _grad_fn(model):
    """Loss and gradients as ``make_hybrid_train_step`` takes them: the per-rank
    loss under ``shard_map`` on a one-device mesh, differentiated outside it."""
    mesh = build_mesh(MeshSpec(dp=1), jax.devices()[:1])
    loss = jax.shard_map(hybrid_loss_fn(model, "flash"), mesh=mesh,
                         in_specs=(model.param_specs(), P(), P()), out_specs=P(), check_vma=False)
    return jax.value_and_grad(loss)


def _flash_fwd_calls(family: str) -> tuple[int, int]:
    """``flash_fwd`` calls in the step's ``jax.value_and_grad`` with remat on:
    ``(all of them, those inside the recomputed forward)``."""
    model = FAMILIES[family][0](True)
    params = jax.eval_shape(lambda: model.init(0))
    jaxpr = jax.make_jaxpr(_grad_fn(model))(params, *_batch(model)).jaxpr

    def recomputed(jaxpr):
        return sum(kernel_calls(eqn.params["jaxpr"], "flash_fwd") if eqn.primitive.name == "remat2"
                   else sum(recomputed(inner) for inner in jax.core.jaxprs_in_params(eqn.params))
                   for eqn in jaxpr.eqns)

    return kernel_calls(jaxpr, "flash_fwd"), recomputed(jaxpr)


@pytest.mark.parametrize("family", FAMILIES)
def test_the_recomputed_block_does_not_run_the_flash_forward_again(family):
    assert _flash_fwd_calls(family) == (FAMILIES[family][1], 0)


@pytest.mark.parametrize("family", FAMILIES)
def test_without_the_kept_name_the_flash_forward_runs_twice(family, monkeypatch):
    """The counter above sees the difference: the same policy without ``FLASH_OUTPUTS``."""
    module, name, without = POLICIES[family]
    monkeypatch.setattr(module, name, without)
    n = FAMILIES[family][1]
    assert _flash_fwd_calls(family) == (2 * n, n)


@functools.lru_cache(maxsize=None)
def _answer(family: str, remat: bool):
    model = FAMILIES[family][0](remat)
    return jax.jit(_grad_fn(model))(model.init(0), *_batch(model))


@pytest.mark.parametrize("family", FAMILIES)
def test_kept_outputs_give_the_gradients_of_the_block_not_checkpointed(family):
    (loss, grads), (want_loss, want_grads) = _answer(family, True), _answer(family, False)
    np.testing.assert_allclose(loss, want_loss, rtol=1e-6)
    for g, w in zip(jax.tree.leaves(grads), jax.tree.leaves(want_grads)):
        np.testing.assert_allclose(g, w, atol=1e-5 * float(np.abs(w).max()))
