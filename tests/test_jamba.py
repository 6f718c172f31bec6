"""Jamba (``models/jamba.py``) against the plain reference
(``benchmarks/reference/jamba.py``) at ``JambaConfig.tiny()``: one period of
four layers holding both kinds (Mamba, attention, Mamba, Mamba), four query
heads on one key-value head, seeded weights, float32, the scan kernels in the
Pallas interpreter and attention through the flash kernels.

Tolerances. Program and reference hold the same float32 weights and differ in
the order of their sums (blocked kernels, a chunk-free head at this vocabulary)
and in the reference's matmul precision "highest", which the CPU's float32
matmuls already have: the loss agrees to 2e-5 nats of ~6.27 (float32 resolution
there is 5e-7; observed 0) and each gradient leaf to 1e-4 of the leaf's largest
reference magnitude (observed 2e-6). The perturbation test shows that this
bites: with ``D``, ``b_dt`` or one of the three small norms' scales zeroed in
the program alone, some leaf is off by its whole magnitude.
"""

import dataclasses
import functools

import jax
import jax.numpy as jnp
import numpy as np
import optax
import pytest
from jax.sharding import PartitionSpec as P

from benchmarks.reference import jamba as reference
from dsml_tpu.models.jamba import Jamba, JambaConfig
from dsml_tpu.parallel.hybrid import hybrid_loss_fn, init_hybrid, make_hybrid_train_step
from dsml_tpu.parallel.mesh import MeshSpec, build_mesh

LOSS_TOLERANCE_NATS = 2e-5
GRAD_TOLERANCE = 1e-4  # of the leaf's largest reference magnitude
CFG = JambaConfig.tiny()


def _batch(rows=2, seq=40, seed=0):
    rng = np.random.default_rng(seed)
    tokens = rng.integers(0, CFG.vocab_size, (rows, seq + 1)).astype(np.int32)
    return tokens[:, :-1], tokens[:, 1:]


@pytest.fixture(scope="module")
def params():
    return Jamba(CFG).init(0)


@pytest.fixture(scope="module")
def reference_answer(params):
    tokens, targets = _batch()
    return jax.value_and_grad(
        lambda p: reference.loss_fn(p, tokens, targets, n_head=CFG.n_head,
                                    n_kv_head=CFG.n_kv_head, eps=CFG.rms_eps))(params)


@functools.lru_cache(maxsize=None)
def _program(remat):
    """Loss and gradients the way ``make_hybrid_train_step`` takes them: the
    per-rank ``hybrid_loss_fn`` under ``shard_map`` on the framework mesh,
    differentiated outside it."""
    model = Jamba(dataclasses.replace(CFG, remat=remat))
    mesh = build_mesh(MeshSpec(dp=1), jax.devices()[:1])
    batch_spec = P(("dp", "fsdp"), ("sp", "cp"))
    loss = jax.shard_map(hybrid_loss_fn(model, "flash"), mesh=mesh,
                         in_specs=(model.param_specs(), batch_spec, batch_spec),
                         out_specs=P(), check_vma=False)
    return jax.jit(jax.value_and_grad(loss))


def _program_answer(params, remat):
    return _program(remat)(params, *_batch())


def _worst_leaf(grads, grads_ref):
    """The largest |difference| over a leaf's largest reference magnitude, and its path."""
    return max(
        (float(jnp.abs(g - r).max() / jnp.abs(r).max()), jax.tree_util.keystr(path))
        for (path, g), r in zip(jax.tree_util.tree_leaves_with_path(grads), jax.tree.leaves(grads_ref)))


def test_layer_kinds_and_parameter_count(params):
    assert ["ssm" in layer for layer in params["layers"]] == [True, False, True, True]
    d, ff, e, n, r, k = CFG.d_model, CFG.d_ff, CFG.d_inner, CFG.d_state, CFG.dt_rank, CFG.d_conv
    mamba = d * 2 * e + k * e + e + e * (r + 2 * n) + (r + 2 * n) + r * e + e + e * n + e + e * d
    attention = 2 * d * d + 2 * d * (d // CFG.n_head)  # one key-value head
    layer = 3 * d * ff + 2 * d
    assert Jamba(CFG).n_params(params) == CFG.vocab_size * d + d + 4 * layer + 3 * mamba + attention


@pytest.mark.parametrize("remat", [False, True])
def test_loss_and_gradients_match_the_reference(params, reference_answer, remat):
    loss, grads = _program_answer(params, remat)
    loss_ref, grads_ref = reference_answer
    assert abs(float(loss) - float(loss_ref)) <= LOSS_TOLERANCE_NATS
    worst, where = _worst_leaf(grads, grads_ref)
    assert worst <= GRAD_TOLERANCE, where


def test_reference_convolution_is_the_depthwise_operator():
    """The reference's sum over windows against XLA's grouped convolution."""
    rng = np.random.default_rng(1)
    u, w = rng.standard_normal((40, 128), np.float32), rng.standard_normal((4, 128), np.float32)
    grouped = jax.lax.conv_general_dilated(
        u.T[None], w.T[:, None, :], window_strides=(1,), padding=[(3, 0)], feature_group_count=128,
        precision="highest")[0].T
    with jax.default_matmul_precision("highest"):
        np.testing.assert_allclose(reference.causal_depthwise_conv(u, w), grouped, rtol=1e-5, atol=1e-5)


@pytest.mark.parametrize("layers", [(0,), (1,), (0, 1), (3,)], ids=["ssm", "attn", "both", "top"])
def test_reference_layer_grads_are_its_gradients(params, reference_answer, layers):
    """The reference's backward as the benchmark takes it at the published
    widths (block by block, down to the lowest layer asked for) against
    ``jax.grad`` of the same reference, whole."""
    grads = reference.layer_grads(params, *_batch(), layers, n_head=CFG.n_head,
                                  n_kv_head=CFG.n_kv_head, eps=CFG.rms_eps)
    assert sorted(grads) == list(layers)
    worst, where = _worst_leaf(grads, {i: reference_answer[1]["layers"][i] for i in layers})
    assert worst <= 1e-5, where


@pytest.mark.parametrize("leaf", ["d", "b_dt", "dt_norm", "b_norm", "c_norm"])
def test_the_tolerances_bite(params, reference_answer, leaf):
    """Drop one term of the Mamba mixer in the program alone (layer 0):
    the comparison above must fail, by far."""
    broken = jax.tree.map(lambda x: x, params)
    broken["layers"][0]["ssm"][leaf] = jnp.zeros_like(params["layers"][0]["ssm"][leaf])
    loss, grads = _program_answer(broken, False)
    loss_ref, grads_ref = reference_answer
    worst, _ = _worst_leaf(grads, grads_ref)
    assert abs(float(loss) - float(loss_ref)) > LOSS_TOLERANCE_NATS or worst > 100 * GRAD_TOLERANCE


@pytest.mark.parametrize("axes", [{"dp": 2}, {"dp": 1, "fsdp": 2}], ids=["dp2", "fsdp2"])
def test_trains_through_the_hybrid_step(params, reference_answer, devices8, axes):
    """``build_mesh`` -> ``init_hybrid`` -> ``make_hybrid_train_step``: the
    first loss is the reference's on the same batch, and the loss falls."""
    model = Jamba(dataclasses.replace(CFG, remat=True))
    mesh = build_mesh(MeshSpec(**axes), devices8[:2])
    optimizer = optax.adamw(1e-2)
    step = make_hybrid_train_step(model, optimizer, mesh, attn_impl="flash")
    state, opt_state = init_hybrid(model, optimizer, mesh, seed=0)
    tokens, targets = _batch()
    losses = []
    for _ in range(4):
        state, opt_state, loss = step(state, opt_state, tokens, targets)
        losses.append(float(loss))
    assert abs(losses[0] - float(reference_answer[0])) <= LOSS_TOLERANCE_NATS
    assert losses[-1] < losses[0] - 0.1


@pytest.mark.parametrize("axes", [{"tp": 2}, {"sp": 2}, {"cp": 2}, {"pp": 2}])
def test_mesh_axes_the_mixer_does_not_implement_raise(devices8, axes):
    model = Jamba(dataclasses.replace(CFG, n_layer=4))
    mesh = build_mesh(MeshSpec(dp=1, **axes), devices8[:2])
    tokens, targets = _batch()
    with pytest.raises(NotImplementedError, match="Jamba"):
        step = make_hybrid_train_step(model, optax.sgd(0.1), mesh, attn_impl="flash")
        params = jax.eval_shape(lambda: model.init(0))
        step.lower(params, jax.eval_shape(optax.sgd(0.1).init, params), tokens, targets)


@pytest.mark.parametrize("entry", ["init_cache", "prefill", "decode_step", "decode_step_slots",
                                   "generate", "generate_spmd", "init_page_pool"])
def test_serving_entry_points_raise(entry):
    with pytest.raises(NotImplementedError, match="Reach 5"):
        getattr(Jamba(CFG), entry)(None, None)
