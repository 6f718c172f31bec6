"""Ouro at a tiny size on the CPU (two layers of four heads of 16 run four
times, float32, seeded weights): the program against
``benchmarks/reference/ouro.py``, the sum of a shared weight's uses, eight
steps of the program's train step against eight of the reference's, one pass
as the plain stack, the exits' distribution, and what raises."""

import sys
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import optax
import pytest
from jax.sharding import PartitionSpec as P

sys.path.insert(0, str(Path(__file__).resolve().parents[1]))

from benchmarks.reference import ouro as reference  # noqa: E402
from dsml_tpu.models.ouro import Ouro, OuroConfig, exit_log_probs  # noqa: E402
from dsml_tpu.parallel.hybrid import hybrid_loss_fn, init_hybrid, make_hybrid_train_step  # noqa: E402
from dsml_tpu.parallel.mesh import MeshSpec, build_mesh  # noqa: E402

SEQ = 64
# float32 on both sides: the two differ in the order of sums alone (measured at most 5e-7 of a leaf)
LEAF_TOLERANCE = 1e-4


def _sizes(cfg: OuroConfig) -> reference.Sizes:
    return reference.Sizes(num_attention_heads=cfg.n_head, head_dim=cfg.head_dim, total_ut_steps=cfg.total_ut_steps,
                           rms_norm_eps=cfg.rms_eps, rope_theta=cfg.rope_theta, entropy_weight=cfg.entropy_weight)


def _batch(cfg, rows=2, seed=0):
    tokens = np.random.default_rng(seed).integers(0, cfg.vocab_size, (rows, SEQ + 1)).astype(np.int32)
    return tokens[:, :-1], tokens[:, 1:]


def _program(model):
    """Loss and gradients as ``make_hybrid_train_step`` takes them: the per-rank
    loss under ``shard_map`` on a one-device mesh, differentiated outside it."""
    mesh = build_mesh(MeshSpec(dp=1), jax.devices()[:1])
    loss = jax.shard_map(hybrid_loss_fn(model, "flash"), mesh=mesh,
                         in_specs=(model.param_specs(), P(), P()), out_specs=P(), check_vma=False)
    return jax.jit(jax.value_and_grad(loss))


def _error(got, want):
    return float(jnp.linalg.norm((got - want).ravel()) / jnp.linalg.norm(want.ravel()))


def _assert_leaves_match(got, want, tolerance=LEAF_TOLERANCE):
    paths = jax.tree_util.tree_flatten_with_path(want)[0]
    errors = {jax.tree_util.keystr(p): _error(g, w) for (p, w), g in zip(paths, jax.tree.leaves(got))}
    assert max(errors.values()) < tolerance, errors


@pytest.mark.parametrize("remat", [False, True])
def test_loss_and_every_leafs_gradient_match_the_reference(remat):
    cfg = OuroConfig.tiny(remat=remat)
    model, (x, y) = Ouro(cfg), _batch(cfg)
    params = model.init(0)
    loss, grads = _program(model)(params, x, y)
    want, want_grads = jax.value_and_grad(lambda p: reference.loss_fn(p, x, y, s=_sizes(cfg)))(params)
    np.testing.assert_allclose(float(loss), float(want), rtol=1e-5)
    _assert_leaves_match(grads, want_grads)


def test_a_shared_weights_gradient_is_the_sum_of_its_uses():
    """The reference with a copy of the layers for each pass (untied): the
    program's gradient of each shared leaf is the sum over the copies."""
    cfg = OuroConfig.tiny()
    model, (x, y) = Ouro(cfg), _batch(cfg, seed=1)
    params = model.init(1)
    _, grads = _program(model)(params, x, y)
    untied = {**params, "layers": [params["layers"]] * cfg.total_ut_steps}
    by_use = jax.grad(lambda p: reference.loss_fn(p, x, y, s=_sizes(cfg), untied=True))(untied)["layers"]
    summed = jax.tree.map(lambda *uses: sum(uses), *by_use)
    _assert_leaves_match(grads["layers"], summed)
    # each use counts: the last pass alone is not the whole gradient
    assert _error(by_use[-1][0]["attn"]["wq"], summed[0]["attn"]["wq"]) > 0.1


def test_eight_adam_steps_track_the_references():
    """The program's train step (``make_hybrid_train_step``) eight times on
    one batch against ``jax.grad`` of the reference stepped by the same adamw
    from the same weights: what carries from one step to the next (adam's state
    of a weight used in every pass, the gate's) is the reference's. The rate is
    high enough that the weights move: the loss falls by a nat."""
    cfg = OuroConfig.tiny()
    model, optimizer = Ouro(cfg), optax.adamw(1e-2)
    mesh = build_mesh(MeshSpec(dp=1), jax.devices()[:1])
    step = make_hybrid_train_step(model, optimizer, mesh, attn_impl="flash")
    params, opt_state = init_hybrid(model, optimizer, mesh, seed=4)
    start = want = jax.device_get(params)
    want_state = optimizer.init(want)

    @jax.jit
    def reference_step(p, state, x, y):
        loss, g = jax.value_and_grad(lambda p: reference.loss_fn(p, x, y, s=_sizes(cfg)))(p)
        updates, state = optimizer.update(g, state, p)
        return optax.apply_updates(p, updates), state, loss

    got_losses, want_losses = [], []
    x, y = _batch(cfg, seed=10)
    for _ in range(8):
        params, opt_state, loss = step(params, opt_state, x, y)
        want, want_state, want_loss = reference_step(want, want_state, x, y)
        got_losses.append(float(loss))
        want_losses.append(float(want_loss))
    np.testing.assert_allclose(got_losses, want_losses, rtol=1e-5)
    assert want_losses[-1] < want_losses[0] - 1.0
    # each leaf's change over the eight steps, the program's against the reference's
    moved = jax.tree.map(jnp.subtract, jax.device_get(params), start)
    errors = jax.tree.map(_error, moved, jax.tree.map(jnp.subtract, want, start))
    assert max(jax.tree.leaves(errors)) < 1e-2, errors


def test_one_pass_is_the_plain_stack():
    """``total_ut_steps = 1``: one exit with all the weight, no entropy, so the
    loss is the mean next-token loss of the stack's normed output and the gate
    takes no gradient."""
    cfg = OuroConfig.tiny(total_ut_steps=1)
    model, (x, y) = Ouro(cfg), _batch(cfg, seed=2)
    params = model.init(2)
    loss, grads = _program(model)(params, x, y)
    h = model._blocks_spmd(params, jnp.asarray(x), attn_impl="flash")
    logp = jax.nn.log_softmax(h @ params["lm_head"].T)
    plain = -jnp.take_along_axis(logp, jnp.asarray(y)[..., None], axis=-1).mean()
    np.testing.assert_allclose(float(loss), float(plain), rtol=1e-5)
    want = jax.grad(lambda p: reference.loss_fn(p, x, y, s=_sizes(cfg)))(params)
    assert all(float(jnp.abs(g).max()) == 0.0 for g in jax.tree.leaves((grads["exit_gate"], want["exit_gate"])))
    _assert_leaves_match({**grads, "exit_gate": None}, {**want, "exit_gate": None})


@pytest.mark.parametrize("passes", [1, 2, 4])
def test_the_exit_distribution_sums_to_one(passes):
    z = jnp.asarray(np.random.default_rng(passes).standard_normal((passes - 1, 3, 5)) * 4, jnp.float32)
    p = jnp.exp(exit_log_probs(z))
    np.testing.assert_allclose(np.asarray(p.sum(0)), 1.0, rtol=1e-6)
    lam, stay = jax.nn.sigmoid(z), 1.0
    for t in range(passes - 1):  # the product form, in float32 rounding: λ_t Π_{j<t} (1 − λ_j), the last what is left
        np.testing.assert_allclose(np.asarray(p[t]), np.asarray(lam[t] * stay), rtol=1e-4, atol=1e-7)
        stay = stay * (1.0 - lam[t])
    np.testing.assert_allclose(np.asarray(p[-1]), np.asarray(stay * jnp.ones_like(p[-1])), rtol=1e-4, atol=1e-7)


@pytest.mark.parametrize("variant", reference.VARIANTS)
def test_the_references_block_by_block_path_is_its_traceable_loss(variant):
    """What the chip runs of the reference (one jitted block at a time, the
    cotangent pulled down pass by pass) against ``jax.grad`` of its one
    traceable function, in every variant: loss, the watched layers, the gate,
    the final norm and the two tables; and the magnitudes of the bias's terms,
    which bound its gradient."""
    cfg = OuroConfig.tiny()
    model, (x, y) = Ouro(cfg), _batch(cfg, seed=3)
    params, s = model.init(3), _sizes(cfg)
    want_loss, want = jax.value_and_grad(lambda p: reference.loss_fn(p, x, y, s=s, variant=variant))(params)
    np.testing.assert_allclose(reference.loss(params, x, y, s=s, variant=variant), float(want_loss), rtol=1e-5)
    got = reference.grads(params, x, y, (0, 1), s=s, variant=variant, tables=True)
    terms = float(got.pop("exit_gate_terms"))
    assert terms >= abs(float(want["exit_gate"]["b"][0])) and (terms > 0) == (variant not in ("uniform_exits",
                                                                                               "last_exit_only"))
    watched = {"exit_gate": want["exit_gate"], "rms_f": want["rms_f"], "layers": {i: want["layers"][i] for i in (0, 1)},
               "wte": want["wte"], "lm_head": want["lm_head"]}
    if variant in ("uniform_exits", "last_exit_only"):  # the gate left out: no gradient on either path
        assert all(float(jnp.abs(g).max()) == 0.0 for g in jax.tree.leaves((got["exit_gate"], watched["exit_gate"])))
        got, watched = ({**tree, "exit_gate": None} for tree in (got, watched))
    # float8 operands turn a last-bit difference of the two programs' float32 into a step of 2^-3 now and
    # then: measured 0.3% of the gate's weight; every other variant agrees to 1.2e-6
    tolerance = 1e-2 if variant == "matmuls_float8" else LEAF_TOLERANCE
    _assert_leaves_match(got, watched, tolerance)


def test_what_the_family_does_not_run_raises():
    cfg = OuroConfig.tiny()
    model = Ouro(cfg)
    with pytest.raises(NotImplementedError, match="cache for each pass"):
        model.generate(None, None)
    with pytest.raises(NotImplementedError, match="pipeline"):
        model.loss_spmd(None, None, None, pp_axis="pp")
    with pytest.raises(NotImplementedError, match="flash"):
        model._check_axes(None, None, "ring")
