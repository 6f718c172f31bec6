"""Blocked cross-entropy: identical value AND gradients to the dense path,
without materializing logits, in one sweep."""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from dsml_tpu.ops import xent
from dsml_tpu.ops.xent import chunked_softmax_xent


def _dense_xent(h, wte, targets):
    logits = (h @ wte.T).astype(jnp.float32)
    logp = jax.nn.log_softmax(logits)
    return -jnp.take_along_axis(logp, targets[..., None], axis=-1).mean()


@pytest.fixture
def rows_of(monkeypatch):
    """Make the head take blocks of at most ``rows`` tokens at vocabulary
    ``vocab``: the rule reads nothing but shapes and the module's byte budget,
    so a test shrinks the budget (the dW accumulator's share left out)."""

    def set_rows(rows, vocab, d=0):
        monkeypatch.setattr(xent, "_HEAD_BYTES", 4 * vocab * (rows + d))

    return set_rows


def _inputs(seed, n, d, vocab, dtype=jnp.float32):
    rng = np.random.default_rng(seed)
    h = jnp.asarray(rng.standard_normal((n, d)), dtype)
    wte = jnp.asarray(rng.standard_normal((vocab, d)) * 0.2, dtype)
    return h, wte, jnp.asarray(rng.integers(0, vocab, n), jnp.int32)


@pytest.mark.parametrize("vocab,rows,blocks", [
    (1000, 16, (3, 16)), (1024, 12, (4, 16)), (300, 24, (2, 24)),
    (1000, 20, (3, 16)),  # N not a multiple of what was asked for
    (1000, 10, (5, 16)),  # 5 x 16 = 80 rows for 48: two blocks are padding alone
    (1000, 48, (1, 48)),  # one block: no accumulator, no padding
])
def test_chunked_matches_dense_value_and_grads(rows_of, vocab, rows, blocks):
    n, d = 48, 16
    h, wte, targets = _inputs(0, n, d, vocab)
    rows_of(rows, vocab, d)
    assert xent.block_rows(n, vocab, d) == blocks

    dense = _dense_xent(h, wte, targets)
    chunked = chunked_softmax_xent(h, wte, targets)
    np.testing.assert_allclose(float(chunked), float(dense), rtol=1e-6)

    gd = jax.grad(_dense_xent, argnums=(0, 1))(h, wte, targets)
    gc = jax.grad(lambda h, w: chunked_softmax_xent(h, w, targets), argnums=(0, 1))(h, wte)
    for a, b in zip(gc, gd):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), rtol=1e-5, atol=1e-6)


@pytest.mark.parametrize("use", ["scaled", "twice"])
def test_scalar_cotangent_scales_the_forward_gradients(rows_of, use):
    """The gradients are made in the forward for a cotangent of 1; the backward
    only scales them: by 3 for ``3 * loss``, by ``1 + 2 * loss`` where one
    ``value_and_grad`` uses the loss twice."""
    n, d, vocab = 50, 32, 1000
    h, wte, targets = _inputs(5, n, d, vocab)
    rows_of(16, vocab, d)
    assert xent.block_rows(n, vocab, d) == (4, 16)  # the last block: 2 rows and 14 of padding

    def objective(loss):
        def f(h, w):
            value = loss(h, w, targets)
            return 3.0 * value if use == "scaled" else value + value * value
        return jax.value_and_grad(f, argnums=(0, 1))

    (got, g_got), (want, g_want) = objective(chunked_softmax_xent)(h, wte), objective(_dense_xent)(h, wte)
    np.testing.assert_allclose(float(got), float(want), rtol=1e-6)
    for a, b in zip(g_got, g_want):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), rtol=1e-5, atol=1e-6)


def test_chunked_handles_batched_shapes_and_bf16(rows_of):
    h, wte, targets = _inputs(1, 32, 16, 500, jnp.bfloat16)
    h, targets = h.reshape(2, 16, 16), targets.reshape(2, 16)
    rows_of(8, 500, 16)
    assert xent.block_rows(32, 500, 16) == (4, 8)
    loss = chunked_softmax_xent(h, wte, targets)
    dense = _dense_xent(h.astype(jnp.float32).reshape(32, 16), wte.astype(jnp.float32),
                        targets.reshape(32))
    assert np.isclose(float(loss), float(dense), rtol=2e-2)
    g = jax.grad(lambda h: chunked_softmax_xent(h, wte, targets))(h)
    assert g.dtype == jnp.bfloat16 and np.isfinite(np.asarray(g, np.float32)).all()


def test_bf16_gradients_match_the_dense_path(rows_of):
    """bf16 ``h`` and ``wte`` as the cells have them: both gradients come back
    in bf16 and equal the dense path's on the same bf16 values, within the
    tolerance the bf16 loss has (2e-2 of the gradient's largest magnitude)."""
    h, wte, targets = _inputs(6, 32, 16, 500, jnp.bfloat16)
    rows_of(8, 500, 16)
    assert xent.block_rows(32, 500, 16) == (4, 8)
    got = jax.grad(lambda h, w: chunked_softmax_xent(h, w, targets), argnums=(0, 1))(h, wte)
    want = jax.grad(_dense_xent, argnums=(0, 1))(h.astype(jnp.float32), wte.astype(jnp.float32), targets)
    for a, b, like in zip(got, want, (h, wte)):
        assert a.dtype == jnp.bfloat16 and a.shape == like.shape
        scale = float(jnp.abs(b).max())
        np.testing.assert_allclose(np.asarray(a, np.float32), np.asarray(b), atol=2e-2 * scale)


def _eqns(jaxpr):
    """Every equation of ``jaxpr``, those of nested jaxprs (a scan's body, a
    custom VJP's forward) included."""
    for eqn in jaxpr.eqns:
        yield eqn
        for sub in jax.core.jaxprs_in_params(eqn.params):
            yield from _eqns(sub)


@pytest.mark.parametrize("differentiated,dots", [(True, 3), (False, 1)], ids=["value_and_grad", "loss"])
def test_one_loop_and_three_vocabulary_matmuls(rows_of, differentiated, dots):
    """The mechanism's engagement counter: under ``value_and_grad`` the head is
    ONE loop that holds exactly three ``dot_general``s with a vocabulary-sized
    dimension (logits, ``dh``, ``dW``); the loss alone holds one. And the
    largest array anywhere is a block's ``[rows, V]``, never ``[N, V]``."""
    n, d, vocab = 64, 8, 1000
    h, wte, targets = _inputs(7, n, d, vocab)
    rows_of(16, vocab, d)
    assert xent.block_rows(n, vocab, d) == (4, 16)

    def loss(h, w):
        return chunked_softmax_xent(h, w, targets)

    fn = jax.value_and_grad(loss, argnums=(0, 1)) if differentiated else loss
    eqns = list(_eqns(jax.make_jaxpr(fn)(h, wte).jaxpr))
    assert sum(e.primitive.name in ("scan", "while") for e in eqns) == 1
    wide = [e for e in eqns if e.primitive.name == "dot_general"
            and vocab in (*e.invars[0].aval.shape, *e.invars[1].aval.shape)]
    assert len(wide) == dots and len([e for e in eqns if e.primitive.name == "dot_general"]) == dots
    sizes = [int(np.prod(v.aval.shape)) for e in eqns for v in e.outvars if hasattr(v.aval, "shape")]
    assert max(sizes) == 16 * vocab


def test_hybrid_tp1_routes_to_chunked_and_matches(devices8):
    """The hybrid step always carries a tp axis (often unit). With tp=1 the
    vocab is unsharded, so the chunked path must activate there too — the
    GPT-2-small pure-DP headline case — and match the dense loss."""
    from jax import lax
    from jax.sharding import PartitionSpec as P

    from dsml_tpu.models.gpt2 import GPT2, GPT2Config
    from dsml_tpu.parallel.hybrid import hybrid_loss_fn, shard_params
    from dsml_tpu.parallel.mesh import MeshSpec, build_mesh

    cfg = GPT2Config(vocab_size=700, max_seq=64, n_layer=2, n_head=4, d_model=32,
                     d_ff=64, xent_chunk=256)
    model = GPT2(cfg)
    params = model.init(3)
    rng = np.random.default_rng(4)
    x = jnp.asarray(rng.integers(0, 700, (8, 64)), jnp.int32)
    y = jnp.roll(x, -1, 1)
    dense = float(jax.jit(GPT2(dataclasses.replace(cfg, xent_chunk=0)).loss)(params, x, y))

    mesh = build_mesh(MeshSpec(dp=8, sp=1, tp=1), devices8)
    sharded = jax.jit(
        jax.shard_map(
            lambda p, xx, yy: lax.pmean(hybrid_loss_fn(model)(p, xx, yy), ("dp", "sp")),
            mesh=mesh,
            in_specs=(model.param_specs(), P("dp", "sp"), P("dp", "sp")),
            out_specs=P(),
            check_vma=False,
        )
    )
    placed = shard_params(params, mesh, model.param_specs())
    got = float(sharded(placed, x, y))
    assert np.isclose(got, dense, rtol=1e-5), (got, dense)


def test_gpt2_uses_chunked_loss_above_threshold(rows_of):
    """A GPT-2 with vocab > xent_chunk must produce the same loss/grads via
    the blocked path (here four blocks of 32 tokens) as with it disabled
    (``xent_chunk=0``: dense)."""
    from dsml_tpu.models.gpt2 import GPT2, GPT2Config

    base = GPT2Config(vocab_size=700, max_seq=64, n_layer=2, n_head=4, d_model=32,
                      d_ff=64, xent_chunk=256)
    dense_cfg = dataclasses.replace(base, xent_chunk=0)
    rng = np.random.default_rng(2)
    x = jnp.asarray(rng.integers(0, 700, (2, 64)), jnp.int32)
    y = jnp.roll(x, -1, 1)
    params = GPT2(base).init(0)
    rows_of(32, 700, 32)
    assert xent.block_rows(128, 700, 32) == (4, 32)

    l_chunked = float(jax.jit(GPT2(base).loss)(params, x, y))
    l_dense = float(jax.jit(GPT2(dense_cfg).loss)(params, x, y))
    assert np.isclose(l_chunked, l_dense, rtol=1e-5)

    g_c = jax.jit(jax.grad(GPT2(base).loss))(params, x, y)
    g_d = jax.jit(jax.grad(GPT2(dense_cfg).loss))(params, x, y)
    for a, b in zip(jax.tree.leaves(g_c), jax.tree.leaves(g_d)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), rtol=2e-4, atol=1e-6)


def test_1f1b_head_takes_the_blocked_path(devices8, rows_of):
    """The 1F1B schedule runs the head per microbatch under a ``shard_map``
    that tracks varying axes: the sweep's carry and the cotangent of the
    replicated ``wte`` must carry the operands' axes. One SGD step, blocked
    head (blocks of 8 rows) against the dense head."""
    import optax

    from dsml_tpu.models.gpt2 import GPT2, GPT2Config
    from dsml_tpu.parallel.hybrid import init_hybrid, make_hybrid_train_step
    from dsml_tpu.parallel.mesh import MeshSpec, build_mesh

    mesh = build_mesh(MeshSpec(pp=2, dp=2, sp=2), devices8)
    cfg = GPT2Config.tiny()
    rng = np.random.default_rng(12)
    x = rng.integers(0, cfg.vocab_size, (8, cfg.max_seq)).astype(np.int32)
    y = np.roll(x, -1, 1).astype(np.int32)
    rows_of(8, cfg.vocab_size)
    outs = []
    for chunk in (0, 64):
        model, opt = GPT2(dataclasses.replace(cfg, xent_chunk=chunk)), optax.sgd(0.5)
        step = make_hybrid_train_step(model, opt, mesh, n_microbatches=2, schedule="1f1b")
        params, opt_state = init_hybrid(model, opt, mesh, seed=5)
        params, _, loss = step(params, opt_state, x, y)
        outs.append((float(loss), params))
    (l_dense, p_dense), (l_blocked, p_blocked) = outs
    assert np.isclose(l_blocked, l_dense, rtol=1e-5)
    for a, b in zip(jax.tree.leaves(p_blocked), jax.tree.leaves(p_dense)):
        a, b = np.asarray(a, np.float64), np.asarray(b, np.float64)
        assert np.max(np.abs(a - b)) <= 1e-5 * (np.max(np.abs(b)) + 1e-8)


@pytest.mark.parametrize("rows", [16, 48])  # several blocks with padding, and one
def test_default_weights_give_the_mean_bit_equal(rows_of, rows):
    """``weighted_softmax_xent`` at ``1 / n`` a row is the mean head: the same
    sweep, so loss and both gradients are the same bits."""
    n, d, vocab = 48, 16, 1000
    h, wte, targets = _inputs(3, n, d, vocab)
    rows_of(rows, vocab, d)
    mean = jax.value_and_grad(lambda h, w: chunked_softmax_xent(h, w, targets), argnums=(0, 1))(h, wte)
    weighted = jax.value_and_grad(
        lambda h, w: xent.weighted_softmax_xent(h, w, targets, jnp.full(n, 1.0 / n))[0], argnums=(0, 1))(h, wte)
    for a, b in zip(jax.tree.leaves(weighted), jax.tree.leaves(mean)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


@pytest.mark.parametrize("rows", [16, 48])
def test_row_weights_and_row_losses_match_plain_jnp(rows_of, rows):
    """Arbitrary weights of each row: the sum ``Σ w·CE``, its gradients with the
    weights held constant, and each row's own loss, against ``jnp``."""
    n, d, vocab = 48, 16, 1000
    h, wte, targets = _inputs(4, n, d, vocab)
    weights = jnp.asarray(np.random.default_rng(5).uniform(0.0, 2.0, n), jnp.float32)
    rows_of(rows, vocab, d)

    def plain(h, w):
        logp = jax.nn.log_softmax((h @ w.T).astype(jnp.float32))
        ce = -jnp.take_along_axis(logp, targets[:, None], axis=-1)[:, 0]
        return jnp.sum(weights * ce), ce

    (want, want_rows), want_grads = jax.value_and_grad(plain, argnums=(0, 1), has_aux=True)(h, wte)
    (got, got_rows), got_grads = jax.value_and_grad(
        lambda h, w: xent.weighted_softmax_xent(h, w, targets, weights), argnums=(0, 1), has_aux=True)(h, wte)
    np.testing.assert_allclose(float(got), float(want), rtol=1e-6)
    np.testing.assert_allclose(np.asarray(got_rows), np.asarray(want_rows), rtol=1e-5, atol=1e-6)
    for a, b in zip(got_grads, want_grads):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), rtol=1e-5, atol=1e-6)


def test_weights_learned_beside_the_sweep_get_the_exact_gradient(rows_of):
    """``Σ sg(p)·CE`` from the sweep plus ``Σ (p − sg(p))·sg(CE)`` has the value and
    every gradient of ``Σ p·CE``, ``p`` a function of parameters (a softmax here)."""
    n, d, vocab = 48, 16, 1000
    h, wte, targets = _inputs(6, n, d, vocab)
    logits_p = jnp.asarray(np.random.default_rng(7).standard_normal(n), jnp.float32)
    rows_of(16, vocab, d)

    def plain(h, w, z):
        logp = jax.nn.log_softmax((h @ w.T).astype(jnp.float32))
        return jnp.sum(jax.nn.softmax(z) * -jnp.take_along_axis(logp, targets[:, None], axis=-1)[:, 0])

    def swept(h, w, z):
        p = jax.nn.softmax(z)
        loss, ce = xent.weighted_softmax_xent(h, w, targets, jax.lax.stop_gradient(p))
        return loss + jnp.sum((p - jax.lax.stop_gradient(p)) * ce)

    want = jax.value_and_grad(plain, argnums=(0, 1, 2))(h, wte, logits_p)
    got = jax.value_and_grad(swept, argnums=(0, 1, 2))(h, wte, logits_p)
    for a, b in zip(jax.tree.leaves(got), jax.tree.leaves(want)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), rtol=1e-5, atol=1e-6)
