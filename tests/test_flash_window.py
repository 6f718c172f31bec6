"""The window in the flash kernels (interpreted): forward and all three
gradients against the dense-mask form, at lengths that are and are not block
multiples and windows shorter than, equal to and longer than a block; the tile
classes at the window's old edge; and ``window=None`` the kernels they were."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from dsml_tpu.ops import flash
from dsml_tpu.ops.flash import (flash_attention, flash_attention_lse, flash_attention_packed,
                                flash_block_grads)


def _dense(q, k, v, window):
    """Softmax attention under the dense mask ``0 <= i - j < window``."""
    s = q.shape[2]
    scores = jnp.einsum("bhqd,bhkd->bhqk", q, k) / np.sqrt(q.shape[-1])
    distance = jnp.arange(s)[:, None] - jnp.arange(s)[None, :]
    visible = (distance >= 0) & ((distance < window) if window is not None else True)
    return jnp.einsum("bhqk,bhkd->bhqd", jax.nn.softmax(jnp.where(visible, scores, -jnp.inf), -1), v)


def _qkv(seq, heads=2, head_dim=32, seed=0):
    keys = jax.random.split(jax.random.key(seed), 4)
    q, k, v = (jax.random.normal(key, (1, heads, seq, head_dim)) for key in keys[:3])
    return q, k, v, jax.random.normal(keys[3], (1, heads, seq, head_dim))


# seq, block, window: block multiples and not; the window under, at and over a block, and over the sequence
CASES = [(256, 64, 40), (256, 64, 64), (256, 64, 100), (256, 64, 128), (256, 64, 1), (256, 64, 400),
         (200, 64, 48), (200, 64, 64), (200, 64, 130), (136, 128, 24)]


@pytest.mark.parametrize("seq,block,window", CASES)
def test_windowed_forward_and_gradients_match_the_dense_mask(seq, block, window):
    q, k, v, weight = _qkv(seq)

    def loss(attend):
        return lambda q, k, v: jnp.sum(attend(q, k, v) * weight)

    kernel = jax.jit(jax.value_and_grad(loss(
        lambda q, k, v: flash_attention(q, k, v, block_q=block, block_k=block, window=window)), (0, 1, 2)))
    dense = jax.jit(jax.value_and_grad(loss(lambda q, k, v: _dense(q, k, v, window)), (0, 1, 2)))
    (got, got_grads), (want, want_grads) = kernel(q, k, v), dense(q, k, v)
    np.testing.assert_allclose(got, want, rtol=2e-5)
    for g, w in zip(got_grads, want_grads):
        np.testing.assert_allclose(g, w, atol=2e-5 * float(jnp.abs(w).max()))


def test_windowed_lse_and_packed_entry():
    q, k, v, _ = _qkv(192, heads=2, head_dim=64)
    out, lse = jax.jit(lambda q, k, v: flash_attention_lse(q, k, v, block_q=64, block_k=64, window=70))(q, k, v)
    np.testing.assert_allclose(out, _dense(q, k, v, 70), atol=2e-5)
    scores = jnp.einsum("bhqd,bhkd->bhqk", q, k) / 8.0
    distance = jnp.arange(192)[:, None] - jnp.arange(192)[None, :]
    want_lse = jax.nn.logsumexp(jnp.where((distance >= 0) & (distance < 70), scores, -jnp.inf), -1)
    np.testing.assert_allclose(lse, want_lse, atol=2e-5)
    flat = [t.transpose(0, 2, 1, 3).reshape(1, 192, 128) for t in (q, k, v)]
    packed, _ = jax.jit(lambda *t: flash_attention_packed(t, 64, block_q=64, block_k=64, window=70))(*flat)
    np.testing.assert_allclose(packed, out.transpose(0, 2, 1, 3).reshape(1, 192, 128), atol=2e-5)


def test_windowed_block_grads_match_the_differentiated_call():
    q, k, v, do = _qkv(128)
    out, lse = flash_attention_lse(q, k, v, block_q=64, block_k=64, window=50)
    got = jax.jit(lambda *t: flash_block_grads(*t, block_q=64, block_k=64, window=50))(q, k, v, out, lse, do)
    want = jax.grad(lambda q, k, v: jnp.sum(flash_attention(q, k, v, block_q=64, block_k=64, window=50) * do),
                    (0, 1, 2))(q, k, v)
    for g, w in zip(got, want):
        np.testing.assert_allclose(g, w, atol=2e-5)


@pytest.mark.parametrize("window,causal", [(0, True), (16, False)])
def test_a_window_needs_causal_attention_and_a_key(window, causal):
    q, k, v, _ = _qkv(64)
    with pytest.raises(ValueError, match="window"):
        flash_attention(q, k, v, causal=causal, window=window)


@pytest.mark.parametrize("q0,k0,expected", [
    (0, 0, "masked"),       # the diagonal tile: causal edge
    (128, 64, "clear"),     # inside every query's window of 128, all keys earlier
    (128, 0, "masked"),     # the window's old edge crosses it
    (256, 0, "skipped"),    # every key older than every query's window
    (0, 64, "skipped"),     # every key in the future
])
def test_tile_classes_at_both_edges(q0, k0, expected):
    """``_per_tile_class`` with blocks of 64 and a window of 128: which body
    runs for the tile whose first query / key sit at ``q0`` / ``k0``."""
    ran = []

    def when(predicate):
        return lambda body: bool(predicate) and body()

    original, flash.pl.when = flash.pl.when, when
    try:
        flash._per_tile_class(lambda masked: ran.append("masked" if masked else "clear"),
                              jnp.int32(q0), jnp.int32(k0), 10**6, True, False, 64, 64, 128)
    finally:
        flash.pl.when = original
    assert (ran or ["skipped"]) == [expected]
    assert bool(flash._seen(jnp.int32(q0), jnp.int32(k0), 64, 64, 128)) == (expected != "skipped")


def _parents_mask(s, q0, k0, kv_stop, causal, mask_kv, q_axis):
    """``ops/flash.py::_mask`` as it stood before the window (PR 33)."""
    def pos(start, axis):
        shape = [1, 1]
        shape[axis] = s.shape[axis]
        return start + jax.lax.broadcasted_iota(jnp.int32, tuple(shape), axis)

    last = None
    if causal:
        last = pos(q0, q_axis)
    if mask_kv:
        last = kv_stop - 1 if last is None else jnp.minimum(last, kv_stop - 1)
    return jnp.where(pos(k0, 1 - q_axis) <= last, s, flash._NEG_INF)


@pytest.mark.parametrize("causal,mask_kv,q_axis", [(True, False, 0), (True, True, 1), (False, True, 0)])
def test_without_a_window_the_mask_is_the_parents(causal, mask_kv, q_axis):
    """``window=None`` leaves the kernels' bodies as they were: the mask traces
    to the parent's operations, one compare and one select on the tile."""
    s = jnp.zeros((64, 128), jnp.float32)
    ours = jax.make_jaxpr(lambda s, q0, k0, stop: flash._mask(s, q0, k0, stop, causal, mask_kv, q_axis))(s, 3, 5, 100)
    parents = jax.make_jaxpr(lambda s, q0, k0, stop: _parents_mask(s, q0, k0, stop, causal, mask_kv, q_axis))(s, 3, 5, 100)
    assert str(ours) == str(parents)
    assert bool(flash._seen(7, 70, 64)) == (70 <= 7 + 63) and not bool(flash._seen(0, 64, 64))


@pytest.mark.parametrize("seq,block", [(256, 64), (200, 64)])
def test_window_none_is_bit_equal_to_the_call_without_it(seq, block):
    q, k, v, weight = _qkv(seq)

    def grads(**window):
        return jax.jit(jax.value_and_grad(
            lambda q, k, v: jnp.sum(flash_attention(q, k, v, block_q=block, block_k=block, **window) * weight),
            (0, 1, 2)))(q, k, v)

    (a, ga), (b, gb) = grads(), grads(window=None)
    assert np.array_equal(a, b) and all(np.array_equal(x, y) for x, y in zip(ga, gb))
    # a window that holds every key computes the same attention through the masked bodies
    (c, gc) = grads(window=seq)
    np.testing.assert_allclose(c, a, rtol=1e-6)
    for x, y in zip(gc, ga):
        np.testing.assert_allclose(x, y, atol=1e-5)


@pytest.mark.parametrize("seq,block,window", CASES)
def test_windowed_walk_is_bit_equal_to_the_rectangle(monkeypatch, seq, block, window):
    """The grid walks only the tiles the window and the diagonal leave (PR
    36); with the offsets' knowledge denied it steps through the rectangle
    and ``_seen`` decides inside the step. Same tiles, same order, same bits,
    forward and all three gradients; and the walk is the shorter whenever a
    tile could be left out."""
    q, k, v, weight = _qkv(seq)

    def grads():
        return jax.jit(jax.value_and_grad(
            lambda q, k, v: jnp.sum(flash_attention(q, k, v, block_q=block, block_k=block, window=window) * weight),
            (0, 1, 2)))(q, k, v)

    (walk, walk_grads) = grads()
    monkeypatch.setattr(flash, "_static_offset", lambda q_start, k_start: None)
    (rectangle, rectangle_grads) = grads()
    assert np.array_equal(walk, rectangle)
    assert all(np.array_equal(a, b) for a, b in zip(walk_grads, rectangle_grads))

    walked, tiles = flash.grid_steps(seq, seq, 32, window=window, block_q=block, block_k=block)
    block, padded = flash._pad_choice(seq, block)  # 200 and 136 tile exactly by 8: no padding, many small tiles
    blocks = padded // block
    assert tiles == blocks * blocks and walked < tiles
    reach = -(-(window - 1) // block) + 1  # the kv blocks a q block's window can touch
    assert walked == sum(min(qi + 1, reach) for qi in range(blocks))
