"""The selective-scan kernel pair (``ops/selective_scan.py``) in the Pallas
interpreter against its ``lax.scan`` oracle: ``y`` and all six gradients.

Tolerances. Both sides compute in float32 and differ in the order of sums (the
kernel adds ``dA`` over time blocks, ``dB`` / ``dC`` over channel blocks), so
float32 inputs agree to 1e-4 of each array's largest magnitude. bfloat16 inputs
are rounded once on the way out of either side (2^-8 = 4e-3 a value), so they
agree to 1e-2.
"""

import jax
import jax.numpy as jnp
import pytest

from dsml_tpu.ops.selective_scan import selective_scan, selective_scan_reference

_STATE = 16
_NAMES = ("u", "delta", "A", "B", "C", "D")


def _operands(seq, channels, dtype, rows=1):
    ks = jax.random.split(jax.random.key(seq + channels), 6)
    u = jax.random.normal(ks[0], (rows, seq, channels)).astype(dtype)
    delta = jax.nn.softplus(jax.random.normal(ks[1], (rows, seq, channels)) - 2.0).astype(dtype)
    a = -jnp.exp(0.5 * jax.random.normal(ks[2], (channels, _STATE)))
    b = jax.random.normal(ks[3], (rows, seq, _STATE)).astype(dtype)
    c = jax.random.normal(ks[4], (rows, seq, _STATE)).astype(dtype)
    d = jax.random.normal(ks[5], (channels,))
    return u, delta, a, b, c, d


@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
@pytest.mark.parametrize("channels", [128, 256], ids=["one-channel-block", "two-channel-blocks"])
@pytest.mark.parametrize("seq", [32, 40], ids=["whole-time-blocks", "ragged-last-block"])
def test_kernel_pair_matches_the_scan_oracle(seq, channels, dtype):
    operands = _operands(seq, channels, jnp.dtype(dtype))
    weight = jax.random.normal(jax.random.key(7), (1, seq, channels))

    def kernels(*ops):  # time blocks of 16, channel blocks of 128, forward and backward
        return selective_scan(*ops, block_s=16, block_e=128, block_e_bwd=128)

    def value_and_grads(scan):
        def objective(*ops):
            y = scan(*ops)
            return (y.astype(jnp.float32) * weight).sum(), y

        return jax.jit(jax.value_and_grad(objective, argnums=tuple(range(6)), has_aux=True))

    (_, y), grads = value_and_grads(kernels)(*operands)
    (_, y_ref), grads_ref = value_and_grads(selective_scan_reference)(*operands)
    tolerance = 1e-4 if dtype == "float32" else 1e-2
    assert y.dtype == operands[0].dtype and y.shape == operands[0].shape
    for name, got, want in zip(("y", *_NAMES), (y, *grads), (y_ref, *grads_ref)):
        assert got.dtype == want.dtype and got.shape == want.shape, name
        got, want = got.astype(jnp.float32), want.astype(jnp.float32)
        assert float(jnp.abs(got - want).max()) <= tolerance * float(jnp.abs(want).max()), name


def test_padding_steps_leave_the_state_alone():
    """A length short of a time block is padded with ``delta = 0`` steps: the
    prefix of a longer sequence's ``y`` is the shorter sequence's ``y``."""
    u, delta, a, b, c, d = _operands(48, 128, jnp.float32)
    scan = jax.jit(lambda *ops: selective_scan(*ops, block_s=16))
    whole = scan(u, delta, a, b, c, d)
    part = scan(u[:, :21], delta[:, :21], a, b[:, :21], c[:, :21], d)
    assert float(jnp.abs(whole[:, :21] - part).max()) <= 1e-5


@pytest.mark.parametrize("block_s,channels,message", [
    (200, 128, "block_s=200"),       # over 128 and not a multiple of it
    (12, 128, "block_s=12"),         # not whole sublane tiles of rows
    (128, 192, "192 channels"),      # not whole lane tiles
])
def test_geometries_the_kernels_cannot_tile_raise(block_s, channels, message):
    operands = _operands(256, channels, jnp.float32)
    with pytest.raises(ValueError, match=message):
        selective_scan(*operands, block_s=block_s)
