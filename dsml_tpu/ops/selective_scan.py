"""Selective scan (Mamba-1, Gu & Dao 2023) as a Pallas kernel pair.

The recurrence, per batch row and channel ``e``, over a state of ``N`` values:

    s_t[e, :] = exp(delta_t[e] * A[e, :]) * s_{t-1}[e, :] + delta_t[e] * u_t[e] * B_t[:]
    y_t[e]    = sum_n C_t[n] * s_t[e, n] + D[e] * u_t[e],        s_0 = 0

Every (channel, state) pair decays at a rate of its own, so this is no matrix
product: it is VPU work, serial in time. Written with ``lax.associative_scan``
it materialises ``[S, E, N]`` float32 several times over, and as a ``lax.scan``
its backward saves the same; neither fits a long sequence beside the weights.

``ssm_scan_fwd`` walks time in blocks of ``block_s`` for one (row, channel
block): channels on lanes, the state's ``N`` on sublanes, the state in VMEM
(float32) across the time blocks. It writes ``y`` and the state at the START of
each time block (``[B, S / block_s, N, E]`` float32: all the backward needs).
``ssm_scan_bwd`` walks the time blocks in reverse: it recomputes one block's
states from its boundary into VMEM, then steps back through them carrying the
state's cotangent, and returns ``du``, ``ddelta`` and partial sums of ``dA``
(per row), ``dB``, ``dC`` (per channel block) and ``dD`` (per row) that the
wrapper adds up. No ``[S, E, N]`` array exists in HBM in either direction.

``B`` and ``C`` enter transposed (``[B, N, S]``: state on sublanes, time on
lanes), so that step ``t``'s column is one masked lane reduction away from the
``[N, 1]`` the state tile multiplies; ``delta`` and ``u`` rows broadcast along
sublanes. Inside, everything is float32 whatever the inputs' type.

On the CPU the kernels run in the Pallas interpreter (``flash._interpret_
default``: one rule for every kernel of a step). ``selective_scan_reference``
is the plain ``lax.scan`` the tests hold the pair to: an oracle, not a second
path of any model.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import lax
from jax.ad_checkpoint import checkpoint_name
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from dsml_tpu.ops import flash

__all__ = ["selective_scan", "selective_scan_reference", "SCAN_OUTPUTS"]

SCAN_OUTPUTS = "ssm_scan_outputs"  # the checkpoint name of y and the block-boundary states

_F32 = jnp.float32
_STEPS = 8  # time steps a loop iteration: one float32 sublane tile of rows


def selective_scan_reference(u, delta, a, b, c, d):
    """The oracle: u, delta ``[B, S, E]``, a ``[E, N]``, b, c ``[B, S, N]``, d
    ``[E]`` -> y ``[B, S, E]`` in u's type, float32 inside, time as a
    sequential ``lax.scan``."""
    a32, d32 = a.astype(_F32), d.astype(_F32)

    def row(u, delta, b, c):
        def step(state, inputs):
            u_t, delta_t, b_t, c_t = inputs
            state = jnp.exp(delta_t[:, None] * a32) * state + (delta_t * u_t)[:, None] * b_t[None, :]
            return state, state @ c_t + d32 * u_t

        return lax.scan(step, jnp.zeros(a.shape, _F32), (u, delta, b, c))[1]

    return jax.vmap(row)(*(t.astype(_F32) for t in (u, delta, b, c))).astype(u.dtype)


def _column(tile, lane, j):
    """Column ``j`` (traced) of ``tile [N, w]`` as ``[N, 1]``."""
    return jnp.sum(jnp.where(lane == j, tile, 0.0), axis=1, keepdims=True)


def _chunk_of(r0, chunk):
    """Where row ``r0`` of a time block lies in its transposed operands: the
    aligned lane offset of its ``chunk``-wide group and the column inside it."""
    off = pl.multiple_of((r0 // chunk) * chunk, chunk)
    return off, r0 - off


def _fwd_kernel(u_ref, dl_ref, at_ref, bt_ref, ct_ref, d_ref, y_ref, hb_ref,
                h_scr, dl_scr, x_scr, y_scr, *, block_s, chunk):
    n, block_e = h_scr.shape

    @pl.when(pl.program_id(2) == 0)
    def _init():
        h_scr[...] = jnp.zeros_like(h_scr)

    hb_ref[0, 0] = h_scr[...]
    u = u_ref[0].astype(_F32)
    dl_scr[...] = dl_ref[0].astype(_F32)
    x_scr[...] = dl_scr[...] * u
    a = at_ref[...]
    lane = lax.broadcasted_iota(jnp.int32, (n, chunk), 1)
    sub = lax.broadcasted_iota(jnp.int32, (_STEPS, block_e), 0)

    def group(g, h):
        r0 = pl.multiple_of(g * _STEPS, _STEPS)
        off, j0 = _chunk_of(r0, chunk)
        dl8, x8 = dl_scr[pl.ds(r0, _STEPS), :], x_scr[pl.ds(r0, _STEPS), :]
        bt, ct = bt_ref[0, :, pl.ds(off, chunk)], ct_ref[0, :, pl.ds(off, chunk)]
        y8 = jnp.zeros((_STEPS, block_e), _F32)
        for k in range(_STEPS):
            h = jnp.exp(dl8[k:k + 1] * a) * h + x8[k:k + 1] * _column(bt, lane, j0 + k)
            y_row = jnp.sum(h * _column(ct, lane, j0 + k), axis=0, keepdims=True)
            y8 = jnp.where(sub == k, y_row, y8)
        y_scr[pl.ds(r0, _STEPS), :] = y8
        return h

    h_scr[...] = lax.fori_loop(0, block_s // _STEPS, group, h_scr[...])
    y_ref[0] = (y_scr[...] + d_ref[...] * u).astype(y_ref.dtype)


def _bwd_kernel(u_ref, dl_ref, at_ref, bt_ref, ct_ref, d_ref, dy_ref, hb_ref,
                du_ref, ddl_ref, da_ref, dbt_ref, dct_ref, dd_ref,
                s_scr, a_scr, g_scr, u_scr, dl_scr, x_scr, dy_scr, du_scr, ddl_scr,
                dbt_scr, dct_scr, *, block_s, chunk):
    n, block_e = g_scr.shape
    groups = block_s // _STEPS

    @pl.when(pl.program_id(2) == 0)  # the LAST time block: time runs backwards here
    def _init():
        g_scr[...] = jnp.zeros_like(g_scr)
        da_ref[...] = jnp.zeros_like(da_ref)
        dd_ref[...] = jnp.zeros_like(dd_ref)

    u_scr[...] = u_ref[0].astype(_F32)
    dl_scr[...] = dl_ref[0].astype(_F32)
    x_scr[...] = dl_scr[...] * u_scr[...]
    dy_scr[...] = dy_ref[0].astype(_F32)
    a = at_ref[...]
    lane = lax.broadcasted_iota(jnp.int32, (n, chunk), 1)
    sub = lax.broadcasted_iota(jnp.int32, (_STEPS, block_e), 0)

    # the block's states again, from its boundary: s_scr[t + 1] is s_t, a_scr[t] its decay
    s_scr[0] = hb_ref[0, 0]

    def recompute(g, h):
        r0 = pl.multiple_of(g * _STEPS, _STEPS)
        off, j0 = _chunk_of(r0, chunk)
        dl8, x8 = dl_scr[pl.ds(r0, _STEPS), :], x_scr[pl.ds(r0, _STEPS), :]
        bt = bt_ref[0, :, pl.ds(off, chunk)]
        for k in range(_STEPS):
            decay = jnp.exp(dl8[k:k + 1] * a)
            h = decay * h + x8[k:k + 1] * _column(bt, lane, j0 + k)
            a_scr[r0 + k] = decay
            s_scr[r0 + k + 1] = h
        return h

    lax.fori_loop(0, groups, recompute, s_scr[0])

    def back(i, carry):
        g, da = carry  # g = decay_{t+1} * ds_{t+1}: what the later steps send back to s_t
        r0 = pl.multiple_of((groups - 1 - i) * _STEPS, _STEPS)
        off, j0 = _chunk_of(r0, chunk)
        rows = pl.ds(r0, _STEPS)
        dl8, x8, u8, dy8 = dl_scr[rows, :], x_scr[rows, :], u_scr[rows, :], dy_scr[rows, :]
        cols = pl.ds(off, chunk)
        bt, ct = bt_ref[0, :, cols], ct_ref[0, :, cols]
        dbt, dct = dbt_scr[:, cols], dct_scr[:, cols]
        du8 = jnp.zeros((_STEPS, block_e), _F32)
        ddl8 = jnp.zeros((_STEPS, block_e), _F32)
        for k in reversed(range(_STEPS)):
            at_k = lane == j0 + k
            dy_row, dl_row = dy8[k:k + 1], dl8[k:k + 1]
            b_col = _column(bt, lane, j0 + k)
            ds = _column(ct, lane, j0 + k) * dy_row + g
            dct = jnp.where(at_k, jnp.sum(s_scr[r0 + k + 1] * dy_row, axis=1, keepdims=True), dct)
            dbt = jnp.where(at_k, jnp.sum(ds * x8[k:k + 1], axis=1, keepdims=True), dbt)
            g = a_scr[r0 + k] * ds
            w = g * s_scr[r0 + k]            # d(decay_t) * decay_t
            da = da + w * dl_row
            through_b = jnp.sum(ds * b_col, axis=0, keepdims=True)   # d(delta_t u_t)
            ddl_row = jnp.sum(w * a, axis=0, keepdims=True) + u8[k:k + 1] * through_b
            ddl8 = jnp.where(sub == k, ddl_row, ddl8)
            du8 = jnp.where(sub == k, dl_row * through_b, du8)
        du_scr[rows, :] = du8
        ddl_scr[rows, :] = ddl8
        dbt_scr[:, cols] = dbt
        dct_scr[:, cols] = dct
        return g, da

    g, da = lax.fori_loop(0, groups, back, (g_scr[...], jnp.zeros((n, block_e), _F32)))
    g_scr[...] = g
    da_ref[0] += da
    dd_ref[0] += jnp.sum(dy_scr[...] * u_scr[...], axis=0, keepdims=True)
    du_ref[0] = (du_scr[...] + d_ref[...] * dy_scr[...]).astype(du_ref.dtype)
    ddl_ref[0] = ddl_scr[...].astype(ddl_ref.dtype)
    dbt_ref[0, 0] = dbt_scr[...]
    dct_ref[0, 0] = dct_scr[...]


def _vmem(block_shape, index_map):
    return pl.BlockSpec(block_shape, index_map, memory_space=pltpu.VMEM)


def _scratch(*shape):
    return pltpu.VMEM(shape, _F32)


def _params():
    return pltpu.CompilerParams(dimension_semantics=("parallel", "parallel", "arbitrary"))


def _scan_fwd(u, delta, at, bt, ct, d2, block_s, block_e, interpret):
    bsz, s, e = u.shape
    n = at.shape[0]
    nt, chunk = s // block_s, min(block_s, 128)
    kernel = functools.partial(_fwd_kernel, block_s=block_s, chunk=chunk)
    return pl.pallas_call(
        kernel,
        grid=(bsz, e // block_e, nt),
        in_specs=[
            _vmem((1, block_s, block_e), lambda b, ei, ti: (b, ti, ei)),
            _vmem((1, block_s, block_e), lambda b, ei, ti: (b, ti, ei)),
            _vmem((n, block_e), lambda b, ei, ti: (0, ei)),
            _vmem((1, n, block_s), lambda b, ei, ti: (b, 0, ti)),
            _vmem((1, n, block_s), lambda b, ei, ti: (b, 0, ti)),
            _vmem((1, block_e), lambda b, ei, ti: (0, ei)),
        ],
        out_specs=[
            _vmem((1, block_s, block_e), lambda b, ei, ti: (b, ti, ei)),
            _vmem((1, 1, n, block_e), lambda b, ei, ti: (b, ti, 0, ei)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((bsz, s, e), u.dtype),
            jax.ShapeDtypeStruct((bsz, nt, n, e), _F32),
        ],
        scratch_shapes=[_scratch(n, block_e), _scratch(block_s, block_e),
                        _scratch(block_s, block_e), _scratch(block_s, block_e)],
        compiler_params=_params(),
        interpret=interpret,
        name="ssm_scan_fwd",
    )(u, delta, at, bt, ct, d2)


def _scan_bwd(u, delta, at, bt, ct, d2, dy, hb, block_s, block_e, interpret):
    bsz, s, e = u.shape
    n = at.shape[0]
    nt, ne, chunk = s // block_s, e // block_e, min(block_s, 128)
    kernel = functools.partial(_bwd_kernel, block_s=block_s, chunk=chunk)

    def rows(b, ei, ti):
        return b, nt - 1 - ti, ei

    def cols(b, ei, ti):
        return b, 0, nt - 1 - ti

    return pl.pallas_call(
        kernel,
        grid=(bsz, ne, nt),
        in_specs=[
            _vmem((1, block_s, block_e), rows),
            _vmem((1, block_s, block_e), rows),
            _vmem((n, block_e), lambda b, ei, ti: (0, ei)),
            _vmem((1, n, block_s), cols),
            _vmem((1, n, block_s), cols),
            _vmem((1, block_e), lambda b, ei, ti: (0, ei)),
            _vmem((1, block_s, block_e), rows),
            _vmem((1, 1, n, block_e), lambda b, ei, ti: (b, nt - 1 - ti, 0, ei)),
        ],
        out_specs=[
            _vmem((1, block_s, block_e), rows),
            _vmem((1, block_s, block_e), rows),
            _vmem((1, n, block_e), lambda b, ei, ti: (b, 0, ei)),
            _vmem((1, 1, n, block_s), lambda b, ei, ti: (b, ei, 0, nt - 1 - ti)),
            _vmem((1, 1, n, block_s), lambda b, ei, ti: (b, ei, 0, nt - 1 - ti)),
            _vmem((1, 1, block_e), lambda b, ei, ti: (b, 0, ei)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((bsz, s, e), u.dtype),
            jax.ShapeDtypeStruct((bsz, s, e), delta.dtype),
            jax.ShapeDtypeStruct((bsz, n, e), _F32),
            jax.ShapeDtypeStruct((bsz, ne, n, s), _F32),
            jax.ShapeDtypeStruct((bsz, ne, n, s), _F32),
            jax.ShapeDtypeStruct((bsz, 1, e), _F32),
        ],
        scratch_shapes=[
            _scratch(block_s + 1, n, block_e), _scratch(block_s, n, block_e), _scratch(n, block_e),
            *(_scratch(block_s, block_e) for _ in range(6)),
            _scratch(n, block_s), _scratch(n, block_s),
        ],
        compiler_params=_params(),
        interpret=interpret,
        name="ssm_scan_bwd",
    )(u, delta, at, bt, ct, d2, dy, hb)


def _kernel_operands(a, b, c, d):
    """The layouts the kernels read: ``A`` and ``D`` with channels on lanes,
    ``B`` and ``C`` with time on lanes, all float32."""
    return (a.astype(_F32).T, b.astype(_F32).transpose(0, 2, 1), c.astype(_F32).transpose(0, 2, 1),
            d.astype(_F32)[None, :])


@functools.partial(jax.custom_vjp, nondiff_argnums=(6, 7, 8, 9))
def _scan(u, delta, a, b, c, d, block_s, block_e, block_e_bwd, interpret):
    return _scan_fwd_rule(u, delta, a, b, c, d, block_s, block_e, block_e_bwd, interpret)[0]


def _scan_fwd_rule(u, delta, a, b, c, d, block_s, block_e, block_e_bwd, interpret):
    y, hb = _scan_fwd(u, delta, *_kernel_operands(a, b, c, d), block_s, block_e, interpret)
    # named so that a remat policy can keep the forward kernel's two outputs and not run it again
    y, hb = checkpoint_name(y, SCAN_OUTPUTS), checkpoint_name(hb, SCAN_OUTPUTS)
    return y, (u, delta, a, b, c, d, hb)


def _scan_bwd_rule(block_s, block_e, block_e_bwd, interpret, res, dy):
    u, delta, a, b, c, d, hb = res
    du, ddelta, da, dbt, dct, dd = _scan_bwd(
        u, delta, *_kernel_operands(a, b, c, d), dy, hb, block_s, block_e_bwd, interpret)
    return (du, ddelta, da.sum(0).T.astype(a.dtype),
            dbt.sum(1).transpose(0, 2, 1).astype(b.dtype),
            dct.sum(1).transpose(0, 2, 1).astype(c.dtype), dd.sum((0, 1)).astype(d.dtype))


_scan.defvjp(_scan_fwd_rule, _scan_bwd_rule)


def _channel_block(e: int, preferred: int) -> int:
    """The widest of ``preferred``, 512, 256, 128 that divides ``e``; a narrower
    ``e`` is one block."""
    for block in (preferred, 512, 256, 128):
        if block <= preferred and e % block == 0:
            return block
    if e < 128:
        return e
    raise ValueError(f"selective_scan: {e} channels are not a whole number of 128-lane tiles")


def selective_scan(u, delta, a, b, c, d, *, block_s: int = 128, block_e: int = 1024,
                   block_e_bwd: int = 512, interpret: bool | None = None):
    """``y [B, S, E]`` of the recurrence in the module docstring, in ``u``'s
    type, differentiable in all six arguments. u, delta ``[B, S, E]``; a ``[E,
    N]`` (negative); b, c ``[B, S, N]``; d ``[E]``.

    ``block_s`` time steps a grid step (a multiple of 128, or a multiple of 8
    that covers a shorter sequence whole); a length that is not a whole number
    of blocks is padded with ``delta = 0`` steps, which leave the state as it
    is. ``block_e`` / ``block_e_bwd`` channels a grid step, forward / backward:
    the forward holds one state tile and is fastest at the widest block tried
    (a layer at ``[1, 8192, 5120]``: 2.85 ms at 1024, 3.16 at 512); the backward
    holds a block's ``block_s x N x block_e`` states and decays in VMEM, 4 MB
    each at 128 x 16 x 512, its fastest (6.36 ms; 15.4 at 128). PERF.md §6."""
    s, e = u.shape[1], u.shape[2]
    if interpret is None:
        interpret = flash._interpret_default()
    if s < block_s:
        block_s = -(-s // _STEPS) * _STEPS
    if block_s % _STEPS or (block_s > 128 and block_s % 128):
        raise ValueError(f"selective_scan: block_s={block_s} is neither a multiple of 128 nor "
                         f"a multiple of {_STEPS} under 128")
    pad = -s % block_s
    if pad:
        u, delta, b, c = (jnp.pad(t, ((0, 0), (0, pad), (0, 0))) for t in (u, delta, b, c))
    y = _scan(u, delta, a, b, c, d, block_s, _channel_block(e, block_e),
              _channel_block(e, block_e_bwd), interpret)
    return y[:, :s] if pad else y
