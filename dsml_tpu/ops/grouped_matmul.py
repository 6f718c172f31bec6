"""Grouped matrix products as Pallas TPU kernels: the matmuls of a layer of
sparse experts, over rows sorted by expert.

``x [M, K]`` holds the rows of every group one after another, each group
padded to whole row tiles of ``tile`` rows; ``tile_group [M / tile]`` names the
group of each tile (non-decreasing; every group owns a tile at least, so an
empty group still gets its zero gradient written). ``w [G, K, N]`` is one
matrix a group. Then

- ``gmm_fwd``: ``y[tile i] = x[tile i] · w[tile_group[i]]``;
- ``gmm_dx``: the same body with ``w`` read transposed, ``dx = dy · wᵀ``;
- ``gmm_dw``: ``dw[g] = Σ_{tiles i of g} x[tile i]ᵀ · dy[tile i]``, summed in a
  float32 scratch over a group's consecutive tiles and written once a group.
  One call a block of columns: the float32 scratch is one ``[K block, N]`` of a
  group's gradient (``_ACC_BYTES``; two blocks of 1152 at 2304 x 896), and the
  blocks are set side by side afterwards. A train step that holds many such
  layers then feeds each gradient to its optimizer update as it is made, where
  XLA holds one whole custom call's output per leaf to the end of the backward
  (planned on a v5e with four layers of 64 experts: 14.23 GB against 15.70;
  PERF.md §6, PR 35).

**Work from shapes alone.** The grid is ``M / tile`` steps, every step does its dots, and nothing in a kernel
depends on how the rows fall among the groups: the caller sizes the buffer for
the worst case (:func:`n_row_tiles`) and the padding rows are computed like any
other (``jax.experimental.pallas.ops.tpu.megablox`` skips the tiles past the
groups' end, so its time follows the router; PERF.md §6, PR 35). A tile's
matrix stays in VMEM while consecutive tiles name the same group: Pallas
fetches a block again only when its index changes.

A grid step takes a whole ``[tile, K]`` block of rows and a whole ``[K, N]``
matrix (4.1 MB in bf16 at 2304 x 896), so there is no reduction axis in the
grid and no accumulator in ``gmm_fwd`` / ``gmm_dx``; ``gmm_dw`` cuts the wider
of ``K`` and ``N`` so that its float32 accumulator stays under ``_ACC_BYTES``. Operands
go into the MXU in their own dtype and accumulate in float32. Off the TPU the
same kernels run under the Pallas interpreter.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from dsml_tpu.ops import flash

__all__ = ["grouped_matmul", "n_row_tiles"]

_NN = (((1,), (0,)), ((), ()))
_NT = (((1,), (1,)), ((), ()))
_ACC_BYTES = 5 * 2**20   # gmm_dw's float32 accumulator, one [K block, N] of a group's gradient
_VMEM_FLOOR = 32 * 2**20


def n_row_tiles(n_rows: int, n_groups: int, tile: int) -> int:
    """Row tiles that hold ``n_rows`` rows in ``n_groups`` groups however they
    fall: each group padded to whole tiles and owning one at least."""
    return -(-n_rows // tile) + n_groups


def _params(interpret: bool, vmem: int):
    if interpret:
        return None
    return pltpu.CompilerParams(dimension_semantics=("arbitrary",), vmem_limit_bytes=max(2 * vmem, _VMEM_FLOOR))


def _gmm_kernel(group_ref, x_ref, w_ref, o_ref, *, dims):
    del group_ref  # read by the index maps
    o_ref[...] = lax.dot_general(x_ref[...], w_ref[...], dims,
                                 preferred_element_type=jnp.float32).astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("tile", "transpose_w", "interpret"))
def _gmm(x, w, tile_group, *, tile, transpose_w, interpret):
    m, k = x.shape
    n = w.shape[1] if transpose_w else w.shape[2]
    vmem = 2 * (tile * k + k * n + tile * n) * x.dtype.itemsize + tile * n * 4
    return pl.pallas_call(
        functools.partial(_gmm_kernel, dims=_NT if transpose_w else _NN),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(m // tile,),
            in_specs=[pl.BlockSpec((tile, k), lambda i, g: (i, 0)),
                      pl.BlockSpec((None, *w.shape[1:]), lambda i, g: (g[i], 0, 0))],
            out_specs=pl.BlockSpec((tile, n), lambda i, g: (i, 0)),
        ),
        out_shape=jax.ShapeDtypeStruct((m, n), x.dtype),
        compiler_params=_params(interpret, vmem),
        interpret=interpret,
        name="gmm_dx" if transpose_w else "gmm_fwd",
    )(tile_group, x, w)


def _column_blocks(width: int, rows: int) -> int:
    """The fewest equal 128-lane-aligned blocks of ``width`` columns that keep
    ``rows x block`` float32 values within ``_ACC_BYTES``."""
    fits = [n for n in range(1, width // 128 + 1)
            if width % (128 * n) == 0 and rows * (width // n) * 4 <= _ACC_BYTES]
    return fits[0] if fits else 1


def _dw_kernel(group_ref, x_ref, dy_ref, o_ref, acc, *, n_tiles):
    i = pl.program_id(0)
    group = group_ref[i]
    first = jnp.logical_or(i == 0, group_ref[jnp.maximum(i - 1, 0)] != group)
    last = jnp.logical_or(i == n_tiles - 1, group_ref[jnp.minimum(i + 1, n_tiles - 1)] != group)

    @pl.when(first)
    def _zero():
        acc[...] = jnp.zeros_like(acc)

    acc[...] += lax.dot_general(x_ref[...].T, dy_ref[...], _NN, preferred_element_type=jnp.float32)

    @pl.when(last)
    def _write():
        o_ref[...] = acc[...].astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("tile", "n_groups", "interpret"))
def _gmm_dw(x, dy, tile_group, *, tile, n_groups, interpret):
    """``dw [G, K, N]``, one call a block of columns of the wider of ``K`` and
    ``N`` (module docstring), the blocks set side by side."""
    (m, k), n = x.shape, dy.shape[1]
    wide = 1 if k >= n else 2  # the axis of dw that is cut
    blocks = _column_blocks(*((k, n) if wide == 1 else (n, k)))
    tk, tn = (k // blocks, n) if wide == 1 else (k, n // blocks)
    vmem = 2 * (tile * tk + tile * tn + tk * tn) * x.dtype.itemsize + 2 * tk * tn * 4

    def block(b):
        return pl.pallas_call(
            functools.partial(_dw_kernel, n_tiles=m // tile),
            grid_spec=pltpu.PrefetchScalarGridSpec(
                num_scalar_prefetch=1,
                grid=(m // tile,),
                in_specs=[pl.BlockSpec((tile, tk), lambda i, g: (i, b if wide == 1 else 0)),
                          pl.BlockSpec((tile, tn), lambda i, g: (i, b if wide == 2 else 0))],
                out_specs=pl.BlockSpec((None, tk, tn), lambda i, g: (g[i], 0, 0)),
                scratch_shapes=[pltpu.VMEM((tk, tn), jnp.float32)],
            ),
            out_shape=jax.ShapeDtypeStruct((n_groups, tk, tn), x.dtype),
            compiler_params=_params(interpret, vmem),
            interpret=interpret,
            name="gmm_dw",
        )(tile_group, x, dy)

    return jnp.concatenate([block(b) for b in range(blocks)], axis=wide)


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4))
def _grouped(x, w, tile_group, tile, interpret):
    return _gmm(x, w, tile_group, tile=tile, transpose_w=False, interpret=interpret)


def _grouped_fwd(x, w, tile_group, tile, interpret):
    return _grouped(x, w, tile_group, tile, interpret), (x, w, tile_group)


def _grouped_bwd(tile, interpret, res, dy):
    x, w, tile_group = res
    dx = _gmm(dy, w, tile_group, tile=tile, transpose_w=True, interpret=interpret)
    dw = _gmm_dw(x, dy, tile_group, tile=tile, n_groups=w.shape[0], interpret=interpret)
    return dx, dw, None


_grouped.defvjp(_grouped_fwd, _grouped_bwd)


def grouped_matmul(x: jax.Array, w: jax.Array, tile_group: jax.Array, tile: int,
                   interpret: bool | None = None) -> jax.Array:
    """``y [M, N]`` with ``y[tile i] = x[tile i] · w[tile_group[i]]`` for
    ``x [M, K]``, ``w [G, K, N]``, ``tile_group [M / tile]`` int32,
    non-decreasing and naming every group at least once. Differentiable in
    ``x`` and ``w`` (module docstring). Rows a group does not fill are
    computed like the others: the caller leaves them out of what it reads,
    and hands their cotangent in as zeros so that ``dw`` sees none of them."""
    (m, k), (groups, k_w, _) = x.shape, w.shape
    if k != k_w or m % tile or tile_group.shape != (m // tile,) or tile % 16:
        raise ValueError(f"grouped_matmul: x {x.shape}, w {w.shape}, tile_group {tile_group.shape}, "
                         f"tile {tile} do not fit together")
    if interpret is None:
        interpret = flash._interpret_default()  # one switch for every kernel of a step
    return _grouped(x, w, tile_group.astype(jnp.int32), tile, interpret)
