"""Flash attention as Pallas TPU kernels — the framework's hot-op kernels.

The reference has no on-device compute at all (its "GPUs" stream bytes,
``DSML/gpu_device_service/gpu_device_server.go:26-49``); its intended compute
API (vestigial ``RunForward``/``RunBackward`` RPCs, SURVEY.md §8.9) is
realized in this framework as jitted XLA graphs — and, for the attention hot
op, as hand-written Pallas kernels so the [seq, seq] score matrix never
touches HBM:

- forward: blockwise q·kᵀ on the MXU with online-softmax accumulators
  (running row-max, running denominator) held in VMEM scratch across a q
  block's kv blocks; emits the per-row logsumexp.
- backward: ONE kernel (``flash_dkv``), kv-major over the score tiles ("The
  grid", below): the tile is recomputed once, transposed
  (``sᵀ = k·qᵀ``, ``p = exp(s − L)`` from the forward's saved logsumexp
  rather than stored probabilities), and feeds five dots: ``sᵀ``, ``dpᵀ``
  and the three gradients, each accumulated transposed (``dvᵀ += doᵀ·p``,
  ``dkᵀ += qᵀ·ds``, ``dqᵀ += kᵀ·dsᵀ``: a ``[d, block]`` result fills the
  MXU's width where ``[block, d]`` leaves half of it idle at head 64).
  ``dk``/``dv`` accumulate over a kv block's q blocks in block-sized scratch;
  ``dq`` sums over the kv blocks, which the walk leaves and comes back to,
  so its float32 accumulator is the whole query length of one (batch, lane
  block), resident in VMEM across that walk (4 MB at 8192 rows of 128
  lanes). A query whose resident ``dq`` does not fit beside the tile (``_fused_bwd_vmem`` against
  ``_VMEM_BUDGET``: a rule on shapes alone, about 50k rows at head 64 in
  bf16) keeps the two-kernel split, ``flash_dq`` over kv blocks then the
  same ``flash_dkv`` without its ``dq`` part, seven dots. The logsumexp output is differentiable too (its
  cotangent folds into ds as ``p · g_lse``), which is what lets whole
  flash calls be COMBINED downstream.
- :func:`ring_flash_attention` — sequence-parallel attention where every
  ring hop is one flash call: q/k blocks carry their global position
  offsets (SMEM scalars, so the causal mask is correct for any hop pair),
  K/V rotate via ``ppermute``, and the per-hop (out, lse) pairs merge with
  logsumexp weights. Exact full attention at O(block²) VMEM per chip —
  Ring Self-Attention (SURVEY.md §5.7) with a flash inner loop. For cp
  TRAINING prefer ``ops.ring_attention`` (``attn_impl="ring2"``): same
  merge math plus bidirectional streaming, causal hop skipping, and a
  backward that re-streams KV instead of letting autodiff save every
  visiting block (this one's residuals grow O(S) with ring size).
- :func:`flash_block_grads` — the raw one-block backward given MERGED
  (out, lse) statistics; the primitive that re-streaming backward calls.

Layout. The kernels take ``[B, S, W]`` arrays and a grid step works one
block of the minor dimension (:func:`_operands`). Head-major, ``[batch·heads,
seq, head_dim]``: the block is the whole minor dimension, one head
(:func:`flash_attention`, :func:`flash_attention_lse`,
:func:`flash_block_grads`: ring, Ulysses, cp, the serving prefill). Packed,
the projections' own ``[batch, seq, heads·head_dim]``: the block is 128
lanes, ``128 // head_dim`` heads (two of 64, one of 128), and ``o``, ``dq``,
``dk``, ``dv`` leave the same way (:func:`flash_attention_packed`). For
GPT-2's fused projection q, k and v are three views of the ONE ``[batch, seq,
3·d]`` output of ``wqkv``, found by lane-block offsets (no slice is made; the
cotangent is one such array, ``dq`` written into its own lanes by the kernel). No head-major copy exists on either side of the kernels, and a
head of 64 is not stored at 128 lanes in HBM. ONE body serves both layouts: it
loops over the heads of its block. A head's scores come from a dot over the
block's lanes with the other heads' lanes of ``q`` zeroed (64 live lanes of
128 fill the MXU as a 64-wide operand does); ``p·v`` yields the block's lanes
and each head keeps its own; in the backward a head is a row range of the
TRANSPOSED operand blocks and accumulators (an aligned slice), so every
gradient dot still yields ``[head_dim, block]``. ``lse`` and ``delta −
g_lse`` stay a row a head, lane-major over seq. Callers choose by shapes
(:func:`flash_packs`), not by an option. The two calls are jitted
(``_flash_fwd``, ``_flash_bwd_calls``): a model's like layers then trace and lower
each kernel once, not once a layer.

Dtypes. Every dot takes its operands in the INPUTS' dtype and accumulates in
float32 (``preferred_element_type``): bf16 ``q``/``k``/``v``/``do`` blocks go
into the MXU as they lie in HBM, and ``p`` / ``ds`` are cast to that dtype
immediately before the dots that consume them (``p·v``; ``doᵀ·p``, ``qᵀ·ds``,
``kᵀ·dsᵀ``) — the precision every other matmul of a bf16 model has, and what
plain attention feeds ``p·v`` under the same dtype. float32 inputs keep
float32 dots. Whatever is a statistic or an accumulator is float32 always:
the scores ``s``, the running max ``m`` and denominator ``l``, ``lse``,
``delta − g_lse``, ``exp``, and the ``acc`` / ``dk_acc`` / ``dv_acc`` /
``dq_acc`` scratch. The softmax scale multiplies the [block_q, d] ``q``
block where that is exact (a power of two: head 64) and the float32 scores
otherwise.
The dkv kernel works the TRANSPOSED tile (``sᵀ = k·qᵀ``), so the row
statistics are used lane-major as stored; the transposed left operand of a
gradient dot is a ``[block, lanes]`` operand block (``do``, ``q``, ``k``,
transposed once a grid step), never the tile. ``dsᵀ`` is cast to the operand dtype once and feeds both its dots.

Sequences that don't tile into blocks run through a PADDED path: zero-pad
to a block multiple (≤ 25% waste), mask the padded kv tail inside the
kernels via a ``kv_stop`` SMEM scalar, slice padded q rows off outputs —
cp/ring shards make odd residual lengths the common case.
``DSML_FLASH_BLOCK`` overrides the swept block defaults (docs/TUNING.md).

The grid. Every kernel here but the ring hop's runs on ``(batch, lane blocks,
tiles)``: the last axis walks a LIST of score tiles (:func:`_walk`), whose
tables (``q_of[t]``, ``kv_of[t]`` and a word of flags: first / last tile of
its q block in a q-major walk, of its kv block in the kv-major one, first /
last appearance of a q block for the riding ``dq``) are scalar-prefetched, so
the index maps read them and Pallas fetches the blocks of the tiles on the
list and of no other. Where a call's offsets are known as it is traced
(``q_start`` and ``k_start`` Python ints: every single-chip caller, at 0 and
0) the list is made then, in numpy, of the tiles :func:`_seen` lets through:
causal tiles wholly above the diagonal are not on it, nor, under a sliding
``window`` (one more static argument of every entry point), tiles wholly
older than every query's window; a q block or kv block left with no tile
keeps one, for its accumulator's zeros and its write. The order is the
rectangle's own with those tiles left out (forward and ``flash_dq`` q-major,
each q block's kv blocks ascending; ``flash_dkv`` kv-major, each kv block's q
blocks ascending), so every sum is accumulated as it always was and values are
bit-equal to the rectangle's. Where the offsets are traced (the ring and cp
callers) the list is the whole rectangle in that order. One body either way:
``pl.when(_seen(...))`` inside the step keeps the dots off a tile that sees
nothing (on the rectangle it decides; on a made list it only ever says no to a
block's one kept tile), and the forward runs a tile that neither edge crosses
and that ends before ``kv_stop`` with no mask at all (:func:`_per_tile_class`).
:func:`grid_steps` counts both for a call's shapes.
On non-TPU backends the same kernels run under the Pallas interpreter
(``interpret=True``), which is how tests validate them on the CI CPU mesh;
on TPU they compile through Mosaic.

Used by ``dsml_tpu.models.gpt2`` / ``llama`` via ``attn_impl="flash"``
(single-chip: packed where :func:`flash_packs` says so) and
``attn_impl="ring_flash"`` (sequence-parallel).
"""

from __future__ import annotations

import functools
import math
import os

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax
from jax.ad_checkpoint import checkpoint_name
from jax.experimental import pallas as pl

from dsml_tpu.ops.collectives import ring_pass

try:  # pltpu is importable on CPU builds too; guard anyway
    from jax.experimental.pallas import tpu as pltpu
except ImportError:  # pragma: no cover
    pltpu = None

__all__ = [
    "FLASH_OUTPUTS",
    "flash_attention",
    "flash_attention_lse",
    "flash_attention_packed",
    "flash_packs",
    "flash_block_grads",
    "flash_stream_hop",
    "grid_steps",
    "ring_flash_attention",
]

FLASH_OUTPUTS = "flash_outputs"  # the checkpoint name of the forward kernel's out and lse
_NEG_INF = -1e30
_MAX_FLOOR = -1e20  # running-max floor: keeps exp() sane for fully-masked rows


def _interpret_default() -> bool:
    return jax.default_backend() != "tpu"


def _vmem_spec(block_shape, index_map):
    if pltpu is not None:
        return pl.BlockSpec(block_shape, index_map, memory_space=pltpu.VMEM)
    return pl.BlockSpec(block_shape, index_map)


def _smem_spec():
    if pltpu is not None:
        return pl.BlockSpec(memory_space=pltpu.SMEM)
    return pl.BlockSpec()  # pragma: no cover


def _scratch(shape):
    if pltpu is not None:
        return pltpu.VMEM(shape, jnp.float32)
    return pl.MemoryRef(shape, jnp.float32)  # pragma: no cover


def _pick_block(seq: int, preferred: int) -> int | None:
    # 512 in the fallback ladder matters since the auto default became 1024:
    # without it a kv length divisible by 512 but not 1024 (4608, 5632, ...)
    # would degrade straight to 256-wide blocks
    for b in (preferred, 512, 256, 128, 64, 32, 16, 8):
        if b <= preferred and seq % b == 0:
            return b
    return None


def _pad_choice(seq: int, preferred: int) -> tuple[int, int]:
    """(block, padded_len): exact ladder tiling when ``seq`` divides a ladder
    block (today's path, byte-identical); otherwise the largest ladder block
    whose zero-padding waste stays ≤ 25% of the padded length (floor 8).
    Ring/cp shards make odd residual lengths the COMMON case, and a
    sub-block pad — masked off via the kernels' kv_stop scalar — beats
    falling off the kernel onto the O(s²) XLA path."""
    b = _pick_block(seq, preferred)
    if b is not None:
        return b, seq
    for cand in (preferred, 512, 256, 128, 64, 32, 16, 8):
        if cand > preferred:
            continue
        padded = -(-seq // cand) * cand
        if (padded - seq) * 4 <= padded:
            return cand, padded
    return 8, -(-seq // 8) * 8


def _env_block_override() -> tuple[int | None, int | None]:
    """``DSML_FLASH_BLOCK`` override for the auto block defaults: ``"B"``
    (both blocks) or ``"BQxBK"``. Lets cp-sharded (shorter per-rank)
    sequences be tuned without editing the kernel; explicit ``block_q``/
    ``block_k`` arguments still win. Malformed or non-multiple-of-8 values
    are ignored — a bad env var must degrade to the swept defaults, never
    crash a trace (docs/TUNING.md)."""
    raw = os.environ.get("DSML_FLASH_BLOCK", "").strip().lower()
    if not raw:
        return None, None
    try:
        if "x" in raw:
            q_s, k_s = raw.split("x", 1)
            bq, bk = int(q_s), int(k_s)
        else:
            bq = bk = int(raw)
    except ValueError:
        return None, None
    if bq < 8 or bk < 8 or bq % 8 or bk % 8:
        return None, None
    return bq, bk


def _default_blocks(
    s_q: int, s_kv: int, block_q: int | None, block_k: int | None,
    head_dim: int | None = None,
) -> tuple[int, int]:
    """Block defaults (``scripts/flash_block_sweep.py`` on a v5e, head
    dim 64 — the GPT-2 shape): 1024x1024 at sequence lengths >= 4096 (fewer
    grid steps, each a read-modify-write of the block-sized dk/dv scratch
    and of one block of the backward's resident dq), 512x512 below; anything
    wider than 1024 fails TPU compilation on VMEM at d=64. The 1024
    widening is GATED on head_dim <= 64: kernel VMEM scales with
    block x head_dim, so a d=128 model (Llama presets) at the same block
    could exhaust VMEM outright where the 512 default compiles — wider
    heads keep 512x512 until a sweep at that head_dim says otherwise.
    Callers can still pin blocks explicitly (the ring path does,
    per-shard); lengths the preferred block doesn't divide degrade through
    _pick_block's ladder.

    ``DSML_FLASH_BLOCK`` ("B" or "BQxBK") overrides the swept auto defaults
    — the tuning knob for cp-sharded per-rank lengths the sweep never saw —
    but explicit arguments always win over the env."""
    env_q, env_k = _env_block_override()
    if block_q is None:
        block_q = env_q
    if block_k is None:
        block_k = env_k
    widen = head_dim is not None and head_dim <= 64
    if block_q is None:
        block_q = 1024 if (s_q >= 4096 and widen) else 512
    if block_k is None:
        block_k = 1024 if (s_kv >= 4096 and widen) else 512
    return block_q, block_k


_NT = (((1,), (1,)), ((), ()))  # a·bᵀ: contract the head dim of both
_NN = (((1,), (0,)), ((), ()))  # a·b


def _dot(a, b, dims):
    """MXU dot on the operands' own dtype (bf16 blocks go in as bf16),
    accumulated in float32."""
    return jax.lax.dot_general(a, b, dims, preferred_element_type=jnp.float32)


def _scale_folds(scale: float) -> bool:
    """True when ``scale`` is a power of two (head 64: 0.125): multiplying
    the [block_q, d] q block by it is then exact in any float dtype, so the
    scale leaves the [block_q, block_k] score tile. Any other scale (head
    128) stays on the float32 scores."""
    return math.frexp(scale)[0] == 0.5


def _stat_lanes(block_k: int) -> int:
    """Lane width of the forward's running statistics. 128 — one value a
    lane, ``m`` replicated and ``l`` as per-lane partial sums — wherever the
    score tile is whole 128-lane groups: every per-tile update is then
    vreg-wise and the one cross-lane op left on a tile is the row max
    (``l``'s lanes are summed once per q block, in ``_finish``). A narrower
    tile keeps plain [block_q, 1] columns."""
    return 128 if block_k % 128 == 0 else 1


def _lanes(x, n: int):
    """[rows, n] out of ``x`` [rows, w] whose lanes all hold the row's
    value: whole lane groups are sliced or repeated, no cross-lane op."""
    w = x.shape[1]
    if n <= w:
        return x[:, :n]
    if n % w == 0:
        return jnp.tile(x, (1, n // w))
    return jnp.broadcast_to(x[:, :1], (x.shape[0], n))


def _fold_lanes(p, w: int):
    """Row sums of ``p`` kept as ``w`` per-lane partials: the tile's lane
    groups added vreg-wise (``w`` = 1: the plain row sum)."""
    if w == 1:
        return jnp.sum(p, -1, keepdims=True)
    out = p[:, :w]
    for c in range(1, p.shape[1] // w):
        out = out + p[:, c * w:(c + 1) * w]
    return out


def _row_chunks(block: int) -> list[slice]:
    """The forward works a tile 512 rows at a time: the rows' softmax
    chains are independent, and at 1024 rows one chunk's exp and row max
    overlap the other's dots (measured on a v5e at 1024x1024, PERF.md §6;
    dq and dkv read the same either way, so they take the tile whole)."""
    rows = 512 if block % 512 == 0 else block
    return [slice(r, r + rows) for r in range(0, block, rows)]


def _head_lanes(lanes: int, head_dim: int) -> list:
    """One entry for each head of a ``lanes``-wide block: the ``[1, lanes]``
    mask of the head's own lanes, or ``None`` where the block is one head
    (the head-major form, and a head of 128 in the packed one)."""
    if lanes == head_dim:
        return [None]
    lane = jax.lax.broadcasted_iota(jnp.int32, (1, lanes), 1)
    return [(lane >= j * head_dim) & (lane < (j + 1) * head_dim) for j in range(lanes // head_dim)]


def _only(x, own):
    """``x`` [rows, lanes] with the lanes of every other head zeroed: a dot
    that contracts the block's lanes then contracts this head's alone (64
    live lanes of 128 fill the MXU as a 64-wide operand does)."""
    return x if own is None else jnp.where(own, x, jnp.zeros_like(x))


def _weave(parts, heads):
    """[rows, lanes] that holds, in each head's lanes, that head's ``parts``
    entry (each entry is [rows, lanes] itself: a dot that yields the block's
    lanes costs what one that yields a head's does)."""
    out = parts[-1]
    for part, own in zip(parts[-2::-1], heads[-2::-1]):
        out = jnp.where(own, part, out)
    return out


def _mask(s, q0, k0, kv_stop, causal, mask_kv, q_axis, window=None):
    """Mask the score tile ``s`` whose first query / key sit at global
    positions ``q0`` / ``k0``; queries lie along ``q_axis`` of ``s`` (0, or
    1 for the transposed tile of the dkv kernel). A key survives when it is
    at or before its query (causal) and before ``kv_stop`` (zero-padded kv
    tail: its columns must not enter the softmax denominator). Positions
    are one column and one row vector, so the tile itself sees one compare
    and one select; a ``window`` (the last ``window`` keys, the query's own
    among them) is a second compare."""
    def pos(start, axis):
        shape = [1, 1]
        shape[axis] = s.shape[axis]
        return start + jax.lax.broadcasted_iota(jnp.int32, tuple(shape), axis)

    last = None  # the last key position each query may see
    if causal:
        last = pos(q0, q_axis)
    if mask_kv:
        last = kv_stop - 1 if last is None else jnp.minimum(last, kv_stop - 1)
    keys = pos(k0, 1 - q_axis)
    kept = keys <= last
    if window is not None:
        kept = jnp.logical_and(kept, keys > pos(q0, q_axis) - window)
    return jnp.where(kept, s, _NEG_INF)


def _seen(q0, k0, block_q, block_k=None, window=None):
    """False for a tile whose every key is in the future of its every query,
    or (``window``) older than the window of its every query: a causal kernel
    does no dot on it. Python's operators only: the same test lists the live
    tiles in numpy as a call is traced (:func:`_live_tiles`) and, where the
    offsets are traced, decides inside the grid step."""
    seen = k0 <= q0 + block_q - 1
    if window is not None:
        seen = seen & (k0 + block_k - 1 > q0 - window)
    return seen


def _per_tile_class(compute, q0, k0, kv_stop, causal, mask_kv, block_q, block_k, window=None):
    """Run the forward's ``compute(masked)`` as the tile's place demands.
    Three classes, told apart by SMEM scalars: every key in the future of
    every query, or older than every query's ``window`` — skipped; every key
    at or before every query, inside every query's window and before
    ``kv_stop`` — the body with no mask at all; anything else — the masked
    body. The backward kernels run the masked body on every tile they do
    not skip: a second body measured no faster there and every body is
    traced and lowered once a layer (PERF.md §6)."""
    if not (causal or mask_kv):
        compute(False)
        return
    clear = True  # nothing in the tile is masked
    if causal:
        clear = k0 + block_k - 1 <= q0
    if window is not None:
        clear = jnp.logical_and(clear, k0 > q0 + block_q - 1 - window)
    if mask_kv:
        clear = jnp.logical_and(clear, k0 + block_k <= kv_stop)
    crossed = jnp.logical_not(clear)
    if causal:
        crossed = jnp.logical_and(crossed, _seen(q0, k0, block_q, block_k, window))
    pl.when(clear)(lambda: compute(False))
    pl.when(crossed)(lambda: compute(True))


# ---------------------------------------------------------------------------
# the walk: one grid axis over a list of tiles
# ---------------------------------------------------------------------------

# What a grid step needs to know of its place in the walk, one bit each: it is
# the first / last tile of its block on the major axis (the q block of a
# q-major walk, the kv block of a kv-major one: the block whose accumulator the
# walk holds in scratch), and the first / last tile in which its block on the
# other axis appears at all (the riding dq's q block in the kv-major walk).
_ROW_FIRST, _ROW_LAST, _SEEN_FIRST, _SEEN_LAST = 1, 2, 4, 8


def _static_offset(q_start, k_start) -> int | None:
    """``q_start - k_start`` where both are known as the call is traced
    (Python ints: every single-chip caller, at 0 and 0), else ``None`` (the
    ring and cp callers hand tracers)."""
    if isinstance(q_start, int) and isinstance(k_start, int):
        return q_start - k_start
    return None


def _live_tiles(q_blocks, kv_blocks, block_q, block_k, causal, window, offset):
    """``[q_blocks, kv_blocks]`` bool: the tiles a call's grid walks. With a
    known ``offset`` between the first query and the first key, those
    :func:`_seen` lets through, and for a q block or a kv block that has none
    its first tile all the same (its accumulator owes its zeros and its
    write; ``_seen`` still keeps the dots off it). With ``offset`` ``None``
    (traced) or without ``causal``, the whole rectangle: the list no one could
    shorten."""
    live = np.ones((q_blocks, kv_blocks), bool)
    if causal and offset is not None:
        q0 = offset + np.arange(q_blocks)[:, None] * block_q
        live &= _seen(q0, np.arange(kv_blocks)[None, :] * block_k, block_q, block_k, window)
        live[~live.any(1), 0] = True
        live[0, ~live.any(0)] = True
    return live


def _walk(live, kv_major: bool):
    """The tables of a walk over ``live``: ``(q_of, kv_of, flags)``, int32
    ``[n_live]`` each, scalar-prefetched so that the index maps read them
    (as one interleaved array they read 0.03-0.05% slower in four cells and
    no faster in any: PERF.md §6, PR 36).
    q-major (the forward, ``flash_dq``): each q block's kv blocks ascending;
    kv-major (``flash_dkv``): each kv block's q blocks ascending. Either is
    the rectangle's own order with the dead tiles left out, so every sum is
    accumulated in the order it always was."""
    major, minor = np.nonzero(live.T if kv_major else live)
    n = major.size
    edge = major[1:] != major[:-1]
    flags = np.zeros(n, np.int32)
    flags[np.r_[True, edge]] |= _ROW_FIRST
    flags[np.r_[edge, True]] |= _ROW_LAST
    flags[np.unique(minor, return_index=True)[1]] |= _SEEN_FIRST
    flags[n - 1 - np.unique(minor[::-1], return_index=True)[1]] |= _SEEN_LAST
    q_of, kv_of = (minor, major) if kv_major else (major, minor)
    return tuple(jnp.asarray(t, jnp.int32) for t in (q_of, kv_of, flags))


def _step(q_of, kv_of, flags):
    """``(qi, ki, flag)`` of this grid step: its tile, and ``flag(bit)`` for
    what :func:`_walk` noted of it."""
    t = pl.program_id(2)
    word = flags[t]
    return q_of[t], kv_of[t], lambda bit: (word & bit) != 0


def _side_spec(block_q, block_k, lanes, of_q: bool, at: int = 0):
    """Spec of a q-side (``of_q``) or kv-side operand or output on the walk's
    grid: the step's block of the sequence, lane block ``at + g``."""
    return _vmem_spec((1, block_q if of_q else block_k, lanes),
                      lambda b, g, t, q_of, kv_of, _: (b, (q_of if of_q else kv_of)[t], at + g))


def _stat_spec(heads, block_q, groups):
    """Spec of the per-row statistics (``lse``, ``delta − g_lse``) on the
    walk's grid: a row a head, lane-major over the step's q block."""
    return _vmem_spec((heads, 8, block_q), lambda b, g, t, q_of, kv_of, _: (b * groups + g, 0, q_of[t]))


def _walk_grid(tables, batch, groups, in_specs, out_specs, scratch_shapes):
    """The grid every flash kernel here runs on: ``(batch, lane blocks, tiles
    of the walk)``, the walk's ``tables`` first among the operands."""
    return pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=len(tables), grid=(batch, groups, tables[0].shape[0]),
        in_specs=in_specs, out_specs=out_specs, scratch_shapes=scratch_shapes)


def grid_steps(s_q: int, s_kv: int, head_dim: int, causal: bool = True, window: int | None = None,
               offset: int | None = 0, block_q: int | None = None, block_k: int | None = None) -> tuple[int, int]:
    """``(walked, rectangle)``: the grid steps a call at these lengths takes
    for one (batch, lane block), and the tiles of its rectangle (what it took
    before the walk, and takes with ``offset=None``)."""
    block_q, block_k = _default_blocks(s_q, s_kv, block_q, block_k, head_dim)
    (bq, pq), (bk, pk) = _pad_choice(s_q, block_q), _pad_choice(s_kv, block_k)
    live = _live_tiles(pq // bq, pk // bk, bq, bk, causal, window, offset)
    return int(live.sum()), live.size


# ---------------------------------------------------------------------------
# forward
# ---------------------------------------------------------------------------


def _fwd_kernel(q_of, kv_of, flags, *refs, **static):
    qi, ki, flag = _step(q_of, kv_of, flags)
    _fwd_tile(qi, ki, flag(_ROW_FIRST), flag(_ROW_LAST), *refs, **static)


def _fwd_tile(qi, ki, first, last, qs_ref, ks_ref, kstop_ref, q_ref, k_ref, v_ref, o_ref, lse_ref, acc, m_scr, l_scr, *, head_dim, causal, block_q, block_k, mask_kv, window=None, v_dim=None):
    """One grid step of the forward on tile ``(qi, ki)``, the ``first`` /
    ``last`` of its q block. All four are values the caller read at the top
    level of its kernel: a wrapping kernel that delegates here from inside
    ``pl.when`` must not leave a ``program_id`` read for a cond branch
    (interpret mode substitutes the primitive only where it is bound in the
    outer kernel jaxpr). ``v_dim``: a value head of another width than the
    query-key ``head_dim`` (one head a block: the head-major form)."""
    q0 = qs_ref[0] + qi * block_q
    k0 = ks_ref[0] + ki * block_k
    scale = head_dim**-0.5
    fold = _scale_folds(scale)
    heads = _head_lanes(acc.shape[1], v_dim or head_dim)

    @pl.when(first)
    def _init():
        acc[:] = jnp.zeros_like(acc)
        m_scr[:] = jnp.full_like(m_scr, _MAX_FLOOR)
        l_scr[:] = jnp.zeros_like(l_scr)

    def compute(masked):
        q, k, v = q_ref[0], k_ref[0], v_ref[0]
        if fold:
            q = q * scale
        for rows in _row_chunks(block_q):
            corrs, pvs = [], []
            for j, own in enumerate(heads):
                s = _dot(_only(q[rows], own), k, _NT)
                if not fold:
                    s = s * scale
                if masked:
                    s = _mask(s, q0 + rows.start, k0, kstop_ref[0], causal, mask_kv, q_axis=0, window=window)
                m_prev = m_scr[j, rows]
                m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1, keepdims=True))
                corr = jnp.exp(m_prev - m_new)
                p = jnp.exp(s - _lanes(m_new, block_k))
                l_scr[j, rows] = l_scr[j, rows] * corr + _fold_lanes(p, l_scr.shape[2])
                m_scr[j, rows] = m_new
                corrs.append(_lanes(corr, acc.shape[1]))
                pvs.append(_dot(p.astype(v.dtype), v, _NN))
            acc[rows] = acc[rows] * _weave(corrs, heads) + _weave(pvs, heads)

    _per_tile_class(compute, q0, k0, kstop_ref[0], causal, mask_kv, block_q, block_k, window)

    @pl.when(last)
    def _finish():
        l_fin = [jnp.maximum(jnp.sum(l_scr[j], -1, keepdims=True), 1e-30) for j in range(len(heads))]
        o_ref[0] = (acc[:] / _weave([_lanes(l, acc.shape[1]) for l in l_fin], heads)).astype(o_ref.dtype)
        # lse is stored [heads, 8, seq] — 8 identical sublanes keep the block
        # shape Mosaic-tileable (last two dims (8, block_q))
        for j, l in enumerate(l_fin):
            lse_ref[j] = jnp.broadcast_to((m_scr[j, :, :1] + jnp.log(l)).reshape(1, block_q), (8, block_q))


def _operands(qkv, head_dim):
    """How the kernels find q, k and v in what they were handed:
    ``(q, k, v, at, lanes, v_lanes, groups)``. A grid step takes one
    ``lanes``-wide block of q's and k's minor dimension and one
    ``v_lanes``-wide block of v's (and of the output's), ``groups`` of them
    cover the heads, and ``at`` is the lane block where each operand's first
    head lies. Three arrays ``[B, S, W]``: where ``W`` is one head (the
    head-major form, ``B`` = batch·heads) the block is the whole minor
    dimension, and v's head may be narrower or wider than q's and k's
    (latent attention: a query-key width of 192, a value width of 128); else
    the arrays are a projection's own output, ``W`` = heads·head_dim, and a
    block is 128 lanes, ``128 // head_dim`` heads, of one width in all three.
    ONE array ``[B, S, 3·W]`` is all three side by side, as a fused
    projection leaves them."""
    if len(qkv) == 1:
        blocks = qkv[0].shape[2] // 3 // 128
        return *qkv * 3, (0, blocks, 2 * blocks), 128, 128, blocks
    q, k, v = qkv
    if q.shape[2] == head_dim:
        return q, k, v, (0, 0, 0), head_dim, v.shape[2], 1
    return q, k, v, (0, 0, 0), 128, 128, q.shape[2] // 128


@functools.partial(jax.jit, static_argnums=(4, 5, 6, 7, 8, 9, 10, 11))
def _flash_fwd(qkv, q_start, k_start, kv_stop, causal, block_q, block_k, interpret, mask_kv, head_dim, window=None, offset=None):
    q, k, v, (q_at, k_at, v_at), lanes, v_lanes, groups = _operands(qkv, head_dim)
    batch, s_q = q.shape[:2]
    heads = lanes // head_dim
    tables = _walk(_live_tiles(s_q // block_q, k.shape[1] // block_k, block_q, block_k, causal, window, offset), kv_major=False)
    side = functools.partial(_side_spec, block_q, block_k, lanes)
    v_side = functools.partial(_side_spec, block_q, block_k, v_lanes)

    kernel = functools.partial(
        _fwd_kernel, head_dim=head_dim, causal=causal,
        block_q=block_q, block_k=block_k, mask_kv=mask_kv, window=window,
        **({} if v_lanes == lanes else {"v_dim": v_lanes // heads}),
    )
    out, lse = pl.pallas_call(
        kernel,
        grid_spec=_walk_grid(
            tables, batch, groups,
            in_specs=[
                _smem_spec(),
                _smem_spec(),
                _smem_spec(),
                side(True, q_at),
                side(False, k_at),
                v_side(False, v_at),
            ],
            out_specs=[
                v_side(True),
                _stat_spec(heads, block_q, groups),
            ],
            scratch_shapes=[
                _scratch((block_q, v_lanes)),
                _scratch((heads, block_q, _stat_lanes(block_k))),
                _scratch((heads, block_q, _stat_lanes(block_k))),
            ],
        ),
        out_shape=[
            jax.ShapeDtypeStruct((batch, s_q, groups * v_lanes), q.dtype),
            jax.ShapeDtypeStruct((batch * groups * heads, 8, s_q), jnp.float32),
        ],
        interpret=interpret,
        name="flash_fwd",
    )(*tables, _scalar(q_start), _scalar(k_start), _scalar(kv_stop), q, k, v)
    return out, lse


def _scalar(x):
    return jnp.atleast_1d(jnp.asarray(x, jnp.int32))


# ---------------------------------------------------------------------------
# fused ring hop: flash forward + in-kernel KV streaming to the neighbor
# ---------------------------------------------------------------------------


def _stream_fwd_kernel(qs_ref, ks_ref, kstop_ref, pred_ref, nbr_ref,
                       q_ref, k_ref, v_ref, ksend_ref, vsend_ref,
                       o_ref, lse_ref, knext_ref, vnext_ref,
                       acc, m_scr, l_scr, send_sem, recv_sem, *,
                       head_dim, causal, block_q, block_k, q_blocks, kv_blocks,
                       n_bh, mask_kv, barrier):
    """:func:`_fwd_tile` on a grid of its own, ``(batch·heads, q_blocks,
    kv_blocks)``, with the ring hop absorbed: at the FIRST grid
    step the resident KV shard starts a remote async copy into the
    neighbor's receive buffers (``pltpu.make_async_remote_copy``), the
    whole flash grid then computes while those bytes fly, and the LAST
    grid step waits both directions' semaphores — the MXU never idles on
    an XLA-visible ppermute between hops. ``pred_ref`` carries the causal
    hop-skip predicate INTO the kernel (a skipped pair writes the
    (0, lse-floor) identity the ring merge ignores) because the stream
    must run even when the math doesn't — every block tours the full
    ring regardless of masking. ``nbr_ref`` = (destination, source)
    logical device ids; the barrier handshake makes sure both neighbors'
    kernels (and so their receive buffers) exist before any send."""
    b = pl.program_id(0)
    qi = pl.program_id(1)
    ki = pl.program_id(2)

    def _rdma(i, src, dst):
        return pltpu.make_async_remote_copy(
            src, dst, send_sem.at[i], recv_sem.at[i],
            device_id=nbr_ref[0],
            device_id_type=pltpu.DeviceIdType.LOGICAL,
        )

    first = (b == 0) & (qi == 0) & (ki == 0)
    last = ((b == n_bh - 1) & (qi == q_blocks - 1) & (ki == kv_blocks - 1))

    @pl.when(first)
    def _send():
        if barrier:
            # both neighbors must have entered this collective before a
            # byte moves — their receive buffers are this kernel's outputs
            bsem = pltpu.get_barrier_semaphore()
            pltpu.semaphore_signal(
                bsem, 1, device_id=nbr_ref[0],
                device_id_type=pltpu.DeviceIdType.LOGICAL)
            pltpu.semaphore_signal(
                bsem, 1, device_id=nbr_ref[1],
                device_id_type=pltpu.DeviceIdType.LOGICAL)
            pltpu.semaphore_wait(bsem, 2)
        _rdma(0, ksend_ref, knext_ref).start()
        _rdma(1, vsend_ref, vnext_ref).start()

    @pl.when(pred_ref[0] != 0)
    def _math():
        _fwd_tile(qi, ki, ki == 0, ki == kv_blocks - 1,
                  qs_ref, ks_ref, kstop_ref, q_ref, k_ref, v_ref,
                  o_ref, lse_ref, acc, m_scr, l_scr, head_dim=head_dim,
                  causal=causal, block_q=block_q, block_k=block_k, mask_kv=mask_kv)

    @pl.when((pred_ref[0] == 0) & (ki == kv_blocks - 1))
    def _masked():
        # the hop-skip identity: zero out, floored lse — exactly what the
        # unfused ring's lax.cond branch emits, so the merge math is
        # bit-identical between schedules
        o_ref[0] = jnp.zeros_like(o_ref[0])
        lse_ref[0] = jnp.full_like(lse_ref[0], -1e30)

    @pl.when(last)
    def _settle():
        _rdma(0, ksend_ref, knext_ref).wait()
        _rdma(1, vsend_ref, vnext_ref).wait()


def flash_stream_hop(
    q: jax.Array,
    k: jax.Array,
    v: jax.Array,
    pred,
    dst,
    src,
    causal: bool = True,
    q_start: jax.Array | int = 0,
    k_start: jax.Array | int = 0,
    block_q: int | None = None,
    block_k: int | None = None,
    interpret: bool | None = None,
    collective_id: int = 7,
):
    """One FUSED ring-attention hop: flash attention of ``q`` against the
    resident ``k``/``v`` shard while that same shard streams to logical
    device ``dst`` inside the kernel's DMA pipeline. Returns
    ``(out, lse, k_next, v_next)`` — the attention pair for the merge plus
    the NEXT hop's residents, received from ``src`` (the opposite ring
    neighbor) into this call's output buffers.

    ``pred`` is the causal hop-skip predicate (traced bool): when false
    the kernel skips every score block and emits the ``(0, −1e30)`` merge
    identity, but the KV stream still runs — masked hops move bytes, not
    math, exactly like the unfused schedule's bare ppermute. The compute
    operands ride the padded-block path (odd shard lengths); the STREAMED
    buffers are the unpadded originals, so wire bytes match
    ``ring_kv_wire_bytes`` exactly.

    Logical device ids index ``jax.devices()`` order, which equals the
    ring rank only when the ring axis is the mesh's sole (or major-order
    equivalent) axis — ``ops.ring_attention`` only routes here under that
    condition (``DSML_RING_FUSED=dma``). Off-TPU the kernel runs under
    the Pallas interpreter, whose remote-copy emulation is how CI pins
    hop parity on the CPU mesh."""
    b, h, s_q, d = q.shape
    s_kv = k.shape[2]
    block_q, block_k = _default_blocks(s_q, s_kv, block_q, block_k, d)
    bq, pq = _pad_choice(s_q, block_q)
    bk, pk = _pad_choice(s_kv, block_k)
    if interpret is None:
        interpret = _interpret_default()
    mask_kv = pk != s_kv
    ksend, vsend = _flat3(k), _flat3(v)  # unpadded residents are what tours the ring
    qf, kf, vf = _pad_rows(_flat3(q), pq), _pad_rows(ksend, pk), _pad_rows(vsend, pk)
    kv_stop = k_start + s_kv
    bh = qf.shape[0]
    q_blocks, kv_blocks = pq // bq, pk // bk
    kernel = functools.partial(
        _stream_fwd_kernel, head_dim=d, causal=causal, block_q=bq,
        block_k=bk, q_blocks=q_blocks, kv_blocks=kv_blocks, n_bh=bh,
        mask_kv=mask_kv, barrier=not interpret,
    )
    any_spec = pl.BlockSpec(memory_space=pl.ANY)
    nbr = jnp.stack([jnp.asarray(dst, jnp.int32), jnp.asarray(src, jnp.int32)])
    pred_arr = jnp.atleast_1d(jnp.asarray(pred, jnp.int32))
    out, lse, k_next, v_next = pl.pallas_call(
        kernel,
        grid=(bh, q_blocks, kv_blocks),
        in_specs=[
            _smem_spec(), _smem_spec(), _smem_spec(),
            _smem_spec(), _smem_spec(),
            _vmem_spec((1, bq, d), lambda b, qi, ki: (b, qi, 0)),
            _vmem_spec((1, bk, d), lambda b, qi, ki: (b, ki, 0)),
            _vmem_spec((1, bk, d), lambda b, qi, ki: (b, ki, 0)),
            any_spec, any_spec,
        ],
        out_specs=[
            _vmem_spec((1, bq, d), lambda b, qi, ki: (b, qi, 0)),
            _vmem_spec((1, 8, bq), lambda b, qi, ki: (b, 0, qi)),
            any_spec, any_spec,
        ],
        out_shape=[
            jax.ShapeDtypeStruct((bh, pq, d), q.dtype),
            jax.ShapeDtypeStruct((bh, 8, pq), jnp.float32),
            jax.ShapeDtypeStruct(ksend.shape, ksend.dtype),
            jax.ShapeDtypeStruct(vsend.shape, vsend.dtype),
        ],
        scratch_shapes=[
            _scratch((bq, d)), _scratch((1, bq, _stat_lanes(bk))), _scratch((1, bq, _stat_lanes(bk))),
            pltpu.SemaphoreType.DMA((2,)), pltpu.SemaphoreType.DMA((2,)),
        ],
        compiler_params=pltpu.CompilerParams(
            collective_id=collective_id,
        ) if not interpret else None,
        interpret=interpret,
    )(_scalar(q_start), _scalar(k_start), _scalar(kv_stop), pred_arr, nbr,
      qf, kf, vf, ksend, vsend)
    if pq != s_q:
        out = out[:, :s_q]
        lse = lse[:, :, :s_q]
    return (out.reshape(b, h, s_q, d), lse[:, 0, :].reshape(b, h, s_q),
            k_next.reshape(b, h, s_kv, d), v_next.reshape(b, h, s_kv, d))


# ---------------------------------------------------------------------------
# backward
# ---------------------------------------------------------------------------


def _dq_kernel(q_of, kv_of, flags, qs_ref, ks_ref, kstop_ref, q_ref, k_ref, v_ref, do_ref, lse_ref, dd_ref, dq_ref, acc, *, head_dim, causal, block_q, block_k, mask_kv, window=None, v_dim=None):
    """``dq`` of one q block, accumulated over the kv blocks. ``v_dim``: a
    value head of another width than the query-key ``head_dim`` comes one
    head a block (the head-major form), so ``do`` and ``v`` are that head's
    whole block and ``dp = do·vᵀ`` contracts it as it stands."""
    qi, ki, flag = _step(q_of, kv_of, flags)  # a q-major walk, as the forward's
    q0 = qs_ref[0] + qi * block_q
    k0 = ks_ref[0] + ki * block_k
    scale = head_dim**-0.5
    fold = _scale_folds(scale)
    heads = _head_lanes(acc.shape[1], head_dim)

    @pl.when(flag(_ROW_FIRST))
    def _init():
        acc[:] = jnp.zeros_like(acc)

    def compute():
        q, k, v, do = q_ref[0], k_ref[0], v_ref[0], do_ref[0]
        if fold:
            q = q * scale
        dqs = []
        for j, own in enumerate(heads):
            s = _dot(_only(q, own), k, _NT)
            if not fold:
                s = s * scale
            if causal or mask_kv:
                s = _mask(s, q0, k0, kstop_ref[0], causal, mask_kv, q_axis=0, window=window)
            # the row statistics arrive lane-major over seq and are relaid as
            # columns on every tile: keeping the columns in scratch across the
            # kv blocks measured slower (PERF.md §6)
            p = jnp.exp(s - lse_ref[j, 0].reshape(block_q, 1))
            ds = p * (_dot(_only(do, own), v, _NT) - dd_ref[j, 0].reshape(block_q, 1))
            dqs.append(_dot(ds.astype(k.dtype), k, _NN))
        acc[:] = acc[:] + _weave(dqs, heads)

    if causal:
        pl.when(_seen(q0, k0, block_q, block_k, window))(compute)
    else:
        compute()

    @pl.when(flag(_ROW_LAST))
    def _finish():
        dq_ref[0] = (acc[:] * scale).astype(dq_ref.dtype)


def _dkv_kernel(q_of, kv_of, flags, qs_ref, ks_ref, kstop_ref, q_ref, k_ref, v_ref, do_ref, lse_ref, dd_ref, dk_ref, dv_ref, *rest, head_dim, causal, block_q, block_k, mask_kv, window=None, v_dim=None):
    """``dk`` and ``dv`` of one kv block, accumulated over the q blocks; given
    a third output and scratch (``dq_ref``, ``dq_acc``) also ``dq``, from the
    tile it already holds.

    All three accumulate TRANSPOSED, ``[lanes, block]`` float32: each gradient
    dot then yields a block-wide result from a head's ``d`` rows where
    ``[block, d]`` would leave the columns past ``d`` of the 128-wide MXU idle
    (head 64), and what is transposed is a ``[block, lanes]`` operand block,
    never the tile (kernel-only at ``[48, 8192, 64]``: 13.94 ms a call with
    ``dq += ds·k``, 12.58 with ``dqᵀ += kᵀ·dsᵀ``, 10.82 with ``dk`` and
    ``dv`` transposed too; PERF.md §6). Each is transposed back once, on its
    way out. Of a transposed operand block the heads are row ranges, aligned
    slices: every gradient dot takes ONE head's ``[d, block]`` and yields
    that head's rows of the accumulator, so a block of two heads does the
    dots two blocks of one head do. ``v_dim``: ``dv``'s rows where the value
    head is of another width than the query-key ``head_dim`` (one head a
    block).

    The walk is kv-major (:func:`_walk`), so ``dq`` sums over what the walk
    leaves and comes back to: its accumulator is the whole query length of one
    (batch, lane block), ``[q_blocks, lanes, block_q]``, resident across that
    walk. Block ``qi`` is zeroed at the first tile the walk holds of it and
    written out at the last, both outside the ``_seen`` predicate: the walk
    holds a tile of every q block, and where the offsets are traced it holds
    the rectangle, of which a q block may be skipped on every step and still
    owes its zeros. ``dq_ref`` is the whole query length too, in ``q``'s
    dtype, and goes to HBM once a (batch, lane block)."""
    if len(rest) == 2:
        (dk_acc, dv_acc), dq_ref, dq_acc = rest, None, None
    else:
        dq_ref, dk_acc, dv_acc, dq_acc = rest
    qi, ki, flag = _step(q_of, kv_of, flags)
    q0 = qs_ref[0] + qi * block_q
    k0 = ks_ref[0] + ki * block_k
    scale = head_dim**-0.5
    fold = _scale_folds(scale)
    heads = _head_lanes(dk_acc.shape[0], head_dim)

    @pl.when(flag(_ROW_FIRST))
    def _init():
        dk_acc[:] = jnp.zeros_like(dk_acc)
        dv_acc[:] = jnp.zeros_like(dv_acc)

    if dq_ref is not None:
        @pl.when(flag(_SEEN_FIRST))
        def _init_dq():
            dq_acc[qi] = jnp.zeros(dq_acc.shape[1:], dq_acc.dtype)

    def compute():
        # the TRANSPOSED tile, [block_k, block_q] (k is the left operand): the
        # row statistics are used as the lane-major row vectors they are
        # stored as, and each gradient dot contracts the tile over the axis
        # it already has in place
        q, k, v, do = q_ref[0], k_ref[0], v_ref[0], do_ref[0]
        if fold:
            q = q * scale  # scales sᵀ here and dk below: dk = scale · dsᵀ·q
        q_t, k_t, do_t = q.T, k.T, do.T  # [lanes, block]: a head is a row range
        for j, own in enumerate(heads):
            mine = slice(j * head_dim, (j + 1) * head_dim)
            mine_v = mine if v_dim is None else slice(j * v_dim, (j + 1) * v_dim)
            st = _dot(k, _only(q, own), _NT)
            if not fold:
                st = st * scale
            if causal or mask_kv:
                st = _mask(st, q0, k0, kstop_ref[0], causal, mask_kv, q_axis=1, window=window)
            pt = jnp.exp(st - lse_ref[j, :1])
            dv_acc[mine_v] = dv_acc[mine_v] + _dot(do_t[mine_v], pt.astype(do.dtype), _NT)  # dvᵀ += doᵀ·p
            # cast once, feeds both its dots
            dst = (pt * (_dot(v, _only(do, own), _NT) - dd_ref[j, :1])).astype(q.dtype)
            dk_acc[mine] = dk_acc[mine] + _dot(q_t[mine], dst, _NT)  # dkᵀ += qᵀ·ds
            if dq_ref is not None:
                dq_acc[qi, mine] = dq_acc[qi, mine] + _dot(k_t[mine], dst, _NN)  # dqᵀ[q block] += kᵀ·dsᵀ

    if causal:  # q blocks entirely before this kv block, or whose windows end before it, see none of it
        pl.when(_seen(q0, k0, block_q, block_k, window))(compute)
    else:
        compute()

    @pl.when(flag(_ROW_LAST))
    def _finish():
        dk = dk_acc[:] if fold else dk_acc[:] * scale
        dk_ref[0] = dk.T.astype(dk_ref.dtype)
        dv_ref[0] = dv_acc[:].T.astype(dv_ref.dtype)

    if dq_ref is not None:
        @pl.when(flag(_SEEN_LAST))
        def _finish_dq():
            rows = pl.ds(pl.multiple_of(qi * block_q, block_q), block_q)
            dq_ref[0, rows, :] = (dq_acc[qi] * scale).T.astype(dq_ref.dtype)


# What the fused backward may ask of VMEM: half of the 128 MiB a v5e (v5p,
# v6e) core has. Its call sets the scoped limit to its own plan, never below
# Mosaic's default and never the whole budget: a limit far above the need
# slows the kernel and what XLA runs around it (kernel-only, ms a call at
# [48, 8192, 64] / [20, 8192, 128]: 10.77 / 5.96 at 16 MiB, 10.82 / 5.96 at
# 24-32, 10.86 / 6.59 at 48-64; PERF.md §6). ``_fused_bwd_vmem`` plans above
# what Mosaic allocates (30 MiB at [8192, 64] and 1024x1024 blocks where it
# takes 10 to 12; 15 at [8192, 128] and 512x512 where it takes 10 to 12).
_VMEM_BUDGET = 64 * 2**20
_VMEM_DEFAULT = 16 * 2**20


def _fused_bwd_vmem(s_q: int, d: int, block_q: int, block_k: int, itemsize: int) -> int:
    """VMEM bytes of the backward with ``dq`` riding the dkv tile: what is
    resident for a whole (batch, head) (the float32 ``dqᵀ`` and the ``dq``
    output in the inputs' dtype, double-buffered, lanes padded to 128), the
    tile's float32 intermediates (``sᵀ``, ``pᵀ``, ``dpᵀ``, ``dsᵀ``) with
    their casts, and the double-buffered operand and output blocks with the
    two block accumulators."""
    lanes = -(-d // 128) * 128
    resident = (s_q // block_q) * d * max(block_q, 128) * 4 + 2 * s_q * lanes * itemsize
    tile = block_q * block_k * (4 * 4 + 2 * itemsize)
    blocks = 2 * (2 * block_q + 4 * block_k) * lanes * itemsize + 2 * block_k * lanes * 4
    return resident + tile + blocks


def _flash_bwd(qkv, o, lse8, do, glse, q_start, k_start, kv_stop, causal, block_q, block_k, interpret, mask_kv, head_dim, window=None, offset=None):
    """``(dq, dk, dv)`` in the inputs' dtypes and layout, ``[B, S, W]`` each;
    where q, k, v came as one ``[B, S, 3·W]`` array ``dq`` is such an array
    too, written in q's lanes alone: the cotangent-to-be, whose other lanes
    the caller fills with ``dk`` and ``dv`` (two in-place updates where a
    concatenation is three passes and a buffer more). One kernel
    (``flash_dkv``, ``dq`` riding it) wherever the resident ``dq`` of one
    (batch, lane block) fits VMEM beside the tile: a rule on the shapes in
    hand and nothing else. A longer query keeps the pair, ``flash_dq`` then
    ``flash_dkv``."""
    q, _, _, _, lanes, v_lanes, _ = _operands(qkv, head_dim)
    vmem = _fused_bwd_vmem(q.shape[1], max(lanes, v_lanes), block_q, block_k, q.dtype.itemsize)
    return _flash_bwd_calls(qkv, o, lse8, do, glse, q_start, k_start, kv_stop, causal, block_q, block_k,
                            interpret, mask_kv, head_dim, vmem if vmem <= _VMEM_BUDGET else None, window, offset)


@functools.partial(jax.jit, static_argnums=(8, 9, 10, 11, 12, 13, 14, 15, 16))
def _flash_bwd_calls(qkv, o, lse8, do, glse, q_start, k_start, kv_stop, causal, block_q, block_k, interpret, mask_kv, head_dim, vmem, window=None, offset=None):
    """:func:`_flash_bwd`'s kernels, ``dq`` riding ``flash_dkv`` in ``vmem``
    bytes of VMEM or, with ``None``, the pair. Jitted, like ``_flash_fwd``:
    a model's like layers trace and lower each kernel once."""
    q, k, v, (q_at, k_at, v_at), lanes, v_lanes, groups = _operands(qkv, head_dim)
    batch, s_q = q.shape[:2]
    s_kv = k.shape[1]
    heads = lanes // head_dim
    v_dim = v_lanes // heads
    live = _live_tiles(s_q // block_q, s_kv // block_k, block_q, block_k, causal, window, offset)
    # ds = p · (dp − delta + g_lse): delta = Σ do·o over a head's lanes, and
    # g_lse is the cotangent of the lse output. Both are per query row, so
    # their difference is taken here once, not on every score tile. The sum is
    # a matmul with the heads' 0/1 lane masks: it reads do and o where they
    # lie and lands a row a head, lane-major over seq like lse (a reshape to
    # [.., heads, head_dim] and a sum has XLA relayout the float32 products
    # first); the products of two bf16 are exact in float32 and HIGHEST keeps them so
    width = groups * v_lanes
    own = (jnp.arange(width)[:, None] // v_dim == jnp.arange(width // v_dim)).astype(jnp.float32)
    delta = jnp.einsum("bsw,wh->bhs", do.astype(jnp.float32) * o.astype(jnp.float32), own,
                       precision=lax.Precision.HIGHEST)
    dd = delta.reshape(-1, s_q) - glse  # [B·heads, s_q]
    dd = jnp.broadcast_to(dd[:, None, :], (dd.shape[0], 8, s_q))  # sublane-aligned like lse
    scalars = (_scalar(q_start), _scalar(k_start), _scalar(kv_stop))
    fused = vmem is not None
    dq_shape = jax.ShapeDtypeStruct(q.shape, q.dtype)  # q's own array: all three where they came as one
    kv_shape = jax.ShapeDtypeStruct((batch, s_kv, groups * lanes), k.dtype)
    dv_shape = jax.ShapeDtypeStruct((batch, s_kv, groups * v_lanes), v.dtype)

    static = dict(head_dim=head_dim, causal=causal, block_q=block_q, block_k=block_k, mask_kv=mask_kv, window=window)
    if v_lanes != lanes:
        static["v_dim"] = v_dim
    stat = _stat_spec(heads, block_q, groups)
    side = functools.partial(_side_spec, block_q, block_k, lanes)
    v_side = functools.partial(_side_spec, block_q, block_k, v_lanes)
    in_specs = [_smem_spec(), _smem_spec(), _smem_spec(),
                side(True, q_at), side(False, k_at), v_side(False, v_at), v_side(True), stat, stat]

    dq = None
    if not fused:
        tables = _walk(live, kv_major=False)
        dq = pl.pallas_call(
            functools.partial(_dq_kernel, **static),
            grid_spec=_walk_grid(tables, batch, groups, in_specs, side(True, q_at), [_scratch((block_q, lanes))]),
            out_shape=dq_shape,
            interpret=interpret,
            name="flash_dq",
        )(*tables, *scalars, q, k, v, do, lse8, dd)

    tables = _walk(live, kv_major=True)
    out_specs = [side(False), v_side(False)]
    out_shape = [kv_shape, dv_shape]
    scratch_shapes = [_scratch((lanes, block_k)), _scratch((v_lanes, block_k))]
    compiler_params = None
    if fused:
        out_specs.append(_vmem_spec((1, s_q, lanes), lambda b, g, *_: (b, 0, q_at + g)))
        out_shape.append(dq_shape)
        scratch_shapes.append(_scratch((s_q // block_q, lanes, block_q)))
        if not interpret:
            compiler_params = pltpu.CompilerParams(vmem_limit_bytes=max(vmem, _VMEM_DEFAULT))
    dk, dv, *riding = pl.pallas_call(
        functools.partial(_dkv_kernel, **static),
        grid_spec=_walk_grid(tables, batch, groups, in_specs, out_specs, scratch_shapes),
        out_shape=out_shape,
        compiler_params=compiler_params,
        interpret=interpret,
        name="flash_dkv",
    )(*tables, *scalars, q, k, v, do, lse8, dd)
    if fused:
        (dq,) = riding
    return dq, dk, dv


# ---------------------------------------------------------------------------
# differentiable core (out AND lse)
# ---------------------------------------------------------------------------


@functools.partial(jax.custom_vjp, nondiff_argnums=(4, 5, 6, 7, 8, 9, 10, 11))
def _flash(qkv, q_start, k_start, kv_stop, causal, block_q, block_k, interpret, mask_kv, head_dim, window=None, offset=None):
    """``qkv`` is ``(q, k, v)`` or the one array that holds all three
    (:func:`_operands`); ``(out [B, S, W], lse [B·heads, S])``. ``q_start``
    and ``k_start`` reach the rules as tracers whatever the caller held, so
    what it knew of them as it traced (:func:`_static_offset`) rides beside
    ``window``, static."""
    out, lse8 = _flash_fwd(qkv, q_start, k_start, kv_stop, causal, block_q, block_k, interpret, mask_kv, head_dim, window, offset)
    return out, lse8[:, 0, :]


def _flash_fwd_rule(qkv, q_start, k_start, kv_stop, causal, block_q, block_k, interpret, mask_kv, head_dim, window, offset):
    out, lse8 = _flash_fwd(qkv, q_start, k_start, kv_stop, causal, block_q, block_k, interpret, mask_kv, head_dim, window, offset)
    # named so that a remat policy can keep the forward kernel's two outputs and not run it again
    out, lse8 = checkpoint_name(out, FLASH_OUTPUTS), checkpoint_name(lse8, FLASH_OUTPUTS)
    return (out, lse8[:, 0, :]), (qkv, out, lse8, q_start, k_start, kv_stop)


def _flash_bwd_rule(causal, block_q, block_k, interpret, mask_kv, head_dim, window, offset, res, g):
    qkv, out, lse8, q_start, k_start, kv_stop = res
    g_out, g_lse = g
    grads = _flash_bwd(
        qkv, out, lse8, g_out, g_lse.astype(jnp.float32), q_start, k_start, kv_stop, causal,
        block_q, block_k, interpret, mask_kv, head_dim, window, offset,
    )
    if len(qkv) == 1:  # one array in, one cotangent out: dk and dv set beside dq, as k and v lay beside q
        dq, dk, dv = grads
        width = dk.shape[2]
        grads = (lax.dynamic_update_slice(lax.dynamic_update_slice(dq, dk, (0, 0, width)), dv, (0, 0, 2 * width)),)
    return grads, None, None, None


_flash.defvjp(_flash_fwd_rule, _flash_bwd_rule)


# ---------------------------------------------------------------------------
# public API
# ---------------------------------------------------------------------------


def _flat3(t):
    b, h, s, d = t.shape
    return t.reshape(b * h, s, d)


def _pad_rows(t, rows: int):
    """``t`` [B, S, ...] zero-padded along S to ``rows`` (as it is when it has them)."""
    if t.shape[1] == rows:
        return t
    return jnp.pad(t, [(0, 0), (0, rows - t.shape[1])] + [(0, 0)] * (t.ndim - 2))


def _attend(qkv, head_dim, causal, q_start, k_start, block_q, block_k, interpret, window=None):
    """The differentiable call at ANY length on ``(q, k, v)``, ``[B, S, W]``
    each, or the one array ``[B, S, 3·W]`` that holds all three
    (:func:`_operands`): ``(out [B, S, W], lse [B·heads, S])``. Lengths the
    block ladder can't tile are zero-padded up to a block multiple (≤ 25%
    waste), the padded kv tail masked off inside the kernels via the
    ``kv_stop`` SMEM scalar and the padded q rows sliced away. ``window``
    (static; ``None`` = every earlier key) keeps of each query's keys the last
    ``window``, its own among them: tiles wholly older than it are skipped as
    the causal-future ones are."""
    if window is not None and not (causal and window >= 1):
        raise ValueError(f"a window of {window} keys needs causal attention and at least the query's own key")
    s_q, s_kv = qkv[0].shape[1], qkv[-1].shape[1]
    block_q, block_k = _default_blocks(s_q, s_kv, block_q, block_k, head_dim)
    bq, pq = _pad_choice(s_q, block_q)
    bk, pk = _pad_choice(s_kv, block_k)
    if interpret is None:
        interpret = _interpret_default()
    if (pq, pk) != (s_q, s_kv):
        if len(qkv) == 1:
            qkv = tuple(jnp.split(qkv[0], 3, axis=-1))  # q and kv pad apart
        # padded q rows are ZERO (s = 0·k exactly — no overflow risk in the
        # backward's p = exp(s − lse)) and sliced off below; the slice's
        # transpose zero-pads their cotangent, so autodiff needs no help
        qkv = (_pad_rows(qkv[0], pq), _pad_rows(qkv[1], pk), _pad_rows(qkv[2], pk))
    kv_stop = k_start + s_kv  # global position the REAL kv columns end at
    out, lse = _flash(qkv, q_start, k_start, kv_stop, causal, bq, bk, interpret, pk != s_kv, head_dim, window,
                      _static_offset(q_start, k_start))
    return out[:, :s_q], lse[:, :s_q]


def flash_packs(n_head: int, head_dim: int) -> bool:
    """True where a model should hand the kernels its projections' own
    ``[batch, seq, n_head·head_dim]`` arrays: two whole heads share each
    128-lane block (head 64) and whole blocks fill the array. There the
    packed layout also stores nothing at twice its bytes and halves the grid
    steps, and the kernels themselves run 10-19% faster than on head-major
    arrays. A rule on shapes, from what the chip measured (PERF.md §6, PR
    33): a head of 128 CAN be read the same way (:func:`flash_attention_packed`
    takes it) but fills its block alone, and at ``[1, 8192, 20x128]`` the
    backward kernel ran 7.6% slower on the strided blocks than on head-major
    ones, more than the copies it saves; narrower heads (four or more to a
    block) no chip has measured. Both stay head-major, as does any sharded
    sequence."""
    return head_dim == 64 and n_head % 2 == 0


def flash_attention_packed(
    qkv,
    head_dim: int,
    causal: bool = True,
    block_q: int | None = None,
    block_k: int | None = None,
    interpret: bool | None = None,
    window: int | None = None,
):
    """Flash attention in the projections' own layout: no head-major copy of
    q, k, v on the way in, nor of the output (and of ``do``, ``dq``, ``dk``,
    ``dv`` in the backward) on the way out.

    ``qkv`` is ``(q, k, v)``, each ``[batch, seq, heads·head_dim]`` (grouped
    keys and values repeated to the query heads by the caller), or ONE array
    ``[batch, seq, 3·heads·head_dim]`` holding q, k and v side by side as a
    fused projection leaves them (its cotangent is one array too). Returns ``(out [batch, seq, heads·head_dim],
    lse [batch, heads, seq])``, both differentiable. Whole heads must fill
    128-lane blocks and whole blocks the arrays (heads of 64 in pairs, heads
    of 128); the same kernel bodies run as under :func:`flash_attention_lse`,
    a grid step taking the ``128 // head_dim`` heads of one block."""
    qkv = tuple(qkv) if isinstance(qkv, (tuple, list)) else (qkv,)
    batch, seq, width = qkv[0].shape
    n_head, rest = divmod(width, head_dim * (3 if len(qkv) == 1 else 1))
    if rest or head_dim not in (64, 128) or (n_head * head_dim) % 128:
        raise ValueError(f"{n_head} heads of {head_dim} do not fill 128-lane blocks: got {qkv[0].shape}")
    out, lse = _attend(qkv, head_dim, causal, 0, 0, block_q, block_k, interpret, window)
    return out, lse.reshape(batch, n_head, seq)


def flash_attention_lse(
    q: jax.Array,
    k: jax.Array,
    v: jax.Array,
    causal: bool = True,
    q_start: jax.Array | int = 0,
    k_start: jax.Array | int = 0,
    block_q: int | None = None,
    block_k: int | None = None,
    interpret: bool | None = None,
    window: int | None = None,
):
    """Flash attention returning ``(out, lse)``. Shapes: q/k/v
    [batch, heads, seq, head_dim] → out same-as-v, lse [batch, heads, seq_q]
    (float32 logsumexp over the kv positions this call saw). v's head may be
    of another width than q's and k's (latent attention: 192 and 128); the
    scale is then q's ``head_dim ** -0.5``.

    ``q_start``/``k_start`` are the GLOBAL positions of the first q/k row
    (traced values allowed) — the causal mask compares global positions, so
    ring/sharded callers can run any (q-block, kv-block) pair. Both outputs
    are differentiable. ANY length runs through the kernel: lengths the
    block ladder can't tile exactly are zero-padded up to a block multiple
    (≤ 25% waste), with the padded kv tail masked off inside the kernels via
    a ``kv_stop`` SMEM scalar and padded q rows sliced away — cp/ring shards
    make odd residual lengths the common case, so the kernel rather than an
    XLA fallback must own them. With ``window`` (causal only) query ``i`` sees
    key ``j`` iff ``0 <= i - j < window``, by the same global positions.
    """
    b, h, s_q, d = q.shape
    out, lse = _attend((_flat3(q), _flat3(k), _flat3(v)), d, causal, q_start, k_start, block_q, block_k, interpret, window)
    return out.reshape(b, h, s_q, v.shape[-1]), lse.reshape(b, h, s_q)


def flash_attention(
    q: jax.Array,
    k: jax.Array,
    v: jax.Array,
    causal: bool = True,
    block_q: int | None = None,
    block_k: int | None = None,
    interpret: bool | None = None,
    window: int | None = None,
) -> jax.Array:
    """Flash attention. Shapes: [batch, heads, seq, head_dim] (v's own
    width may differ: :func:`flash_attention_lse`).

    Numerically equivalent to ``dsml_tpu.ops.attention.attention`` (tests
    assert it) but never materializes the [seq, seq] score matrix — peak
    memory is O(block_q · block_k) per core instead of O(seq²) per head.
    Sequences that don't tile into blocks run through the kernel's padded
    path (zero-padded to a block multiple, kv tail masked via ``kv_stop``)
    rather than falling back to the O(s²) XLA graph.
    """
    if q.ndim != 4:
        raise ValueError(f"expected [batch, heads, seq, head_dim], got {q.shape}")
    out, _ = flash_attention_lse(q, k, v, causal, 0, 0, block_q, block_k, interpret, window)
    return out


def flash_block_grads(
    q: jax.Array,
    k: jax.Array,
    v: jax.Array,
    out: jax.Array,
    lse: jax.Array,
    do: jax.Array,
    g_lse: jax.Array | None = None,
    causal: bool = True,
    q_start: jax.Array | int = 0,
    k_start: jax.Array | int = 0,
    block_q: int | None = None,
    block_k: int | None = None,
    interpret: bool | None = None,
    window: int | None = None,
) -> tuple[jax.Array, jax.Array, jax.Array]:
    """Raw flash backward of ONE (q-shard, kv-block) pair given MERGED
    statistics — the primitive ring attention's own backward re-streams KV
    through (``ops.ring_attention``).

    ``out``/``lse`` are the TOTAL attention output and logsumexp over EVERY
    kv block (the ring's merged accumulators), so the kernels' recomputed
    ``p = exp(s − lse)`` are the globally-correct softmax rows and the
    returned ``(dq, dk, dv)`` are this block pair's exact contributions to
    the full-attention gradients — summing them over all kv blocks
    reproduces the single-call flash backward. No custom-vjp wrapper: the
    caller owns the accumulation (dq locally, dk/dv around the reverse
    ring). Handles untileable lengths through the same padded path as
    :func:`flash_attention_lse`.

    Shapes: q/out/do [b, h, s_q, hd], k/v [b, h, s_kv, hd], lse/g_lse
    [b, h, s_q] (``g_lse``: cotangent of the merged lse output, None = 0).
    Returns float32 (dq, dk, dv) with the unpadded input shapes.
    """
    b, h, s_q, d = q.shape
    s_kv = k.shape[2]
    block_q, block_k = _default_blocks(s_q, s_kv, block_q, block_k, d)
    bq, pq = _pad_choice(s_q, block_q)
    bk, pk = _pad_choice(s_kv, block_k)
    if interpret is None:
        interpret = _interpret_default()
    # padded q rows: q = 0 ⇒ s = 0 exactly and do = 0 ⇒ ds = 0, so a
    # zero-padded lse (p = exp(0 − 0) = 1) contributes nothing anywhere
    # a real gradient lands; their dq rows are sliced off below
    qf, of, dof = (_pad_rows(_flat3(t), pq) for t in (q, out, do))
    kf, vf = (_pad_rows(_flat3(t), pk) for t in (k, v))
    lse_f = _pad_rows(lse.reshape(b * h, s_q).astype(jnp.float32), pq)
    glse_f = (
        jnp.zeros_like(lse_f) if g_lse is None
        else _pad_rows(g_lse.reshape(b * h, s_q).astype(jnp.float32), pq)
    )
    lse8 = jnp.broadcast_to(lse_f[:, None, :], (b * h, 8, pq))
    dq, dk, dv = _flash_bwd(
        (qf, kf, vf), of, lse8, dof, glse_f, q_start, k_start, k_start + s_kv,
        causal, bq, bk, interpret, pk != s_kv, d, window, _static_offset(q_start, k_start),
    )
    dq = dq[:, :s_q].astype(jnp.float32).reshape(b, h, s_q, d)
    dk = dk[:, :s_kv].astype(jnp.float32).reshape(b, h, s_kv, d)
    dv = dv[:, :s_kv].astype(jnp.float32).reshape(b, h, s_kv, v.shape[-1])
    return dq, dk, dv


def ring_flash_attention(
    q: jax.Array,
    k: jax.Array,
    v: jax.Array,
    axis_name: str,
    causal: bool = True,
    block_q: int | None = None,
    block_k: int | None = None,
) -> jax.Array:
    """Ring attention with a flash kernel per hop (call under ``shard_map``).

    Each rank holds a sequence shard [batch, heads, seq/n, head_dim]; K/V
    rotate ``n−1`` hops around the ring. Every hop is ONE
    :func:`flash_attention_lse` call whose global offsets make the causal
    mask exact for that (q-shard, kv-shard) pair; the per-hop (out, lse)
    pairs then merge with logsumexp weights:

        lse_tot = logsumexp_i(lse_i);  out = Σᵢ exp(lse_i − lse_tot)·out_i

    which reconstructs exact full attention (hops that are entirely masked
    contribute lse ≈ −∞ → weight 0). Scores never exceed
    O(block_q·block_k) on any chip. Gradients flow through the kernels'
    custom VJP (including the lse term). Falls back to the XLA ring
    (``ops.attention.ring_attention``) when the shard doesn't tile.
    """
    n = lax.axis_size(axis_name)
    if n == 1:
        return flash_attention(q, k, v, causal, block_q, block_k)
    seq_block = q.shape[-2]
    # per-SHARD kv length decides the block defaults (each hop's flash call
    # sees one shard of K/V)
    block_q, block_k = _default_blocks(seq_block, seq_block, block_q, block_k,
                                       q.shape[-1])
    if _pick_block(seq_block, block_q) is None or _pick_block(seq_block, block_k) is None:
        from dsml_tpu.ops.attention import ring_attention

        return ring_attention(q, k, v, axis_name, causal)
    rank = lax.axis_index(axis_name)

    # Online merge (same shape as ops.attention.ring_attention's fold): only
    # ONE running (out, lse) pair is alive — stacking all n hops would hold
    # the full sequence in f32 on every chip, defeating the point of SP.
    run_out = None
    run_lse = None
    kv = (k, v)
    for hop in range(n):
        k_off = (rank - hop) % n  # whose K/V block is resident this hop
        o, l = flash_attention_lse(
            q, kv[0], kv[1], causal,
            q_start=rank * seq_block, k_start=k_off * seq_block,
            block_q=block_q, block_k=block_k,
        )
        o = o.astype(jnp.float32)
        if run_out is None:
            run_out, run_lse = o, l
        else:
            new_lse = jnp.logaddexp(run_lse, l)
            w_prev = jnp.exp(run_lse - new_lse)[..., None]
            w_new = jnp.exp(l - new_lse)[..., None]
            run_out = w_prev * run_out + w_new * o
            run_lse = new_lse
        if hop != n - 1:
            kv = ring_pass(kv, axis_name, +1)

    return run_out.astype(q.dtype)
