"""Block-scaled quantization + compressed collective schedules.

The reference's memory/communication literature (ActNN/GACT activation
compression, SURVEY.md §2.4 folder 7; gradient-compression systems in folder
6) realized TPU-first:

- :func:`quantize_int8` / :func:`dequantize_int8` — blockwise absmax-scaled
  int8 with *stochastic* rounding (unbiased: E[q·scale] = x), so compressed
  gradients don't bias SGD. On TPU the quantizer is a Pallas kernel using
  the on-core PRNG (``pltpu.prng_random_bits``) per the TPU kernel playbook;
  elsewhere an XLA path with ``jax.random`` does the same math.
- :func:`compressed_all_reduce` — the v1 compressed sync: each rank
  quantizes its contribution, int8 blocks + f32 scales all-gather, every
  rank dequantizes and reduces locally. O(n) wire bytes per rank — kept as
  the latency-optimal small-payload shape and the A/B baseline.
- :func:`quantized_ring_all_reduce` — the v2 schedule (EQuARX-style,
  PAPERS.md): block-scaled int8 **or int4** quantization *inside* the
  2(n−1)-step ring. Scatter-reduce hops quantize the outgoing chunk,
  dequantize-accumulate at the receiver, re-quantize for the next hop; the
  all-gather half circulates each owner's quantized representation
  UNCHANGED (one quantization per reduced segment — no per-hop error
  compounding, and every rank dequantizes the same bytes, so the
  all-reduce postcondition holds bit-exactly across ranks). Bandwidth-
  optimal volume at 8/4 bits per element instead of v1's
  gather-everything; ``bidirectional=True`` is the full-duplex ring2.
- :func:`quantized_flat_reduce_scatter` — the same quantized scatter-reduce
  half standalone, with ``flat_reduce_scatter``'s rank-i-gets-segment-i
  layout: the ZeRO-2 bucket sync primitive.
- :func:`quantize_roundtrip` / error feedback — deterministic-rounding
  compression round trip; ``parallel.bucketing`` folds the residual
  ``x − roundtrip(x)`` into the next step's gradients so repeated
  quantized syncs don't drift (EF-SGD).
- :func:`compressed_checkpoint` — ActNN-style compressed rematerialization:
  ``jax.checkpoint`` whose stash is the int8-quantized input activation, so
  the per-layer residual footprint drops ~4× below even plain remat.

``dsml_tpu.parallel.dp`` exposes the gradient paths as ``algorithm="q8"``
(v1) and ``"q8_ring" / "q8_ring2" / "q4_ring" / "q4_ring2" / "quant"``
(v2; ``"quant"`` resolves per dtype from ``DSML_QUANT`` — see
:func:`quant_algorithm_for`). ``GPT2Config.remat = "int8"`` selects the
activation path; the GPT-2 int4 KV cache shares :func:`pack_int4` /
:func:`unpack_int4`.
"""

from __future__ import annotations

import dataclasses
import functools
import os

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl

try:  # pltpu is importable on CPU builds too; guard anyway
    from jax.experimental.pallas import tpu as pltpu
except ImportError:  # pragma: no cover
    pltpu = None

__all__ = [
    "QuantizedTensor",
    "QuantScheme",
    "QuantizedWeight",
    "get_scheme",
    "default_qblock",
    "quant_algorithm_for",
    "weight_quant_mode",
    "quantize_weight_blocks",
    "dequantize_weight_blocks",
    "quantized_matmul",
    "pack_int4",
    "unpack_int4",
    "quantize_kv_rows",
    "dequantize_kv_rows",
    "kv_row_bytes",
    "quantize_int8",
    "dequantize_int8",
    "quantize_roundtrip",
    "quantized_ring_all_reduce",
    "quantized_flat_reduce_scatter",
    "quantized_ring_wire_bytes",
    "compressed_all_reduce",
    "compressed_checkpoint",
]

_BLOCK = 512  # elements per scale block


@jax.tree_util.register_pytree_node_class
@dataclasses.dataclass
class QuantizedTensor:
    """Blockwise int8 tensor. A pytree whose array children are (values,
    scales) and whose size/shape/dtype ride as STATIC aux data — so it can
    cross jit/custom_vjp boundaries (e.g. as a ``compressed_checkpoint``
    residual) without the metadata leaking into the trace."""

    values: jax.Array  # int8, [blocks, _BLOCK]
    scales: jax.Array  # f32, [blocks, 1]
    size: int  # original element count (static)
    shape: tuple  # original shape (static)
    dtype: object  # original dtype (static)

    def tree_flatten(self):
        return (self.values, self.scales), (self.size, self.shape, self.dtype)

    @classmethod
    def tree_unflatten(cls, aux, children):
        return cls(*children, *aux)


def _blocked(x: jax.Array):
    flat = x.astype(jnp.float32).reshape(-1)
    size = flat.shape[0]
    padded = -(-size // _BLOCK) * _BLOCK
    if padded != size:
        flat = jnp.pad(flat, (0, padded - size))
    return flat.reshape(-1, _BLOCK), size


def _quantize_xla(blocks: jax.Array, key: jax.Array):
    scales = jnp.maximum(jnp.max(jnp.abs(blocks), axis=-1, keepdims=True) / 127.0, 1e-12)
    y = blocks / scales
    # stochastic rounding: floor(y + u), u ~ U[0,1) — unbiased for any y
    u = jax.random.uniform(key, blocks.shape, jnp.float32)
    q = jnp.clip(jnp.floor(y + u), -127, 127).astype(jnp.int8)
    return q, scales


def _quantize_pallas(blocks: jax.Array, seed: jax.Array):
    """TPU path: one Pallas program per 8-row block strip, on-core PRNG."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    rows = blocks.shape[0]
    strip = 8  # f32 sublane tile
    padded_rows = -(-rows // strip) * strip
    if padded_rows != rows:
        blocks = jnp.pad(blocks, ((0, padded_rows - rows), (0, 0)))

    def kernel(seed_ref, x_ref, q_ref, s_ref):
        pltpu.prng_seed(seed_ref[0] + pl.program_id(0))
        x = x_ref[:]
        scale = jnp.maximum(jnp.max(jnp.abs(x), axis=-1, keepdims=True) / 127.0, 1e-12)
        y = x / scale
        bits = pltpu.bitcast(pltpu.prng_random_bits(y.shape), jnp.uint32)
        # u in [0,1) from the top 24 bits; floor(y+u) = unbiased round.
        # (bitcast the shifted bits to int32 — values < 2^24 so sign-safe;
        # Mosaic has no direct uint32→f32 cast)
        u = pltpu.bitcast(bits >> 8, jnp.int32).astype(jnp.float32) * (1.0 / (1 << 24))
        q_ref[:] = jnp.clip(jnp.floor(y + u), -127, 127).astype(jnp.int8)
        s_ref[:] = jnp.broadcast_to(scale, s_ref.shape)

    # no interpret fallback: the Pallas interpreter has no rules for the TPU
    # PRNG primitives — callers route non-TPU backends to the XLA path
    q, s = pl.pallas_call(
        kernel,
        grid=(padded_rows // strip,),
        in_specs=[
            pl.BlockSpec(memory_space=pltpu.SMEM),
            pl.BlockSpec((strip, _BLOCK), lambda i: (i, 0), memory_space=pltpu.VMEM),
        ],
        out_specs=[
            pl.BlockSpec((strip, _BLOCK), lambda i: (i, 0), memory_space=pltpu.VMEM),
            pl.BlockSpec((strip, 128), lambda i: (i, 0), memory_space=pltpu.VMEM),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((padded_rows, _BLOCK), jnp.int8),
            jax.ShapeDtypeStruct((padded_rows, 128), jnp.float32),
        ],
    )(jnp.atleast_1d(seed).astype(jnp.int32), blocks)
    return q[:rows], s[:rows, :1]


def quantize_int8(x: jax.Array, seed: jax.Array | int = 0, use_pallas: bool | None = None) -> QuantizedTensor:
    """Blockwise (512-element) absmax int8 quantization, stochastically
    rounded. ``seed`` varies the rounding noise (pass the training step)."""
    blocks, size = _blocked(x)
    if use_pallas is None:
        use_pallas = jax.default_backend() == "tpu"
    if use_pallas:
        q, s = _quantize_pallas(blocks, jnp.asarray(seed, jnp.int32))
    else:
        key = jax.random.PRNGKey(jnp.asarray(seed, jnp.int32))
        q, s = _quantize_xla(blocks, key)
    return QuantizedTensor(q, s, size, tuple(x.shape), x.dtype)


def dequantize_int8(qt: QuantizedTensor) -> jax.Array:
    flat = (qt.values.astype(jnp.float32) * qt.scales).reshape(-1)[: qt.size]
    return flat.reshape(qt.shape).astype(qt.dtype)


# ---------------------------------------------------------------------------
# Quant schemes (int8 / int4), env knobs, shared nibble packing
# ---------------------------------------------------------------------------

_SCHEME_TABLE = {"int8": (8, 127), "int4": (4, 7)}


@dataclasses.dataclass(frozen=True)
class QuantScheme:
    """Static description of one block-scaled integer format: ``bits`` on
    the wire per element, symmetric range ``[-qmax, qmax]``, one f32 scale
    per ``block`` elements. int4 packs two values per byte
    (:func:`pack_int4`), so its block must be even."""

    name: str  # "int8" | "int4"
    bits: int
    qmax: int
    block: int

    @property
    def wire_bytes_per_block(self) -> int:
        """Bytes one quantized block occupies on the wire: packed values
        plus its f32 scale."""
        return self.block * self.bits // 8 + 4


def default_qblock() -> int:
    """Elements per scale block: 512 (the v1 ``quantize_int8`` block, kept —
    docs/TUNING.md), overridable via ``DSML_QBLOCK``. Malformed, non-positive
    or odd values fall back (odd blocks would split an int4 nibble pair)."""
    try:
        b = int(os.environ.get("DSML_QBLOCK", _BLOCK))
    except ValueError:
        return _BLOCK
    return b if b > 0 and b % 2 == 0 else _BLOCK


def get_scheme(name: str, block: int | None = None) -> QuantScheme:
    """Resolve ``"int8"``/``"int4"`` (or a :class:`QuantScheme`, returned
    as-is) to a scheme with ``block`` elements per scale (default:
    :func:`default_qblock`)."""
    if isinstance(name, QuantScheme):
        return name
    if name not in _SCHEME_TABLE:
        raise ValueError(
            f"unknown quant scheme {name!r}; choose from {sorted(_SCHEME_TABLE)}"
        )
    bits, qmax = _SCHEME_TABLE[name]
    block = default_qblock() if block is None else int(block)
    if block <= 0 or block % 2:
        raise ValueError(f"quant block must be positive and even, got {block}")
    return QuantScheme(name, bits, qmax, block)


_ALGO_FOR_SCHEME = {
    ("int8", "ring"): "q8_ring",
    ("int8", "ring2"): "q8_ring2",
    ("int4", "ring"): "q4_ring",
    ("int4", "ring2"): "q4_ring2",
}
# the sweep-chosen default (docs/TUNING.md § Quantized collectives): int8
# keeps the loss trajectory within tolerance without error feedback being
# mandatory, ring2 rides full-duplex ICI at half the per-direction payload
_DEFAULT_QUANT = "int8:ring2"


def quant_algorithm_for(dtype) -> str:
    """The ``DSML_QUANT`` env knob: which quantized sync a given gradient
    dtype should use when the caller says ``algorithm="quant"``.

    Grammar: ``SCHEME[:ALGO]`` applied to every float dtype, or a per-dtype
    comma list ``float32=int8:ring2,bfloat16=int4:ring2`` (unlisted dtypes
    fall back to the ``default=`` entry, else the built-in default).
    SCHEME ∈ {int8, int4, none}; ALGO ∈ {ring, ring2} (default ring2).
    ``none`` means sync that dtype unquantized (the fp32 ring). Malformed
    values fall back to the default rather than failing a training step.
    """
    key = str(jnp.dtype(dtype)) if not isinstance(dtype, str) else dtype
    raw = os.environ.get("DSML_QUANT", "").strip() or _DEFAULT_QUANT
    chosen = None
    if "=" in raw:
        table = {}
        for item in raw.split(","):
            if "=" in item:
                k, _, v = item.partition("=")
                table[k.strip()] = v.strip()
        chosen = table.get(key, table.get("default"))
    else:
        chosen = raw
    if not chosen:
        chosen = _DEFAULT_QUANT
    scheme, _, algo = chosen.partition(":")
    scheme, algo = scheme.strip(), (algo.strip() or "ring2")
    if scheme == "none":
        return algo if algo in ("ring", "ring2") else "ring"
    if scheme not in _SCHEME_TABLE or algo not in ("ring", "ring2"):
        scheme, _, algo = _DEFAULT_QUANT.partition(":")
    return _ALGO_FOR_SCHEME[(scheme, algo)]


def pack_int4(q: jax.Array) -> jax.Array:
    """Pack int values in ``[-7, 7]`` two-per-byte along the last axis
    (must be even): offset to ``q+8`` ∈ [1, 15], contiguous HALVES — the
    first half of the axis rides the high nibbles, the second half the low
    — so the unpack is a concat of two shift/mask ops, never an
    interleaving gather. This is THE nibble layout: the GPT-2 int4 KV
    cache and the int4 collective wire format both use it (bit-identity
    to the original KV-cache packing pinned in tests)."""
    if q.shape[-1] % 2:
        raise ValueError(f"pack_int4 needs an even last axis, got {q.shape}")
    q = q.astype(jnp.int32) + 8
    half = q.shape[-1] // 2
    return (q[..., :half] << 4 | q[..., half:]).astype(jnp.uint8)


def unpack_int4(p: jax.Array) -> jax.Array:
    """Inverse of :func:`pack_int4`: ``[..., k]`` packed bytes →
    ``[..., 2k]`` int8 in ``[-7, 7]`` (channel halves contiguous)."""
    hi = (p >> 4).astype(jnp.int8) - 8
    lo = (p & 0xF).astype(jnp.int8) - 8
    return jnp.concatenate([hi, lo], axis=-1)


def quantize_kv_rows(x: jax.Array, mode: str = "int4"):
    """Symmetric absmax quantization of KV rows ``[..., rows, hd]`` →
    ``(values, f32 scales [..., rows, 1])`` — one scale PER ROW (a row is
    one token position's K or V vector), so a row quantizes independently
    of every other row in its page: cache/page writes never touch other
    positions' scales, and the bytes are identical whether the rows live
    in a dense ``[b, h, max_seq, hd]`` cache or a paged ``[pages, h,
    page_size, hd]`` pool (the page-table gather parity the paged KV
    cache rests on). ``mode="int8"`` stores int8 values directly;
    ``"int4"`` packs two offset nibbles per byte (:func:`pack_int4` —
    even ``hd`` required). THE one KV codec: the GPT-2/Llama dense
    quantized cache and the serving page pool both quantize through
    here (bit-identity pinned in tests)."""
    if mode not in ("int8", "int4"):
        raise ValueError(f"unknown KV quant mode {mode!r}; choose 'int8' or 'int4'")
    x32 = x.astype(jnp.float32)
    a = jnp.max(jnp.abs(x32), axis=-1, keepdims=True)
    if mode == "int4":
        if x.shape[-1] % 2:
            raise ValueError(
                f"int4 KV rows need an even trailing dim, got {x.shape}"
            )
        s = jnp.where(a > 0, a / 7.0, 1.0)
        return pack_int4(jnp.clip(jnp.round(x32 / s), -7, 7)), s
    s = jnp.where(a > 0, a / 127.0, 1.0)
    return jnp.round(x32 / s).astype(jnp.int8), s


def dequantize_kv_rows(values: jax.Array, scales: jax.Array,
                       mode: str = "int4") -> jax.Array:
    """Inverse of :func:`quantize_kv_rows` → f32 rows ``[..., rows, hd]``.
    The serving hot path never calls this (attention feeds the int8
    values into its dots and folds the scales after — see
    ``GPT2._cache_attn_inputs``); it exists for codec round-trip tests
    and host-side tooling that wants the dequantized rows."""
    if mode not in ("int8", "int4"):
        raise ValueError(f"unknown KV quant mode {mode!r}; choose 'int8' or 'int4'")
    q = unpack_int4(values) if mode == "int4" else values
    return q.astype(jnp.float32) * scales


def kv_row_bytes(head_dim: int, mode: str | None) -> int:
    """HBM bytes one K or V row (one position, one head) costs under
    ``mode`` (None = f32), scale included — the analytic accounting the
    paged-KV capacity tests and docs/TUNING.md sizing rules use."""
    if mode is None:
        return 4 * head_dim
    if mode == "int8":
        return head_dim + 4  # int8 values + one f32 scale
    if mode == "int4":
        if head_dim % 2:
            raise ValueError(f"int4 KV rows need an even head_dim, got {head_dim}")
        return head_dim // 2 + 4  # two nibbles per byte + one f32 scale
    raise ValueError(f"unknown KV quant mode {mode!r}")


# ---------------------------------------------------------------------------
# Blocked weight quantization + the dequant-fused decode matmul
# ---------------------------------------------------------------------------
# Decode is weight-HBM-bandwidth-bound: the matmul's cost is reading the
# weight, not the FLOPs. The w8a16 per-channel path (models.common.
# quantize_weights_int8) already halves/quarters the bytes and lets XLA fuse
# the convert into the read; this section is the KERNEL form of the same
# idea — weights live in HBM as int8 or nibble-packed int4 with one f32
# scale per (k-block, output channel), and a Pallas matmul unpacks the
# integers INSIDE VMEM and folds the scale AFTER each per-block dot
# (sum_k x·q is integer-exact in f32; one multiply per block per channel
# recovers the dequantized partial sum). The full-width weight never exists
# outside a VMEM tile, at 4x (int8) / 8x (int4) HBM compression vs f32.


@jax.tree_util.register_pytree_node_class
@dataclasses.dataclass
class QuantizedWeight:
    """A block-quantized matmul weight contracting on its FIRST axis.

    ``qw`` holds the integer codes over the PADDED 2-D form ``[d_p, n_p]``
    (int8) or ``[d_p // 2, n_p]`` (int4: each k-block's two row-halves
    packed hi/lo per byte — :func:`pack_int4`'s halves convention applied
    along the contraction axis, so the in-kernel unpack is two shift/mask
    ops and a concat, never a gather). ``qs`` is one f32 scale per
    (k-block, output channel): ``[d_p // block, n_p]``. The ORIGINAL shape
    and dtype ride as static aux so the tensor crosses jit boundaries and
    ``jax.tree`` maps like any param leaf."""

    qw: jax.Array  # int8 [d_p, n_p] | uint8 [d_p//2, n_p]
    qs: jax.Array  # f32 [d_p // block, n_p]
    scheme: str  # "int8" | "int4" (static)
    block: int  # k elements per scale block (static)
    shape: tuple  # original weight shape, first axis = contraction (static)
    dtype: object  # original dtype (static)

    def tree_flatten(self):
        return (self.qw, self.qs), (self.scheme, self.block, self.shape, self.dtype)

    @classmethod
    def tree_unflatten(cls, aux, children):
        return cls(*children, *aux)

    @property
    def hbm_bytes(self) -> int:
        """Resident compressed bytes: packed codes + scales."""
        return int(self.qw.nbytes + self.qs.nbytes)

    @property
    def dense_bytes(self) -> int:
        """What the SAME weight would cost dense at its original dtype —
        the compression-ratio denominator."""
        import numpy as np

        n = 1
        for s in self.shape:
            n *= int(s)
        return n * jnp.dtype(self.dtype).itemsize if n else 0


def weight_quant_mode() -> str | None:
    """The serving weight-codec knob: ``DSML_WEIGHT_QUANT`` ∈ {unset/"0"/
    "off"/"none" (full-precision weights), "int8"/"8", "int4"/"4"}.
    Malformed values degrade to off — a bad env var must never refuse to
    serve. Read once per batcher construction (docs/TUNING.md § Kernel
    fusion)."""
    raw = os.environ.get("DSML_WEIGHT_QUANT", "").strip().lower()
    if raw in ("int8", "8"):
        return "int8"
    if raw in ("int4", "4"):
        return "int4"
    return None


def _weight_pads(d: int, n: int, block: int) -> tuple[int, int, int]:
    """(kb, d_p, n_p): the effective k-block and padded operand dims. The
    contraction axis pads only to the 8-row sublane (≤ 7 wasted rows) and
    ``kb`` is the LARGEST multiple-of-8 divisor of that padded length not
    exceeding the scheme block — never a round-up to a full block, which
    would pad a 768-deep projection to 1024 and eat a third of the
    compression the codec exists to buy. Real model dims (768, 3072,
    4096 …) land on kb ∈ {384, 512} with zero waste; channels pad to the
    128-lane width (zero columns, scale 1 — exact zeros)."""
    d_p = -(-d // 8) * 8
    cap = min(int(block), d_p)
    kb = max(k for k in range(8, cap + 1, 8) if d_p % k == 0)
    n_p = -(-n // 128) * 128
    return kb, d_p, n_p


def quantize_weight_blocks(w: jax.Array, scheme="int8",
                           block: int | None = None) -> QuantizedWeight:
    """Block-quantize a matmul weight for the dequant-fused kernel:
    deterministic round-to-nearest, symmetric absmax per (k-block, output
    channel). ``w``'s FIRST axis is the contraction axis; trailing axes
    flatten into output channels (GPT-2's fused ``wqkv [d, 3, d]`` keeps a
    scale per (block, slot, channel) exactly like the per-channel path).
    Zero blocks take scale 1.0 so padding quantizes to exact zeros — pad
    rows contribute nothing to any dot."""
    sch = get_scheme(scheme, block)
    if w.ndim < 2:
        raise ValueError(f"weight quant needs a matmul weight, got shape {w.shape}")
    d = int(w.shape[0])
    orig_shape = tuple(int(s) for s in w.shape)
    wf = w.astype(jnp.float32).reshape(d, -1)
    n = int(wf.shape[1])
    kb, d_p, n_p = _weight_pads(d, n, sch.block)
    if (d_p, n_p) != (d, n):
        wf = jnp.pad(wf, ((0, d_p - d), (0, n_p - n)))
    nb = d_p // kb
    blocks = wf.reshape(nb, kb, n_p)
    a = jnp.max(jnp.abs(blocks), axis=1)  # [nb, n_p]
    qs = jnp.where(a > 0, a / sch.qmax, 1.0).astype(jnp.float32)
    q = jnp.clip(jnp.round(blocks / qs[:, None, :]), -sch.qmax, sch.qmax)
    if sch.bits == 4:
        half = kb // 2
        hi = q[:, :half].astype(jnp.int32) + 8
        lo = q[:, half:].astype(jnp.int32) + 8
        qw = (hi << 4 | lo).astype(jnp.uint8).reshape(d_p // 2, n_p)
    else:
        qw = q.astype(jnp.int8).reshape(d_p, n_p)
    return QuantizedWeight(qw, qs, sch.name, kb, orig_shape, w.dtype)


def _unpack_weight_block(raw: jax.Array, int4: bool) -> jax.Array:
    """One VMEM weight tile → f32 codes: int4 tiles hold a k-block's two
    row-halves per byte (hi nibbles = rows [0, kb/2), lo = [kb/2, kb)) —
    THE same float sequence the reference dequantization commits to, so
    kernel and oracle agree exactly on int-representable values."""
    if int4:
        hi = (raw >> 4).astype(jnp.int8) - 8
        lo = (raw & 0xF).astype(jnp.int8) - 8
        return jnp.concatenate([hi, lo], axis=0).astype(jnp.float32)
    return raw.astype(jnp.float32)


def dequantize_weight_blocks(qwt: QuantizedWeight) -> jax.Array:
    """Reference inverse → f32 at the ORIGINAL shape. The serving hot path
    never calls this on-device (that would materialize the full-width
    weight in HBM — exactly what the fused kernel exists to avoid); it is
    the parity oracle."""
    nb, n_p = qwt.qs.shape
    kb = qwt.block
    if qwt.scheme == "int4":
        raw = qwt.qw.reshape(nb, kb // 2, n_p)
        hi = (raw >> 4).astype(jnp.int8) - 8
        lo = (raw & 0xF).astype(jnp.int8) - 8
        q = jnp.concatenate([hi, lo], axis=1).astype(jnp.float32)
    else:
        q = qwt.qw.reshape(nb, kb, n_p).astype(jnp.float32)
    full = (q * qwt.qs[:, None, :]).reshape(nb * kb, n_p)
    d = qwt.shape[0]
    n = 1
    for s in qwt.shape[1:]:
        n *= int(s)
    return full[:d, :n].reshape(qwt.shape)


def _qmm_kernel(x_ref, w_ref, s_ref, o_ref, acc, *, nb, int4):
    """Grid (m tiles, n tiles, k blocks), k innermost: each step unpacks
    one weight tile in VMEM, takes the integer-code dot, and folds the
    per-(block, channel) scale AFTER the dot — one multiply per partial
    sum instead of one per weight element."""
    ki = pl.program_id(2)

    @pl.when(ki == 0)
    def _init():
        acc[:] = jnp.zeros_like(acc)

    w = _unpack_weight_block(w_ref[:], int4)
    part = jax.lax.dot_general(
        x_ref[:].astype(jnp.float32), w,
        (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32,
    )
    acc[:] = acc[:] + part * s_ref[:]

    @pl.when(ki == nb - 1)
    def _flush():
        o_ref[:] = acc[:]


def quantized_matmul_vmem_bytes(bm: int, kb: int, bn: int, int4: bool) -> int:
    """Analytic VMEM working set of one fused-matmul grid step, at the
    Mosaic-padded footprint, with Pallas' automatic double buffering on
    every streamed operand (×2) — the guard the kernel route checks
    before committing to a block shape (docs/TUNING.md § Kernel fusion)."""
    from dsml_tpu.ops.vmem_budget import vmem_block_bytes

    x_b = vmem_block_bytes((bm, kb), 4)
    w_b = vmem_block_bytes((kb // 2, bn) if int4 else (kb, bn), 1)
    s_b = vmem_block_bytes((1, bn), 4)
    o_b = vmem_block_bytes((bm, bn), 4)
    acc = vmem_block_bytes((bm, bn), 4)
    return 2 * (x_b + w_b + s_b + o_b) + acc


def quantized_matmul(x: jax.Array, qwt: QuantizedWeight,
                     interpret: bool | None = None) -> jax.Array:
    """``x [m, d] @ dequant(qwt) → f32 [m, n]`` with the dequantization
    fused into the matmul: integer codes stream HBM→VMEM at their packed
    width, unpack + scale-fold happen per VMEM tile. Off-TPU the kernel
    runs under the Pallas interpreter (same float sequence — the CPU
    parity pin); a block shape that would blow the VMEM budget raises a
    ``ValueError`` (shrink ``DSML_QBLOCK`` or raise
    ``DSML_VMEM_LIMIT_MB``)."""
    from dsml_tpu.ops.vmem_budget import require_vmem

    m, d = x.shape
    nb, n_p = qwt.qs.shape
    kb = qwt.block
    d_p = nb * kb
    int4 = qwt.scheme == "int4"
    if interpret is None:
        interpret = jax.default_backend() != "tpu"

    bm = -(-m // 8) * 8
    if bm > 128:
        bm = 128
    m_p = -(-m // bm) * bm
    bn = 128
    require_vmem(
        quantized_matmul_vmem_bytes(bm, kb, bn, int4),
        f"dequant-fused matmul block {bm}x{kb}x{bn} ({qwt.scheme})",
    )
    xf = x.astype(jnp.float32)
    if (m_p, d_p) != (m, d):
        xf = jnp.pad(xf, ((0, m_p - m), (0, d_p - d)))
    grid = (m_p // bm, n_p // bn, nb)
    kernel = functools.partial(_qmm_kernel, nb=nb, int4=int4)
    out = pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((bm, kb), lambda mi, ni, ki: (mi, ki),
                         memory_space=pltpu.VMEM),
            pl.BlockSpec((kb // 2 if int4 else kb, bn),
                         lambda mi, ni, ki: (ki, ni),
                         memory_space=pltpu.VMEM),
            pl.BlockSpec((1, bn), lambda mi, ni, ki: (ki, ni),
                         memory_space=pltpu.VMEM),
        ],
        out_specs=pl.BlockSpec((bm, bn), lambda mi, ni, ki: (mi, ni),
                               memory_space=pltpu.VMEM),
        out_shape=jax.ShapeDtypeStruct((m_p, n_p), jnp.float32),
        scratch_shapes=[pltpu.VMEM((bm, bn), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary"),
        ) if not interpret else None,
        interpret=interpret,
    )(xf, qwt.qw, qwt.qs)
    n = 1
    for s in qwt.shape[1:]:
        n *= int(s)
    return out[:m, :n]


def _block_quant(blocks: jax.Array, scheme: QuantScheme, seed=None):
    """Blockwise absmax quantization of ``[rows, block]`` f32. ``seed=None``
    = deterministic round-to-nearest (the error-feedback pairing: the
    residual exactly accounts the committed rounding); a seed = stochastic
    ``floor(y + u)`` (unbiased — the no-EF pairing, where zero-mean noise
    is what prevents step-correlated bias)."""
    scales = jnp.maximum(
        jnp.max(jnp.abs(blocks), axis=-1, keepdims=True) / scheme.qmax, 1e-12
    )
    y = blocks / scales
    if seed is None:
        q = jnp.round(y)
    else:
        u = jax.random.uniform(
            jax.random.PRNGKey(jnp.asarray(seed, jnp.int32)), blocks.shape, jnp.float32
        )
        q = jnp.floor(y + u)
    return jnp.clip(q, -scheme.qmax, scheme.qmax).astype(jnp.int8), scales


def _to_wire(q: jax.Array, scheme: QuantScheme) -> jax.Array:
    return pack_int4(q) if scheme.bits == 4 else q


def _from_wire(w: jax.Array, scheme: QuantScheme) -> jax.Array:
    return unpack_int4(w) if scheme.bits == 4 else w


def quantize_roundtrip(flat: jax.Array, scheme="int8") -> jax.Array:
    """``dequantize(quantize(flat))`` under deterministic rounding — the
    local compression a rank commits when it first ships ``flat``. The
    error-feedback residual is ``flat − quantize_roundtrip(flat)``: the
    dominant, locally-attributable term of the ring's compression error
    (later hops re-quantize *mixed* partial sums, which no single rank can
    account — the residual is a first-order correction, and the loss
    trajectories of ``tests/test_bucketing.py`` are what pin that it
    suffices)."""
    sch = get_scheme(scheme)
    flat = flat.astype(jnp.float32).reshape(-1)
    size = flat.shape[0]
    padded = -(-size // sch.block) * sch.block
    if padded != size:
        flat = jnp.pad(flat, (0, padded - size))
    q, scales = _block_quant(flat.reshape(-1, sch.block), sch)
    return (q.astype(jnp.float32) * scales).reshape(-1)[:size]


def quantized_ring_wire_bytes(
    n_elems: int, n_ranks: int, scheme="int8", bidirectional: bool = False
) -> int:
    """Analytic per-rank wire bytes of one quantized ring all-reduce:
    2(n−1) hops, each shipping one padded segment's packed values + f32
    block scales. The counterpart fp32 number is
    ``ops.collectives.ring_wire_bytes`` — their ratio is the wire-byte
    reduction (static shapes ⇒ exact, not sampled)."""
    sch = get_scheme(scheme)
    if n_ranks <= 1:
        return 0
    k = 2 if bidirectional else 1
    quantum = k * n_ranks * sch.block
    padded = -(-n_elems // quantum) * quantum
    blocks_per_seg = padded // (k * n_ranks) // sch.block
    per_hop = blocks_per_seg * sch.wire_bytes_per_block
    return k * 2 * (n_ranks - 1) * per_hop


def compressed_gather_wire_bytes(n_elems: int, n_ranks: int) -> int:
    """Analytic per-rank wire bytes of the v1 ``compressed_all_reduce``
    gather exchange: every rank receives the other n−1 ranks' full int8
    payload + f32 block scales — O(n) per rank, the wire-byte shape the
    ring schedules exist to beat."""
    if n_ranks <= 1:
        return 0
    blocks = -(-n_elems // _BLOCK)
    return (n_ranks - 1) * (blocks * _BLOCK + blocks * 4)


def _ring_perms(n: int) -> dict:
    # one definition of the ring neighborhood for every ring schedule
    from dsml_tpu.ops.collectives import ring_perm_tables

    return ring_perm_tables(n)


def _dither_seed(blocks: jax.Array, base, rank, salt: int) -> jax.Array:
    """Stochastic-rounding seed for one hop's chunk: the chunk's own bits
    (varies per step with the data) mixed with the caller seed, rank, and
    hop salt so no two ranks/hops share a dither pattern. ONE definition —
    the all-reduce and reduce-scatter schedules must never drift apart."""
    return (
        jnp.sum(lax.bitcast_convert_type(blocks, jnp.int32), dtype=jnp.int32)
        + base
        + rank * jnp.int32(7919)
        + jnp.int32(salt)
    )


def _quant_chunk_wire(blocks, scheme: QuantScheme, stochastic, base, rank, salt):
    """``[rows, block]`` f32 → (wire values, scales): the one quantize-
    for-the-wire step both ring schedules ship each hop through."""
    if stochastic:
        q, sc = _block_quant(blocks, scheme, seed=_dither_seed(blocks, base, rank, salt))
    else:
        q, sc = _block_quant(blocks, scheme)
    return _to_wire(q, scheme), sc


def _dequant_wire(wire, sc, scheme: QuantScheme) -> jax.Array:
    """Inverse of :func:`_quant_chunk_wire`, flattened to 1-D."""
    return (_from_wire(wire, scheme).astype(jnp.float32) * sc).reshape(-1)


def quantized_ring_all_reduce(
    x: jax.Array,
    axis_name: str,
    scheme="int8",
    bidirectional: bool = False,
    mean: bool = True,
    stochastic: bool = True,
    seed: jax.Array | int = 0,
) -> jax.Array:
    """Block-scaled quantized ring all-reduce (SUM/AVG), inside
    ``shard_map``.

    The 2(n−1)-step ring schedule of ``ops.collectives`` with quantization
    *inside* it (EQuARX, PAPERS.md): every scatter-reduce hop quantizes its
    outgoing chunk to ``scheme`` (int8 or packed int4 + one f32 scale per
    block), the receiver dequantizes and accumulates in f32, and the next
    hop re-quantizes the partial sum. The all-gather half quantizes each
    fully-reduced segment ONCE (by its owner) and circulates the wire
    representation unchanged — no per-hop error compounding, and since
    every rank dequantizes the owner's exact bytes the result is
    bit-identical across ranks (the all-reduce postcondition, pinned in
    tests). ``bidirectional=True`` splits the payload into two halves
    running opposite directions (the ring2 full-duplex shape).

    Wire bytes: ~2(n−1)/n · ``bits``/8 per element (+4/block for scales)
    vs the fp32 ring's 2(n−1)/n · 4 — ≈4× (int8) / ≈8× (int4) fewer.

    ``stochastic=True`` (default) dithers each hop's rounding with a seed
    folded from the chunk's own bits + rank + hop, so slowly-moving
    coordinates don't see the same rounding direction every step;
    ``stochastic=False`` is deterministic round-to-nearest — the ERROR
    FEEDBACK pairing (the residual then accounts the committed error
    exactly, and resume is trivially bit-reproducible).

    Zero-padding up to a multiple of ``directions·n·block`` keeps hop
    boundaries block-aligned: pad lanes quantize to exactly 0 (absmax
    scaling maps 0 → 0 under both roundings), only ever combine with other
    ranks' pad lanes, and are sliced off before return — the no-leak
    property the odd-tail regression test pins."""
    sch = get_scheme(scheme)
    if not jnp.issubdtype(jnp.result_type(x), jnp.floating):
        raise ValueError(
            f"quantized ring all-reduce needs a float input, got {jnp.result_type(x)}"
        )
    n = lax.axis_size(axis_name)
    if n == 1:
        return x
    orig_shape, orig_dtype = x.shape, x.dtype
    signs = (+1, -1) if bidirectional else (+1,)
    k = len(signs)
    flat = x.astype(jnp.float32).reshape(-1)
    size = flat.shape[0]
    quantum = k * n * sch.block
    padded = -(-size // quantum) * quantum
    if padded != size:
        flat = jnp.pad(flat, (0, padded - size))
    part = padded // k
    seg = part // n
    rows = seg // sch.block
    rank = lax.axis_index(axis_name)
    perms = _ring_perms(n)
    base = jnp.asarray(seed, jnp.int32) * jnp.int32(1_000_003)

    def q_chunk(chunk, salt):
        # data-dependent dither, decorrelated across ranks AND hops
        return _quant_chunk_wire(
            chunk.reshape(rows, sch.block), sch, stochastic, base, rank, salt
        )

    def dq(wire, sc):
        return _dequant_wire(wire, sc, sch)

    parts = []
    for d, s in enumerate(signs):
        buf = flat[d * part : (d + 1) * part].reshape(n, seg)
        # Scatter-reduce: quantize → ship → dequantize-accumulate, per hop.
        for step in range(n - 1):
            send_idx = (rank - s * step) % n
            recv_idx = (rank - s * (step + 1)) % n
            chunk = lax.dynamic_index_in_dim(buf, send_idx, 0, keepdims=False)
            wire, sc = q_chunk(chunk, salt=2 * step + (s < 0))
            wire = lax.ppermute(wire, axis_name, perms[s])
            sc = lax.ppermute(sc, axis_name, perms[s])
            resident = lax.dynamic_index_in_dim(buf, recv_idx, 0, keepdims=False)
            buf = lax.dynamic_update_index_in_dim(
                buf, resident + dq(wire, sc), recv_idx, 0
            )
        # All-gather: the owner quantizes its reduced segment ONCE; hops
        # forward the received wire bytes untouched, so segment i is the
        # same dequantization everywhere (incl. on the owner itself, which
        # replaces its f32 copy with its own round trip).
        own_idx = (rank + s) % n
        carry_w, carry_s = q_chunk(
            lax.dynamic_index_in_dim(buf, own_idx, 0, keepdims=False),
            salt=1_000 + (s < 0),
        )
        out = lax.dynamic_update_index_in_dim(buf, dq(carry_w, carry_s), own_idx, 0)
        for step in range(n - 1):
            carry_w = lax.ppermute(carry_w, axis_name, perms[s])
            carry_s = lax.ppermute(carry_s, axis_name, perms[s])
            recv_idx = (rank - s * step) % n
            out = lax.dynamic_update_index_in_dim(out, dq(carry_w, carry_s), recv_idx, 0)
        parts.append(out.reshape(-1))
    full = parts[0] if k == 1 else jnp.concatenate(parts)
    full = full[:size]
    if mean:
        full = full / n
    return full.reshape(orig_shape).astype(orig_dtype)


def quantized_flat_reduce_scatter(
    flat: jax.Array,
    axis_name: str,
    scheme="int8",
    mean: bool = True,
    stochastic: bool = True,
    seed: jax.Array | int = 0,
) -> tuple[jax.Array, int]:
    """Quantized ring reduce-scatter of a flat vector: the scatter-reduce
    half of :func:`quantized_ring_all_reduce` alone, with
    ``ops.collectives.flat_reduce_scatter``'s layout contract — rank i is
    left with contiguous segment i of the (mean) reduction, f32, and
    ``padded`` is the length rounded up to a multiple of the axis size
    (NOT of the block: segments block-pad per hop internally, so the shard
    length matches the unquantized path's and ZeRO-2's sharded optimizer
    state keeps its exact shapes). The ZeRO-2 bucket primitive: (n−1) hops
    at ``bits``/8 bytes per element instead of fp32."""
    sch = get_scheme(scheme)
    if not jnp.issubdtype(jnp.result_type(flat), jnp.floating):
        raise ValueError(
            f"quantized reduce-scatter needs a float input, got {jnp.result_type(flat)}"
        )
    n = lax.axis_size(axis_name)
    x = flat.astype(jnp.float32).reshape(-1)
    size = x.shape[0]
    padded = -(-size // n) * n
    if padded != size:
        x = jnp.pad(x, (0, padded - size))
    if n == 1:
        return x, padded
    seg = padded // n
    rows = -(-seg // sch.block)
    blockpad = rows * sch.block - seg
    buf = x.reshape(n, seg)
    rank = lax.axis_index(axis_name)
    perm = _ring_perms(n)[+1]
    base = jnp.asarray(seed, jnp.int32) * jnp.int32(1_000_003)
    # virtual rank r−1 runs the forward schedule, so ownership lands on
    # segment (vr+1) = r — flat_reduce_scatter's rank-i-gets-segment-i rule
    vr = (rank - 1) % n

    def q_chunk(chunk, salt):
        if blockpad:
            chunk = jnp.pad(chunk, (0, blockpad))
        return _quant_chunk_wire(
            chunk.reshape(rows, sch.block), sch, stochastic, base, rank, salt
        )

    def dq(wire, sc):
        return _dequant_wire(wire, sc, sch)[:seg]

    for step in range(n - 1):
        send_idx = (vr - step) % n
        recv_idx = (vr - step - 1) % n
        chunk = lax.dynamic_index_in_dim(buf, send_idx, 0, keepdims=False)
        wire, sc = q_chunk(chunk, salt=step)
        wire = lax.ppermute(wire, axis_name, perm)
        sc = lax.ppermute(sc, axis_name, perm)
        resident = lax.dynamic_index_in_dim(buf, recv_idx, 0, keepdims=False)
        buf = lax.dynamic_update_index_in_dim(buf, resident + dq(wire, sc), recv_idx, 0)
    shard = lax.dynamic_index_in_dim(buf, rank, 0, keepdims=False)
    if mean:
        shard = shard / n
    return shard, padded


def compressed_all_reduce(
    x: jax.Array, axis_name: str, seed: jax.Array | int = 0, mean: bool = True
) -> jax.Array:
    """8-bit all-reduce: quantize locally, all-gather int8 values + scales
    (≈4× fewer bytes on the wire than f32), dequantize-and-reduce locally.
    Call under ``shard_map``. Unbiased: stochastic rounding makes the
    expected result equal the exact (mean) reduction."""
    n = lax.axis_size(axis_name)
    if n == 1:
        return x
    # de-correlate rounding noise across ranks so errors average out
    rank_seed = jnp.asarray(seed, jnp.int32) * jnp.int32(1_000_003) + lax.axis_index(axis_name)
    qt = quantize_int8(x, rank_seed)
    vals = lax.all_gather(qt.values, axis_name)  # [n, blocks, B] int8
    scales = lax.all_gather(qt.scales, axis_name)  # [n, blocks, 1]
    total = jnp.sum(vals.astype(jnp.float32) * scales, axis=0)
    out = total.reshape(-1)[: qt.size].reshape(qt.shape)
    if mean:
        out = out / n
    return out.astype(x.dtype)


def compressed_checkpoint(fn, seed: jax.Array | int | None = None):
    """Compressed rematerialization (the reference's §7 Memory literature —
    ActNN `chen21z.pdf` / GACT `liu22v.pdf`, SURVEY.md §2.4): like
    ``jax.checkpoint``, the backward recomputes ``fn``'s internals instead of
    storing them — but where plain remat stashes the layer INPUT at full
    precision, this stashes it blockwise-int8 (4× smaller than f32, 2× than
    bf16), and the backward recomputes from the dequantized stash.

    ``fn(params, x) -> y`` with ``x`` a pytree of activations; float leaves
    are quantized, integer leaves (token ids) stashed exactly. ``params``
    ride in the residuals unquantized — they alias the live param buffers, so
    they cost no extra HBM. Gradients are those of ``fn`` evaluated at the
    dequantized input: exact in expectation (stochastic rounding is
    unbiased), approximation error bounded by the blockwise quantization
    noise — ActNN's accuracy argument. Safe under ``shard_map``: the
    backward's ``jax.vjp`` transposes any collectives inside ``fn`` the same
    way 1F1B's per-tick vjp does.

    ``seed=None`` (default) derives each leaf's rounding seed from the
    leaf's own bits, so the noise de-correlates across layers, microbatches,
    AND training steps with no step-counter plumbing — a fixed seed would
    make the rounding deterministic and turn the zero-mean noise into a
    step-correlated bias (the failure ``compressed_all_reduce`` avoids by
    per-rank seeds). Pass an explicit seed only for reproducibility studies.
    """

    def _q(leaf):
        if jnp.issubdtype(jnp.result_type(leaf), jnp.floating):
            if seed is None:
                # fold the activation's own bits into the seed: changes every
                # step/layer because the values do, costs one reduction over
                # a tensor already in registers. Sum the int32 BITCASTS, not
                # the floats: an f32 sum can saturate to inf/NaN on large
                # bf16 tensors, freezing the seed into a step-constant and
                # reintroducing the correlated-rounding bias; int32 addition
                # wraps, so the reduction is total and value-dependent
                leaf_seed = jnp.sum(
                    lax.bitcast_convert_type(leaf.astype(jnp.float32), jnp.int32)
                )
            else:
                leaf_seed = seed
            return quantize_int8(leaf, leaf_seed)
        return leaf

    def _dq(leaf):
        return dequantize_int8(leaf) if isinstance(leaf, QuantizedTensor) else leaf

    @jax.custom_vjp
    def wrapped(params, x):
        return fn(params, x)

    def fwd(params, x):
        return fn(params, x), (params, jax.tree.map(_q, x))

    def bwd(res, g):
        params, qx = res
        x_hat = jax.tree.map(_dq, qx, is_leaf=lambda l: isinstance(l, QuantizedTensor))
        _, vjp = jax.vjp(fn, params, x_hat)
        return vjp(g)

    wrapped.defvjp(fwd, bwd)
    return wrapped
