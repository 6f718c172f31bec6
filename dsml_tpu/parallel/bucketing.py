"""Gradient bucketing: size-targeted flat buckets for overlap-friendly sync.

The reference's AllReduceRing moved ONE monolithic buffer per sync, and the
port kept that shape: ``parallel/dp.py`` raveled the whole gradient pytree
into a single flat vector before one 2(n−1)-hop ring pass — serializing the
entire backward against the entire exchange. Production data-parallel stacks
(PyTorch DDP, the MLPerf TPU-pod entries — PAPERS.md "Scale MLPerf-0.6
models on Google TPU-v3 Pods") instead partition gradients into
size-targeted buckets and reduce each bucket as an INDEPENDENT collective,
so the compiler's latency-hiding scheduler can overlap the exchange of
already-finished gradients with the backward compute still producing the
rest. For the quantized path the win is structural too: q8 quantizes per
bucket, removing the full-vector ravel→quantize serialization.

Mechanics:

- :func:`plan_buckets` — greedy, order-preserving partition of a pytree's
  leaves into buckets targeting ``bucket_size_mb`` MiB each. Buckets are
  PER-DTYPE (a bucket concatenates raveled leaves, which requires one
  dtype); a leaf larger than the target gets a bucket of its own — leaves
  are never split, matching DDP practice (the unit of readiness in a
  backward pass is the whole parameter's gradient).
- :func:`flatten_buckets` / :func:`unflatten_buckets` — pytree ⇄ list of
  flat per-bucket vectors, exact round trip (0-d leaves, mixed dtypes).
- :func:`bucketed_all_reduce` — the sync: one collective per bucket
  (``ring`` / ``ring2`` / ``naive`` / ``auto`` / ``xla`` via
  ``ops.collectives.all_reduce``, ``q8`` via
  ``ops.quantization.compressed_all_reduce``, or the block-quantized ring
  family ``q8_ring`` / ``q8_ring2`` / ``q4_ring`` / ``q4_ring2`` /
  ``quant`` via ``ops.quantization.quantized_ring_all_reduce`` — int8/int4
  quantization INSIDE the 2(n−1)-step schedule; ``quant`` resolves the
  scheme per bucket dtype from ``DSML_QUANT``), all emitted inside the
  same jitted program. ``bucket_size_mb=None`` reproduces the
  pre-bucketing single-buffer path bit-for-bit (same ``ravel_pytree`` +
  single collective jaxpr) for A/B comparison.
- **Error feedback** (EF-SGD): pass ``error_feedback=`` (a residual pytree
  from :func:`init_error_feedback`, per-rank) and the quantized sync runs
  on ``grads + residual`` with deterministic rounding, returning the new
  residual ``adjusted − roundtrip(adjusted)`` alongside the reduction —
  repeated quantized syncs stop drifting because every bit the compressor
  dropped is re-offered next step. Residuals are checkpointable state
  (``trainer.py`` rides them in the manifest) and f32 regardless of the
  gradient dtype, so a bf16 run's correction isn't itself truncated.

Default bucket size: 4 MiB, overridable via ``DSML_BUCKET_MB`` (chosen
from a sweep on the virtual-8 CPU mesh, not measured on a chip — see
docs/TUNING.md and ROADMAP Speed 3).
"""

from __future__ import annotations

import dataclasses
import os
from typing import Any, Sequence

import jax
import jax.numpy as jnp
from jax import lax
from jax.flatten_util import ravel_pytree

from dsml_tpu.ops.collectives import ReduceOp, all_reduce

__all__ = [
    "BucketPlan",
    "QUANT_RING_ALGORITHMS",
    "default_bucket_mb",
    "plan_buckets",
    "flatten_buckets",
    "unflatten_buckets",
    "bucketed_all_reduce",
    "init_error_feedback",
    "is_quantized_algorithm",
    "supports_error_feedback",
    "plan_quant_wire_bytes",
]

# the v2 block-quantized ring family: algorithm name -> (scheme, bidirectional)
QUANT_RING_ALGORITHMS = {
    "q8_ring": ("int8", False),
    "q8_ring2": ("int8", True),
    "q4_ring": ("int4", False),
    "q4_ring2": ("int4", True),
}


def is_quantized_algorithm(algorithm: str) -> bool:
    """True for every compressed sync: the v1 gather (``q8``), the v2 ring
    family, and the env-resolved ``quant``."""
    return algorithm == "q8" or algorithm == "quant" or algorithm in QUANT_RING_ALGORITHMS


def supports_error_feedback(algorithm: str) -> bool:
    """EF pairs with the deterministic-rounding ring family (and ``quant``,
    which resolves into it). The v1 ``q8`` gather keeps its stochastic
    rounding and stays EF-less — its unbiasedness is its own drift story."""
    return algorithm == "quant" or algorithm in QUANT_RING_ALGORITHMS


def _resolve_quant(algorithm: str, dtype) -> str:
    """Resolve ``"quant"`` per bucket dtype via ``DSML_QUANT``
    (``ops.quantization.quant_algorithm_for``); every other name passes
    through. The result may be plain ``"ring"``/``"ring2"``
    (``DSML_QUANT=none``) — that bucket then syncs unquantized."""
    if algorithm != "quant":
        return algorithm
    from dsml_tpu.ops.quantization import quant_algorithm_for

    return quant_algorithm_for(dtype)


def default_bucket_mb() -> float:
    """The bucket-size default: 4 MiB (chosen from a CPU sweep — see
    docs/TUNING.md), overridable via ``DSML_BUCKET_MB`` (malformed or
    non-positive values fall back — a size must be positive; "no
    bucketing" is ``bucket_size_mb=None`` at the call site, not an env
    value)."""
    try:
        mb = float(os.environ.get("DSML_BUCKET_MB", 4.0))
    except ValueError:
        return 4.0
    return mb if mb > 0 else 4.0


@dataclasses.dataclass(frozen=True)
class BucketPlan:
    """Static partition of a pytree into flat buckets (all fields are
    trace-time constants — shapes/dtypes/indices, never array data)."""

    treedef: Any
    shapes: tuple  # per-leaf shapes
    dtypes: tuple  # per-leaf dtypes
    buckets: tuple  # tuple of tuples of leaf indices, order-preserving

    @property
    def n_buckets(self) -> int:
        return len(self.buckets)

    def bucket_nbytes(self, b: int) -> int:
        return sum(
            _leaf_size(self.shapes[i]) * jnp.dtype(self.dtypes[i]).itemsize
            for i in self.buckets[b]
        )


def _leaf_size(shape) -> int:
    n = 1
    for d in shape:
        n *= int(d)
    return n


def plan_buckets(tree, bucket_size_mb: float) -> BucketPlan:
    """Partition ``tree``'s leaves into per-dtype buckets of ~``bucket_size_mb``
    MiB. Greedy in leaf order: each dtype keeps one open bucket; a leaf
    joins it if the bucket hasn't reached the target yet and the leaf alone
    is under target, else a new bucket opens (so an over-target leaf always
    sits in a bucket of its own). Leaves are never split."""
    leaves, treedef = jax.tree_util.tree_flatten(tree)
    shapes = tuple(tuple(l.shape) for l in leaves)
    dtypes = tuple(jnp.result_type(l) for l in leaves)
    target = max(float(bucket_size_mb), 1e-6) * (1 << 20)
    open_bucket: dict = {}  # dtype -> [list of leaf idx, bytes so far]
    buckets: list = []
    for i, (shape, dtype) in enumerate(zip(shapes, dtypes)):
        nbytes = _leaf_size(shape) * jnp.dtype(dtype).itemsize
        key = str(dtype)
        # an over-target leaf always opens its own bucket (it would blow an
        # open bucket far past target; once placed, the >= target bucket
        # closes itself via the same size check)
        if nbytes < target and key in open_bucket and open_bucket[key][1] < target:
            open_bucket[key][0].append(i)
            open_bucket[key][1] += nbytes
        else:
            open_bucket[key] = [[i], nbytes]
            buckets.append(open_bucket[key][0])
    return BucketPlan(treedef, shapes, dtypes, tuple(tuple(b) for b in buckets))


def flatten_buckets(tree, plan: BucketPlan) -> list:
    """Flat 1-D vector per bucket: the bucket's leaves raveled and
    concatenated in plan order (single-leaf buckets skip the concat)."""
    leaves = jax.tree_util.tree_leaves(tree)
    out = []
    for idxs in plan.buckets:
        if len(idxs) == 1:
            out.append(leaves[idxs[0]].reshape(-1))
        else:
            out.append(jnp.concatenate([leaves[i].reshape(-1) for i in idxs]))
    return out

def unflatten_buckets(flat_buckets: Sequence, plan: BucketPlan):
    """Exact inverse of :func:`flatten_buckets` (shapes/dtypes restored from
    the plan, so a widened reduction dtype is cast back per leaf)."""
    leaves: list = [None] * len(plan.shapes)
    for idxs, flat in zip(plan.buckets, flat_buckets):
        off = 0
        for i in idxs:
            n = _leaf_size(plan.shapes[i])
            leaves[i] = (
                lax.slice_in_dim(flat, off, off + n)
                .reshape(plan.shapes[i])
                .astype(plan.dtypes[i])
            )
            off += n
    return jax.tree_util.tree_unflatten(plan.treedef, leaves)


def _q8_bucket_seed(flat: jax.Array, bucket_index: int) -> jax.Array:
    """Data-dependent dither seed, per bucket: the rounding pattern must
    vary per step (slowly-moving coordinates would otherwise see the same
    rounding direction every step — systematic bias) AND per bucket
    (identical buckets must not share noise). Hashing the bucket's own
    gradient bits decorrelates steps without threading a counter through
    the step signature — the same trick parallel/dp.py used on the
    monolithic vector, now applied per bucket with an index mix-in."""
    as_f32 = flat if flat.dtype == jnp.float32 else flat.astype(jnp.float32)
    return (
        jnp.sum(lax.bitcast_convert_type(as_f32, jnp.int32), dtype=jnp.int32)
        + jnp.int32(bucket_index * 7919)
    )


def _ef_plan(plan: BucketPlan) -> BucketPlan:
    """The residual tree's plan: same partition, every leaf f32 (residuals
    are kept full-precision so a bf16 run's correction isn't truncated)."""
    return dataclasses.replace(plan, dtypes=tuple(jnp.float32 for _ in plan.dtypes))


def init_error_feedback(tree, mesh, axis: str):
    """Zero error-feedback residuals for ``tree``'s gradients: one f32
    buffer per leaf PER RANK (EF residuals are rank-local state — each
    rank's compression error is its own), represented outside ``shard_map``
    as ``[n_ranks, *leaf.shape]`` sharded over ``axis`` so every device
    stores exactly its own residual (1× gradient memory per rank, the
    standard EF cost). Checkpointable like any state tree; across a width
    change use ``parallel.elastic.remap_error_feedback``."""
    from jax.sharding import NamedSharding, PartitionSpec as P

    n = mesh.shape[axis]
    sh = NamedSharding(mesh, P(axis))

    def zeros(leaf):
        # jit with out_shardings materializes each device's row in place —
        # a host/device_put round trip would transiently hold the FULL
        # [n, *shape] buffer on one device (n× gradient memory at startup)
        shape = (n, *jnp.shape(leaf))
        return jax.jit(
            lambda: jnp.zeros(shape, jnp.float32), out_shardings=sh
        )()

    return jax.tree.map(zeros, tree)


def plan_quant_wire_bytes(plan: BucketPlan, n_ranks: int, algorithm: str) -> dict:
    """Analytic per-sync wire bytes by scheme for a bucket plan under a
    quantized algorithm — ``{scheme: bytes}`` (non-float buckets, which
    ride the fp32 ring, land under ``"fp32"``). Static shapes ⇒ exact;
    the dp/zero2 frontends bump ``collective_quant_bytes_total`` with
    this once per step."""
    from dsml_tpu.ops.quantization import (
        compressed_gather_wire_bytes,
        quantized_ring_wire_bytes,
    )
    from dsml_tpu.ops.collectives import ring_wire_bytes

    out: dict = {}
    for b in range(plan.n_buckets):
        dtype = plan.dtypes[plan.buckets[b][0]]
        n_elems = sum(_leaf_size(plan.shapes[i]) for i in plan.buckets[b])
        resolved = _resolve_quant(algorithm, dtype)
        is_float = jnp.issubdtype(dtype, jnp.floating)
        if resolved in QUANT_RING_ALGORITHMS and is_float:
            scheme, bidir = QUANT_RING_ALGORITHMS[resolved]
            nbytes = quantized_ring_wire_bytes(n_elems, n_ranks, scheme, bidir)
        elif resolved == "q8" and is_float:
            scheme = "int8"
            nbytes = compressed_gather_wire_bytes(n_elems, n_ranks)
        else:
            scheme = "fp32"
            nbytes = ring_wire_bytes(
                n_elems, n_ranks, jnp.dtype(dtype).itemsize
            )
        out[scheme] = out.get(scheme, 0) + nbytes
    return out


def _quant_ring_bucket(flat, axis_name, op, resolved, ef_bucket, bucket_index):
    """One float bucket through the quantized ring: with ``ef_bucket`` the
    sync runs on the residual-adjusted gradient under DETERMINISTIC
    rounding and returns the fresh residual; without, stochastic dithering
    (data-seeded, like the v1 q8 path) keeps repeated roundings unbiased."""
    from dsml_tpu.ops.quantization import (
        quantize_roundtrip,
        quantized_ring_all_reduce,
    )

    scheme, bidir = QUANT_RING_ALGORITHMS[resolved]
    mean = op == ReduceOp.AVG
    if ef_bucket is None:
        out = quantized_ring_all_reduce(
            flat, axis_name, scheme, bidirectional=bidir, mean=mean,
            stochastic=True, seed=_q8_bucket_seed(flat, bucket_index),
        )
        return out, None
    adjusted = flat.astype(jnp.float32) + ef_bucket
    out = quantized_ring_all_reduce(
        adjusted, axis_name, scheme, bidirectional=bidir, mean=mean,
        stochastic=False,
    )
    new_ef = adjusted - quantize_roundtrip(adjusted, scheme)
    return out.astype(flat.dtype), new_ef


def bucketed_all_reduce(
    tree,
    axis_name: str,
    op: ReduceOp = ReduceOp.AVG,
    algorithm: str = "ring",
    bucket_size_mb: float | None = None,
    error_feedback=None,
) -> Any:
    """All-reduce a pytree across ``axis_name`` as per-bucket collectives.

    Call under ``shard_map``. ``algorithm`` is any
    ``ops.collectives.all_reduce`` algorithm (``ring``/``ring2``/``naive``/
    ``auto``/``xla``), ``"q8"`` (v1 blockwise-int8 gather exchange —
    ``ops.quantization.compressed_all_reduce`` per bucket), one of the v2
    block-quantized ring schedules (``"q8_ring"``/``"q8_ring2"``/
    ``"q4_ring"``/``"q4_ring2"`` — int8/int4 inside the 2(n−1)-step ring,
    ``ops.quantization.quantized_ring_all_reduce``), or ``"quant"`` (per
    bucket dtype via ``DSML_QUANT``). Quantized syncs are SUM/AVG only;
    non-float buckets always ride the ring uncompressed, since quantizing
    integer gradients would corrupt them.

    ``error_feedback``: a per-rank residual pytree (leaf-shaped — the
    caller inside ``shard_map`` passes its own rank's slice of the
    :func:`init_error_feedback` state). Requires a ring-family quantized
    algorithm; the return becomes ``(reduced_tree, new_residual_tree)``.
    ``bucket_size_mb=None`` under EF means per-dtype buckets (the zero2
    convention), since the residual bookkeeping is plan-shaped.

    ``bucket_size_mb=None`` (without EF) is the pre-bucketing behavior:
    ONE flat buffer via ``ravel_pytree`` and a single collective —
    bit-identical to the old ``parallel/dp.py`` path (same jaxpr), kept
    for A/B measurement.
    """
    op = ReduceOp(op)
    if is_quantized_algorithm(algorithm) and op not in (ReduceOp.SUM, ReduceOp.AVG):
        raise ValueError(f"quantized sync ({algorithm}) supports SUM/AVG, got {op!r}")
    if error_feedback is not None and not supports_error_feedback(algorithm):
        raise ValueError(
            f"error_feedback requires a quantized ring algorithm "
            f"({sorted(QUANT_RING_ALGORITHMS)} or 'quant'), got {algorithm!r}"
        )
    if bucket_size_mb is None and error_feedback is None:
        flat, unravel = ravel_pytree(tree)
        if algorithm == "q8":
            from dsml_tpu.ops.quantization import compressed_all_reduce

            seed = jnp.sum(
                lax.bitcast_convert_type(flat, jnp.int32), dtype=jnp.int32
            )
            flat = compressed_all_reduce(
                flat, axis_name, seed=seed, mean=(op == ReduceOp.AVG)
            )
        elif algorithm == "quant" or algorithm in QUANT_RING_ALGORITHMS:
            resolved = _resolve_quant(algorithm, flat.dtype)
            if resolved in QUANT_RING_ALGORITHMS:
                flat, _ = _quant_ring_bucket(flat, axis_name, op, resolved, None, 0)
            else:
                flat = all_reduce(flat, axis_name, op, resolved)
        else:
            flat = all_reduce(flat, axis_name, op, algorithm)
        return unravel(flat)

    plan = plan_buckets(
        tree, bucket_size_mb if bucket_size_mb is not None else float("inf")
    )
    buckets = flatten_buckets(tree, plan)
    ef_buckets = (
        flatten_buckets(error_feedback, plan) if error_feedback is not None else None
    )
    reduced = []
    new_ef = []
    for b, flat in enumerate(buckets):
        is_float = jnp.issubdtype(flat.dtype, jnp.floating)
        resolved = _resolve_quant(algorithm, flat.dtype)
        if algorithm == "q8" and is_float:
            from dsml_tpu.ops.quantization import compressed_all_reduce

            out = compressed_all_reduce(
                flat, axis_name, seed=_q8_bucket_seed(flat, b),
                mean=(op == ReduceOp.AVG),
            )
        elif resolved in QUANT_RING_ALGORITHMS and is_float:
            ef_b = ef_buckets[b] if ef_buckets is not None else None
            out, ef_out = _quant_ring_bucket(flat, axis_name, op, resolved, ef_b, b)
            if ef_buckets is not None:
                new_ef.append(ef_out)
        else:
            # non-float buckets under a quantized algorithm ride the plain
            # ring; a float bucket whose resolution came back unquantized
            # (DSML_QUANT=none / a plain algorithm) uses that algorithm
            fallback = resolved if not is_quantized_algorithm(resolved) else "ring"
            if ef_buckets is not None and is_float:
                # exact exchange drains the standing residual (a mid-run
                # DSML_QUANT flip must deliver what the compressor owed)
                out = all_reduce(
                    flat.astype(jnp.float32) + ef_buckets[b], axis_name, op, fallback
                ).astype(flat.dtype)
                new_ef.append(jnp.zeros_like(ef_buckets[b]))
            else:
                out = all_reduce(flat, axis_name, op, fallback)
                if ef_buckets is not None:
                    new_ef.append(ef_buckets[b])  # integer bucket: stays zero
        reduced.append(out)
    result = unflatten_buckets(reduced, plan)
    if error_feedback is not None:
        return result, unflatten_buckets(new_ef, _ef_plan(plan))
    return result
