"""XLA collectives over the TPU ICI mesh — the framework's data plane.

This replaces the reference's hand-rolled gRPC "NCCL" (SURVEY.md §5.8): there,
a coordinator drove per-device ``BeginSend``/``BeginReceive``/``StreamSend``
RPCs in a 2(n-1)-step ring schedule
(``DSML/gpu_coordinator_service/gpu_coordinator_server.go:339-356,379-566``),
but the transport was a same-device loopback and the reduction byte-wise uint8
addition (SURVEY.md §8.1-8.3). Here the *intended* semantics are implemented
for real:

- :func:`ring_all_reduce` — the textbook ring all-reduce (scatter-reduce then
  all-gather, 2(n-1) ``ppermute`` steps over the ICI ring), dtype-aware, with
  every :class:`ReduceOp` honored. One jitted program; data never touches the
  host.
- :func:`naive_all_reduce` — gather→reduce(→implicit broadcast) baseline,
  the collective-space analogue of the reference's host-mediated naive path
  (``gpu_coordinator_server.go:611-717``).
- :func:`all_reduce` — dispatcher: XLA's native collectives (``lax.psum`` etc.,
  usually fastest — XLA picks the topology-optimal algorithm), the explicit
  ring, or the naive baseline.
- :func:`reduce_scatter` / :func:`all_gather` / :func:`all_to_all` /
  :func:`ppermute_ring` — the remaining primitives TP/SP/EP layers build on.

All functions in the "inside shard_map" group take an ``axis_name`` and must
be called under ``jax.shard_map`` (or ``pmap``); the "host API" group
(:func:`make_stacked_all_reduce`) builds a jitted mesh program for callers
that hold a host-side stack of per-device buffers (the gRPC coordinator).
"""

from __future__ import annotations

import enum
import functools
from typing import Callable, Sequence

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

__all__ = [
    "ReduceOp",
    "ring_perm_tables",
    "ring_pass",
    "ring_all_reduce",
    "ring2_all_reduce",
    "naive_all_reduce",
    "all_reduce",
    "hierarchical_all_reduce",
    "reduce_scatter",
    "flat_reduce_scatter",
    "flat_all_gather",
    "all_gather",
    "all_to_all",
    "ppermute_ring",
    "ring_wire_bytes",
    "make_stacked_all_reduce",
    "device_buffers_all_reduce",
]


class ReduceOp(enum.IntEnum):
    """Reduction operator. Values match the wire enum ``gpu_sim.ReduceOp``
    (reference ``DSML/proto/gpu_sim.proto:162-168``); unlike the reference,
    every variant is actually honored (fixes SURVEY.md §8.3)."""

    SUM = 0
    PROD = 1
    MIN = 2
    MAX = 3
    AVG = 4  # commented out of the reference proto; supported natively here

    @property
    def combine(self) -> Callable[[jax.Array, jax.Array], jax.Array]:
        return _COMBINE[self]


_COMBINE = {
    ReduceOp.SUM: jnp.add,
    ReduceOp.AVG: jnp.add,
    ReduceOp.PROD: jnp.multiply,
    ReduceOp.MIN: jnp.minimum,
    ReduceOp.MAX: jnp.maximum,
}


def _axis_size(axis_name: str) -> int:
    return lax.axis_size(axis_name)


def ring_perm_tables(n: int) -> dict[int, list[tuple[int, int]]]:
    """Explicit ppermute perm tables for BOTH ring directions: ``+1`` sends
    rank i → i+1 (the reference's forward schedule), ``-1`` the mirror.
    THE one definition of the ring neighborhood — the fp32 ring
    (:func:`ring_all_reduce`/``ring2``), the quantized ring
    (``ops.quantization.quantized_ring_all_reduce``), and ring attention
    (``ops.ring_attention``) all rotate through these tables, so the three
    ring schedules cannot drift apart."""
    return {
        +1: [(i, (i + 1) % n) for i in range(n)],
        -1: [(i, (i - 1) % n) for i in range(n)],
    }


def ring_pass(x, axis_name: str, sign: int = +1):
    """One rotate step of the ring schedule: every leaf of ``x`` hops to the
    ``sign``-direction neighbor (``+1`` = rank i → i+1, ``-1`` = the
    mirror). Accepts a pytree (K/V pairs, (wire, scales) tuples) so callers
    rotate their whole hop state in one call. Must run under ``shard_map``."""
    if sign not in (+1, -1):
        raise ValueError(f"ring_pass sign must be +1 or -1, got {sign!r}")
    perm = ring_perm_tables(_axis_size(axis_name))[sign]
    return jax.tree.map(lambda t: lax.ppermute(t, axis_name, perm), x)


# ---------------------------------------------------------------------------
# Inside-shard_map collectives
# ---------------------------------------------------------------------------


def _ring_all_reduce_impl(x: jax.Array, axis_name: str, op: ReduceOp, signs: tuple) -> jax.Array:
    """THE ring schedule, generalized over directions: the payload splits
    into ``len(signs)`` parts, each running the 2(n−1)-step
    scatter-reduce/all-gather schedule around the ring in its own
    direction (sign +1 = the reference's forward schedule, send segment
    ``(rank−step) mod n`` / receive ``(rank−step−1) mod n``,
    ``gpu_coordinator_server.go:393-404``; sign −1 = the same schedule
    under the rank relabeling r → −r mod n). Each step issues every
    direction's hop back-to-back so the scheduler can overlap them.

    Works on any shape/dtype; the flattened buffer zero-pads up to a
    multiple of ``len(signs)·n`` (like the reference,
    gpu_coordinator_server.go:297-334; pad positions only ever combine
    with other ranks' pad positions and are sliced off before return).
    Small ints accumulate in a wider type so SUM across ranks can't wrap
    (the reference's uint8 wraparound bug, SURVEY.md §8.2)."""
    op = ReduceOp(op)
    n = _axis_size(axis_name)
    if n == 1:
        return x

    orig_shape, orig_dtype = x.shape, x.dtype
    acc_dtype = (
        jnp.promote_types(orig_dtype, jnp.int32)
        if jnp.issubdtype(orig_dtype, jnp.integer) else orig_dtype
    )
    flat = x.astype(acc_dtype).reshape(-1)
    size = flat.shape[0]
    k = len(signs)
    padded = -(-size // (k * n)) * (k * n)
    if padded != size:
        flat = jnp.pad(flat, (0, padded - size))
    seg = padded // (k * n)
    part = padded // k
    bufs = [flat[i * part : (i + 1) * part].reshape(n, seg) for i in range(k)]

    rank = lax.axis_index(axis_name)

    def hop(buf, sign, send_idx, recv_idx, combine):
        chunk = lax.dynamic_index_in_dim(buf, send_idx, axis=0, keepdims=False)
        recv = ring_pass(chunk, axis_name, sign)
        resident = lax.dynamic_index_in_dim(buf, recv_idx, 0, keepdims=False)
        new = combine(resident, recv) if combine is not None else recv
        return lax.dynamic_update_index_in_dim(buf, new, recv_idx, axis=0)

    # Scatter-reduce: after step t, segment (rank − sign·(t+1)) mod n holds
    # the partial reduction of t+2 ranks' contributions.
    for step in range(n - 1):
        bufs = [
            hop(b, s, (rank - s * step) % n, (rank - s * (step + 1)) % n, op.combine)
            for b, s in zip(bufs, signs)
        ]
    # All-gather: circulate each fully-reduced segment around the ring.
    for step in range(n - 1):
        bufs = [
            hop(b, s, (rank - s * (step - 1)) % n, (rank - s * step) % n, None)
            for b, s in zip(bufs, signs)
        ]

    out = bufs[0].reshape(-1) if k == 1 else jnp.concatenate([b.reshape(-1) for b in bufs])
    out = out[:size]
    if op == ReduceOp.AVG:
        out = out / n
    return out.reshape(orig_shape).astype(orig_dtype)


def ring_all_reduce(x: jax.Array, axis_name: str, op: ReduceOp = ReduceOp.SUM) -> jax.Array:
    """Ring all-reduce of ``x`` (same shape on every rank) across
    ``axis_name`` — the reference's forward 2(n−1)-step schedule as one
    XLA program whose sends are ``lax.ppermute`` hops over ICI and whose
    combiner is dtype-aware (see :func:`_ring_all_reduce_impl`)."""
    return _ring_all_reduce_impl(x, axis_name, op, (+1,))


def ring2_all_reduce(x: jax.Array, axis_name: str, op: ReduceOp = ReduceOp.SUM) -> jax.Array:
    """BIDIRECTIONAL ring all-reduce: two half-payloads run the ring
    schedule in OPPOSITE directions simultaneously — TPU ICI links are
    full duplex, so the reverse hops ride otherwise-idle capacity and
    each direction moves only S/2 bytes: ~2× the unidirectional ring's
    bandwidth at the same step count. Exactness vs
    :func:`ring_all_reduce` is pinned in tests for every ReduceOp."""
    return _ring_all_reduce_impl(x, axis_name, op, (+1, -1))


def naive_all_reduce(x: jax.Array, axis_name: str, op: ReduceOp = ReduceOp.SUM) -> jax.Array:
    """Gather-everything-then-reduce baseline (reference
    ``NaiveAllReduce``, gpu_coordinator_server.go:611-717, minus the simulated
    sleeps — the gRPC layer adds those for API parity). Moves n× more data
    than the ring; exists to benchmark the ring against."""
    op = ReduceOp(op)
    n = _axis_size(axis_name)
    if n == 1:
        return x
    gathered = lax.all_gather(x, axis_name)  # [n, ...] on every rank
    if op in (ReduceOp.SUM, ReduceOp.AVG):
        out = jnp.sum(gathered, axis=0)
        if op == ReduceOp.AVG:
            out = out / n
    elif op == ReduceOp.PROD:
        out = jnp.prod(gathered, axis=0)
    elif op == ReduceOp.MIN:
        out = jnp.min(gathered, axis=0)
    else:
        out = jnp.max(gathered, axis=0)
    return out.astype(x.dtype)


def ring_wire_bytes(
    n_elems: int, n_ranks: int, itemsize: int = 4, bidirectional: bool = False
) -> int:
    """Analytic per-rank wire bytes of one full-precision ring all-reduce:
    2(n−1) hops × one segment of the (padded) payload each, at ``itemsize``
    bytes per element. The bidirectional ring moves the same total volume
    (two half-payloads, half the bytes per direction). The fp32 baseline
    the quantized schedules' wire-byte reduction divides by (their
    counterpart is ``ops.quantization.quantized_ring_wire_bytes``);
    static shapes ⇒ exact, not sampled."""
    if n_ranks <= 1:
        return 0
    k = 2 if bidirectional else 1
    quantum = k * n_ranks
    padded = -(-n_elems // quantum) * quantum
    return 2 * (n_ranks - 1) * (padded // n_ranks) * itemsize


@functools.lru_cache(maxsize=8)
def _measured_alpha_beta(path: str) -> tuple[float, float] | None:
    """(α ms/round, β ms/byte) solved from a calibrated collective profile
    (``obs/regress.py --profile`` output, ``DSML_COLLECTIVE_PROFILE``):
    the measured ring and naive p50 at one (payload, device count) give
    two equations in the two alpha-beta unknowns —

        naive = α + (n−1)·S·β          (one round, n−1 shards received)
        ring  = 2(n−1)·α + 2·S·β       (2(n−1) rounds, ~2S bytes)

    Returns None (→ the analytic default) when the profile is missing any
    constant, is malformed, or solves to a non-physical α/β ≤ 0 (e.g. a
    CPU-fallback capture where the "wire" costs nothing) — a bad profile
    must degrade selection to the prior, never crash a trace."""
    import json

    try:
        with open(path) as f:
            constants = json.load(f)["constants"]

        def med(name: str) -> float:
            entry = constants[name]
            return float(entry["median"] if "median" in entry
                         else entry["fresh"])

        naive_ms = med("allreduce_naive_p50_ms")
        ring_ms = med("allreduce_ring_p50_ms")
        payload_b = med("allreduce_payload_mb") * (1 << 20)
        n = int(med("allreduce_devices"))
    except (OSError, ValueError, KeyError, TypeError):
        return None
    denom = payload_b * (2 * (n - 1) ** 2 - 2)
    if n < 2 or denom <= 0:
        return None
    beta = (2 * (n - 1) * naive_ms - ring_ms) / denom
    alpha = naive_ms - (n - 1) * payload_b * beta
    if alpha <= 0 or beta <= 0:
        return None
    return alpha, beta


def auto_all_reduce_algorithm(nbytes: int, n_devices: int, latency_bytes: int = 32768) -> str:
    """Payload-aware algorithm selection (the Blink/TACOS §6 Communication
    literature point — SURVEY.md §2.4: pick the collective schedule by where
    it sits on the latency/bandwidth tradeoff, not one-size-fits-all).

    Alpha-beta model with per-round latency α and per-byte time β: naive
    gather+reduce costs α + (n−1)·S·β (ONE round, every rank receives the
    other n−1 shards); the explicit ring costs 2(n−1)·α + ~2S·β (2(n−1)
    serialized rounds, bandwidth-optimal volume). Naive wins iff
    (n−3)·S·β < (2n−3)·α, i.e. S below a crossover that DEPENDS on n:
    ``latency_bytes`` is α/β — the payload whose transfer time equals one
    round of link latency — and the crossover is
    ``latency_bytes · (2n−3)/(n−3)`` (≈ 2·latency_bytes for large n; at
    n ≤ 3 the ring's extra rounds can never pay for its ≤ 0 byte savings,
    so naive always wins). Both inputs are static at trace time, so the
    choice costs nothing at runtime.

    With ``DSML_COLLECTIVE_PROFILE=<path>`` pointing at a calibrated
    profile (the ``collective_profile.json`` that ``obs/regress.py
    --profile`` exports from bench history), α and β come from MEASURED
    ring/naive latencies instead of the ``latency_bytes`` prior, and the
    choice compares the two predicted costs directly — the first
    calibration step toward the ROADMAP's cost-model planner. A missing or
    malformed profile silently keeps the analytic default.
    """
    if n_devices <= 3:
        return "naive"
    import os

    profile = os.environ.get("DSML_COLLECTIVE_PROFILE")
    if profile:
        ab = _measured_alpha_beta(profile)
        if ab is not None:
            alpha, beta = ab
            naive_ms = alpha + (n_devices - 1) * nbytes * beta
            ring_ms = 2 * (n_devices - 1) * alpha + 2 * nbytes * beta
            return "naive" if naive_ms <= ring_ms else "ring"
    crossover = latency_bytes * (2 * n_devices - 3) / (n_devices - 3)
    return "naive" if nbytes <= crossover else "ring"


def all_reduce(
    x: jax.Array,
    axis_name: str,
    op: ReduceOp = ReduceOp.SUM,
    algorithm: str = "xla",
) -> jax.Array:
    """All-reduce with selectable algorithm.

    ``xla``   — let XLA choose (``lax.psum``/``pmin``/``pmax``/``pmean``);
                on TPU this lowers to topology-aware ICI collectives and is
                the default for training code.
    ``ring``  — the explicit 2(n-1)-step ring (honest ring-latency numbers,
                BASELINE.md metric).
    ``ring2`` — bidirectional ring: two half-payloads in opposite
                directions per step (full-duplex ICI → ~2× ring bandwidth).
    ``naive`` — gather+reduce baseline.
    ``auto``  — pick ring vs naive from the static payload size and axis
                size (:func:`auto_all_reduce_algorithm`): latency-optimal
                one-round gather for small payloads, bandwidth-optimal ring
                for large — for deployments that want the explicit schedules
                (e.g. the wire-API coordinator) with topology awareness.
    """
    op = ReduceOp(op)
    if algorithm == "auto":
        algorithm = auto_all_reduce_algorithm(
            x.size * x.dtype.itemsize, _axis_size(axis_name)
        )
    if algorithm == "ring":
        return ring_all_reduce(x, axis_name, op)
    if algorithm == "ring2":
        return ring2_all_reduce(x, axis_name, op)
    if algorithm == "naive":
        return naive_all_reduce(x, axis_name, op)
    if algorithm != "xla":
        raise ValueError(f"unknown all-reduce algorithm {algorithm!r}")
    if op == ReduceOp.SUM:
        return lax.psum(x, axis_name)
    if op == ReduceOp.AVG:
        return lax.pmean(x, axis_name)
    if op == ReduceOp.MIN:
        return lax.pmin(x, axis_name)
    if op == ReduceOp.MAX:
        return lax.pmax(x, axis_name)
    # XLA has no native product collective; fall back to the ring.
    return ring_all_reduce(x, axis_name, op)


def hierarchical_all_reduce(
    x: jax.Array,
    inner_axis: str,
    outer_axis: str,
    op: ReduceOp = ReduceOp.SUM,
    algorithm: str = "xla",
) -> jax.Array:
    """Topology-aware two-level all-reduce (Blink/TACOS-style hierarchical
    collectives — the reference's §6 Communication literature, SURVEY.md
    §2.4): reduce-scatter over the *inner* (fast, e.g. intra-slice ICI)
    axis, all-reduce only 1/n_inner of the payload over the *outer* (slow,
    e.g. DCN) axis, then all-gather back over the inner axis. The slow hop
    carries n_inner× less data than a flat all-reduce over both axes.

    Result equals ``all_reduce`` over both axes for every :class:`ReduceOp`.
    """
    op = ReduceOp(op)
    n_inner = _axis_size(inner_axis)
    if n_inner == 1:
        return all_reduce(x, outer_axis, op, algorithm)
    inner_op = outer_op = op
    if op == ReduceOp.AVG:
        # average exactly once: SUM through both levels, divide at the end
        inner_op = outer_op = ReduceOp.SUM
    orig_shape, orig_dtype = x.shape, x.dtype
    acc_dtype = (
        jnp.promote_types(orig_dtype, jnp.int32)
        if jnp.issubdtype(orig_dtype, jnp.integer)
        else orig_dtype
    )
    flat = x.astype(acc_dtype).reshape(-1)
    size = flat.shape[0]
    padded = -(-size // n_inner) * n_inner
    if padded != size:
        # pad with the op's identity so pad lanes can't perturb real lanes
        flat = jnp.pad(
            flat, (0, padded - size),
            constant_values=_identity_pad_value(op, acc_dtype),
        )
    shard = reduce_scatter(flat.reshape(n_inner, padded // n_inner), inner_axis, inner_op)
    shard = all_reduce(shard, outer_axis, outer_op, algorithm)
    out = lax.all_gather(shard, inner_axis, axis=0, tiled=False).reshape(-1)[:size]
    if op == ReduceOp.AVG:
        out = out / (n_inner * _axis_size(outer_axis))
    return out.reshape(orig_shape).astype(orig_dtype)


def _identity_pad_value(op: ReduceOp, dtype) -> int | float:
    """The reduction identity for ``op`` on ``dtype`` — what padding must be
    filled with so pad lanes can't perturb real lanes when lanes from
    different ranks combine."""
    op = ReduceOp(op)
    if op == ReduceOp.PROD:
        return 1
    if op in (ReduceOp.MIN, ReduceOp.MAX):
        if jnp.issubdtype(dtype, jnp.floating):
            hi, lo = jnp.inf, -jnp.inf
        else:
            info = jnp.iinfo(dtype)
            hi, lo = info.max, info.min
        return hi if op == ReduceOp.MIN else lo
    return 0  # SUM / AVG


def flat_reduce_scatter(
    flat: jax.Array, axis_name: str, op: ReduceOp = ReduceOp.SUM
) -> tuple[jax.Array, int]:
    """Reduce-scatter a flat vector: rank i is left with contiguous segment
    i of the reduction. Returns ``(shard, padded_size)`` where ``shard`` has
    ``padded_size // n`` elements and ``padded_size`` is the vector length
    rounded up to a multiple of the axis size (identity-padded, so pad lanes
    are inert). The bucketed-gradient primitive: ZeRO-2 grad sync emits one
    of these per bucket (``dsml_tpu.parallel.bucketing``), each an
    independent collective XLA can overlap with remaining backward compute.
    """
    op = ReduceOp(op)
    n = _axis_size(axis_name)
    size = flat.shape[0]
    padded = -(-size // n) * n
    if padded != size:
        flat = jnp.pad(
            flat, (0, padded - size),
            constant_values=_identity_pad_value(op, flat.dtype),
        )
    shard = reduce_scatter(flat.reshape(n, padded // n), axis_name, op)
    return shard.reshape(-1), padded


def flat_all_gather(shard: jax.Array, axis_name: str, size: int) -> jax.Array:
    """Inverse of :func:`flat_reduce_scatter`'s layout: concatenate every
    rank's flat segment and drop the padding, returning the first ``size``
    elements."""
    return lax.all_gather(shard, axis_name, axis=0, tiled=True).reshape(-1)[:size]


def reduce_scatter(x: jax.Array, axis_name: str, op: ReduceOp = ReduceOp.SUM) -> jax.Array:
    """Reduce across ranks, leaving rank i with shard i along axis 0 —
    the first half of the ring all-reduce, exposed for FSDP/ZeRO-style
    sharded optimizers."""
    op = ReduceOp(op)
    n = _axis_size(axis_name)
    if x.shape[0] % n != 0:
        raise ValueError(f"reduce_scatter: leading dim {x.shape[0]} not divisible by axis size {n}")
    if op in (ReduceOp.SUM, ReduceOp.AVG):
        out = lax.psum_scatter(x, axis_name, scatter_dimension=0, tiled=True)
        if op == ReduceOp.AVG:
            out = out / n
        return out
    # Non-additive ops: reduce fully, then slice this rank's shard.
    full = naive_all_reduce(x, axis_name, op)
    shard = x.shape[0] // n
    return lax.dynamic_slice_in_dim(full, lax.axis_index(axis_name) * shard, shard, axis=0)


def all_gather(x: jax.Array, axis_name: str, axis: int = 0, tiled: bool = True) -> jax.Array:
    """Concatenate every rank's ``x`` along ``axis``."""
    return lax.all_gather(x, axis_name, axis=axis, tiled=tiled)


def all_to_all(x: jax.Array, axis_name: str, split_axis: int, concat_axis: int) -> jax.Array:
    """All-to-all: split ``x`` n-ways along ``split_axis``, exchange, concat
    along ``concat_axis`` — the Ulysses sequence-parallelism primitive
    (SURVEY.md §5.7: heads↔sequence re-sharding)."""
    return lax.all_to_all(x, axis_name, split_axis=split_axis, concat_axis=concat_axis, tiled=True)


def ppermute_ring(x: jax.Array, axis_name: str, shift: int = 1) -> jax.Array:
    """Rotate ``x`` ``shift`` hops around the ring (K/V rotation for ring
    attention; the reference's BeginSend→next-rank intent, gpu_sim.proto:38)."""
    n = _axis_size(axis_name)
    perm = [(i, (i + shift) % n) for i in range(n)]
    return lax.ppermute(x, axis_name, perm)


# ---------------------------------------------------------------------------
# Host-facing API (used by the gRPC coordinator)
# ---------------------------------------------------------------------------


@functools.lru_cache(maxsize=None)
def _stacked_all_reduce_fn(mesh: Mesh, axis_name: str, op: ReduceOp, algorithm: str):
    # Keyed per (mesh, axis, op, algorithm); jax.jit itself specializes
    # per input shape/dtype and retains those executables.
    spec = P(axis_name)

    @functools.partial(
        jax.jit,
        in_shardings=NamedSharding(mesh, spec),
        out_shardings=NamedSharding(mesh, spec),
        donate_argnums=(0,),
    )
    @functools.partial(
        jax.shard_map, mesh=mesh, in_specs=spec, out_specs=spec, check_vma=False
    )
    def fn(stacked):  # stacked: [1, ...] per-device shard
        return all_reduce(stacked[0], axis_name, op, algorithm)[None]

    return fn


@functools.lru_cache(maxsize=None)
def _buffer_all_reduce_fn(mesh: Mesh, axis_name: str, op: ReduceOp, algorithm: str, dtype_str: str):
    """Jitted byte-buffer all-reduce: per-shard [1, count] uint8 in/out,
    reinterpreted as ``dtype_str`` for the reduction. NO donation — the
    inputs are the device servers' live registry buffers, which must stay
    valid for later Memcpy reads."""
    spec = P(axis_name)
    dt = jnp.dtype(dtype_str)

    @functools.partial(
        jax.jit,
        in_shardings=NamedSharding(mesh, spec),
        out_shardings=NamedSharding(mesh, spec),
    )
    @functools.partial(
        jax.shard_map, mesh=mesh, in_specs=spec, out_specs=spec, check_vma=False
    )
    def fn(stacked_u8):  # [1, count] uint8 per shard
        flat = stacked_u8[0]
        if dt.itemsize > 1:
            x = lax.bitcast_convert_type(flat.reshape(-1, dt.itemsize), dt)
        else:
            x = lax.bitcast_convert_type(flat, dt)
        x = all_reduce(x, axis_name, op, algorithm)
        u8 = lax.bitcast_convert_type(x, jnp.uint8)
        return u8.reshape(-1)[None]

    return fn


def device_buffers_all_reduce(
    buffers: Sequence[jax.Array],
    mesh: Mesh,
    op: ReduceOp = ReduceOp.SUM,
    algorithm: str = "ring",
    dtype: str = "float32",
) -> list[jax.Array]:
    """All-reduce per-chip byte buffers WITHOUT any host round-trip.

    ``buffers[i]`` is a flat uint8 ``jax.Array`` resident on
    ``mesh.devices.flat[i]`` (the device server's registry buffer, viewed as
    ``dtype`` for the reduction). The shards are assembled into one global
    array in place (``jax.make_array_from_single_device_arrays`` — no
    copies), the jitted ring/psum program runs over the mesh, and the result
    comes back as one on-device array per chip, ready for
    ``BufferRegistry.put_array``. This is the coordinator's local-chip fast
    path: the reference shipped every ring step through gRPC + host memory
    (``gpu_coordinator_server.go:427-515``); here the ends stay in HBM too.
    """
    axis_name = mesh.axis_names[0]
    n = mesh.shape[axis_name]
    if len(buffers) != n:
        raise ValueError(f"expected {n} buffers for mesh axis {axis_name!r}, got {len(buffers)}")
    count = buffers[0].shape[0]
    if count % np.dtype(dtype).itemsize:
        raise ValueError(f"{count} bytes is not a multiple of {dtype} itemsize")
    for i, b in enumerate(buffers):
        if b.ndim != 1 or b.dtype != jnp.uint8 or b.shape[0] != count:
            raise ValueError(f"buffer {i}: expected flat uint8[{count}], got {b.dtype}{b.shape}")
    sharding = NamedSharding(mesh, P(axis_name))
    global_arr = jax.make_array_from_single_device_arrays(
        (n, count), sharding, [b.reshape(1, count) for b in buffers]
    )
    out = _buffer_all_reduce_fn(mesh, axis_name, ReduceOp(op), algorithm, str(np.dtype(dtype)))(
        global_arr
    )
    per_device = {s.device: s.data for s in out.addressable_shards}
    return [per_device[d].reshape(-1) for d in mesh.devices.flat]


def make_stacked_all_reduce(
    mesh: Mesh, op: ReduceOp = ReduceOp.SUM, algorithm: str = "ring", axis_name: str | None = None
) -> Callable[[np.ndarray], jax.Array]:
    """Build a jitted all-reduce over a host-side stack of per-device buffers.

    Input: array of shape ``[n_devices, ...]`` where slice i is device i's
    contribution (the coordinator's view of one buffer per communicator rank).
    Output: same shape, every slice equal to the reduction — i.e. the
    postcondition the reference's ``AllReduceRing`` advertised but never
    delivered (SURVEY.md §8.4). The whole 2(n-1)-step ring runs as ONE jitted
    program over the mesh; the host only pays one H2D + one D2H.
    """
    axis_name = axis_name or mesh.axis_names[0]
    op = ReduceOp(op)

    def run(stacked: np.ndarray) -> jax.Array:
        n = mesh.shape[axis_name]
        if stacked.shape[0] != n:
            raise ValueError(f"expected leading dim {n}, got {stacked.shape}")
        fn = _stacked_all_reduce_fn(mesh, axis_name, op, algorithm)
        return fn(jnp.asarray(stacked))

    return run
