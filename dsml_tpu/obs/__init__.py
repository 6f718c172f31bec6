"""dsml_tpu.obs — unified observability: metrics, spans, goodput/MFU.

One subsystem for the accounting the reference coordinator treated as its
core product (device health, per-algorithm all-reduce latency) and the
accounting a production TPU trainer actually needs (step-time breakdown,
goodput across preemptions, MFU):

- :mod:`~dsml_tpu.obs.registry` — process-wide thread-safe metrics
  registry (counters / gauges / fixed-bound histograms, labeled), JSONL +
  Prometheus-text exposition. DISABLED by default (``DSML_OBS=1`` or
  :func:`enable` turns it on); disabled writes cost one branch.
- :mod:`~dsml_tpu.obs.spans` — nestable host-side span tracer with
  ``block_until_ready`` fencing, Chrome trace-event JSON export, per-span
  p50/p90 summaries.
- :mod:`~dsml_tpu.obs.step_stats` — per-step phase breakdown, goodput
  (productive ÷ wall across preemption/restore), MFU from
  ``models.common`` FLOP estimates.
- :mod:`~dsml_tpu.obs.export` — rotation-safe JSONL sink
  (:class:`MetricsLogger`) + opt-in HTTP ``/metrics`` endpoint.

Failure forensics (``docs/OBSERVABILITY.md`` § Failure forensics):

- :mod:`~dsml_tpu.obs.flight_recorder` — bounded ring of recent
  structured events; dumps a self-contained postmortem bundle (events
  JSONL + registry snapshot + Chrome trace + env fingerprint + log tail
  + all-thread stacks) on unhandled exception, SIGTERM, or on demand.
- :mod:`~dsml_tpu.obs.sentinels` — NaN/Inf-loss, grad-norm-explosion and
  loss-spike sentinels with per-sentinel ``warn``/``dump``/``halt``
  policies (``DSML_SENTINELS``), checked at the trainer's existing
  ``loss_sync`` point.
- :mod:`~dsml_tpu.obs.hangwatch` — armable deadline watchdog
  (``DSML_HANGWATCH``): trainer per loss-sync window, coordinator per
  wire op, checkpoint writer per commit; expiry dumps stacks + a bundle.

Cluster plane (``docs/OBSERVABILITY.md`` § Cluster):

- :mod:`~dsml_tpu.obs.cluster` — cross-process aggregation: identity-
  stamped snapshots, exact-sum counter / bucket-wise histogram merge into
  ONE fleet exposition with ``host``/``pid``/``role`` labels, fleet
  goodput + straggler ranking, and Chrome-trace stitching with
  handshake-based clock-offset alignment (HTTP scrape of
  ``start_metrics_server``'s ``/cluster.json`` or gRPC pull/push over the
  ``comm/`` ObsPlane service).
- :mod:`~dsml_tpu.obs.regress` — perf-regression gate over the committed
  ``BENCH_r*.json`` history (median ± k·MAD noise bands); ``python -m
  dsml_tpu.obs.regress`` exits nonzero on regression and exports the
  calibrated collective-latency profile for the cost-model planner.

Memory ledger (``docs/OBSERVABILITY.md`` § Memory ledger):

- :mod:`~dsml_tpu.obs.memory` — per-subsystem device-byte attribution
  (:class:`MemoryLedger`): static claims at allocation sites (params /
  optimizer / EF residuals / measured activation temps) + weakly-held
  live sources (KV page pools, migration/checkpoint staging), reconciled
  against ``jax.Device.memory_stats()`` at scrape time with an
  ``hbm_unattributed_bytes`` residual gauge and explicit provenance
  (``hbm_source``). Per-step peak watermarks ride postmortem bundles
  (``memory.json``); OOM-shaped crashes dump through
  :func:`~dsml_tpu.obs.memory.maybe_dump_oom`.

Request tracing + SLO budgets (``docs/OBSERVABILITY.md`` § Request
tracing & SLO budgets):

- :class:`~dsml_tpu.obs.spans.TraceContext` — request-scoped trace
  identity minted at ``Router.submit`` and propagated through prefill
  dispatch, the handoff codec/donor headers, decode injection, and
  retire/requeue; every stage emits trace-tagged spans + Chrome flow
  events so the stitched timeline renders one request as a causal chain.
- :mod:`~dsml_tpu.obs.slo` — per-SLOClass SLI windows, rolling error
  budgets with multi-window (fast/slow) burn-rate status, per-class
  goodput counters, and the p99 tail-attribution report (which stage —
  queue/prefill/handoff/first-decode/decode — dominates the tail);
  merged fleet-wide by ``MergedView.report()``. Tail-bucket histogram
  samples carry trace_id EXEMPLARS in the JSONL/``/metrics.json``
  expositions.

Metric names, label sets, and the span taxonomy are specified in
``docs/OBSERVABILITY.md``.
"""

from __future__ import annotations

from dsml_tpu.obs import flight_recorder, hangwatch, sentinels  # noqa: F401
from dsml_tpu.obs.export import (  # noqa: F401
    MetricsLogger,
    MetricsServer,
    start_metrics_server,
)
from dsml_tpu.obs.flight_recorder import (  # noqa: F401
    FlightRecorder,
    get_flight_recorder,
)
from dsml_tpu.obs.hangwatch import HangWatch, TrailingDeadline, get_hangwatch  # noqa: F401
from dsml_tpu.obs.memory import (  # noqa: F401
    MemoryLedger,
    get_memory_ledger,
)
from dsml_tpu.obs.registry import (  # noqa: F401
    DEFAULT_LATENCY_BUCKETS_MS,
    Counter,
    Gauge,
    Histogram,
    ObsUnavailable,
    Registry,
    enabled,
    get_registry,
)
from dsml_tpu.obs.registry import disable as _registry_disable
from dsml_tpu.obs.registry import enable as _registry_enable
from dsml_tpu.obs.sentinels import (  # noqa: F401
    SentinelConfig,
    SentinelTripped,
    TrainingSentinels,
)
from dsml_tpu.obs.spans import (  # noqa: F401
    SpanTracer,
    TraceContext,
    get_tracer,
    span,
)
from dsml_tpu.obs.step_stats import (  # noqa: F401
    STEP_PHASES,
    GoodputTracker,
    StepBreakdown,
    mfu,
)

__all__ = [
    "Registry", "Counter", "Gauge", "Histogram", "ObsUnavailable",
    "get_registry", "enable", "disable", "enabled",
    "DEFAULT_LATENCY_BUCKETS_MS",
    "SpanTracer", "TraceContext", "span", "get_tracer",
    "StepBreakdown", "GoodputTracker", "mfu", "STEP_PHASES",
    "MetricsLogger", "MetricsServer", "start_metrics_server",
    "record_collective_plan", "observe_collective_latency_ms",
    "observe_recovery_ms", "record_quant_sync_bytes",
    "FlightRecorder", "get_flight_recorder", "dump_postmortem",
    "MemoryLedger", "get_memory_ledger",
    "SentinelConfig", "SentinelTripped", "TrainingSentinels",
    "HangWatch", "TrailingDeadline", "get_hangwatch",
    "ClockSync", "ClusterAggregator", "merge_snapshots", "snapshot",
    "stitch_traces", "trace_summary",
]

# cluster-plane names resolve lazily (PEP 562): ``python -m
# dsml_tpu.obs.cluster`` would otherwise warn about the module being
# imported as a side effect of its own package __init__
_CLUSTER_NAMES = ("ClockSync", "ClusterAggregator", "merge_snapshots",
                  "snapshot", "stitch_traces", "trace_summary")


def __getattr__(name: str):
    if name in _CLUSTER_NAMES:
        from dsml_tpu.obs import cluster as _cluster

        return getattr(_cluster, name)
    raise AttributeError(f"module {__name__!r} has no attribute {name!r}")


def enable(forensics: bool = True) -> None:
    """Turn observability on: flip the default registry live and (unless
    ``forensics=False``) install the failure-forensics layer — the
    flight-recorder crash hooks (``sys.excepthook`` / SIGTERM /
    ``faulthandler``) and the ring-buffer log handler whose tail rides in
    every postmortem bundle. ``disable()`` tears all of it down."""
    _registry_enable()
    if forensics:
        from dsml_tpu.utils.logging import install_ring_handler

        install_ring_handler()
        flight_recorder.install()


def disable() -> None:
    """Turn observability off and tear down the forensics hooks installed
    by :func:`enable` (prior excepthook/signal/faulthandler dispositions
    are restored)."""
    from dsml_tpu.utils.logging import uninstall_ring_handler

    flight_recorder.uninstall()
    uninstall_ring_handler()
    _registry_disable()


def dump_postmortem(reason: str = "on_demand",
                    directory: str | None = None) -> str:
    """Write a postmortem bundle NOW (works even with the registry
    disabled); returns the bundle directory."""
    return get_flight_recorder().dump(reason, directory=directory)


def record_collective_plan(algorithm: str, tree, bucket_size_mb,
                           axis: str = "dp",
                           registry: Registry | None = None) -> None:
    """Record a gradient-sync bucket plan's shape (bucket count, per-bucket
    and total bytes) labeled by collective algorithm + mesh axis.

    Called from INSIDE step builders at trace time: shapes/dtypes are
    static there, so this runs once per compilation — never per step —
    and costs nothing while tracing is the price already being paid.
    ``bucket_size_mb=None`` records ONE bucket of the tree's total bytes
    — the dp/hybrid single-buffer ``ravel_pytree`` path (raw leaf bytes;
    its dtype promotion is not modeled). Callers whose ``None`` means
    per-dtype buckets (zero2) resolve it to ``float("inf")`` first."""
    reg = registry if registry is not None else get_registry()
    if not reg.enabled:
        return
    if bucket_size_mb is None:
        import math

        import jax
        import jax.numpy as jnp

        sizes = [sum(
            math.prod(l.shape) * jnp.dtype(jnp.result_type(l)).itemsize
            for l in jax.tree.leaves(tree)
        )]
        n_buckets = 1
    else:
        from dsml_tpu.parallel.bucketing import plan_buckets

        plan = plan_buckets(tree, bucket_size_mb)
        sizes = [plan.bucket_nbytes(b) for b in range(plan.n_buckets)]
        n_buckets = plan.n_buckets
    labels = {"algorithm": algorithm, "axis": axis}
    reg.counter(
        "collective_sync_compiles_total",
        "gradient-sync step compilations", labels=("algorithm", "axis"),
    ).inc(**labels)
    reg.gauge(
        "collective_sync_buckets",
        "buckets per gradient sync", labels=("algorithm", "axis"),
    ).set(n_buckets, **labels)
    reg.gauge(
        "collective_sync_bytes",
        "total gradient bytes per sync", labels=("algorithm", "axis"),
    ).set(sum(sizes), **labels)
    hist = reg.histogram(
        "collective_bucket_bytes",
        "per-bucket payload bytes", labels=("algorithm", "axis"),
        buckets=(1 << 16, 1 << 18, 1 << 20, 1 << 22, 1 << 24, 1 << 26, 1 << 28),
    )
    for nbytes in sizes:
        hist.observe(nbytes, **labels)
    # one trace-time event per compile: a postmortem shows WHICH sync plan
    # (algorithm / bucket count / payload) the dying step was running.
    # Default-registry callers only — a private registry (bench isolation)
    # must not leak its plans into the process-global ring
    if reg is get_registry():
        flight_recorder.record(
            "collective_plan", algorithm=algorithm, axis=axis,
            buckets=n_buckets, bytes=int(sum(sizes)),
        )


def record_quant_sync_bytes(bytes_by_scheme: dict, algorithm: str,
                            axis: str = "dp",
                            registry: Registry | None = None) -> None:
    """One quantized gradient sync's wire bytes →
    ``collective_quant_bytes_total{scheme,algorithm,axis}``.

    ``bytes_by_scheme`` is the ANALYTIC per-sync byte count from
    ``parallel.bucketing.plan_quant_wire_bytes`` (static shapes ⇒ exact).
    The dp/zero2 frontends call this once per step from the host-side
    dispatch wrapper — a dict walk and one no-op-able counter write, never
    a device sync — so the counter is a true cumulative total, unlike the
    trace-time plan gauges which record once per compile."""
    reg = registry if registry is not None else get_registry()
    if not reg.enabled or not bytes_by_scheme:
        return
    c = reg.counter(
        "collective_quant_bytes_total",
        "wire bytes shipped by quantized gradient syncs (analytic per-sync "
        "count; fp32 rows are the uncompressed buckets riding along)",
        labels=("scheme", "algorithm", "axis"),
    )
    for scheme, nbytes in bytes_by_scheme.items():
        c.inc(nbytes, scheme=scheme, algorithm=algorithm, axis=axis)


def observe_recovery_ms(stage: str, ms: float,
                        registry: Registry | None = None) -> None:
    """One elastic-recovery latency sample →
    ``controller_recovery_ms{stage}`` (stages: ``reconfigure`` /
    ``checkpoint_fallback`` / ``grow_keep`` / ``grow_replay``) — the
    distribution behind the chaos report's recovery p50/p99
    (``python -m dsml_tpu.runtime.chaos``, docs/ELASTIC.md)."""
    reg = registry if registry is not None else get_registry()
    if not reg.enabled:
        return
    reg.histogram(
        "controller_recovery_ms",
        "elastic-controller recovery latency", labels=("stage",),
    ).observe(ms, stage=stage)


def observe_collective_latency_ms(algorithm: str, ms: float,
                                  payload_bytes: int | None = None,
                                  axis: str = "dp",
                                  registry: Registry | None = None) -> None:
    """One measured collective latency sample →
    ``collective_latency_ms{algorithm,axis}`` (the EQuARX-style
    per-algorithm accounting surface; ``utils.tracing.ring_latency_ms``
    feeds it)."""
    reg = registry if registry is not None else get_registry()
    if not reg.enabled:
        return
    reg.histogram(
        "collective_latency_ms",
        "measured all-reduce latency", labels=("algorithm", "axis"),
    ).observe(ms, algorithm=algorithm, axis=axis)
    if payload_bytes is not None:
        reg.counter(
            "collective_latency_sampled_bytes_total",
            "payload bytes of measured collectives", labels=("algorithm", "axis"),
        ).inc(payload_bytes, algorithm=algorithm, axis=axis)
