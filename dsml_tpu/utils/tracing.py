"""Tracing/profiling: XLA profiler hooks + collective latency measurement.

The reference's entire observability story is wall-clock ``time.Now()``
pairs around the naive all-reduce (SURVEY.md §5.1). Here:

- :func:`trace` — context manager around ``jax.profiler`` producing a
  TensorBoard-loadable XLA trace (per-op device timelines, fusion view).
  Capture failures raise
  :class:`~dsml_tpu.obs.ObsUnavailable` with remediation text instead of
  an opaque backend traceback.
- :func:`time_jitted` — p50/p90 wall latency of an already-jitted callable
  with proper warmup + ``block_until_ready`` fencing. Samples feed the
  observability registry (``time_jitted_ms`` histogram) when it is
  enabled.
- :func:`ring_latency_ms` — the BASELINE.md headline: p50 latency of the
  2(n-1)-step ring all-reduce at a given payload size, timed as ONE device
  program (no host staging in the loop). Samples feed
  ``collective_latency_ms{algorithm=...}``, the per-algorithm accounting
  surface.
"""

from __future__ import annotations

import contextlib
import time
from typing import Callable

import numpy as np

from dsml_tpu.obs import ObsUnavailable, get_registry, observe_collective_latency_ms
from dsml_tpu.utils.logging import get_logger

log = get_logger("tracing")


@contextlib.contextmanager
def trace(log_dir: str):
    """Capture an XLA profiler trace into ``log_dir``.

    The capture can fail in environment-dependent ways (no profiler
    backend linked into the CPU wheel, a second concurrent capture); each
    surfaces as :class:`ObsUnavailable` naming the fix instead of a raw
    backend stack."""
    import jax

    def _unavailable(stage: str, e: Exception) -> ObsUnavailable:
        return ObsUnavailable(
            f"jax.profiler trace {stage} failed on this jax build "
            f"({jax.__version__}): {e!r}. Remediation: ensure no other "
            "capture is active, that the backend links a profiler "
            "(CPU wheels may not), and that the device is reachable; for "
            "host-side timing that always works, use dsml_tpu.obs.span "
            "(Chrome trace-event export) instead."
        )

    try:
        jax.profiler.start_trace(log_dir)
    except Exception as e:  # noqa: BLE001 — backend-dependent failure set
        raise _unavailable("start", e) from e
    body_failed = False
    try:
        yield log_dir
    except BaseException:
        body_failed = True
        raise
    finally:
        try:
            jax.profiler.stop_trace()
        except Exception as e:  # noqa: BLE001
            if body_failed:
                # the body's exception is already propagating — a raise here
                # would REPLACE it with the (secondary) capture failure
                log.warning("profiler stop_trace failed during unwind: %r", e)
            else:
                raise _unavailable("stop", e) from e
        else:
            log.info("profiler trace written to %s", log_dir)


def time_jitted(fn: Callable, *args, iters: int = 20, warmup: int = 3) -> dict:
    """Latency stats (ms) of ``fn(*args)``; the result must be a jax array
    (or pytree with one leaf to fence on)."""
    import jax

    def fence(out):
        jax.tree.leaves(out)[0].block_until_ready()

    for _ in range(warmup):
        fence(fn(*args))
    samples = []
    for _ in range(iters):
        t0 = time.perf_counter()
        fence(fn(*args))
        samples.append((time.perf_counter() - t0) * 1000.0)
    arr = np.asarray(samples)
    reg = get_registry()
    if reg.enabled:
        hist = reg.histogram("time_jitted_ms", "time_jitted wall samples")
        for ms in samples:
            hist.observe(ms)
    return {
        "p50_ms": float(np.percentile(arr, 50)),
        "p90_ms": float(np.percentile(arr, 90)),
        "mean_ms": float(arr.mean()),
        "iters": iters,
        "samples_ms": [round(s, 6) for s in samples],
    }


def ring_latency_ms(mesh, payload_bytes: int = 1 << 20, algorithm: str = "ring") -> dict:
    """p50 latency of an all-reduce of ``payload_bytes`` per device over
    ``mesh`` (default 1 MB — the reference's benchmark payload, which it
    'reduced' in 8 ms of simulated loopback; this number is a real
    collective). The buffers stay on device; only the timing fence touches
    the host."""
    import jax
    import jax.numpy as jnp
    from jax.sharding import NamedSharding, PartitionSpec as P

    from dsml_tpu.ops.collectives import ReduceOp, all_reduce

    axis = mesh.axis_names[0] if len(mesh.axis_names) == 1 else "dp"
    n = mesh.shape[axis]
    elems = payload_bytes // 4

    spec = P(axis)
    fn = jax.jit(
        jax.shard_map(
            lambda x: all_reduce(x[0], axis, ReduceOp.SUM, algorithm)[None],
            mesh=mesh, in_specs=spec, out_specs=spec, check_vma=False,
        ),
        in_shardings=NamedSharding(mesh, spec),
        out_shardings=NamedSharding(mesh, spec),
    )
    x = jax.device_put(
        jnp.ones((n, elems), jnp.float32), NamedSharding(mesh, spec)
    )
    stats = time_jitted(fn, x)
    for ms in stats.pop("samples_ms"):
        observe_collective_latency_ms(
            algorithm, ms, payload_bytes=payload_bytes, axis=axis
        )
    stats.update(payload_bytes=payload_bytes, devices=n, algorithm=algorithm)
    return stats
