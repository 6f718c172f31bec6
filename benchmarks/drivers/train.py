"""The loop for cells of kind ``train``: the hybrid train step as a user calls
it (``build_mesh`` -> ``init_hybrid`` -> ``make_hybrid_train_step``), one
program, one process.

Set-up is everything before the window: weights on the device from the seed,
the plain reference's loss on the first batch, trace + lower + compile (or the
cache's read) of the cell's one step program, ``warm_steps`` real steps. Then
steps are dispatched back to back for ``--seconds``, each on a fresh batch made
on the host inside the loop; the host fetches each loss one step late, so one
step is always queued behind the one running and the device never waits for
the host. If the window ends before ``loss_step`` the run goes on to it,
outside the timing. A traced run then records ``TRACED_STEPS`` more steps under
``jax.profiler`` and reduces them (``benchmarks/trace_reduce.py``).
"""

from __future__ import annotations

import importlib
import math
import shutil
import statistics
import time

import jax
import optax

from benchmarks import flops, harness, trace_reduce
from dsml_tpu.parallel.hybrid import init_hybrid, make_hybrid_train_step
from dsml_tpu.parallel.mesh import MeshSpec, build_mesh

TRACED_STEPS = 5


class StepLoop:
    """Closed loop, one step in flight. Step numbers are 1-based and count
    every step since the weights were made."""

    def __init__(self, step, params, opt_state, generator):
        self.step, self.params, self.opt_state, self.generator = step, params, opt_state, generator
        self.next_step = 1
        self.losses: list[float] = []   # losses[k - 1] is step k's
        self.ready_at: list[float] = []  # host clock when step k's loss arrived
        self.batch_s: list[float] = []
        self.dispatch_s: list[float] = []

    def _fetch(self, loss) -> None:
        with jax.profiler.TraceAnnotation("bench.fetch"):
            self.losses.append(float(loss))
        self.ready_at.append(time.perf_counter())

    def advance(self, more) -> None:
        """Dispatch while ``more()``; return with every loss fetched."""
        pending = None
        while more():
            t0 = time.perf_counter()
            with jax.profiler.TraceAnnotation("bench.batch"):
                x, y = self.generator.batch(self.next_step)
            t1 = time.perf_counter()
            with jax.profiler.TraceAnnotation("bench.dispatch"):
                self.params, self.opt_state, loss = self.step(self.params, self.opt_state, x, y)
            self.dispatch_s.append(time.perf_counter() - t1)
            self.batch_s.append(t1 - t0)
            self.next_step += 1
            if pending is not None:
                self._fetch(pending)
            pending = loss
        if pending is not None:
            self._fetch(pending)


def _step_memory(compiled) -> dict:
    mem = compiled.memory_analysis()
    sizes = {k: getattr(mem, f"{k}_size_in_bytes") for k in ("argument", "output", "alias", "temp")}
    sizes["live"] = sizes["argument"] + sizes["temp"] + sizes["output"] - sizes["alias"]
    return sizes


def build_step(config: dict, traffic: dict, devices: list, rehearse: bool = False):
    """The cell's model, mesh, optimizer and jitted step, as a user builds them."""
    family = importlib.import_module(f"benchmarks.families.{config['family']}")
    model = family.program_model(config, rehearse)
    mesh = build_mesh(MeshSpec(**traffic["mesh"]), devices)
    optimizer = getattr(optax, traffic["step"]["optimizer"])(traffic["step"]["learning_rate"])
    step = make_hybrid_train_step(model, optimizer, mesh, attn_impl=traffic["step"]["attn_impl"])
    return family, model, mesh, optimizer, step


def run(r: harness.Run) -> dict:
    traffic, chips = r.traffic, r.traffic["chips"]
    devices = jax.devices()[:chips]
    family, model, mesh, optimizer, step = build_step(r.config, traffic, devices, r.rehearse)
    shape = family.shape(r.config, r.rehearse)
    seq = min(traffic["seq"], shape["max_seq"]) if r.rehearse else traffic["seq"]
    rows = (2 if r.rehearse else traffic["rows_per_chip"]) * chips
    if seq > shape["max_seq"]:
        raise ValueError(f"traffic seq {seq} exceeds the configuration's {shape['max_seq']} positions")
    tokens_per_step = rows * seq
    harness.note(phase="plan", rows=rows, seq=seq, tokens_per_step=tokens_per_step, shape=shape,
                 mesh=traffic["mesh"], step=traffic["step"], rehearse=r.rehearse)

    t0 = time.perf_counter()
    params, opt_state = init_hybrid(model, optimizer, mesh, seed=r.seed)
    jax.block_until_ready((params, opt_state))
    init_s = time.perf_counter() - t0

    generator = importlib.import_module(
        f"benchmarks.traffic.{traffic['data']['generator']}"
    ).Generator(traffic["data"], r.seed, shape["vocab_size"], rows, seq)
    x1, y1 = generator.batch(1)
    t0 = time.perf_counter()
    reference_loss = family.reference_loss(r.config, params, x1, y1, r.rehearse)
    reference_s = time.perf_counter() - t0

    cache_before = r.compile_watch.snapshot()
    t0 = time.perf_counter()
    lowered = step.lower(params, opt_state, x1, y1)
    lower_s = time.perf_counter() - t0
    t0 = time.perf_counter()
    compiled = lowered.compile()
    compile_s = time.perf_counter() - t0
    step_memory = _step_memory(compiled)
    del lowered, compiled
    harness.note(phase="compile", init_s=init_s, reference_s=reference_s, lower_s=lower_s,
                 compile_s=compile_s, compile_cache=r.compile_watch.since(cache_before),
                 step_memory_bytes=step_memory)

    loop = StepLoop(step, params, opt_state, generator)
    del params, opt_state
    warm_ms = []
    for _ in range(traffic["warm_steps"]):
        t0 = time.perf_counter()
        loop.advance(lambda n=loop.next_step: loop.next_step == n)
        warm_ms.append((time.perf_counter() - t0) * 1e3)
    harness.note(phase="warm", step_ms=warm_ms, losses=loop.losses,
                 compile_cache=r.compile_watch.since(cache_before))

    in_window = r.compile_watch.snapshot()
    first = loop.next_step
    t_start = time.perf_counter()
    setup_s = t_start - r.t_process_start
    loop.advance(lambda: (loop.ready_at[-1] if loop.next_step > first + 1 else t_start)
                 - t_start < r.seconds)
    n_window = loop.next_step - first
    window_s = loop.ready_at[-1] - t_start
    compiled_in_window = r.compile_watch.since(in_window)
    if compiled_in_window["requests"] or compiled_in_window["backend_compiles"]:
        raise RuntimeError(f"a program compiled inside the measured window: {compiled_in_window}")
    ready = loop.ready_at[first - 1:]
    step_ms = [(b - a) * 1e3 for a, b in zip(ready, ready[1:])]
    tok_s_chip = n_window * tokens_per_step / window_s / chips

    loss_step = traffic["loss_step"]
    loop.advance(lambda: loop.next_step <= loss_step)
    harness.note(phase="window", steps=n_window, window_s=window_s, first_step=first,
                 steps_to_loss_step=max(0, loss_step - (first + n_window - 1)),
                 losses=loop.losses[first - 1:])

    traced = None
    if r.trace:
        shutil.rmtree(r.trace_dir, ignore_errors=True)
        options = jax.profiler.ProfileOptions()
        options.python_tracer_level = 0  # the benchmark's TraceAnnotations are host events, kept
        stop_at = loop.next_step + TRACED_STEPS
        jax.profiler.start_trace(r.trace_dir, profiler_options=options)
        try:
            loop.advance(lambda: loop.next_step < stop_at)
        finally:
            jax.profiler.stop_trace()
        events = trace_reduce.load(r.trace_dir)
        if events["devices"] or not r.rehearse:  # a CPU rehearsal's trace has no device plane
            traced = trace_reduce.reduce(events, TRACED_STEPS)

    check = traffic["check"]
    lo, hi = check["fall_window"]
    late = statistics.fmean(loop.losses[lo - 1:hi])
    failed = sum(not math.isfinite(v) for v in loop.losses)
    checks = {
        "reference": abs(loop.losses[0] - reference_loss) <= check["reference_tolerance_nats"],
        "finite": failed == 0,
        "fell": late < loop.losses[0] - check["fall_margin_nats"],
    }
    harness.note(phase="check", checks=checks, step1_loss=loop.losses[0],
                 reference_loss=reference_loss, reference_diff=loop.losses[0] - reference_loss,
                 late_mean_loss=late)

    stats_peaks = [(d.memory_stats() or {}).get("peak_bytes_in_use", 0) for d in devices]
    peak = None if r.rehearse else harness.peak_for(devices[0].device_kind)
    notes = {
        "chips": chips, "tokens_per_step": tokens_per_step, "peak": peak,
        "tok_s_chip": tok_s_chip, "step_ms": step_ms,
        "dispatch_ms": [s * 1e3 for s in loop.dispatch_s[first - 1:first - 1 + n_window]],
        "batch_ms": [s * 1e3 for s in loop.batch_s[first - 1:first - 1 + n_window]],
        "init_s": init_s, "lower_s": lower_s, "compile_s": compile_s,
        "step_memory_bytes": step_memory,
        "flops_per_step": flops.train_flops(shape, tokens_per_step, seq),
        "attention_flops_per_step": flops.attention_train_flops(shape, tokens_per_step, seq),
        "attention_bytes_per_step": flops.attention_train_bytes(shape, tokens_per_step),
    }
    return {
        "correct": all(checks.values()),
        "attempted": loop.next_step - 1,
        "failed": failed,
        "end_to_end": {"tok_s_chip": tok_s_chip, f"loss_at_{loss_step}": loop.losses[loss_step - 1],
                       "setup_s": setup_s},
        # memory_stats' peak leaves the step's temp out on this runtime (PERF.md §5), so the
        # compiler's plan for the step stands in where it is the larger
        "memory_peak_bytes": max([step_memory["live"], *stats_peaks]),
        "memory_stats_peak_bytes": stats_peaks,
        "notes": notes,
        "trace": traced,
    }
