"""The observability subsystem (``dsml_tpu/obs/``, docs/OBSERVABILITY.md):
registry correctness under concurrency, exposition formats, Chrome
trace-event schema, goodput math across a simulated preemption+restore,
disabled-mode no-op behavior, and the wiring into the hot paths.
"""

from __future__ import annotations

import io
import json
import threading
import urllib.request

import numpy as np
import pytest

from dsml_tpu.obs import (
    GoodputTracker,
    MetricsLogger,
    ObsUnavailable,
    Registry,
    SpanTracer,
    StepBreakdown,
    mfu,
    start_metrics_server,
)


# ---------------------------------------------------------------------------
# registry
# ---------------------------------------------------------------------------


def test_counter_gauge_histogram_basics():
    reg = Registry(enabled=True)
    c = reg.counter("events_total", "help text", labels=("kind",))
    c.inc(kind="a")
    c.inc(2.5, kind="a")
    c.inc(kind="b")
    assert c.value(kind="a") == 3.5
    assert c.value(kind="b") == 1.0
    assert c.value(kind="never") == 0.0
    with pytest.raises(ValueError):
        c.inc(-1, kind="a")  # counters are monotonic
    with pytest.raises(ValueError):
        c.inc(wrong_label="a")

    g = reg.gauge("depth")
    assert g.value() is None
    g.set(7)
    g.set(3)
    assert g.value() == 3.0

    h = reg.histogram("lat_ms", buckets=(1.0, 10.0, 100.0))
    for v in (0.5, 1.0, 5.0, 50.0, 5000.0):
        h.observe(v)
    s = h.summary()
    assert s["count"] == 5
    assert s["sum"] == pytest.approx(5056.5)
    assert s["p50"] == 5.0

    # get-or-create returns the same object; kind/label conflicts raise
    assert reg.counter("events_total", labels=("kind",)) is c
    with pytest.raises(ValueError):
        reg.gauge("events_total")
    with pytest.raises(ValueError):
        reg.counter("events_total", labels=("other",))


def test_concurrent_writers_exact_totals():
    """Thread hammer: counts/observations from racing writers land exactly."""
    reg = Registry(enabled=True)
    c = reg.counter("hits_total", labels=("worker",))
    h = reg.histogram("obs_ms")
    n_threads, n_iter = 8, 1000

    def work(w: int):
        for i in range(n_iter):
            c.inc(worker=str(w % 2))  # two contended label series
            h.observe(float(i % 7))

    threads = [threading.Thread(target=work, args=(w,)) for w in range(n_threads)]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    assert c.value(worker="0") + c.value(worker="1") == n_threads * n_iter
    assert h.summary()["count"] == n_threads * n_iter


def test_prometheus_and_jsonl_exposition():
    reg = Registry(enabled=True)
    reg.counter("req_total", "requests", labels=("algorithm",)).inc(3, algorithm="ring")
    reg.gauge("q").set(2)
    h = reg.histogram("lat_ms", buckets=(1.0, 10.0))
    h.observe(0.5)
    h.observe(5.0)
    h.observe(500.0)

    text = reg.to_prometheus_text()
    assert '# TYPE req_total counter' in text
    assert 'req_total{algorithm="ring"} 3' in text
    assert 'lat_ms_bucket{le="1.0"} 1' in text
    assert 'lat_ms_bucket{le="10.0"} 2' in text
    assert 'lat_ms_bucket{le="+Inf"} 3' in text
    assert 'lat_ms_count 3' in text

    records = [json.loads(line) for line in reg.to_jsonl().splitlines()]
    by_name = {r["name"]: r for r in records}
    assert by_name["req_total"]["value"] == 3
    assert by_name["lat_ms"]["buckets"]["+Inf"] == 3
    assert by_name["lat_ms"]["count"] == 3
    assert all("time" in r for r in records)


def test_histogram_bucket_conflict_raises():
    reg = Registry(enabled=True)
    h = reg.histogram("occ", buckets=(0.5, 1.0))
    # omitting buckets fetches the existing histogram, whatever its bounds
    assert reg.histogram("occ") is h
    # EXPLICIT different bounds must not silently reuse the first ones
    with pytest.raises(ValueError, match="already registered with buckets"):
        reg.histogram("occ", buckets=(1.0, 10.0))


def test_disabled_registry_is_noop():
    reg = Registry(enabled=False)
    c = reg.counter("x_total")
    g = reg.gauge("g")
    h = reg.histogram("h_ms")
    c.inc()
    g.set(5)
    h.observe(1.0)
    assert c.value() == 0.0
    assert g.value() is None
    assert h.summary() == {"count": 0}
    assert reg.collect() == []
    assert reg.to_prometheus_text() == ""
    # enabling later makes the SAME metric objects live — no re-wiring
    reg.enable()
    c.inc()
    assert c.value() == 1.0


# ---------------------------------------------------------------------------
# spans
# ---------------------------------------------------------------------------


def test_chrome_trace_schema_sorted_and_matched():
    reg = Registry(enabled=True)
    tracer = SpanTracer(registry=reg)
    with tracer.span("outer"):
        with tracer.span("inner", detail=7):
            pass
        with tracer.span("inner"):
            pass
    trace = tracer.chrome_trace()
    events = trace["traceEvents"]
    assert len(events) == 6
    # JSON-serializable and ts-sorted (chrome://tracing requirement)
    json.dumps(trace)
    ts = [e["ts"] for e in events]
    assert ts == sorted(ts)
    # every B has a matching E, stack-ordered per tid
    stack = []
    for e in events:
        assert e["ph"] in ("B", "E") and {"name", "ts", "pid", "tid"} <= set(e)
        if e["ph"] == "B":
            stack.append(e["name"])
        else:
            assert stack.pop() == e["name"]
    assert stack == []
    s = tracer.summaries()
    assert s["inner"]["count"] == 2
    assert s["outer"]["count"] == 1
    assert s["outer"]["p50"] >= s["inner"]["p50"]


def test_span_fence_blocks_on_device_value():
    import jax
    import jax.numpy as jnp

    reg = Registry(enabled=True)
    tracer = SpanTracer(registry=reg)
    x = jnp.ones((64, 64))
    with tracer.span("matmul", fence=(x @ x)):
        pass
    assert tracer.summaries()["matmul"]["count"] == 1


def test_span_eviction_drops_orphan_ends(monkeypatch):
    """Past the event cap, the oldest quarter is cut — E events whose B
    fell in the cut must go too, or chrome://tracing mis-nests the rest."""
    from dsml_tpu.obs import spans as spans_mod

    monkeypatch.setattr(spans_mod, "_EVENT_CAP", 8)
    reg = Registry(enabled=True)
    tracer = SpanTracer(registry=reg)
    with tracer.span("outer"):  # its B will be evicted, its E survives
        for i in range(6):
            with tracer.span(f"s{i}"):
                pass
    events = tracer.chrome_trace()["traceEvents"]
    stack = []
    for e in events:
        if e["ph"] == "B":
            stack.append(e["name"])
        else:
            assert stack and stack.pop() == e["name"], events
    assert stack == []  # every kept event is part of a matched pair


def test_span_disabled_records_nothing():
    reg = Registry(enabled=False)
    tracer = SpanTracer(registry=reg)
    with tracer.span("never"):
        pass
    assert tracer.chrome_trace()["traceEvents"] == []
    assert tracer.summaries() == {}


# ---------------------------------------------------------------------------
# step stats / goodput / mfu
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("phases, tail, coverage", [
    # three timed phases and an untimed tail
    ({"data": 1.0, "forward_backward": 6.0, "optimizer": 2.0}, 1.0, 90.0),
    # the five canonical phases, each fenced: only the step's own
    # bookkeeping is left outside them
    ({"data": 1.0, "forward_backward": 5.0, "grad_sync": 2.0,
      "optimizer": 1.0, "checkpoint_stall": 0.75}, 0.25, 97.5),
], ids=["three_phases_untimed_tail", "five_canonical_phases"])
def test_step_breakdown_coverage(phases, tail, coverage):
    from dsml_tpu.obs.step_stats import STEP_PHASES

    clock = FakeClock()
    reg = Registry(enabled=True)
    bd = StepBreakdown(registry=reg, clock=clock)
    for _ in range(3):
        with bd.step():
            for name, seconds in phases.items():
                with bd.phase(name):
                    clock.advance(seconds)
            clock.advance(tail)
    s = bd.summary()
    assert s["steps"] == 3
    assert set(s["phases"]) == set(phases) <= set(STEP_PHASES)
    assert s["phases"]["forward_backward"]["total_s"] == pytest.approx(
        3 * phases["forward_backward"])
    assert s["phases"]["data"]["mean_ms"] == pytest.approx(1000.0)
    assert s["step_wall_s"] == pytest.approx(30.0)
    assert s["coverage_pct"] == pytest.approx(coverage)
    if len(phases) == len(STEP_PHASES):
        assert tuple(s["phases"]) == STEP_PHASES  # the canonical names, in order


class FakeClock:
    def __init__(self):
        self.t = 100.0

    def __call__(self) -> float:
        return self.t

    def advance(self, dt: float) -> None:
        self.t += dt


def test_goodput_across_preemption_and_restore():
    """The goodput story of a preempted run: 60 s of productive stepping,
    a preemption, a restart that re-does 10 s of work, and checkpoint
    overhead — goodput is productive ÷ wall over the WHOLE job."""
    clock = FakeClock()
    reg = Registry(enabled=True)
    # incarnation 1: 60 s productive, then 5 s checkpointing, then preempted
    gp1 = GoodputTracker(registry=reg, clock=clock)
    with gp1.productive():
        clock.advance(60.0)
    gp1.mark("checkpoint_save", epoch=3)
    clock.advance(5.0)
    gp1.mark("preemption")
    assert gp1.productive_s == pytest.approx(60.0)

    # 15 s of downtime while the job waits for capacity
    clock.advance(15.0)

    # incarnation 2 carries incarnation 1's productive seconds; wall keeps
    # running from ITS OWN start, so the job-level wall is tracked by the
    # caller handing in the original start via the same clock
    gp2 = GoodputTracker(registry=reg, clock=clock,
                         carry_s=gp1.productive_s)
    gp2.mark("restore", epoch=3)
    with gp2.productive():
        clock.advance(10.0)  # redone work is still productive stepping
    with gp2.productive():
        clock.advance(30.0)
    s = gp2.summary()
    assert s["productive_s"] == pytest.approx(100.0)
    assert s["wall_s"] == pytest.approx(40.0)
    # job-level goodput: productive 100 over (gp1 wall 65 + down 15 + 40)
    job_wall = 65.0 + 15.0 + s["wall_s"]
    assert 100.0 / job_wall == pytest.approx(0.8333, abs=1e-3)
    assert [e["event"] for e in gp1.events] == ["checkpoint_save", "preemption"]
    assert s["events"][0]["event"] == "restore"
    # the registry counted every lifecycle event
    assert reg.counter(
        "goodput_events_total", labels=("event",)
    ).value(event="restore") == 1.0


def test_goodput_clamps_and_zero_wall():
    clock = FakeClock()
    gp = GoodputTracker(registry=Registry(enabled=True), clock=clock)
    assert gp.goodput() == 0.0  # zero wall
    gp.add_productive(50.0)
    clock.advance(10.0)
    assert gp.goodput() == 1.0  # clamped


def test_mfu():
    assert mfu(45e12, 90e12) == pytest.approx(0.5)
    assert mfu(45e12, None) is None
    assert mfu(45e12, 0) is None


def test_transformer_flops_match_hand_count():
    """models.common.transformer_train_flops against the count written out
    by hand, for both the GPT-2 and the GQA/SwiGLU (Llama) forms."""
    from dsml_tpu.models.common import mlp_train_flops, transformer_train_flops
    from dsml_tpu.models.gpt2 import GPT2Config
    from dsml_tpu.models.llama import LlamaConfig

    cfg = GPT2Config.small()
    T, seq = 8 * 1024, 1024
    d, ff, L, V = cfg.d_model, cfg.d_ff, cfg.n_layer, cfg.vocab_size
    fwd = L * (2 * T * d * 3 * d + 2 * T * d * d + 2 * 2 * T * seq * d // 2
               + 2 * 2 * T * d * ff) + 2 * T * d * V
    assert transformer_train_flops(cfg, T, seq) == 3 * fwd

    lcfg = LlamaConfig.tinyllama_1b()
    T, seq = 2 * 2048, 2048
    d, ff, L, V = lcfg.d_model, lcfg.d_ff, lcfg.n_layer, lcfg.vocab_size
    kv = lcfg.n_kv_head / lcfg.n_head
    lfwd = L * (2 * T * d * d + int(2 * 2 * T * d * d * kv) + 2 * T * d * d
                + 2 * 2 * T * seq * d // 2 + 3 * 2 * T * d * ff) + 2 * T * d * V
    assert transformer_train_flops(lcfg, T, seq, gated_mlp=True) == 3 * lfwd

    assert mlp_train_flops(101_770, 1250) == 6 * 101_770 * 1250


# ---------------------------------------------------------------------------
# export: rotation + HTTP endpoint + compat re-export
# ---------------------------------------------------------------------------


def test_metrics_logger_rotation(tmp_path):
    path = str(tmp_path / "m.jsonl")
    logger = MetricsLogger(path, max_bytes=300)
    for i in range(40):
        logger.log(step=i, loss=1.0 / (i + 1))
    assert (tmp_path / "m.jsonl.1").exists()
    # both generations hold intact JSON lines; the live file is under cap
    for p in (tmp_path / "m.jsonl", tmp_path / "m.jsonl.1"):
        lines = p.read_text().splitlines()
        assert lines and all(json.loads(ln) for ln in lines)
    assert (tmp_path / "m.jsonl").stat().st_size <= 300
    assert logger.last(step=39)["loss"] == pytest.approx(1.0 / 40)


def test_metrics_logger_compat_reexport():
    # the pre-obs import path keeps working (trainer and user code use it)
    from dsml_tpu.obs.export import MetricsLogger as New
    from dsml_tpu.utils.metrics import MetricsLogger as Old

    assert Old is New
    logger = Old()
    logger.log(epoch=1, avg_loss=0.5)
    assert logger.last(epoch=1)["avg_loss"] == 0.5


def test_http_metrics_endpoint():
    reg = Registry(enabled=True)
    reg.counter("served_total", "requests", labels=("algorithm",)).inc(
        5, algorithm="ring"
    )
    srv = start_metrics_server(reg, port=0)
    try:
        text = urllib.request.urlopen(srv.address + "/metrics", timeout=5).read().decode()
        assert 'served_total{algorithm="ring"} 5' in text
        data = json.loads(
            urllib.request.urlopen(srv.address + "/metrics.json", timeout=5).read()
        )
        assert data[0]["name"] == "served_total"
        with pytest.raises(urllib.error.HTTPError):
            urllib.request.urlopen(srv.address + "/nope", timeout=5)
    finally:
        srv.stop()


def test_progressbar_non_tty_single_line():
    from dsml_tpu.utils.metrics import ProgressBar

    stream = io.StringIO()  # isatty() → False
    bar = ProgressBar(10, desc="Epoch 1", stream=stream)
    for _ in range(10):
        bar.update()
    bar.close()
    out = stream.getvalue()
    assert "\r" not in out  # no carriage-return spam in CI logs
    assert out.count("\n") == 1
    assert out.startswith("Epoch 1 10/10")

    silent = io.StringIO()
    bar = ProgressBar(10, stream=silent, enabled=False)
    bar.update(10)
    bar.close()
    assert silent.getvalue() == ""


# ---------------------------------------------------------------------------
# tracing satellites: ObsUnavailable guard + registry routing
# ---------------------------------------------------------------------------


def test_trace_raises_obs_unavailable(monkeypatch, tmp_path):
    import jax

    from dsml_tpu.utils.tracing import trace

    def boom(path):
        raise RuntimeError("profiler backend exploded")

    monkeypatch.setattr(jax.profiler, "start_trace", boom)
    with pytest.raises(ObsUnavailable, match="Remediation"):
        with trace(str(tmp_path)):
            pass


def test_trace_stop_failure_does_not_mask_body_exception(monkeypatch, tmp_path):
    """A body exception must propagate even when the unwinding capture's
    stop_trace also fails — the secondary failure is logged, not raised."""
    import jax

    from dsml_tpu.utils.tracing import trace

    monkeypatch.setattr(jax.profiler, "start_trace", lambda p: None)

    def stop_boom():
        raise RuntimeError("capture died with the body")

    monkeypatch.setattr(jax.profiler, "stop_trace", stop_boom)
    with pytest.raises(ValueError, match="the real error"):
        with trace(str(tmp_path)):
            raise ValueError("the real error")
    # with a healthy body, the stop failure itself surfaces as ObsUnavailable
    with pytest.raises(ObsUnavailable, match="stop"):
        with trace(str(tmp_path)):
            pass


def test_time_jitted_routes_into_registry():
    import jax
    import jax.numpy as jnp

    from dsml_tpu import obs
    from dsml_tpu.utils.tracing import time_jitted

    reg = obs.get_registry()
    was = reg.enabled
    reg.enable()
    try:
        f = jax.jit(lambda x: x * 2.0)
        stats = time_jitted(f, jnp.ones((16,)), iters=4, warmup=1)
        assert stats["p50_ms"] >= 0
        assert len(stats["samples_ms"]) == 4
        hist = reg.histogram("time_jitted_ms")
        assert hist.summary()["count"] >= 4
    finally:
        if not was:
            reg.disable()


def test_ring_latency_routes_per_algorithm(mesh8):
    from dsml_tpu import obs
    from dsml_tpu.utils.tracing import ring_latency_ms

    reg = obs.get_registry()
    was = reg.enabled
    reg.enable()
    try:
        stats = ring_latency_ms(mesh8, payload_bytes=1 << 14, algorithm="naive")
        assert stats["algorithm"] == "naive"
        hist = reg.histogram(
            "collective_latency_ms", labels=("algorithm", "axis")
        )
        # mesh8's single axis is named "dev" — the label follows the mesh
        assert hist.summary(algorithm="naive", axis="dev")["count"] >= 1
    finally:
        if not was:
            reg.disable()


@pytest.mark.parametrize("algorithm", ["ring", "ring2", "naive", "q8"])
def test_collective_latency_histogram_per_algorithm(mesh8, algorithm):
    """Every explicit algorithm of the bucketed sync runs and lands in its
    own ``collective_latency_ms`` series, whose exposed buckets are
    cumulative and end in a ``+Inf`` bucket equal to the sample count."""
    import jax
    import jax.numpy as jnp
    from jax.sharding import PartitionSpec as P

    from dsml_tpu import obs
    from dsml_tpu.ops.collectives import ReduceOp
    from dsml_tpu.parallel.bucketing import bucketed_all_reduce
    from dsml_tpu.utils.tracing import time_jitted

    rng = np.random.default_rng(0)
    tree = {f"w{i}": jnp.asarray(rng.standard_normal(4096), jnp.float32)
            for i in range(4)}
    fn = jax.jit(jax.shard_map(
        lambda t: bucketed_all_reduce(t, "dev", ReduceOp.AVG, algorithm, 0.02),
        mesh=mesh8, in_specs=P(), out_specs=P(), check_vma=False,
    ))
    reg = Registry(enabled=True)
    samples = time_jitted(fn, tree, iters=6)["samples_ms"]
    for ms in samples:
        obs.observe_collective_latency_ms(
            algorithm, ms, payload_bytes=4 * 4096 * 4, axis="dev", registry=reg)
    hist = reg.histogram("collective_latency_ms", labels=("algorithm", "axis"))
    summary = hist.summary(algorithm=algorithm, axis="dev")
    assert summary["count"] == len(samples) > 0
    assert summary["p90"] >= summary["p50"]
    (rec,) = [r for r in reg.collect() if r["name"] == "collective_latency_ms"]
    assert rec["labels"] == {"algorithm": algorithm, "axis": "dev"}
    counts = list(rec["buckets"].values())
    assert counts == sorted(counts)
    assert rec["buckets"]["+Inf"] == summary["count"]


# ---------------------------------------------------------------------------
# hot-path wiring: trace-time bucket plans, checkpoint metrics, trainer
# ---------------------------------------------------------------------------


def test_dp_step_records_collective_plan(dp_mesh8):
    import jax.numpy as jnp
    import optax

    from dsml_tpu import obs
    from dsml_tpu.parallel.dp import make_dp_train_step

    reg = obs.get_registry()
    was = reg.enabled
    reg.enable()
    try:
        def loss_fn(p, x, y):
            return jnp.mean((x @ p["w"] - y[:, None]) ** 2)

        params = {"w": jnp.ones((8, 1))}
        opt = optax.sgd(0.1)
        step = make_dp_train_step(loss_fn, opt, dp_mesh8, algorithm="ring")
        x = jnp.ones((16, 8), jnp.float32)
        y = jnp.ones((16,), jnp.float32)
        step(params, opt.init(params), x, y)  # compile = trace = record
        buckets = reg.gauge(
            "collective_sync_buckets", labels=("algorithm", "axis")
        ).value(algorithm="ring", axis="dp")
        nbytes = reg.gauge(
            "collective_sync_bytes", labels=("algorithm", "axis")
        ).value(algorithm="ring", axis="dp")
        assert buckets is not None and buckets >= 1
        assert nbytes == 8 * 1 * 4  # the one f32 [8,1] gradient leaf
        assert reg.counter(
            "collective_sync_compiles_total", labels=("algorithm", "axis")
        ).value(algorithm="ring", axis="dp") >= 1
    finally:
        if not was:
            reg.disable()
        reg_reset_safe()


def reg_reset_safe():
    """Tests that enable the GLOBAL registry clear what they wrote so
    later tests (and other modules' assertions) see a clean slate."""
    from dsml_tpu import obs

    reg = obs.get_registry()
    if not reg.enabled:
        reg.reset()


def test_checkpoint_writer_metrics(tmp_path):
    import jax.numpy as jnp

    from dsml_tpu import obs
    from dsml_tpu.checkpoint import CheckpointManager

    reg = obs.get_registry()
    was = reg.enabled
    reg.enable()
    try:
        with CheckpointManager(str(tmp_path), max_to_keep=1) as mgr:
            mgr.save(1, {"w": jnp.ones((4,))})
            mgr.save(2, {"w": jnp.ones((4,))})
            mgr.wait_until_finished()
        assert reg.histogram(
            "checkpoint_commit_ms", labels=("writer",)
        ).summary(writer="ckpt-writer")["count"] >= 2
        assert reg.counter(
            "checkpoint_saves_total", labels=("mode",)
        ).value(mode="sync") >= 2
        # max_to_keep=1 garbage-collected step 1 — and said so
        assert reg.counter("checkpoint_gc_total").value() >= 1
        assert reg.gauge(
            "checkpoint_queue_depth", labels=("writer",)
        ).value(writer="ckpt-writer") == 0
    finally:
        if not was:
            reg.disable()
        reg_reset_safe()


def test_trainer_emits_goodput_and_breakdown(tmp_path):
    import numpy as np

    from dsml_tpu import obs
    from dsml_tpu.models.mlp import MLP
    from dsml_tpu.trainer import TrainConfig, Trainer
    from dsml_tpu.utils.data import Dataset

    rng = np.random.default_rng(0)
    n = 64
    data = Dataset(
        train_x=rng.standard_normal((n, 784)).astype(np.float32),
        train_y=rng.integers(0, 10, n).astype(np.int32),
        test_x=rng.standard_normal((16, 784)).astype(np.float32),
        test_y=rng.integers(0, 10, 16).astype(np.int32),
    )
    reg = obs.get_registry()
    was = reg.enabled
    reg.enable()
    try:
        cfg = TrainConfig(epochs=2, batch_size=16, checkpoint_dir=str(tmp_path),
                          save_every=1, keep_checkpoints=2)
        trainer = Trainer(MLP(), cfg)
        trainer.train(data)
        rec = trainer.metrics.records[-1]  # the final summary record
        gsum = rec["obs_goodput"]
        assert 0.0 < gsum["goodput"] <= 1.0
        assert any(e["event"] == "checkpoint_save" for e in gsum["events"])
        bsum = rec["obs_step_breakdown"]
        assert bsum["steps"] == 2 * (n // 16)
        assert {"data", "step_dispatch"} <= set(bsum["phases"])
        assert "checkpoint_stall" in bsum["phases"]
        assert reg.gauge("train_goodput").value() == pytest.approx(
            gsum["goodput"], abs=1e-6
        )
    finally:
        if not was:
            reg.disable()
        reg_reset_safe()


def test_serving_admission_and_occupancy_metrics():
    import jax
    import numpy as np

    from dsml_tpu import obs
    from dsml_tpu.models.gpt2 import GPT2, GPT2Config
    from dsml_tpu.serving import ContinuousBatcher

    cfg = GPT2Config(vocab_size=64, max_seq=64, n_layer=1, n_head=2,
                     d_model=32, d_ff=64)
    model = GPT2(cfg)
    params = model.init(0)
    reg = obs.get_registry()
    was = reg.enabled
    reg.enable()
    try:
        srv = ContinuousBatcher(model, params, n_slots=2, prompt_buckets=(16,))
        rng = np.random.default_rng(0)
        for _ in range(3):
            srv.submit(rng.integers(0, 64, (8,)).astype(np.int32), 4)
        srv.run()
        # serving metrics carry replica + role labels (a standalone
        # batcher is replica "0" role "decode"; DecodeFleet restamps the
        # replica per spawn, the disaggregated fleet stamps both)
        assert reg.histogram(
            "serving_admission_ms", labels=("replica", "role")
        ).summary(replica="0", role="decode")["count"] == 3
        assert reg.histogram(
            "serving_slot_occupancy", labels=("replica", "role"),
            buckets=(0.125, 0.25, 0.375, 0.5, 0.625, 0.75, 0.875, 1.0),
        ).summary(replica="0", role="decode")["count"] >= 1
        assert reg.counter(
            "serving_tokens_total", labels=("replica", "role")
        ).value(replica="0", role="decode") == 3 * 4
    finally:
        if not was:
            reg.disable()
        reg_reset_safe()
