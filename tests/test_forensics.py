"""Failure forensics (``dsml_tpu/obs/`` flight recorder + sentinels +
hangwatch, docs/OBSERVABILITY.md § Failure forensics): sentinel policies
on injected NaN/Inf losses, loss-spike z-score math, hangwatch firing on
an artificial stall with matched thread stacks, SIGTERM/excepthook dump
round-trips (subprocess), bundle schema, the commit-deadline sentinel,
coordinator straggler derivation, and the disabled-mode no-op contract.
"""

from __future__ import annotations

import json
import os
import signal
import subprocess
import sys
import threading
import time

import numpy as np
import pytest

from dsml_tpu import obs
from dsml_tpu.obs.flight_recorder import FlightRecorder
from dsml_tpu.obs.hangwatch import HangWatch, TrailingDeadline, config_from_env
from dsml_tpu.obs.sentinels import (
    SentinelConfig,
    SentinelTripped,
    TrainingSentinels,
)

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _private(tmp_path, **sentinel_cfg):
    """A fully private (registry, recorder, sentinels) triple whose bundles
    land under tmp_path — no process-global state touched."""
    reg = obs.Registry(enabled=True)
    rec = FlightRecorder(registry=reg, directory=str(tmp_path))
    sent = TrainingSentinels(SentinelConfig(**sentinel_cfg),
                             registry=reg, recorder=rec)
    return reg, rec, sent


def _bundles(tmp_path):
    return sorted(p for p in tmp_path.iterdir() if p.is_dir())


# ---------------------------------------------------------------------------
# sentinels
# ---------------------------------------------------------------------------


def test_nonfinite_warn_counts_but_does_not_dump_or_raise(tmp_path):
    reg, rec, sent = _private(tmp_path, nonfinite="warn")
    sent.check(1, float("nan"))
    sent.check(2, float("inf"))
    sent.check(3, float("-inf"))
    c = reg.counter("sentinel_trips_total", labels=("sentinel", "policy"))
    assert c.value(sentinel="nonfinite", policy="warn") == 3
    assert _bundles(tmp_path) == []
    # trips also land in the flight ring
    kinds = [e["kind"] for e in rec.events()]
    assert kinds.count("sentinel_trip") == 3


def test_nonfinite_dump_writes_one_bundle_per_sentinel(tmp_path):
    reg, rec, sent = _private(tmp_path, nonfinite="dump")
    sent.check(1, float("nan"))
    sent.check(2, float("nan"))  # same sentinel: no second bundle
    assert len(_bundles(tmp_path)) == 1
    assert reg.counter(
        "sentinel_trips_total", labels=("sentinel", "policy")
    ).value(sentinel="nonfinite", policy="dump") == 2


def test_nonfinite_halt_raises_with_bundle(tmp_path):
    reg, rec, sent = _private(tmp_path, nonfinite="halt")
    rec.record("step", step=1)
    with pytest.raises(SentinelTripped) as e:
        sent.check(7, float("nan"))
    assert e.value.sentinel == "nonfinite"
    assert e.value.bundle is not None and os.path.isdir(e.value.bundle)
    events = [json.loads(ln) for ln in
              open(os.path.join(e.value.bundle, "events.jsonl"))]
    assert any(ev["kind"] == "sentinel_trip" for ev in events)


def test_off_policy_ignores_everything(tmp_path):
    reg, rec, sent = _private(tmp_path, nonfinite="off", spike="off",
                              gradnorm="off")
    sent.check(1, float("nan"), grad_norm=float("inf"))
    assert sent.trips == []
    assert _bundles(tmp_path) == []


def test_loss_spike_zscore_math_on_synthetic_spike(tmp_path):
    """Pin the z-score arithmetic: a constant-ish window then one spike.
    With window values ~N(1, 0.01), a loss of 2.0 is ~100 sigma out."""
    reg, rec, sent = _private(tmp_path, nonfinite="warn", spike="halt")
    rng = np.random.default_rng(0)
    losses = 1.0 + 0.01 * rng.standard_normal(40)
    for i, v in enumerate(losses):
        sent.check(i, float(v))

    # the helper matches a hand-rolled population z-score over the window
    win = list(sent._window)
    mean, std = np.mean(win), np.std(win)
    z_manual = (2.0 - mean) / std
    assert sent.spike_zscore(2.0) == pytest.approx(z_manual, rel=1e-6)
    assert z_manual > sent.config.spike_z  # the spike really is a spike

    with pytest.raises(SentinelTripped) as e:
        sent.check(len(losses), 2.0)
    assert e.value.sentinel == "spike"
    # a value inside the band does NOT trip (fresh instance, same stream)
    reg2, rec2, sent2 = _private(tmp_path, spike="halt")
    for i, v in enumerate(losses):
        sent2.check(i, float(v))
    sent2.check(len(losses), float(np.mean(win)))  # no raise


def test_spike_needs_warmup_before_judging(tmp_path):
    reg, rec, sent = _private(tmp_path, spike="halt")
    sent.check(0, 1.0)
    sent.check(1, 1.0)
    sent.check(2, 1000.0)  # only 2 samples < spike_min_steps: no trip
    assert sent.trips == []


def test_gradnorm_sentinel(tmp_path):
    reg, rec, sent = _private(tmp_path, gradnorm="halt")
    sent.check(1, 0.5, grad_norm=10.0)  # fine
    with pytest.raises(SentinelTripped) as e:
        sent.check(2, 0.5, grad_norm=1e6)
    assert e.value.sentinel == "gradnorm"
    # non-finite grad norm goes through the nonfinite sentinel
    reg2, rec2, sent2 = _private(tmp_path, nonfinite="halt", gradnorm="off")
    with pytest.raises(SentinelTripped) as e:
        sent2.check(3, 0.5, grad_norm=float("nan"))
    assert e.value.sentinel == "nonfinite"


def test_sentinel_config_from_env():
    assert SentinelConfig.from_env("") is None
    assert SentinelConfig.from_env("0") is None
    assert SentinelConfig.from_env("off") is None
    cfg = SentinelConfig.from_env("1")
    assert (cfg.nonfinite, cfg.spike, cfg.gradnorm) == ("halt", "warn", "warn")
    cfg = SentinelConfig.from_env("dump")
    assert (cfg.nonfinite, cfg.spike, cfg.gradnorm) == ("dump", "dump", "dump")
    cfg = SentinelConfig.from_env(
        "nonfinite=halt,spike=off,gradnorm=warn,spike_z=4.5,gradnorm_max=100"
    )
    assert cfg.nonfinite == "halt" and cfg.spike == "off"
    assert cfg.spike_z == 4.5 and cfg.gradnorm_max == 100.0
    with pytest.raises(ValueError):
        SentinelConfig.from_env("nonfinite=explode")
    with pytest.raises(ValueError):
        SentinelConfig.from_env("unknown_sentinel=halt")
    assert TrainingSentinels.maybe_from_env() is None  # env unset in tests


# ---------------------------------------------------------------------------
# hangwatch
# ---------------------------------------------------------------------------


def test_hangwatch_fires_on_artificial_stall_with_matched_stacks(tmp_path):
    reg = obs.Registry(enabled=True)
    rec = FlightRecorder(registry=reg, directory=str(tmp_path))
    hw = HangWatch(registry=reg, recorder=rec, name="test-hw")
    try:
        rec.record("step", step=1)
        hw.arm("train_step", 0.05, step=1)
        deadline = time.monotonic() + 5.0
        while not hw.fired and time.monotonic() < deadline:
            time.sleep(0.01)  # the "stall": the step never completes
        assert len(hw.fired) == 1
        assert reg.counter(
            "hang_suspected_total", labels=("watcher",)
        ).value(watcher="train_step") == 1

        bundle = hw.fired[0]["bundle"]
        assert bundle and os.path.isdir(bundle)
        stacks = open(os.path.join(bundle, "stacks.txt")).read()
        # the bundle's stacks must include the thread that armed the
        # deadline — i.e. the one presumed stuck — matched by name
        armed_by = hw.fired[0]["armed_by_thread"]
        assert f"thread {armed_by}" in stacks
        assert "time.sleep" in stacks or "test_hangwatch" in stacks
        events = [json.loads(ln) for ln in
                  open(os.path.join(bundle, "events.jsonl"))]
        assert any(e["kind"] == "hang_suspected" for e in events)
        assert any(e["kind"] == "step" for e in events)
    finally:
        hw.close()


def test_hangwatch_disarm_prevents_fire_and_is_idempotent(tmp_path):
    reg = obs.Registry(enabled=True)
    rec = FlightRecorder(registry=reg, directory=str(tmp_path))
    hw = HangWatch(registry=reg, recorder=rec, name="test-hw2")
    try:
        tok = hw.arm("op", 0.1)
        hw.disarm(tok)
        hw.disarm(tok)  # double-disarm is a no-op
        time.sleep(0.25)
        assert hw.fired == []
        assert hw.armed_count() == 0
        # context-manager form
        with hw.watching("op2", 5.0):
            pass
        assert hw.armed_count() == 0
    finally:
        hw.close()


def test_trailing_deadline_k_times_median():
    td = TrailingDeadline(multiplier=10.0, floor_s=0.5, min_samples=3)
    assert td.timeout_s() is None
    td.observe(0.1)
    td.observe(0.1)
    assert td.timeout_s() is None  # still warming up
    td.observe(0.3)
    assert td.timeout_s() == pytest.approx(1.0)  # 10 × median(0.1,0.1,0.3)
    td2 = TrailingDeadline(multiplier=2.0, floor_s=5.0, min_samples=1)
    td2.observe(0.001)
    assert td2.timeout_s() == 5.0  # floored


def test_hangwatch_config_from_env():
    assert config_from_env("") is None
    assert config_from_env("0") is None
    assert config_from_env("1").multiplier == 10.0
    assert config_from_env("25").multiplier == 25.0
    with pytest.raises(ValueError):
        config_from_env("banana")
    with pytest.raises(ValueError):
        config_from_env("-3")


# ---------------------------------------------------------------------------
# flight recorder: ring semantics + bundle schema
# ---------------------------------------------------------------------------


def test_ring_is_bounded_and_ordered(tmp_path):
    reg = obs.Registry(enabled=True)
    rec = FlightRecorder(capacity=16, registry=reg, directory=str(tmp_path))
    for i in range(50):
        rec.record("step", step=i)
    events = rec.events()
    assert len(events) == 16
    assert [e["step"] for e in events] == list(range(34, 50))  # newest win
    seqs = [e["seq"] for e in events]
    assert seqs == sorted(seqs)


def test_bundle_schema_round_trip(tmp_path):
    reg = obs.Registry(enabled=True)
    rec = FlightRecorder(registry=reg, directory=str(tmp_path))
    reg.counter("demo_total").inc()
    for i in range(5):
        rec.record("step", step=i)
    path = rec.dump("schema_check", extra={"k": "v"})

    names = sorted(os.listdir(path))
    assert names == [
        "MANIFEST.json", "events.jsonl", "fingerprint.json",
        "log_tail.jsonl", "memory.json", "registry.json", "stacks.txt",
        "trace.json",
    ]
    manifest = json.load(open(os.path.join(path, "MANIFEST.json")))
    assert manifest["reason"] == "schema_check"
    assert manifest["event_count"] == 5
    assert manifest["extra"] == {"k": "v"}
    assert sorted(manifest["files"]) == [n for n in names if n != "MANIFEST.json"]
    assert "errors" not in manifest

    events = [json.loads(ln) for ln in open(os.path.join(path, "events.jsonl"))]
    assert [e["step"] for e in events] == list(range(5))
    registry = json.load(open(os.path.join(path, "registry.json")))
    assert any(r["name"] == "demo_total" for r in registry)
    trace = json.load(open(os.path.join(path, "trace.json")))
    assert isinstance(trace["traceEvents"], list)
    fp = json.load(open(os.path.join(path, "fingerprint.json")))
    assert fp["pid"] == os.getpid() and "python" in fp
    stacks = open(os.path.join(path, "stacks.txt")).read()
    assert "MainThread" in stacks
    # the memory ledger snapshot rides every bundle (resolved through
    # THIS recorder's registry — a private recorder gets its own ledger)
    mem = json.load(open(os.path.join(path, "memory.json")))
    assert mem["schema"] == "dsml.obs.memory_ledger/1"
    assert "claimed_total_bytes" in mem and "watermarks" in mem


def test_dump_with_exception_records_traceback(tmp_path):
    reg = obs.Registry(enabled=True)
    rec = FlightRecorder(registry=reg, directory=str(tmp_path))
    try:
        raise ValueError("boom at step 12")
    except ValueError as e:
        path = rec.dump("unhandled_exception", exc=e)
    manifest = json.load(open(os.path.join(path, "MANIFEST.json")))
    assert manifest["exception"]["type"] == "ValueError"
    assert "boom at step 12" in manifest["exception"]["message"]
    assert any("raise ValueError" in ln
               for ln in manifest["exception"]["traceback"])


def test_disabled_mode_is_a_noop(tmp_path):
    reg = obs.Registry(enabled=False)
    rec = FlightRecorder(registry=reg, directory=str(tmp_path))
    for i in range(10):
        rec.record("step", step=i)
    assert len(rec) == 0
    # sentinels/hangwatch stay un-built without their env vars
    assert TrainingSentinels.maybe_from_env() is None
    assert config_from_env(None) is None
    # an explicit on-demand dump still works (events empty, snapshots live)
    path = rec.dump("on_demand")
    assert os.path.isfile(os.path.join(path, "events.jsonl"))
    assert open(os.path.join(path, "events.jsonl")).read() == ""


# ---------------------------------------------------------------------------
# crash hooks: SIGTERM + excepthook round trips (subprocess — the hooks
# must fire in a dying process, which pytest's own would intercept)
# ---------------------------------------------------------------------------

_CHILD_PRELUDE = """
import os, signal, sys
os.environ["JAX_PLATFORMS"] = "cpu"
import dsml_tpu.obs as obs
obs.enable()
rec = obs.get_flight_recorder()
for i in range(60):
    rec.record("step", step=i)
from dsml_tpu.utils.logging import get_logger
get_logger("child").info("about to die")
"""


def _run_child(body: str, tmp_path) -> subprocess.CompletedProcess:
    env = dict(os.environ)
    env.update({
        "DSML_POSTMORTEM_DIR": str(tmp_path),
        "JAX_PLATFORMS": "cpu",
        "DSML_OBS": "1",
    })
    return subprocess.run(
        [sys.executable, "-c", _CHILD_PRELUDE + body],
        capture_output=True, text=True, timeout=120, cwd=REPO, env=env,
    )


def _one_bundle(tmp_path, reason: str):
    dirs = [p for p in tmp_path.iterdir() if p.is_dir() and reason in p.name]
    assert len(dirs) == 1, f"expected one {reason} bundle, got {dirs}"
    return dirs[0]


def test_sigterm_dump_round_trip(tmp_path):
    proc = _run_child("os.kill(os.getpid(), signal.SIGTERM)\n", tmp_path)
    # the handler chains to the default disposition: killed by SIGTERM
    assert proc.returncode != 0
    bundle = _one_bundle(tmp_path, "sigterm")
    events = [json.loads(ln) for ln in open(bundle / "events.jsonl")]
    assert sum(e["kind"] == "step" for e in events) == 60
    log_tail = [json.loads(ln) for ln in open(bundle / "log_tail.jsonl")]
    assert any("about to die" in r["msg"] for r in log_tail)


def test_sigterm_hook_preserves_deliberate_sig_ign(tmp_path):
    """An app that set SIGTERM to SIG_IGN before obs.enable() must still
    survive a SIGTERM — the hook dumps the bundle, then keeps ignoring."""
    script = (
        "import os, signal, sys\n"
        "os.environ['JAX_PLATFORMS'] = 'cpu'\n"
        "signal.signal(signal.SIGTERM, signal.SIG_IGN)\n"
        "import dsml_tpu.obs as obs\n"
        "obs.enable()\n"
        "obs.get_flight_recorder().record('step', step=1)\n"
        "os.kill(os.getpid(), signal.SIGTERM)\n"
        "print('survived')\n"
    )
    env = dict(os.environ)
    env.update({"DSML_POSTMORTEM_DIR": str(tmp_path), "JAX_PLATFORMS": "cpu"})
    proc = subprocess.run([sys.executable, "-c", script], capture_output=True,
                          text=True, timeout=120, cwd=REPO, env=env)
    assert proc.returncode == 0 and "survived" in proc.stdout
    _one_bundle(tmp_path, "sigterm")  # the bundle was still written


def test_unhandled_exception_dump_round_trip(tmp_path):
    proc = _run_child("raise RuntimeError('run died at 3am')\n", tmp_path)
    assert proc.returncode != 0
    assert "run died at 3am" in proc.stderr  # the hook chains to the default
    bundle = _one_bundle(tmp_path, "unhandled_exception")
    manifest = json.load(open(bundle / "MANIFEST.json"))
    assert manifest["exception"]["type"] == "RuntimeError"
    assert manifest["event_count"] >= 60


def test_enable_disable_tear_down_cleanly(tmp_path, monkeypatch):
    monkeypatch.setenv("DSML_POSTMORTEM_DIR", str(tmp_path))
    prev_hook = sys.excepthook
    prev_sig = signal.getsignal(signal.SIGTERM)
    obs.enable()
    try:
        from dsml_tpu.utils.logging import get_ring_handler

        assert sys.excepthook is not prev_hook
        assert get_ring_handler() is not None
    finally:
        obs.disable()
    from dsml_tpu.utils.logging import get_ring_handler

    assert sys.excepthook is prev_hook
    assert signal.getsignal(signal.SIGTERM) == prev_sig
    assert get_ring_handler() is None
    assert not obs.enabled()


# ---------------------------------------------------------------------------
# ring-buffer log handler (utils.logging)
# ---------------------------------------------------------------------------


def test_log_ring_handler_bounds_and_structure():
    from dsml_tpu.utils.logging import RingBufferHandler, get_logger

    handler = RingBufferHandler(capacity=8)
    logger = get_logger("ringtest")
    logger.addHandler(handler)
    try:
        for i in range(20):
            logger.info("message %d", i)
    finally:
        logger.removeHandler(handler)
    records = handler.records()
    assert len(records) == 8  # bounded; newest win
    assert records[-1]["msg"] == "message 19"
    assert records[0]["msg"] == "message 12"
    assert records[0]["level"] == "INFO"
    assert records[0]["logger"].endswith("ringtest")


# ---------------------------------------------------------------------------
# async-writer commit-deadline sentinel
# ---------------------------------------------------------------------------


def test_async_writer_slow_commit_warns_with_label_and_depth():
    import logging

    from dsml_tpu.checkpoint.async_writer import AsyncWriter

    messages: list[str] = []

    class _Capture(logging.Handler):
        def emit(self, record):
            messages.append(record.getMessage())

    # the dsml root logger doesn't propagate (caplog can't see it); attach
    # the capture handler directly
    logger = logging.getLogger("dsml.ckpt-writer")
    cap = _Capture(level=logging.WARNING)
    logger.addHandler(cap)
    try:
        w = AsyncWriter(name="t-writer", deadline_s=0.05)
        release = threading.Event()
        w.submit(lambda: release.wait(timeout=5.0), label="step 42")
        t0 = time.monotonic()
        waiter = threading.Thread(target=w.wait, daemon=True)
        waiter.start()
        # the deadline passes while the commit is stuck; wait() must warn
        # rather than block silently
        deadline = time.monotonic() + 5.0
        while time.monotonic() < deadline:
            if any("still blocked" in m for m in messages):
                break
            time.sleep(0.01)
        release.set()
        waiter.join(timeout=5.0)
        assert not waiter.is_alive()
        blocked = [m for m in messages if "still blocked" in m]
        assert blocked, "wait() never warned about the overdue commit"
        assert "step 42" in blocked[0]
        slow = [m for m in messages if "took" in m]
        assert slow and "step 42" in slow[0]  # post-commit deadline warning
        assert time.monotonic() - t0 < 5.0
        w.close()
    finally:
        logger.removeHandler(cap)


def test_async_writer_commit_events_in_flight_ring():
    from dsml_tpu.checkpoint.async_writer import AsyncWriter

    reg = obs.get_registry()
    was = reg.enabled
    reg.enable()
    try:
        rec = obs.get_flight_recorder()
        before = len([e for e in rec.events() if e["kind"] == "checkpoint_commit"])
        w = AsyncWriter(name="t-writer2")
        w.submit(lambda: None, label="step 7")
        w.wait()
        w.close()
        commits = [e for e in rec.events() if e["kind"] == "checkpoint_commit"]
        assert len(commits) == before + 1
        assert commits[-1]["label"] == "step 7" and commits[-1]["ok"] is True
    finally:
        if not was:
            reg.disable()


# ---------------------------------------------------------------------------
# coordinator: probe latency histogram + straggler gauge
# ---------------------------------------------------------------------------


def test_coordinator_probe_latency_and_straggler_gauge():
    import grpc

    from dsml_tpu.comm.coordinator import (
        Communicator,
        CoordinatorConfig,
        CoordinatorRuntime,
        DeviceInfo,
    )

    class _FakeStub:
        def __init__(self, delay_s=0.0, dead=False):
            self.delay_s, self.dead = delay_s, dead

        def GetDeviceMetadata(self, request, timeout=None):  # noqa: N802
            if self.dead:
                raise grpc.RpcError("dead")
            time.sleep(self.delay_s)
            return object()

    class _FakeChannel:
        def close(self):
            pass

    rt = CoordinatorRuntime(CoordinatorConfig(
        health_interval_s=3600.0, straggler_multiplier=3.0,
    ))
    reg = obs.get_registry()
    was = reg.enabled
    reg.enable()
    try:
        # uniform 10 ms probes + one 200 ms straggler: the 3× median bar
        # (30 ms) separates them with margin even under scheduler noise
        infos = [
            DeviceInfo(r, 100 + r, f"fake:{r}", _FakeStub(delay_s=d),
                       _FakeChannel(), None)
            for r, d in enumerate([0.01, 0.01, 0.01, 0.2])
        ] + [DeviceInfo(4, 104, "fake:4", _FakeStub(dead=True),
                        _FakeChannel(), None)]
        comm = Communicator(1, infos)
        rt._check_comm_health(comm)

        assert reg.gauge("coordinator_stragglers").value() == 1
        hist = reg.histogram("coordinator_probe_ms", labels=("device",))
        assert hist.summary(device="103")["count"] == 1
        assert hist.summary(device="103")["p50"] >= 100.0  # the slow probe
        assert hist.summary(device="100")["count"] == 1
        assert hist.summary(device="104") == {"count": 0}  # dead: no timing
        probes = reg.counter("coordinator_health_probes_total",
                             labels=("outcome",))
        assert probes.value(outcome="alive") >= 4
        assert probes.value(outcome="failed") >= 1
        health = [e for e in obs.get_flight_recorder().events()
                  if e["kind"] == "health_probe"]
        assert health and health[-1]["stragglers"] == 1
    finally:
        rt.stop()
        if not was:
            reg.disable()


# ---------------------------------------------------------------------------
# E2E acceptance: injected NaN halts the trainer, leaving a full bundle
# ---------------------------------------------------------------------------


def test_trainer_nan_halt_leaves_complete_postmortem(tmp_path, monkeypatch):
    """ISSUE 5 acceptance: a trainer run with an injected NaN halts under
    policy ``halt`` leaving a bundle with ≥ 50 trailing events, the
    registry snapshot, the log tail, and all-thread stacks."""
    from dsml_tpu.models.mlp import MLP
    from dsml_tpu.trainer import TrainConfig, Trainer
    from dsml_tpu.utils.data import synthetic_classification

    monkeypatch.setenv("DSML_SENTINELS", "nonfinite=halt")
    monkeypatch.setenv("DSML_HANGWATCH", "1")
    monkeypatch.setenv("DSML_POSTMORTEM_DIR", str(tmp_path))
    obs.enable()
    try:
        data = synthetic_classification(1280, features=16, classes=4, seed=1)
        cfg = TrainConfig(epochs=1, batch_size=16, lr=0.05, sync_every=32)
        # the injected NaN: every row the loader serves after the first sync
        # window (epoch 1 is shuffled from ``seed + 1``), so the sync at step
        # 32 reads a finite loss and the next one, a window later, trips.
        # One window is the default 32 steps: 64 collective-bearing steps
        # queued unsynced on the 8-device CPU mesh abort in XLA's in-process
        # rendezvous when the host is loaded (ROADMAP Design 14)
        order = np.arange(len(data.train_x))
        np.random.default_rng(cfg.seed + 1).shuffle(order)
        data.train_x[order[cfg.sync_every * cfg.batch_size:]] = np.nan
        model = MLP(sizes=(16, 32, 4))
        trainer = Trainer(model, cfg)
        with pytest.raises(SentinelTripped) as e:
            trainer.train(data)
        bundle = e.value.bundle
        assert bundle is not None and os.path.isdir(bundle)

        events = [json.loads(ln) for ln in open(os.path.join(bundle, "events.jsonl"))]
        assert len(events) >= 50, f"only {len(events)} trailing events"
        kinds = {ev["kind"] for ev in events}
        assert {"train_start", "step", "loss_sync", "sentinel_trip"} <= kinds
        # the sync before the injection was clean; the trip saw the NaN at
        # the next sync point, within one window of the first bad step
        syncs = {ev["step"]: ev["loss"] for ev in events if ev["kind"] == "loss_sync"}
        assert np.isfinite(syncs[32]) and not np.isfinite(syncs[64])
        trip = [ev for ev in events if ev["kind"] == "sentinel_trip"][-1]
        assert trip["step"] == 64

        registry = json.load(open(os.path.join(bundle, "registry.json")))
        names = {r["name"] for r in registry}
        assert "sentinel_trips_total" in names and "step_phase_ms" in names
        log_tail = open(os.path.join(bundle, "log_tail.jsonl")).read().strip()
        assert log_tail, "bundle carries no log tail"
        stacks = open(os.path.join(bundle, "stacks.txt")).read()
        assert "MainThread" in stacks
        # the halt propagated between arm and the normal step end — the
        # per-step hangwatch deadline must have been disarmed on the way out
        assert obs.get_hangwatch().armed_count() == 0
    finally:
        obs.disable()
        obs.get_flight_recorder().clear()
