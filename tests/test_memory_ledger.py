"""Memory ledger (obs/memory.py, docs/OBSERVABILITY.md § Memory ledger):
attribution math pinned against hand-counted bytes, scrape-time
reconciliation through the collect hook, OOM-injection bundle schema,
fleet merge of the ledger gauges, and the disabled-mode no-op contract.
"""

import json
import os
import threading

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import NamedSharding, PartitionSpec as P

from dsml_tpu import obs
from dsml_tpu.obs import memory as obs_memory
from dsml_tpu.obs.memory import (
    MemoryLedger,
    is_oom,
    maybe_dump_oom,
    tree_nbytes,
)


def _stats(in_use, peak, limit):
    return [{"device": "synthetic", "bytes_in_use": in_use,
             "peak_bytes_in_use": peak, "bytes_limit": limit}]


# ---------------------------------------------------------------------------
# attribution math
# ---------------------------------------------------------------------------


def test_tree_nbytes_pinned_against_hand_count():
    tree = {
        "w": jnp.zeros((16, 32), jnp.float32),   # 2048 B
        "b": jnp.zeros((8,), jnp.bfloat16),      # 16 B
        "host": np.zeros((4, 4), np.float64),    # 128 B
        "scalar": 3.0,                            # free
        "none": None,                             # free
    }
    assert tree_nbytes(tree) == 16 * 32 * 4 + 8 * 2 + 128


def test_tree_nbytes_per_device_counts_the_shard(devices8):
    from jax.sharding import Mesh

    mesh = Mesh(np.asarray(devices8).reshape(8), ("dp",))
    sharded = jax.device_put(
        jnp.zeros((64, 16), jnp.float32), NamedSharding(mesh, P("dp"))
    )
    replicated = jax.device_put(
        jnp.zeros((10,), jnp.float32), NamedSharding(mesh, P())
    )
    tree = {"s": sharded, "r": replicated}
    # per-device: one eighth of the sharded leaf + the full replicated leaf
    assert tree_nbytes(tree, per_device=True) == 64 * 16 * 4 // 8 + 40
    # logical total is unchanged by sharding
    assert tree_nbytes(tree) == 64 * 16 * 4 + 40


def test_claim_tree_records_exact_bytes():
    reg = obs.Registry(enabled=True)
    led = MemoryLedger(registry=reg, stats_fn=lambda: [])
    tree = {"w": jnp.zeros((100,), jnp.float32)}
    assert led.claim_tree("params", tree) == 400
    assert led.claimed() == {"params": {"total": 400.0}}
    # re-claiming REPLACES (absolute semantics, not a delta)
    led.claim_tree("params", {"w": jnp.zeros((10,), jnp.float32)})
    assert led.claimed_bytes("params") == 40.0


def test_live_sources_sum_and_die_with_their_owner():
    reg = obs.Registry(enabled=True)
    led = MemoryLedger(registry=reg, stats_fn=lambda: [])

    class Pool:
        def src(self):
            return {"live": 100.0, "free": 50.0}

    a, b = Pool(), Pool()
    led.register_source("kv_pages", a.src, name="a")
    led.register_source("kv_pages", b.src, name="b")
    assert led.claimed()["kv_pages"] == {"live": 200.0, "free": 100.0}
    del a
    assert led.claimed()["kv_pages"] == {"live": 100.0, "free": 50.0}
    # re-registering the same (subsystem, name) replaces, never doubles
    led.register_source("kv_pages", b.src, name="b")
    assert led.claimed()["kv_pages"]["live"] == 100.0


# ---------------------------------------------------------------------------
# reconciliation through the collect hook
# ---------------------------------------------------------------------------


def test_collect_hook_reconciles_claims_against_measured():
    reg = obs.Registry(enabled=True)
    led = MemoryLedger(registry=reg, stats_fn=lambda: _stats(1000, 1400, 4000))
    led.set_claim("params", 700)
    led.set_claim("kv_pages", 200, detail="live")
    recs = {(r["name"],) + tuple(sorted(r["labels"].items())): r
            for r in reg.collect()}

    def val(name, **labels):
        return recs[(name,) + tuple(sorted(labels.items()))]["value"]

    assert val("hbm_claimed_bytes", subsystem="params", detail="total") == 700
    assert val("hbm_claimed_bytes", subsystem="kv_pages", detail="live") == 200
    assert val("hbm_claimed_total_bytes") == 900
    assert val("hbm_measured_bytes", kind="bytes_in_use") == 1000
    assert val("hbm_measured_bytes", kind="peak_bytes_in_use") == 1400
    assert val("hbm_measured_bytes", kind="bytes_limit") == 4000
    assert val("hbm_unattributed_bytes") == 100  # 1000 measured - 900 claimed
    assert val("hbm_headroom_bytes") == 3000
    assert val("hbm_source", source="memory_stats") == 1
    assert led.unattributed_bytes() == 100
    assert led.headroom_bytes() == 3000


def test_statless_backend_reports_claimed_provenance():
    reg = obs.Registry(enabled=True)
    led = MemoryLedger(registry=reg, stats_fn=lambda: [])
    led.set_claim("params", 512)
    assert led.measure()["available"] is False
    assert led.headroom_bytes() is None
    assert led.unattributed_bytes() is None
    led.note_step_peak(7)
    (mark,) = led.watermarks()
    assert mark == pytest.approx({"t": mark["t"], "peak_bytes": 512.0,
                                  "source": "claimed", "step": 7})
    names = {r["name"] for r in reg.collect()}
    assert "hbm_measured_bytes" not in names  # nothing invented
    snap = led.snapshot()
    assert snap["schema"] == obs_memory.SCHEMA
    assert snap["measured"]["source"] == "claimed"
    assert snap["unattributed_bytes"] is None


def test_measured_watermark_prefers_device_peak():
    reg = obs.Registry(enabled=True)
    led = MemoryLedger(registry=reg, stats_fn=lambda: _stats(900, 1234, 4000))
    led.set_claim("params", 10)
    led.note_step_peak(1, label="recovery:reconfigure")
    (mark,) = led.watermarks()
    assert mark["peak_bytes"] == 1234.0
    assert mark["source"] == "memory_stats"
    assert mark["label"] == "recovery:reconfigure"


def test_dead_source_and_provenance_flip_leave_no_stale_gauges():
    """Scrape-time gauges are re-DERIVED, not accreted: a retired
    batcher's pool series must vanish from the next exposition, and a
    provenance flip must leave exactly one hbm_source series."""
    flip = {"stats": []}
    reg = obs.Registry(enabled=True)
    led = MemoryLedger(registry=reg, stats_fn=lambda: flip["stats"])

    class Pool:
        def src(self):
            return {"live": 4096.0}

    p = Pool()
    led.register_source("kv_pages", p.src, name="p")
    recs = [r for r in reg.collect() if r["name"] == "hbm_claimed_bytes"]
    assert any(r["labels"]["subsystem"] == "kv_pages" for r in recs)
    assert [r["labels"]["source"] for r in reg.collect()
            if r["name"] == "hbm_source"] == ["claimed"]
    del p  # the batcher retires
    recs = [r for r in reg.collect() if r["name"] == "hbm_claimed_bytes"]
    assert not any(r["labels"]["subsystem"] == "kv_pages" for r in recs)
    # provenance flips to measured: exactly ONE source series, and the
    # measured rows appear; flip back: measured rows clear again
    flip["stats"] = _stats(100, 120, 400)
    assert [r["labels"]["source"] for r in reg.collect()
            if r["name"] == "hbm_source"] == ["memory_stats"]
    assert any(r["name"] == "hbm_measured_bytes" for r in reg.collect())
    flip["stats"] = []
    assert [r["labels"]["source"] for r in reg.collect()
            if r["name"] == "hbm_source"] == ["claimed"]
    assert not any(r["name"] == "hbm_measured_bytes" for r in reg.collect())


def test_failed_poll_is_retried_not_cached(monkeypatch):
    """A half-dead backend at first measure (the elastic-recovery window)
    must not demote the process to 'claimed' forever: only a CLEAN
    no-stats poll caches unavailability."""
    state = {"calls": 0}

    def flaky():
        state["calls"] += 1
        if state["calls"] == 1:
            return None  # enumeration failed — retry later
        return _stats(10, 10, 100)

    monkeypatch.setattr(obs_memory, "_device_memory_stats", flaky)
    reg = obs.Registry(enabled=True)
    led = MemoryLedger(registry=reg)  # picks up the (patched) default
    assert led.measure()["available"] is False
    assert led._stats_available is None  # NOT cached as statless
    assert led.measure()["available"] is True  # the retry succeeded
    # a CLEAN statless answer does cache (no per-step re-polling)
    monkeypatch.setattr(obs_memory, "_device_memory_stats", lambda: [])
    reg2 = obs.Registry(enabled=True)
    led2 = MemoryLedger(registry=reg2)
    assert led2.measure()["available"] is False
    assert led2._stats_available is False


# ---------------------------------------------------------------------------
# the hybrid step's wiring: claims at init, one watermark a step
# ---------------------------------------------------------------------------


def test_hybrid_init_claims_and_step_watermarks(devices8):
    """``init_hybrid`` claims params and optimizer state at what one device
    holds (dp only: every leaf replicated, so the hand count is plain shape
    arithmetic), and the wrapped step lands one source-stamped watermark a
    call."""
    import math

    import optax

    from dsml_tpu.models.gpt2 import GPT2, GPT2Config
    from dsml_tpu.parallel.hybrid import init_hybrid, make_hybrid_train_step
    from dsml_tpu.parallel.mesh import MeshSpec, build_mesh

    def hand_count(tree):
        return sum(math.prod(l.shape) * np.dtype(l.dtype).itemsize
                   for l in jax.tree.leaves(tree) if hasattr(l, "shape"))

    reg = obs.get_registry()
    was = reg.enabled
    reg.enable()
    led = obs_memory.get_memory_ledger()
    led.clear()
    try:
        cfg = GPT2Config.tiny()
        model = GPT2(cfg)
        optimizer = optax.adam(1e-3)
        mesh = build_mesh(MeshSpec(dp=8), devices8)
        params, opt_state = init_hybrid(model, optimizer, mesh)
        claims = led.claimed()
        assert claims["params"]["hybrid"] == hand_count(params) > 0
        assert claims["optimizer"]["hybrid"] == hand_count(opt_state)
        # adam's m and v double the parameter bytes (plus its count)
        assert claims["optimizer"]["hybrid"] >= 2 * claims["params"]["hybrid"]

        step = make_hybrid_train_step(model, optimizer, mesh)
        rng = np.random.default_rng(0)
        x = rng.integers(0, cfg.vocab_size, (8, cfg.max_seq)).astype(np.int32)
        y = np.roll(x, -1, 1).astype(np.int32)
        for _ in range(3):
            params, opt_state, loss = step(params, opt_state, x, y)
        assert np.isfinite(float(loss))
        marks = led.watermarks()
        assert len(marks) == 3
        assert marks[-1]["peak_bytes"] > 0
        # CPU devices report no memory_stats(): provenance says so
        assert marks[-1]["source"] == "claimed"
    finally:
        led.clear()
        if not was:
            reg.disable()
            reg.reset()


# ---------------------------------------------------------------------------
# disabled-mode no-op contract
# ---------------------------------------------------------------------------


def test_disabled_ledger_is_a_noop():
    reg = obs.Registry(enabled=False)
    calls = []

    def stats():
        calls.append(1)
        return _stats(1, 1, 1)

    led = MemoryLedger(registry=reg, stats_fn=stats)
    assert led.claim_tree("params", {"w": jnp.zeros((9,), jnp.float32)}) == 0
    led.set_claim("optimizer", 100)
    led.note_step_peak(1)
    assert led.claimed() == {}
    assert led.watermarks() == []
    assert reg.collect() == []  # no series materialized
    assert calls == []  # note_step_peak never polled the backend
    # reads still work for forensics: snapshot on a disabled ledger
    assert led.snapshot()["claimed_total_bytes"] == 0


# ---------------------------------------------------------------------------
# OOM forensics
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("exc,want", [
    (RuntimeError("RESOURCE_EXHAUSTED: Out of memory allocating 8 bytes"), True),
    (RuntimeError("Resource exhausted: while allocating"), True),
    (ValueError("shapes do not match"), False),
    (None, False),
])
def test_is_oom_matrix(exc, want):
    assert is_oom(exc) is want


def test_is_oom_sees_chained_cause():
    try:
        try:
            raise RuntimeError("Out of memory while trying to allocate")
        except RuntimeError as inner:
            raise ValueError("step failed") from inner
    except ValueError as outer:
        assert is_oom(outer)


def test_oom_injection_bundle_schema(tmp_path):
    reg = obs.Registry(enabled=True)
    led = obs_memory.get_memory_ledger(reg)
    led.set_claim("params", 4096)
    led.note_step_peak(41)
    led.note_step_peak(42)
    rec = obs.FlightRecorder(registry=reg, directory=str(tmp_path))
    exc = RuntimeError("RESOURCE_EXHAUSTED: Out of memory allocating 1 GiB")
    bundle = maybe_dump_oom(exc, recorder=rec)
    assert bundle is not None and "resource_exhausted" in bundle
    assert exc.bundle == bundle  # stamped: crash hooks won't double-dump
    assert maybe_dump_oom(exc, recorder=rec) == bundle  # idempotent
    with open(os.path.join(bundle, "MANIFEST.json")) as f:
        manifest = json.load(f)
    assert manifest["reason"] == "resource_exhausted"
    assert "memory.json" in manifest["files"]
    assert manifest["exception"]["type"] == "RuntimeError"
    with open(os.path.join(bundle, "memory.json")) as f:
        snap = json.load(f)
    assert snap["schema"] == obs_memory.SCHEMA
    assert snap["claimed_total_bytes"] == 4096
    assert [m["step"] for m in snap["watermarks"]] == [41, 42]
    # a non-OOM exception never dumps
    assert maybe_dump_oom(ValueError("nope"), recorder=rec) is None


# ---------------------------------------------------------------------------
# fleet merge
# ---------------------------------------------------------------------------


def test_fleet_merge_memory_gauges():
    from dsml_tpu.obs import cluster as obs_cluster

    ledgers = []
    snaps = []
    for i, (use, limit) in enumerate(((2_000, 10_000), (7_000, 10_000))):
        reg = obs.Registry(enabled=True)
        led = MemoryLedger(
            registry=reg,
            stats_fn=lambda u=use, li=limit: _stats(u, u, li),
        )
        led.set_claim("params", use)
        ledgers.append(led)  # keep the weakly-hooked ledgers alive
        snaps.append(obs_cluster.snapshot(role=f"w{i}", registry=reg,
                                          with_trace=False))
    report = obs_cluster.merge_snapshots(snaps).report()
    head = report["memory"]["headroom_bytes"]
    assert head == {"min": 3_000.0, "mean": 5_500.0, "max": 8_000.0, "n": 2}
    # gauges merge min/mean/max, NEVER a fleet sum
    assert report["memory"]["claimed_total_bytes"]["max"] == 7_000.0
    assert report["memory"]["unattributed_bytes"]["min"] == 0.0


# ---------------------------------------------------------------------------
# consumers: plan_mesh provenance, checkpoint staging
# ---------------------------------------------------------------------------


def test_plan_mesh_provenance_stamped():
    from dsml_tpu.parallel.auto import plan_mesh

    class Reports:
        device_kind = "fake-tpu"

        def memory_stats(self):
            return {"bytes_limit": int(32e9)}

    class Statless:
        def memory_stats(self):
            return None

    measured = plan_mesh(n_devices=8, n_params=1e6, device=Reports())
    assert measured.hbm_source == "memory_stats"
    fallback = plan_mesh(n_devices=8, n_params=1e6, device=Statless())
    assert fallback.hbm_source == "fallback"
    assert any("fallback constant" in r for r in fallback.reasons)
    explicit = plan_mesh(n_devices=8, n_params=1e6, hbm_bytes=16e9)
    assert explicit.hbm_source == "caller"


def test_plan_mesh_consumes_ledger_measured_activations():
    from dsml_tpu.parallel.auto import plan_mesh

    reg = obs.get_registry()
    was = reg.enabled
    led = obs_memory.get_memory_ledger()
    reg.enable()
    try:
        led.record_activation_measurement(9e9, batch=1)
        plan = plan_mesh(n_devices=8, n_params=1e6, hbm_bytes=16e9)
        assert any("ledger-measured" in r for r in plan.reasons)
        assert plan.spec.sp > 1  # 9 GB > the 3.2 GB activation budget
        # the measurement rides WITH its geometry: a re-plan at a larger
        # per-device batch (the elastic-shrink shape) sees bytes rescaled,
        # never the stale absolute number
        assert led.activation_bytes_for(4) == 4 * 9e9
        bigger = plan_mesh(n_devices=8, n_params=1e6, hbm_bytes=16e9,
                           batch_per_device=4)
        assert any("rescaled to batch_per_device=4" in r
                   for r in bigger.reasons)
    finally:
        led.clear()
        if not was:
            reg.disable()


def test_host_subsystem_claims_stay_out_of_device_residual():
    """A queued checkpoint snapshot is HOST RAM: it must show up as a
    claim but never drive the device residual negative mid-commit."""
    reg = obs.Registry(enabled=True)
    led = MemoryLedger(registry=reg, stats_fn=lambda: _stats(1000, 1000, 4000))
    led.set_claim("params", 900)
    led.set_claim("checkpoint_staging", 900)  # snapshot queued
    assert led.claimed_bytes() == 1800       # reported in full
    assert led.device_claimed_bytes() == 900  # reconciliation side
    assert led.unattributed_bytes() == 100    # NOT -800
    recs = {r["name"]: r for r in reg.collect() if not r["labels"]}
    assert recs["hbm_unattributed_bytes"]["value"] == 100
    snap = led.snapshot()
    assert snap["claimed_total_bytes"] == 1800
    assert snap["claimed_device_bytes"] == 900
    assert snap["unattributed_bytes"] == 100


def test_async_writer_staging_source(tmp_path):
    from dsml_tpu.checkpoint.async_writer import AsyncWriter

    reg = obs.get_registry()
    was = reg.enabled
    reg.enable()
    writer = AsyncWriter(name="t-ledger")
    led = obs_memory.get_memory_ledger()
    gate = threading.Event()
    try:
        writer.submit(gate.wait, label="blocked", nbytes=1 << 20)
        assert writer.staged_bytes() == 1 << 20
        assert led.claimed_bytes("checkpoint_staging") == 1 << 20
        gate.set()
        writer.wait()
        assert writer.staged_bytes() == 0
        assert led.claimed_bytes("checkpoint_staging") == 0
    finally:
        gate.set()
        writer.close()
        if not was:
            reg.disable()
