"""Elastic chaos-survival controller: detect → shrink → resume → grow,
proven under fault injection.

The reference marks a communicator permanently dead on first failure
(recovery "none", SURVEY.md §5.3). ``runtime.controller`` closes the loop
the repo's elastic/checkpoint/obs subsystems left open, and these tests
drive it with ``runtime.chaos``'s scripted and seeded kill/restore
schedules. The headline pin is the acceptance criterion: a scripted
schedule with 3 kills + 1 restore on the virtual-8 mesh completes with
ZERO lost steps and final params BIT-IDENTICAL to an uninterrupted run at
the same step count.
"""

import numpy as np
import optax
import pytest

from dsml_tpu.models.gpt2 import GPT2, GPT2Config
from dsml_tpu.parallel.mesh import MeshSpec, build_mesh
from dsml_tpu.runtime import chaos
from dsml_tpu.runtime.chaos import (
    ChaosEvent,
    ChaosSchedule,
    VirtualFleet,
    run_chaos_training,
)
from dsml_tpu.runtime.controller import (
    ControllerConfig,
    DecodeFleet,
    DeviceLost,
    ElasticController,
)


def _model():
    cfg = GPT2Config.tiny()
    return GPT2(cfg), cfg


def _batches(cfg, n_steps, global_batch=8, seed=0):
    rng = np.random.default_rng(seed)
    data = rng.integers(0, cfg.vocab_size,
                        (n_steps + 4, global_batch, cfg.max_seq)).astype(np.int32)

    def provider(step):
        x = data[step - 1]
        return x, np.roll(x, -1, 1).astype(np.int32)

    return provider


def _controller(model, provider, tmp_path, devices, spec=None, **over):
    fleet = VirtualFleet(devices)
    kwargs = dict(
        checkpoint_dir=str(tmp_path / "ck"),
        fleet=fleet,
        config=ControllerConfig(checkpoint_every=over.pop("checkpoint_every", 4),
                                growback=over.pop("growback", "replay"),
                                detect_every=over.pop("detect_every", 1)),
        global_batch=8, seed=0,
    )
    if spec is not None:
        kwargs["mesh"] = build_mesh(spec, devices)
        kwargs["spec"] = spec
    kwargs.update(over)
    return ElasticController(model, optax.adam(1e-2), provider, **kwargs), fleet


# ---------------------------------------------------------------------------
# THE acceptance pin: scripted schedule, ≥3 kills + 1 restore, virtual-8
# ---------------------------------------------------------------------------


def test_scripted_chaos_bit_identical_zero_lost_steps(devices8, tmp_path):
    """3 kills (one signal-injected, two probe-detected) + 1 full restore:
    the run completes every step, the replay grow-back erases the outage
    from the lineage, and the final params are bit-identical to an
    uninterrupted run of the same 24 steps on the same full mesh. Recovery
    p50/p99 are computable from the report (what ``python -m
    dsml_tpu.runtime.chaos --report`` writes)."""
    report = chaos.run_smoke(n_steps=24, seeds=(), serving=False,
                             tmp_dir=str(tmp_path))
    assert chaos.verify(report) == []
    s = report["scripted"]
    assert s["steps_completed"] == 24            # zero lost steps
    assert s["bit_identical"] is True            # outage left no trace
    assert s["kills"] >= 3
    kinds = [r["kind"] for r in s["recoveries"]]
    assert kinds.count("reconfigure") >= 3       # every kill recovered live
    assert "grow_replay" in kinds                # capacity re-adopted
    grow = next(r for r in s["recoveries"] if r["kind"] == "grow_replay")
    assert grow["to_width"] == 8
    assert s["redone_steps"] > 0                 # the replay's honest price
    assert s["goodput"] >= report["goodput_floor"]
    assert s["recovery_p50_ms"] > 0 and s["recovery_p99_ms"] >= s["recovery_p50_ms"]


def test_seeded_schedules_are_deterministic_and_survivable():
    """Same seed → identical schedule (reproducible chaos); kills always
    leave a survivor and a restore always follows."""
    a = ChaosSchedule.seeded(7, n_steps=24)
    b = ChaosSchedule.seeded(7, n_steps=24)
    assert a.events == b.events
    assert a.kills() >= 1
    assert any(e.action == "restore" for e in a.events)
    c = ChaosSchedule.seeded(8, n_steps=24)
    assert c.events != a.events


def test_chaos_env_knob_parses():
    assert chaos.config_from_env("") is None
    assert chaos.config_from_env("0") is None
    assert chaos.config_from_env("1").kills() == 3
    assert chaos.config_from_env("seed:5").events == ChaosSchedule.seeded(5).events
    with pytest.raises(ValueError, match="DSML_CHAOS"):
        chaos.config_from_env("bogus")


# ---------------------------------------------------------------------------
# individual loop legs
# ---------------------------------------------------------------------------


def test_injected_device_lost_signal_detected_without_probe(devices8, tmp_path):
    """The DeviceLost signal queue alone triggers recovery: fleet probing
    is effectively disabled (detect_every huge), so only the injected
    signal can carry the news — and it does, at the right step."""
    model, cfg = _model()
    ctl, fleet = _controller(
        model, _batches(cfg, 8), tmp_path, devices8,
        detect_every=10_000, growback="keep",
    )
    schedule = ChaosSchedule([ChaosEvent(3, "kill", (7,), inject=True)])
    with ctl:
        report = run_chaos_training(ctl, schedule, 8)
    assert report["steps_completed"] == 8
    assert [r["kind"] for r in report["recoveries"]] == ["reconfigure"]
    assert report["recoveries"][0]["resume_step"] == 3
    assert report["recoveries"][0]["lost_devices"] == [devices8[7].id]
    assert ctl.losses and np.isfinite(ctl.losses[8])


def test_signal_lost_device_is_quarantined_from_growback(devices8, tmp_path):
    """A device reported dead by SIGNAL while the fleet view still lists
    it (the StaticFleet shape: jax.devices() never shrinks) must NOT be
    re-adopted at the next checkpoint boundary — re-sharding onto a dead
    device would hang the recovery the controller just performed."""
    from dsml_tpu.runtime.controller import StaticFleet

    model, cfg = _model()
    ctl = ElasticController(
        model, optax.adam(1e-2), _batches(cfg, 12),
        checkpoint_dir=str(tmp_path / "ck"),
        fleet=StaticFleet(devices8),
        config=ControllerConfig(checkpoint_every=4, growback="keep"),
        global_batch=8, seed=0,
    )
    with ctl:
        def on_step(step):
            if step == 3 and not ctl.recoveries:
                ctl.inject(DeviceLost(devices8[6:], "signal-only loss"))

        report = ctl.run(12, on_step=on_step)
    assert report["steps_completed"] == 12
    kinds = [r["kind"] for r in report["recoveries"]]
    assert kinds == ["reconfigure"]          # no grow back onto the dead pair
    assert ctl.spec.n_devices == 4
    assert not any(d.id in {devices8[6].id, devices8[7].id}
                   for d in ctl.mesh.devices.flat)


def test_checkpoint_fallback_on_torn_state(devices8, tmp_path):
    """Losing every tp=1 rank tears the Megatron-sharded leaves wholesale:
    reconfigure refuses (the audit), and the controller falls back to the
    last committed checkpoint, rewinds, and replays — lost work counted,
    no step skipped."""
    model, cfg = _model()
    ctl, fleet = _controller(
        model, _batches(cfg, 8), tmp_path, devices8,
        spec=MeshSpec(dp=4, tp=2), checkpoint_every=2, growback="keep",
    )
    schedule = ChaosSchedule([ChaosEvent(5, "kill", (1, 3, 5, 7))])
    with ctl:
        report = run_chaos_training(ctl, schedule, 8)
    assert report["steps_completed"] == 8
    fallback = [r for r in report["recoveries"]
                if r["kind"] == "checkpoint_fallback"]
    assert len(fallback) == 1
    # kill lands before step 5 runs; last commit was step 4 → exactly the
    # 0 completed-steps-since-checkpoint... the rewind replays step 5 on
    # the survivors, so nothing after the commit was lost
    assert fallback[0]["lost_steps"] == 0
    assert fallback[0]["resume_step"] == 5
    assert ctl.spec.n_devices == 4
    assert np.isfinite(ctl.losses[8])


def test_mid_window_torn_loss_rewinds_and_replays(devices8, tmp_path):
    """A torn loss AFTER steps have run past the checkpoint: the fallback
    rewinds those steps (lost work > 0) and still completes the run."""
    model, cfg = _model()
    ctl, fleet = _controller(
        model, _batches(cfg, 8), tmp_path, devices8,
        spec=MeshSpec(dp=4, tp=2), checkpoint_every=4, growback="keep",
    )
    schedule = ChaosSchedule([ChaosEvent(7, "kill", (1, 3, 5, 7))])
    with ctl:
        report = run_chaos_training(ctl, schedule, 8)
    assert report["steps_completed"] == 8
    fb = next(r for r in report["recoveries"]
              if r["kind"] == "checkpoint_fallback")
    assert fb["lost_steps"] == 2          # steps 5,6 rewound to commit 4
    assert report["redone_steps"] == 2


def test_grow_keep_mode_reshards_without_recompute(devices8, tmp_path):
    """growback='keep': restored capacity is adopted by re-sharding the
    LIVE survivor-width state — zero redone steps, width back to full."""
    model, cfg = _model()
    ctl, fleet = _controller(
        model, _batches(cfg, 12), tmp_path, devices8, growback="keep",
    )
    schedule = ChaosSchedule([
        ChaosEvent(3, "kill", (6,)),
        ChaosEvent(5, "restore", ()),
    ])
    with ctl:
        report = run_chaos_training(ctl, schedule, 12)
    assert report["steps_completed"] == 12
    kinds = [r["kind"] for r in report["recoveries"]]
    assert kinds == ["reconfigure", "grow_keep"]
    assert report["redone_steps"] == 0
    assert ctl.spec.n_devices == 8         # grew back at the boundary
    assert report["recoveries"][1]["resume_step"] == 9  # boundary 8 + 1


def test_manager_lineage_predicate_and_delete(tmp_path):
    """CheckpointManager hooks the controller rides: latest_step(where=)
    finds the newest checkpoint by manifest meta, delete_steps prunes."""
    import jax.numpy as jnp

    from dsml_tpu.checkpoint import CheckpointManager

    with CheckpointManager(str(tmp_path / "m"), max_to_keep=None) as m:
        for step, lineage in ((1, "pure"), (2, "pure"), (3, "mixed")):
            m.save(step, {"w": jnp.full((2,), step)},
                   meta={"lineage": lineage})
        assert m.latest_step() == 3
        assert m.latest_step(where=lambda meta: meta.get("lineage") == "pure") == 2
        assert m.latest_step(where=lambda meta: False) is None
        assert m.delete_steps([2, 3]) == 2
        assert m.all_steps() == [1]


# ---------------------------------------------------------------------------
# serving: decode-replica fleet under chaos
# ---------------------------------------------------------------------------


def _prompts(cfg, n=6, seed=3):
    rng = np.random.default_rng(seed)
    return [rng.integers(1, cfg.vocab_size, rng.integers(3, 9)).astype(np.int32)
            for _ in range(n)]


def test_decode_fleet_replica_kill_zero_token_loss():
    """A replica dies mid-drain: its unfinished requests re-run on the
    survivors and every request's final tokens equal the single-batcher
    reference — a replica loss costs latency, never tokens."""
    from dsml_tpu.serving import ContinuousBatcher

    model, cfg = _model()
    params = model.init(0)
    prompts = _prompts(cfg)
    max_new = 6
    ref = ContinuousBatcher(model, params, n_slots=2)
    ref_rids = [ref.submit(p, max_new) for p in prompts]
    ref_tokens = ref.run()

    fleet = DecodeFleet(
        lambda: ContinuousBatcher(model, params, n_slots=2, max_queue=8),
        min_replicas=2, max_replicas=2, scale_down_idle_ticks=10_000,
    )
    out = chaos.run_chaos_serving(fleet, prompts, max_new,
                                  kill_ticks={2: None})
    assert any(e.get("reason") == "killed" and e.get("requeued", 0) > 0
               for e in fleet.scale_events)
    for frid, rrid in zip(sorted(out["results"]), ref_rids):
        assert out["results"][frid] == ref_tokens[rrid]


def test_decode_fleet_queue_depth_autoscale():
    """Queue depth drives replica count both ways: a burst scales up to
    the cap, an idle fleet scales back to the floor."""
    from dsml_tpu.serving import ContinuousBatcher

    model, cfg = _model()
    params = model.init(0)
    fleet = DecodeFleet(
        lambda: ContinuousBatcher(model, params, n_slots=1, max_queue=2),
        min_replicas=1, max_replicas=3,
        scale_up_queue_depth=1, scale_down_idle_ticks=2,
    )
    for p in _prompts(cfg, n=9):
        fleet.submit(p, 4)
    fleet.run()
    ups = [e for e in fleet.scale_events
           if e["direction"] == "up" and e["reason"] == "queue_depth"]
    assert ups, "queue depth never triggered a scale-up"
    assert max(e["n_replicas"] for e in fleet.scale_events) == 3
    for _ in range(10):  # idle ticks → retire back to the floor
        fleet.tick()
    assert fleet.n_replicas == 1
    downs = [e for e in fleet.scale_events
             if e["direction"] == "down" and e["reason"] == "idle"]
    assert len(downs) == 2


def test_live_coordinator_failure_feed_drives_recovery(devices8, tmp_path):
    """ISSUE 7 satellite / ROADMAP item: the controller's failure_feed
    wired from a LIVE CoordinatorRuntime.add_failure_listener in the
    wire-compat cluster (real gRPC device servers, real health probes) —
    no injected feeds. Killing a device server's socket makes the health
    loop's death verdict arrive as the controller's DeviceLost, and the
    run shrinks and completes."""
    from dsml_tpu.comm.coordinator import CoordinatorConfig, CoordinatorRuntime
    from dsml_tpu.comm.device_server import serve_local_devices
    from dsml_tpu.runtime.controller import StaticFleet

    # device ids == jax device ids, so coordinator verdicts name devices
    # the controller's mesh actually contains
    handles = serve_local_devices(2, base_device_id=0, mem_size=0x4000)
    rt = CoordinatorRuntime(CoordinatorConfig(
        # the default 2 s probe timeout: under a loaded six-worker run a
        # 0.5 s probe of a LIVE server timed out and the health loop failed
        # device 0 before the test killed device 1 (a stopped server refuses
        # the connection at once, so detection is no slower)
        health_interval_s=0.1, dial_retries=2, dial_backoff_s=0.05,
    ))
    model, cfg = _model()
    provider = _batches(cfg, 12)
    spec = MeshSpec(dp=2)
    try:
        rt.comm_init(2, [h.address for h in handles])
        feed = rt.failure_feed()
        controller = ElasticController(
            model, optax.adam(1e-2), provider,
            checkpoint_dir=str(tmp_path / "ck"),
            fleet=StaticFleet(devices8[:2]),
            mesh=build_mesh(spec, devices8[:2]), spec=spec,
            config=ControllerConfig(checkpoint_every=4, detect_every=10_000),
            global_batch=8, seed=0,
            failure_feed=feed,
        )

        import time as _time

        from dsml_tpu.comm.proto import gpu_sim_pb2 as _pb

        killed = {"done": False}

        def on_step(step):
            if step == 4 and not killed["done"]:
                killed["done"] = True
                handles[1].stop()
                # wait for the health loop to probe, fail the comm, and
                # push its verdict; the NEXT step's detection pass drains
                # the feed into a DeviceLost
                deadline = _time.time() + 15.0
                while _time.time() < deadline:
                    if rt.comms[1].status == _pb.FAILED:
                        break
                    _time.sleep(0.05)
                else:
                    raise AssertionError("health loop never failed the comm")

        with controller:
            report = controller.run(12, on_step=on_step)
    finally:
        rt.stop()
        for h in handles:
            h.stop()
    assert report["steps_completed"] == 12
    assert report["n_recoveries"] >= 1
    kinds = [r["kind"] for r in report["recoveries"]]
    assert any(k in ("reconfigure", "checkpoint_fallback") for k in kinds)
    shrink = report["recoveries"][0]
    assert shrink["to_width"] == 1          # survivor-only mesh
    # the verdict named the REAL device id the health loop saw die
    assert [getattr(d, "id", d) for d in shrink["lost_devices"]] == [1]


def test_decode_fleet_metrics_are_labeled_per_replica():
    """ISSUE 7 satellite: DecodeFleet serving metrics carry per-replica
    labels so the aggregator sees N series, not one blended stream."""
    from dsml_tpu import obs
    from dsml_tpu.serving import ContinuousBatcher

    model, cfg = _model()
    params = model.init(0)
    was = obs.enabled()
    obs.enable(forensics=False)
    try:
        reg = obs.get_registry()
        tokens = reg.counter("serving_tokens_total", "tokens emitted",
                             labels=("replica", "role"))
        before = {r: tokens.value(replica=r, role="decode")
                  for r in ("0", "1")}
        fleet = DecodeFleet(
            lambda: ContinuousBatcher(model, params, n_slots=2, max_queue=8),
            min_replicas=2, max_replicas=2, scale_down_idle_ticks=10_000,
        )
        assert [b.obs_replica for b in fleet._replicas.values()] == ["0", "1"]
        for p in _prompts(cfg, n=6):
            fleet.submit(p, 4)
        fleet.run()
        emitted = {r: tokens.value(replica=r, role="decode") - before[r]
                   for r in ("0", "1")}
        # both replicas worked AND their series are distinguishable
        assert emitted["0"] > 0 and emitted["1"] > 0
        assert emitted["0"] + emitted["1"] == 6 * 4
        depth = reg.gauge("serving_queue_depth", labels=("replica", "role"))
        assert depth.value(replica="0", role="decode") is not None
        assert depth.value(replica="1", role="decode") is not None
    finally:
        if not was:
            obs.disable()
