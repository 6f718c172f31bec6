"""Fault injection: prove the chaos-survival guarantee, don't assert it.

``runtime.controller`` claims it rides preemptions end-to-end. This module
is the adversary that makes the claim testable: scripted and seeded-random
kill/restore schedules driven against the training controller (device
loss) and the serving ``DecodeFleet`` (replica loss), with the invariants
checked afterwards:

- **zero lost steps** — the final lineage contains every step exactly
  once (the step counter reaches the target and nothing was skipped);
- **bit-identity** (``growback="replay"``) — final params bit-identical
  to an uninterrupted run at the same step count on the same full mesh;
- **goodput floor** — productive ÷ wall stays above a documented floor
  for the harness (virtual-8 CPU: compiles dominate, floor 0.02 — the
  number is environment-specific, the FLOOR EXISTING is the guarantee);
- **zero token loss** (serving) — every request killed mid-decode
  re-runs on a survivor and its final tokens equal the single-batcher
  reference (greedy decode is a pure function of the prompt).

Faults are injected through the same three doors the controller watches:
the fleet view (``VirtualFleet.kill`` — the health-probe verdict), the
signal queue (``controller.inject(DeviceLost(...))``), and — when
``DSML_HANGWATCH`` is armed — a hangwatch expiry paired with a fleet
kill (the wedged-device shape).

Env knob ``DSML_CHAOS`` selects a schedule for the smoke entry point
(``python -m dsml_tpu.runtime.chaos``): unset/``0`` → off, ``1`` →
the default scripted schedule, ``seed:<n>`` → seeded-random. CI runs the
scripted schedule on the virtual-8 mesh every push (tier1.yml
``chaos-smoke``).
"""

from __future__ import annotations

import dataclasses
import os
import random
import threading
import time

import numpy as np

from dsml_tpu.utils.logging import get_logger

__all__ = [
    "ChaosEvent",
    "ChaosSchedule",
    "VirtualFleet",
    "WireFault",
    "WireFaultPlan",
    "wire_fault_plan",
    "set_wire_fault_plan",
    "config_from_env",
    "run_chaos_training",
    "run_chaos_serving",
    "run_chaos_serving_fleet",
    "run_smoke",
    "run_migration_smoke",
]

log = get_logger("chaos")


@dataclasses.dataclass(frozen=True)
class ChaosEvent:
    """One fault: at ``step`` (training) / ``tick`` (serving), ``kill`` the
    targets or ``restore`` them (empty targets = everything dead)."""

    step: int
    action: str  # "kill" | "restore"
    targets: tuple = ()
    inject: bool = False  # also push a DeviceLost signal (vs probe-only)

    def __post_init__(self):
        if self.action not in ("kill", "restore"):
            raise ValueError(f"unknown chaos action {self.action!r}")


class ChaosSchedule:
    """An ordered list of :class:`ChaosEvent`; scripted or seeded-random."""

    def __init__(self, events):
        self.events = tuple(sorted(events, key=lambda e: e.step))

    @classmethod
    def scripted_default(cls, n_devices: int = 8) -> "ChaosSchedule":
        """The CI smoke schedule: 3 kills at distinct steps (one injected,
        two probe-detected), then a full restore — the ≥3-kills/1-restore
        shape the acceptance criterion names."""
        return cls([
            ChaosEvent(6, "kill", (n_devices - 1,), inject=True),
            ChaosEvent(10, "kill", (2,)),
            ChaosEvent(13, "kill", (0,)),
            ChaosEvent(17, "restore", ()),
        ])

    @classmethod
    def seeded(cls, seed: int, n_steps: int = 24, n_devices: int = 8,
               n_kills: int = 3) -> "ChaosSchedule":
        """Seeded-random schedule: ``n_kills`` distinct devices die at
        distinct steps in the first two-thirds of the run (always leaving
        at least one survivor), then everything restores."""
        rng = random.Random(seed)
        n_kills = min(n_kills, n_devices - 1)
        lo, hi = 2, max(2 * n_steps // 3, 3)
        steps = sorted(rng.sample(range(lo, hi + 1), min(n_kills, hi - lo + 1)))
        targets = rng.sample(range(n_devices), len(steps))
        events = [
            ChaosEvent(s, "kill", (t,), inject=rng.random() < 0.5)
            for s, t in zip(steps, targets)
        ]
        restore_at = min(steps[-1] + rng.randint(2, 5), n_steps - 4)
        events.append(ChaosEvent(max(restore_at, steps[-1] + 1), "restore", ()))
        return cls(events)

    def at(self, step: int) -> list[ChaosEvent]:
        return [e for e in self.events if e.step == step]

    def kills(self) -> int:
        return sum(1 for e in self.events if e.action == "kill")


def config_from_env(spec: str | None = None) -> ChaosSchedule | None:
    """``DSML_CHAOS``: unset/``0`` → None; ``1`` → the scripted default;
    ``seed:<n>`` → :meth:`ChaosSchedule.seeded`."""
    if spec is None:
        spec = os.environ.get("DSML_CHAOS", "")
    spec = spec.strip().lower()
    if spec in ("", "0", "false", "off"):
        return None
    if spec in ("1", "true", "on", "scripted"):
        return ChaosSchedule.scripted_default()
    if spec.startswith("seed:"):
        try:
            return ChaosSchedule.seeded(int(spec[5:]))
        except ValueError as e:
            raise ValueError(f"DSML_CHAOS={spec!r}: bad seed") from e
    raise ValueError(
        f"DSML_CHAOS={spec!r} is not one of 0/1/scripted/seed:<n>"
    )


class VirtualFleet:
    """A fleet view the harness can lie through: ``kill`` hides devices
    from ``available()`` (what a coordinator health probe would report),
    ``restore`` brings them back (capacity returning). Indices are into
    the original device list."""

    def __init__(self, devices):
        self._devices = list(devices)
        self._dead: set[int] = set()

    def available(self) -> list:
        return [d for i, d in enumerate(self._devices) if i not in self._dead]

    def kill(self, *indices: int) -> list:
        dead = []
        for i in indices:
            if i not in self._dead and 0 <= i < len(self._devices):
                self._dead.add(i)
                dead.append(self._devices[i])
        if len(self._dead) >= len(self._devices):
            raise RuntimeError("chaos killed the whole fleet")
        return dead

    def restore(self, *indices: int) -> list:
        back = sorted(self._dead) if not indices else list(indices)
        restored = [self._devices[i] for i in back if i in self._dead]
        self._dead -= set(back)
        return restored

    @property
    def n_dead(self) -> int:
        return len(self._dead)


# ---------------------------------------------------------------------------
# wire faults: the DATA-PLANE adversary (P2P streams)
# ---------------------------------------------------------------------------


@dataclasses.dataclass
class WireFault:
    """One data-plane fault, applied by ``device_server._push_stream``:

    - ``drop`` — truncate the StreamSend mid-stream (the receiver keeps a
      partial prefix, the sender's call errors);
    - ``corrupt`` — flip one byte mid-payload (exactly what the migration
      path's per-chunk CRC32C must catch);
    - ``delay`` — sleep ``delay_s`` before pushing (timeout exercise);
    - ``partition`` — sever the link: the push fails before any byte moves.

    ``nth`` selects the 1-based send ordinal the fault fires on (None =
    every matching send); ``src``/``dst`` restrict to one link."""

    action: str
    nth: int | None = None
    src: int | None = None
    dst: int | None = None
    delay_s: float = 0.1

    _ACTIONS = ("drop", "corrupt", "delay", "partition")

    def __post_init__(self):
        if self.action not in self._ACTIONS:
            raise ValueError(f"unknown wire-fault action {self.action!r}")

    def matches(self, ordinal: int, src, dst) -> bool:
        if self.nth is not None and ordinal != self.nth:
            return False
        if self.src is not None and src != self.src:
            return False
        if self.dst is not None and dst != self.dst:
            return False
        return True

    def apply_payload(self, payload: bytes) -> bytes:
        if self.action == "corrupt":
            mutated = bytearray(payload)
            if mutated:
                mutated[len(mutated) // 2] ^= 0xFF
            return bytes(mutated)
        if self.action == "delay":
            time.sleep(self.delay_s)
        return payload


class WireFaultPlan:
    """A per-link wire-fault schedule, keyed on the process-wide send
    ordinal. Spec grammar (``DSML_CHAOS_WIRE``): semicolon-separated
    ``action@sel[,src=N][,dst=N][,s=SECONDS]`` where ``sel`` is a 1-based
    send ordinal or ``*`` (every send) — e.g.
    ``"drop@1;corrupt@3"`` or ``"delay@*,dst=1,s=0.05"``."""

    def __init__(self, faults):
        self.faults = list(faults)
        self._sends = 0
        self._lock = threading.Lock()
        self.fired: list[dict] = []

    @classmethod
    def parse(cls, spec: str) -> "WireFaultPlan":
        faults = []
        for token in spec.split(";"):
            token = token.strip().lower()
            if not token:
                continue
            head, _, rest = token.partition(",")
            if "@" not in head:
                raise ValueError(f"wire-fault token {token!r}: expected action@sel")
            action, sel = head.split("@", 1)
            fault = {"action": action.strip(),
                     "nth": None if sel.strip() == "*" else int(sel)}
            for kv in rest.split(","):
                kv = kv.strip()
                if not kv:
                    continue
                k, _, v = kv.partition("=")
                if k == "src":
                    fault["src"] = int(v)
                elif k == "dst":
                    fault["dst"] = int(v)
                elif k == "s":
                    fault["delay_s"] = float(v)
                else:
                    raise ValueError(f"wire-fault token {token!r}: unknown key {k!r}")
            faults.append(WireFault(**fault))
        return cls(faults)

    def on_send(self, src, dst) -> WireFault | None:
        """Called by the device server once per outbound stream push;
        returns the fault to apply (if any) and records the firing."""
        with self._lock:
            self._sends += 1
            ordinal = self._sends
            for fault in self.faults:
                if fault.matches(ordinal, src, dst):
                    self.fired.append(
                        {"action": fault.action, "ordinal": ordinal,
                         "src": src, "dst": dst}
                    )
                    log.warning("wire fault: %s on send #%d (%s -> %s)",
                                fault.action, ordinal, src, dst)
                    from dsml_tpu.obs import get_registry

                    reg = get_registry()
                    if reg.enabled:
                        reg.counter(
                            "chaos_wire_faults_total",
                            "injected data-plane faults", labels=("action",),
                        ).inc(action=fault.action)
                    return fault
        return None


_WIRE_UNSET = object()
_WIRE_PLAN = _WIRE_UNSET


def wire_fault_plan() -> WireFaultPlan | None:
    """The process's active wire-fault plan: whatever
    :func:`set_wire_fault_plan` installed, else ``DSML_CHAOS_WIRE`` parsed
    once (None when unset/empty — the zero-cost production answer)."""
    global _WIRE_PLAN
    if _WIRE_PLAN is _WIRE_UNSET:
        spec = os.environ.get("DSML_CHAOS_WIRE", "").strip()
        _WIRE_PLAN = WireFaultPlan.parse(spec) if spec else None
    return _WIRE_PLAN


def set_wire_fault_plan(plan: WireFaultPlan | None) -> None:
    """Install (or, with None, clear) the active plan — the in-process
    test hook; subprocesses use the env knob."""
    global _WIRE_PLAN
    _WIRE_PLAN = plan


def run_chaos_training(controller, schedule: ChaosSchedule,
                       n_steps: int) -> dict:
    """Drive ``controller.run(n_steps)`` with ``schedule`` applied through
    the controller's fleet (which must be a :class:`VirtualFleet`).
    Returns the controller report with the schedule appended."""
    from dsml_tpu.runtime.controller import DeviceLost

    fleet = controller.fleet
    fired: set = set()

    def on_step(step: int) -> None:
        for ev in schedule.at(step):
            if id(ev) in fired:
                continue
            fired.add(id(ev))
            if ev.action == "kill":
                dead = fleet.kill(*ev.targets)
                log.warning("chaos: step %d kill %s", step, list(ev.targets))
                if ev.inject and dead:
                    controller.inject(DeviceLost(dead, "chaos kill"))
            else:
                restored = fleet.restore(*ev.targets)
                log.warning("chaos: step %d restore %d device(s)",
                            step, len(restored))

    report = controller.run(n_steps, on_step=on_step)
    report["schedule"] = [dataclasses.asdict(e) for e in schedule.events]
    return report


def run_chaos_serving(fleet, prompts, max_new: int,
                      kill_ticks: dict[int, int | None],
                      max_ticks: int = 100_000) -> dict:
    """Drive a ``DecodeFleet`` to drain ``prompts`` while killing replicas
    at the scheduled ticks (``{tick: replica_id or None=newest}``).
    Returns ``{"results": {frid: tokens}, "ticks": n}``."""
    frids = [fleet.submit(p, max_new) for p in prompts]
    tick = 0
    while fleet.outstanding:
        if tick in kill_ticks and fleet.n_replicas:
            fleet.kill_replica(kill_ticks[tick])
        fleet.tick()
        tick += 1
        if tick > max_ticks:
            raise RuntimeError(f"serving chaos did not drain in {max_ticks}")
    results = fleet.run(max_ticks=1)  # drains the harvested results
    return {"results": {f: results.get(f, []) for f in frids}, "ticks": tick}


def run_chaos_serving_fleet(router, prompts, max_new: int,
                            kill_ticks: dict[int, tuple],
                            max_ticks: int = 100_000) -> dict:
    """The disaggregated-fleet variant of :func:`run_chaos_serving`: drive
    a ``serving.Router`` to drain ``prompts`` while killing WORKERS at the
    scheduled ticks — ``{tick: ("prefill"|"decode", idx or None=last)}``.
    A prefill worker killed mid-handoff loses its partial chunk state; the
    router re-prefills on a survivor (prefill is a pure function of the
    prompt, so the regenerated KV rows — and therefore the tokens — are
    identical). Returns results plus the requeue counts the verdict needs
    to prove the kill actually interrupted work in flight, and the
    request-tracing verdicts: a killed request's re-run must retire under
    the SAME trace_id with its retry recorded, and its SLO burn must
    count the FULL user-visible latency (original submit → final retire,
    not just the post-requeue leg)."""
    frids = [router.submit(p, max_new) for p in prompts]
    minted = {
        f: (router.trace_of(f).trace_id if router.trace_of(f) else None)
        for f in frids
    }
    tick = 0
    while router.outstanding:
        kill = kill_ticks.get(tick)
        if kill is not None:
            kind, idx = kill
            if kind == "prefill":
                router.kill_prefill_worker(idx)
            elif kind == "decode":
                router.kill_decode_worker(idx)
            else:
                raise ValueError(f"unknown worker kind {kind!r}")
        router.tick()
        tick += 1
        if tick > max_ticks:
            raise RuntimeError(f"serving chaos did not drain in {max_ticks}")
    results = router.run(max_ticks=1)  # drains the harvested results
    # -- tracing verdicts over the requeued set -----------------------------
    requeue_t: dict[int, float] = {}
    for f, t in router.requeue_log:
        requeue_t[f] = t  # last requeue wins (the run that finished)
    requeued = [f for f in requeue_t if f in set(frids)]
    records = router.request_records
    same_trace = all(
        records.get(f, {}).get("trace_id") == minted.get(f)
        and minted.get(f) is not None
        for f in requeued
    )
    retry_recorded = all(
        records.get(f, {}).get("retries", 0) >= 1 for f in requeued
    )
    # full-latency burn: the recorded e2e must EXCEED the post-requeue
    # leg alone — i.e. the clock kept running from the ORIGINAL submit
    # through the kill, not from the retry
    burn_full = all(
        records.get(f, {}).get("e2e_s") is not None
        and records[f].get("finished_mono") is not None
        and records[f]["e2e_s"]
        > (records[f]["finished_mono"] - requeue_t[f]) - 1e-9
        for f in requeued
    )
    return {
        "results": {f: results.get(f, []) for f in frids},
        "ticks": tick,
        "requeued_prefill": router.requeued_prefill,
        "requeued_decode": router.requeued_decode,
        "requeued_requests": len(requeued),
        "trace_requeue_same": int(same_trace),
        "trace_retry_recorded": int(retry_recorded),
        "trace_burn_full_latency": int(burn_full),
    }


# ---------------------------------------------------------------------------
# smoke: the end-to-end guarantee as an executable check (CI)
# ---------------------------------------------------------------------------

# documented goodput floor for THIS harness (virtual-8 CPU mesh, tiny
# model): recovery compiles dominate the wall, so the floor is low — the
# guarantee is that a floor EXISTS and holds, not the CPU number itself
# (docs/ELASTIC.md documents the real-chip expectation separately)
SMOKE_GOODPUT_FLOOR = 0.02


def _bit_identical(tree_a, tree_b) -> bool:
    import jax

    la, lb = jax.tree.leaves(tree_a), jax.tree.leaves(tree_b)
    if len(la) != len(lb):
        return False
    return all(
        np.array_equal(np.asarray(jax.device_get(a)), np.asarray(jax.device_get(b)))
        for a, b in zip(la, lb)
    )


def run_smoke(n_steps: int = 24, seeds: tuple = (), checkpoint_every: int = 4,
              tmp_dir: str | None = None,
              schedule: "ChaosSchedule | None" = None,
              serving: bool = True) -> dict:
    """The acceptance run: scripted schedule (≥3 kills, 1 restore) on the
    virtual-8 mesh with ``growback="replay"`` — final params must be
    bit-identical to an uninterrupted run at the same step count, zero
    steps lost, goodput above :data:`SMOKE_GOODPUT_FLOOR`. ``seeds`` adds
    seeded-random schedules for the recovery-time distribution. Returns a
    report dict; ``verify`` raises on any violated invariant."""
    import shutil
    import tempfile

    import jax
    import optax

    from dsml_tpu.models.gpt2 import GPT2, GPT2Config

    if n_steps < 20:
        raise ValueError(
            f"chaos smoke needs n_steps >= 20 (the scripted schedule kills "
            f"through step 13, restores at 17, and grows at the next "
            f"checkpoint boundary), got {n_steps}"
        )
    from dsml_tpu.parallel.hybrid import init_hybrid, make_hybrid_train_step
    from dsml_tpu.parallel.mesh import MeshSpec, build_mesh
    from dsml_tpu.runtime.controller import ControllerConfig, ElasticController

    devices = jax.devices()[:8]
    if len(devices) < 8:
        raise RuntimeError(f"chaos smoke needs 8 devices, found {len(devices)}")
    cfg = GPT2Config.tiny()
    model = GPT2(cfg)
    optimizer = optax.adam(1e-2)
    global_batch = 8
    rng = np.random.default_rng(0)
    data = rng.integers(0, cfg.vocab_size,
                        (n_steps + 8, global_batch, cfg.max_seq)).astype(np.int32)

    def batch_provider(step: int):
        x = data[step - 1]
        return x, np.roll(x, -1, 1).astype(np.int32)

    spec = MeshSpec(dp=8)
    base = tmp_dir or tempfile.mkdtemp(prefix="dsml_chaos_")
    created = tmp_dir is None
    report: dict = {"n_steps": n_steps}
    try:
        # the uninterrupted reference: the same mesh, same batches, no
        # controller, no checkpoints, no failures
        mesh = build_mesh(spec, devices)
        step_fn = make_hybrid_train_step(model, optimizer, mesh)
        ref_params, ref_opt = init_hybrid(model, optimizer, mesh, seed=0)
        for s in range(1, n_steps + 1):
            ref_params, ref_opt, ref_loss = step_fn(ref_params, ref_opt,
                                                    *batch_provider(s))
        ref_loss = float(ref_loss)

        def one_run(schedule: ChaosSchedule, name: str) -> dict:
            fleet = VirtualFleet(devices)
            ctl = ElasticController(
                model, optimizer, batch_provider,
                checkpoint_dir=os.path.join(base, name),
                fleet=fleet, mesh=build_mesh(spec, devices), spec=spec,
                config=ControllerConfig(checkpoint_every=checkpoint_every,
                                        growback="replay"),
                global_batch=global_batch, seed=0,
            )
            with ctl:
                rep = run_chaos_training(ctl, schedule, n_steps)
            rep["bit_identical"] = _bit_identical(ctl.params, ref_params)
            rep["final_loss"] = ctl.losses.get(n_steps)
            rep["ref_loss"] = ref_loss
            rep["kills"] = schedule.kills()
            return rep

        report["scripted"] = one_run(
            schedule or ChaosSchedule.scripted_default(), "scripted"
        )
        recov = [r["recovery_ms"] for r in report["scripted"]["recoveries"]]
        for seed in seeds:
            rep = one_run(ChaosSchedule.seeded(seed, n_steps), f"seed{seed}")
            report[f"seed{seed}"] = rep
            recov += [r["recovery_ms"] for r in rep["recoveries"]]
        if recov:
            report["recovery_p50_ms"] = round(float(np.percentile(recov, 50)), 3)
            report["recovery_p99_ms"] = round(float(np.percentile(recov, 99)), 3)
            report["recovery_samples"] = len(recov)
        report["goodput_floor"] = SMOKE_GOODPUT_FLOOR
        # ledger staging audit: after every kill→shrink→grow recovery the
        # controllers (and their checkpoint writers / any migrators) are
        # closed — bytes still claimed as staging are a leak, exactly the
        # class a wedged background commit or an unreleased donor span
        # produces
        from dsml_tpu.obs.memory import get_memory_ledger

        led = get_memory_ledger()
        report["ledger_staging_bytes_final"] = (
            led.claimed_bytes("checkpoint_staging")
            + led.claimed_bytes("migration_staging")
        )
        if serving:
            report["serving"] = _serving_smoke(model, cfg, rng)
            report["serving_fleet"] = _serving_fleet_smoke(model, cfg, rng)
            report["serving_paged"] = _paged_serving_smoke(model, cfg, rng)
    finally:
        if created:
            shutil.rmtree(base, ignore_errors=True)
    return report


def _serving_smoke(model, cfg, rng) -> dict:
    """Replica-loss smoke: a 2-replica decode fleet loses a replica
    mid-drain; every request re-runs on a survivor and the final tokens
    must equal the single-batcher reference (greedy ⇒ pure function of
    the prompt)."""
    from dsml_tpu.runtime.controller import DecodeFleet
    from dsml_tpu.serving import ContinuousBatcher

    params = model.init(0)
    prompts = [
        rng.integers(1, cfg.vocab_size, rng.integers(3, 9)).astype(np.int32)
        for _ in range(6)
    ]
    max_new = 6
    ref = ContinuousBatcher(model, params, n_slots=2)
    ref_rids = [ref.submit(p, max_new) for p in prompts]
    ref_tokens = ref.run()

    fleet = DecodeFleet(
        lambda: ContinuousBatcher(model, params, n_slots=2, max_queue=8),
        min_replicas=2, max_replicas=3, scale_up_queue_depth=2,
        scale_down_idle_ticks=4,
    )
    out = run_chaos_serving(fleet, prompts, max_new, kill_ticks={3: None})
    token_loss = sum(
        1 for frid, rrid in zip(sorted(out["results"]), ref_rids)
        if out["results"][frid] != ref_tokens[rrid]
    )
    return {
        "requests": len(prompts),
        "token_mismatches": token_loss,
        "ticks": out["ticks"],
        "scale_events": len(fleet.scale_events),
    }


def _serving_fleet_smoke(model, cfg, rng) -> dict:
    """Disaggregated-fleet loss smoke: a 2-prefill / 2-decode fleet loses
    a PREFILL worker mid-handoff (work in flight — the kill tick lands
    while chunked prefill is running) and later a decode worker; every
    interrupted request re-prefills/re-decodes on survivors and the final
    tokens must equal the single-batcher reference — zero token loss."""
    from dsml_tpu.serving import ContinuousBatcher, build_fleet

    params = model.init(0)
    prompts = [
        rng.integers(1, cfg.vocab_size, rng.integers(8, 24)).astype(np.int32)
        for _ in range(6)
    ]
    max_new = 6
    ref = ContinuousBatcher(model, params, n_slots=2)
    ref_rids = [ref.submit(p, max_new) for p in prompts]
    ref_tokens = ref.run()

    router = build_fleet(
        model, params, n_prefill=2, n_decode=2, prefill_chunk=8,
        n_slots=2, max_queue=8,
    )
    out = run_chaos_serving_fleet(
        router, prompts, max_new,
        kill_ticks={1: ("prefill", None), 6: ("decode", None)},
    )
    token_loss = sum(
        1 for frid, rrid in zip(sorted(out["results"]), ref_rids)
        if out["results"][frid] != ref_tokens[rrid]
    )
    return {
        "requests": len(prompts),
        "token_mismatches": token_loss,
        "ticks": out["ticks"],
        "requeued_prefill": out["requeued_prefill"],
        "requeued_decode": out["requeued_decode"],
        "requeued_requests": out["requeued_requests"],
        "trace_requeue_same": out["trace_requeue_same"],
        "trace_retry_recorded": out["trace_retry_recorded"],
        "trace_burn_full_latency": out["trace_burn_full_latency"],
    }


def _paged_serving_smoke(model, cfg, rng) -> dict:
    """Paged-KV fleet loss smoke (docs/SERVING.md § Paged KV): a paged
    2-prefill/2-decode fleet with a LIVE CoW prefix (registered fleet-wide,
    shared read-only across matching requests) loses a decode worker
    mid-flight. Invariants: re-prefilled requests on survivors produce
    tokens identical to a monolithic paged batcher (greedy + deterministic
    int4 codec ⇒ pure function of the prompt), the CoW sharing was
    actually live when the kill landed, and EVERY worker's pool — the
    killed one's included — reclaims its request pages without leaking
    capacity (only the prefix registry's pages stay held)."""
    from dsml_tpu.serving import ContinuousBatcher, build_fleet

    params = model.init(0)
    page_size = 8
    prefix = rng.integers(1, cfg.vocab_size, 20).astype(np.int32)
    prompts = []
    for i in range(6):
        tail = rng.integers(1, cfg.vocab_size,
                            int(rng.integers(4, 12))).astype(np.int32)
        prompts.append(np.concatenate([prefix, tail]) if i % 2 else
                       rng.integers(1, cfg.vocab_size,
                                    int(rng.integers(8, 24))).astype(np.int32))
    max_new = 6
    ref = ContinuousBatcher(model, params, n_slots=2, prefill_chunk=8,
                            paged_kv="int4", page_size=page_size, n_pages=80)
    ref.register_prefix(prefix)
    ref_rids = [ref.submit(p, max_new) for p in prompts]
    ref_tokens = ref.run()

    router = build_fleet(
        model, params, n_prefill=2, n_decode=2, prefill_chunk=8,
        paged_kv="int4", page_size=page_size, n_slots=2, max_queue=8,
        n_pages=80,
    )
    router.register_prefix(prefix)
    workers = list(router.decode_workers) + list(router.prefill_workers)
    baseline_used = [w.used_pages if hasattr(w, "used_pages")
                     else w._pages.used_pages for w in workers]
    # ledger-level no-leak audit riding alongside the page-count one: the
    # fleet-wide occupied KV BYTES (live + CoW-shared, every worker's pool
    # summed through the memory ledger's sources) must return to this
    # baseline after the drain — a leak that hid page-for-page inside one
    # pool would still move the byte total
    ledger_kv_baseline = _ledger_kv_occupied_bytes()
    frids = [router.submit(p, max_new) for p in prompts]
    tick = 0
    peak_shared = 0
    while router.outstanding:
        if tick == 6:
            router.kill_decode_worker()
        router.tick()
        peak_shared = max(peak_shared, max(
            dw.shared_pages for dw in router.decode_workers
        ))
        tick += 1
        if tick > 100_000:
            raise RuntimeError("paged serving chaos did not drain")
    results = router.run(max_ticks=1)
    token_loss = sum(
        1 for frid, rrid in zip(sorted(frids), ref_rids)
        if results.get(frid, []) != ref_tokens[rrid]
    )
    # no-leak audit over EVERY pool, the killed worker's included: after
    # the drain each pool holds exactly its prefix-registry pages again
    leaked = 0
    for w, base in zip(workers, baseline_used):
        used = (w.used_pages if hasattr(w, "used_pages")
                else w._pages.used_pages)
        leaked += max(used - base, 0)
    ledger_kv_final = _ledger_kv_occupied_bytes()
    report = {
        "requests": len(prompts),
        "token_mismatches": token_loss,
        "ticks": tick,
        "requeued_decode": router.requeued_decode,
        "peak_shared_pages": peak_shared,
        "leaked_pages": leaked,
        "ledger_kv_baseline_bytes": ledger_kv_baseline,
        "ledger_kv_final_bytes": ledger_kv_final,
        "ledger_balanced": int(ledger_kv_final <= ledger_kv_baseline + 0.5),
    }
    report.update(_paged_eviction_leg(model, cfg, rng))
    return report


def _ledger_kv_occupied_bytes() -> float:
    """Fleet-wide OCCUPIED paged-KV bytes (live + CoW-shared) summed over
    every pool the memory ledger's weakly-held sources still see. GC runs
    first so a retired batcher's constant contribution cannot shift a
    baseline-vs-final comparison mid-audit."""
    import gc

    from dsml_tpu.obs.memory import get_memory_ledger

    gc.collect()
    claims = get_memory_ledger().claimed().get("kv_pages", {})
    return float(claims.get("live", 0.0) + claims.get("shared", 0.0))


def _paged_eviction_leg(model, cfg, rng) -> dict:
    """The EVICTION leg (preemption=True, docs/SERVING.md § Paged KV): a
    pool far too small for the worst case forces mid-decode preemptions —
    the lowest-priority slot's pages swap out (or drop for recompute) and
    the request resumes when pages free. Invariants: every PREEMPTED
    request re-emits tokens identical to the uncontended big-pool run
    (preemption is pure scheduling), and the drained pool is back to
    empty — zero page leaks."""
    from dsml_tpu.serving import ContinuousBatcher

    params = model.init(0)
    prompts = [
        rng.integers(1, cfg.vocab_size, l).astype(np.int32)
        for l in (17, 9, 13)
    ]
    budgets = [12, 12, 10]
    ref = ContinuousBatcher(model, params, n_slots=3, prefill_chunk=8,
                            paged_kv="int4", page_size=8, n_pages=40)
    ref_rids = [ref.submit(p, n) for p, n in zip(prompts, budgets)]
    got = ref.run()
    ref_tokens = [got[r] for r in ref_rids]

    srv = ContinuousBatcher(model, params, n_slots=3, prefill_chunk=8,
                            paged_kv="int4", page_size=8, n_pages=8,
                            preemption=True)
    # ledger baseline AFTER both batchers exist, BEFORE any admission:
    # ref has drained, srv is empty — the eviction/resume churn below
    # must return the fleet-wide occupied KV bytes to exactly this
    ledger_baseline = _ledger_kv_occupied_bytes()
    preempted_rids: set = set()
    evict = srv._evict_slot

    def spy(slot):
        preempted_rids.add(int(srv._slot_rid[slot]))
        evict(slot)

    srv._evict_slot = spy
    rids = [srv.submit(p, n) for p, n in zip(prompts, budgets)]
    out = srv.run()
    mismatches = sum(
        1 for rid, want in zip(rids, ref_tokens) if out.get(rid) != want
    )
    resumed_ok = sum(
        1 for rid, want in zip(rids, ref_tokens)
        if rid in preempted_rids and out.get(rid) == want
    )
    ledger_final = _ledger_kv_occupied_bytes()
    return {
        "eviction_preemptions": srv.n_preemptions,
        "eviction_swap": srv.n_swap_evictions,
        "eviction_recompute": srv.n_recompute_evictions,
        "eviction_resumed_identical": resumed_ok,
        "eviction_token_mismatches": mismatches,
        "eviction_leaked_pages": srv.n_pages - 1 - srv.free_pages,
        "eviction_ledger_baseline_bytes": ledger_baseline,
        "eviction_ledger_final_bytes": ledger_final,
        "eviction_ledger_balanced": int(ledger_final <= ledger_baseline + 0.5),
    }


# ---------------------------------------------------------------------------
# migration smoke: the two-host (subprocess-simulated) shrink, under fault
# ---------------------------------------------------------------------------

_DONOR_FLAG = "--serve-migration-donor"


def _donor_main(npz_path: str) -> None:
    """Subprocess body: the DONOR HOST. Loads the state snapshot (the
    addressable view a real donor host would hold live), registers every
    leaf with its device server's StateDonor, prints the bound address as
    a JSON line, and serves P2P streams until stdin closes. Wire faults
    ride ``DSML_CHAOS_WIRE`` in this process's env — the donor is the
    stream SENDER, so drop/corrupt/delay happen on its pushes."""
    import json as _json
    import sys

    from dsml_tpu.comm.device_server import serve_device

    blob = np.load(npz_path)
    # the staging allocator gets the upper half of the registry, so size
    # the device for 2x the largest piece plus the landing headroom
    total = int(sum(blob[k].nbytes for k in blob.files))
    handle = serve_device(97, mem_size=max(0x200000, 4 * total))
    for key in blob.files:
        if key == "__migration_version__":
            handle.runtime.donor.version = int(blob[key])
            continue
        handle.runtime.donor.register_array(key, blob[key])
    print(_json.dumps({"address": handle.address, "keys": len(blob.files)}),
          flush=True)
    sys.stdin.read()  # parent closes the pipe → exit
    handle.stop()


def _export_state_npz(path: str, params, opt_state, version: int) -> int:
    """Host-state snapshot in the donor registry's key scheme (tree paths
    under ``params/`` / ``opt_state/`` — what ``StateDonor.register_state``
    derives from the same trees), stamped with the snapshot's training
    step so the receiver can refuse a stale donor."""
    import jax

    from dsml_tpu.comm.migration import tree_path_str

    arrays = {"__migration_version__": np.asarray(version)}
    for prefix, tree in (("params", params), ("opt_state", opt_state)):
        flat, _ = jax.tree_util.tree_flatten_with_path(tree)
        for p, leaf in flat:
            if leaf is not None and hasattr(leaf, "shape"):
                arrays[tree_path_str(prefix, p)] = np.asarray(jax.device_get(leaf))
    np.savez(path, **arrays)
    return len(arrays) - 1


def _bit_identical_host(tree_a, tree_b) -> bool:
    import jax

    la = [np.asarray(jax.device_get(x)) for x in jax.tree.leaves(tree_a)]
    lb = [np.asarray(jax.device_get(x)) for x in jax.tree.leaves(tree_b)]
    return len(la) == len(lb) and all(np.array_equal(a, b) for a, b in zip(la, lb))


def run_migration_smoke(tmp_dir: str | None = None, reps: int = 1) -> dict:
    """The two-host shrink acceptance run (docs/ELASTIC.md § Multi-host
    recovery): host A (this process) shards GPT2-tiny over [dp=4, tp=2],
    loses its local tp-1 holders, and the surviving copies of that shard
    live only on "host B" — a donor SUBPROCESS serving the state over the
    real gRPC P2P streams, routed through the coordinator's membership
    table. Four legs:

    - ``refusal`` — without a migrator the pull refuses loudly (the pinned
      pre-PR behavior; a shrink would degrade to checkpoint restore);
    - ``clean`` — the same shrink completes via P2P migration, no
      checkpoint restore, params BIT-IDENTICAL to what the checkpoint
      fallback would produce;
    - ``drop`` — one dropped StreamSend: the migrator harvests the partial
      prefix and resumes from the offset; same bits;
    - ``corrupt`` — every push corrupted: per-chunk CRC32C fires, the
      migration aborts cleanly, and an ``ElasticController`` riding the
      same failure falls back to the coordinated checkpoint restore with
      ZERO silent corruption (corrupt bytes never land).

    ``reps`` repeats the clean migration + fallback timing pair for the
    report's recovery-split percentiles. ``verify_migration`` raises the
    violations; the CLI exits nonzero on any."""
    import json
    import shutil
    import subprocess
    import sys
    import tempfile
    import time as _time

    import jax
    import optax

    from dsml_tpu.checkpoint import CheckpointManager
    from dsml_tpu.comm.coordinator import CoordinatorConfig, serve_coordinator
    from dsml_tpu.comm.device_server import serve_device
    from dsml_tpu.comm.migration import (
        MigrationConfig,
        MigrationError,
        ShardMigrator,
    )
    from dsml_tpu.models.gpt2 import GPT2, GPT2Config
    from dsml_tpu.parallel import elastic
    from dsml_tpu.parallel.hybrid import (
        init_hybrid,
        make_hybrid_train_step,
        shard_params,
    )
    from dsml_tpu.parallel.mesh import MeshSpec, build_mesh

    devices = jax.devices()[:8]
    if len(devices) < 8:
        raise RuntimeError(f"migration smoke needs 8 devices, found {len(devices)}")
    base = tmp_dir or tempfile.mkdtemp(prefix="dsml_migrate_")
    created = tmp_dir is None
    report: dict = {}
    procs: list = []
    coordinator = None
    recv = None
    try:
        cfg = GPT2Config.tiny()
        model = GPT2(cfg)
        optimizer = optax.adam(1e-2)
        global_batch = 8
        rng = np.random.default_rng(0)
        data = rng.integers(0, cfg.vocab_size,
                            (16, global_batch, cfg.max_seq)).astype(np.int32)

        def batch_provider(step: int):
            x = data[step - 1]
            return x, np.roll(x, -1, 1).astype(np.int32)

        # host A's live state: 2 steps on [dp=4, tp=2] (device i holds tp
        # rank i%2 — losing {1,3} removes every LOCAL copy of tp shard 1;
        # devices 4..7 play host B, so the shard SURVIVES, remotely)
        spec = MeshSpec(dp=4, sp=1, tp=2)
        mesh8 = build_mesh(spec, devices)
        step_fn = make_hybrid_train_step(model, optimizer, mesh8)
        params, opt_state = init_hybrid(model, optimizer, mesh8, seed=0)
        for s in (1, 2):
            params, opt_state, _ = step_fn(params, opt_state, *batch_provider(s))
        # re-pin DECLARED shardings (jit outputs carry compiler-chosen
        # layouts; the elastic runner's own idiom — see test_elastic)
        import optax.tree_utils as otu
        from jax.sharding import NamedSharding, PartitionSpec as P

        pspecs = model.param_specs()
        params = shard_params(params, mesh8, pspecs)
        param_sh = jax.tree.map(lambda sp: NamedSharding(mesh8, sp), pspecs,
                                is_leaf=lambda sp: isinstance(sp, P))
        repl = NamedSharding(mesh8, P())
        opt_state = otu.tree_map_params(
            optimizer, lambda l, sh: jax.device_put(l, sh), opt_state, param_sh,
            transform_non_params=lambda l: jax.device_put(l, repl),
        )

        ckpt_dir = os.path.join(base, "ckpt")
        manager = CheckpointManager(ckpt_dir, max_to_keep=None)
        manager.save(2, {"params": params, "opt_state": opt_state})
        npz = os.path.join(base, "donor_state.npz")
        n_leaves = _export_state_npz(npz, params, opt_state, version=2)

        lost = [devices[i] for i in (1, 3)]
        survivors = [devices[i] for i in (0, 2, 4, 5, 6, 7)]
        remote_ids = frozenset(devices[i].id for i in (4, 5, 6, 7))
        recv = serve_device(96, mem_size=0x400000)
        coordinator = serve_coordinator(
            config=CoordinatorConfig(health_interval_s=3600.0)
        )

        def spawn_donor(wire_spec: str) -> str:
            env = {**os.environ, "JAX_PLATFORMS": "cpu"}
            env.pop("DSML_CHAOS_WIRE", None)
            if wire_spec:
                env["DSML_CHAOS_WIRE"] = wire_spec
            p = subprocess.Popen(
                [sys.executable, "-m", "dsml_tpu.runtime.chaos",
                 _DONOR_FLAG, npz],
                stdin=subprocess.PIPE, stdout=subprocess.PIPE, env=env,
                text=True,
            )
            procs.append(p)
            return json.loads(p.stdout.readline())["address"]

        def migrator_for(donor_addr: str, **cfg_kw) -> ShardMigrator:
            # coordinator-brokered routing: CommInit installs the peer
            # tables, the membership table names ranks and addresses; the
            # receiver pins the snapshot step it expects (the state at the
            # failure point) so a stale donor would be refused, not landed
            comm = coordinator.runtime.comm_init(2, [recv.address, donor_addr])
            self_rank, donors = coordinator.runtime.broker_migration(
                comm.comm_id, recv.runtime.device_id
            )
            return ShardMigrator(
                recv.runtime, self_rank, donors,
                config=MigrationConfig(**cfg_kw),
                local_address=recv.runtime.bound_address,
                expect_version=2,
            )

        def reconfigure_with(migrator):
            return elastic.reconfigure(
                model, optimizer, params, opt_state,
                surviving_devices=survivors, lost_devices=lost,
                global_batch=global_batch,
                migrator=migrator, non_addressable=remote_ids,
            )

        # --- leg 0: the pinned refusal (no migrator) ----------------------
        try:
            reconfigure_with(None)
            report["refusal"] = {"raised": False}
        except RuntimeError as e:
            report["refusal"] = {
                "raised": True,
                "mentions_non_addressable": "non-addressable" in str(e),
            }

        # --- leg 1: clean migration vs checkpoint fallback, bit-identical -
        donor_addr = spawn_donor("")
        mig = migrator_for(donor_addr, timeout_s=30.0)
        mig_walls, fb_walls = [], []
        state = fb_state = None
        for _ in range(max(reps, 1)):
            t0 = _time.perf_counter()
            state = reconfigure_with(mig)
            mig_walls.append((_time.perf_counter() - t0) * 1e3)
            t0 = _time.perf_counter()
            fb_state = elastic.restore_from_checkpoint(
                manager, model, optimizer, survivors,
                global_batch=global_batch,
            )
            fb_walls.append((_time.perf_counter() - t0) * 1e3)
        report["clean"] = {
            "migrated_pieces": mig.stats["pieces"],
            "migrated_bytes": mig.stats["bytes"],
            "migration_ms": round(mig.stats["ms"], 3),
            "mb_s": round(
                (mig.stats["bytes"] / 1e6) / max(mig.stats["ms"] / 1e3, 1e-9), 3
            ),
            "reps": max(reps, 1),
            "recovery_ms_migration": [round(w, 3) for w in mig_walls],
            "recovery_ms_fallback": [round(w, 3) for w in fb_walls],
            "bit_identical_to_fallback": _bit_identical_host(
                (state.params, state.opt_state),
                (fb_state.params, fb_state.opt_state),
            ),
            "used_fallback": False,
        }
        mig.close()

        # --- leg 2: one dropped StreamSend → harvested prefix + resume ----
        donor_addr = spawn_donor("drop@1")
        mig = migrator_for(donor_addr, timeout_s=30.0)
        drop_state = reconfigure_with(mig)
        report["drop"] = {
            "resumed": mig.stats["resumed"],
            "retries": mig.stats["retries"],
            "bit_identical": _bit_identical_host(
                (drop_state.params, drop_state.opt_state),
                (state.params, state.opt_state),
            ),
        }
        mig.close()

        # --- leg 3: persistent corruption → CRC fires, controller falls
        # back to the coordinated checkpoint restore, zero silent landing --
        donor_addr = spawn_donor("corrupt@*")
        mig = migrator_for(donor_addr, timeout_s=30.0, retries=1)
        crc_fired = False
        try:
            reconfigure_with(mig)
        except MigrationError:
            crc_fired = True
        from dsml_tpu.runtime.controller import (
            ControllerConfig,
            DeviceLost,
            ElasticController,
        )

        fleet = VirtualFleet(devices)
        ctl = ElasticController(
            model, optimizer, batch_provider,
            checkpoint_dir=os.path.join(base, "ctl"),
            fleet=fleet, mesh=mesh8, spec=spec,
            config=ControllerConfig(checkpoint_every=2, growback="keep"),
            global_batch=global_batch, seed=0,
            migrator=mig, non_addressable=remote_ids,
        )

        def on_step(s: int) -> None:
            if s == 3:
                dead = fleet.kill(1, 3)
                if dead:
                    ctl.inject(DeviceLost(dead, "chaos: local tp-1 holders"))

        with ctl:
            ctl_report = ctl.run(4, on_step=on_step)
        rec = ctl_report["recoveries"][0] if ctl_report["recoveries"] else {}
        report["corrupt"] = {
            "crc_fired": crc_fired,
            "integrity_failures": mig.stats["integrity_failures"],
            "controller_kind": rec.get("kind"),
            "controller_fallback_mentions_crc": "CRC" in rec.get("fallback_reason", ""),
            "controller_steps_completed": ctl_report["steps_completed"],
            "losses_finite": bool(
                np.all(np.isfinite(list(ctl.losses.values())))
            ),
        }
        mig.close()
        report["n_leaves"] = n_leaves
        manager.close()
        return report
    finally:
        if coordinator is not None:
            coordinator.stop()
        if recv is not None:
            recv.stop()
        for p in procs:
            try:
                p.stdin.close()
            except OSError:
                pass
        for p in procs:
            try:
                p.wait(timeout=10)
            except Exception:  # noqa: BLE001 — teardown must not mask the report
                p.kill()
        if created:
            shutil.rmtree(base, ignore_errors=True)


def verify_migration(report: dict) -> list[str]:
    """The migration invariants, as a list of violations (empty = pass)."""
    bad: list[str] = []
    refusal = report.get("refusal", {})
    if not refusal.get("raised"):
        bad.append("refusal: pull without a migrator did NOT raise")
    clean = report.get("clean", {})
    if not clean.get("migrated_pieces"):
        bad.append("clean: zero pieces moved over P2P streams")
    if clean.get("used_fallback"):
        bad.append("clean: migration leg used the checkpoint fallback")
    if not clean.get("bit_identical_to_fallback"):
        bad.append("clean: migrated state NOT bit-identical to the "
                   "checkpoint-fallback state")
    drop = report.get("drop", {})
    if not (drop.get("resumed") or drop.get("retries")):
        bad.append("drop: dropped stream neither resumed nor retried")
    if not drop.get("bit_identical"):
        bad.append("drop: resumed migration NOT bit-identical")
    corrupt = report.get("corrupt", {})
    if not corrupt.get("crc_fired"):
        bad.append("corrupt: CRC check did not abort the migration")
    if not corrupt.get("integrity_failures"):
        bad.append("corrupt: no integrity failures counted")
    if corrupt.get("controller_kind") != "checkpoint_fallback":
        bad.append(
            f"corrupt: controller recovered via "
            f"{corrupt.get('controller_kind')!r}, expected checkpoint_fallback"
        )
    if corrupt.get("controller_steps_completed", 0) < 4:
        bad.append("corrupt: controller did not complete the run after fallback")
    return bad


def verify(report: dict) -> list[str]:
    """The invariants, as a list of violations (empty = pass)."""
    bad: list[str] = []
    runs = [(k, v) for k, v in report.items()
            if isinstance(v, dict) and "steps_completed" in v]
    for name, rep in runs:
        if rep["steps_completed"] != report["n_steps"]:
            bad.append(f"{name}: lost steps — completed "
                       f"{rep['steps_completed']}/{report['n_steps']}")
        if rep["kills"] and not rep["recoveries"]:
            bad.append(f"{name}: {rep['kills']} kills but zero recoveries")
        if not rep.get("bit_identical"):
            bad.append(f"{name}: final params NOT bit-identical to the "
                       f"uninterrupted run")
        if rep["goodput"] < report["goodput_floor"]:
            bad.append(f"{name}: goodput {rep['goodput']} below the "
                       f"documented floor {report['goodput_floor']}")
    if not runs:
        bad.append("no chaos runs in the report")
    staging = report.get("ledger_staging_bytes_final", 0)
    if staging > 0:
        bad.append(
            f"ledger: {staging:.0f} staging byte(s) still claimed after "
            "every recovery completed — a checkpoint snapshot or migration "
            "span leaked past its commit"
        )
    srv = report.get("serving")
    if srv is not None and srv.get("token_mismatches", 0) > 0:
        bad.append(f"serving: {srv['token_mismatches']} request(s) lost or "
                   "changed tokens across a replica kill")
    fleet = report.get("serving_fleet")
    if fleet is not None:
        if fleet.get("token_mismatches", 0) > 0:
            bad.append(
                f"serving_fleet: {fleet['token_mismatches']} request(s) "
                "lost or changed tokens across worker kills"
            )
        if not fleet.get("requeued_prefill"):
            bad.append(
                "serving_fleet: the prefill-worker kill interrupted no "
                "work — the mid-handoff re-prefill path went unexercised"
            )
        if not fleet.get("requeued_decode"):
            bad.append(
                "serving_fleet: the decode-worker kill interrupted no "
                "work — the full-pipeline re-run path went unexercised"
            )
        if not fleet.get("trace_requeue_same", 1):
            bad.append(
                "serving_fleet: a killed request's re-run retired under a "
                "DIFFERENT trace_id — the retry must stay on the same trace"
            )
        if not fleet.get("trace_retry_recorded", 1):
            bad.append(
                "serving_fleet: a requeued request retired with zero "
                "recorded retries — the requeue span went unrecorded"
            )
        if not fleet.get("trace_burn_full_latency", 1):
            bad.append(
                "serving_fleet: a requeued request's SLO burn counted only "
                "the post-requeue leg — the budget must pay the FULL "
                "user-visible latency, kill included"
            )
    paged = report.get("serving_paged")
    if paged is not None:
        if paged.get("token_mismatches", 0) > 0:
            bad.append(
                f"serving_paged: {paged['token_mismatches']} request(s) "
                "lost or changed tokens across the decode-worker kill"
            )
        if not paged.get("requeued_decode"):
            bad.append(
                "serving_paged: the decode-worker kill interrupted no work "
                "— the paged re-prefill path went unexercised"
            )
        if not paged.get("peak_shared_pages"):
            bad.append(
                "serving_paged: no CoW prefix page was ever shared — the "
                "kill did not land with sharing live"
            )
        if paged.get("leaked_pages", 0) > 0:
            bad.append(
                f"serving_paged: {paged['leaked_pages']} pool page(s) "
                "leaked past request retirement (the dead worker's pages "
                "must reclaim without shrinking pool capacity)"
            )
        if not paged.get("eviction_preemptions"):
            bad.append(
                "serving_paged: the eviction leg forced no preemption — "
                "the swap/resume path went unexercised"
            )
        if not paged.get("eviction_resumed_identical"):
            bad.append(
                "serving_paged: no preempted request resumed with the "
                "reference tokens — eviction must be pure scheduling"
            )
        if paged.get("eviction_token_mismatches", 0) > 0:
            bad.append(
                f"serving_paged: {paged['eviction_token_mismatches']} "
                "request(s) changed tokens across an eviction/resume"
            )
        if paged.get("eviction_leaked_pages", 0) > 0:
            bad.append(
                f"serving_paged: {paged['eviction_leaked_pages']} page(s) "
                "leaked through the preemption tier (swap-out must "
                "release every reference it takes)"
            )
        # ledger-byte balance (ISSUE 15): the fleet-wide occupied KV
        # BYTES must return to their pre-admission baseline after the
        # kill leg and after the eviction/resume churn — .get(..., 1)
        # keeps pre-ledger report files verifiable
        if not paged.get("ledger_balanced", 1):
            bad.append(
                "serving_paged: ledger KV bytes did not return to baseline "
                f"after the drain ({paged.get('ledger_kv_final_bytes')} vs "
                f"{paged.get('ledger_kv_baseline_bytes')} baseline)"
            )
        if not paged.get("eviction_ledger_balanced", 1):
            bad.append(
                "serving_paged: eviction leg leaked ledger KV bytes "
                f"({paged.get('eviction_ledger_final_bytes')} vs "
                f"{paged.get('eviction_ledger_baseline_bytes')} baseline)"
            )
    return bad


def _main(argv=None) -> int:
    import argparse
    import json

    parser = argparse.ArgumentParser(
        description="chaos smoke: scripted kill/restore schedule on the "
        "virtual-8 mesh; exits nonzero if any survival invariant fails"
    )
    parser.add_argument("--steps", type=int, default=24)
    parser.add_argument("--seeds", type=int, nargs="*", default=[],
                        help="extra seeded-random schedules")
    parser.add_argument("--report", default="",
                        help="write the JSON report here")
    parser.add_argument("--migration", action="store_true",
                        help="run the two-host (subprocess-simulated) "
                        "shard-migration smoke instead: clean P2P shrink "
                        "bit-identical to checkpoint fallback, dropped-stream "
                        "resume, corrupt-chunk CRC abort + coordinated "
                        "fallback (docs/ELASTIC.md § Multi-host recovery); "
                        "exits nonzero on any violated invariant")
    parser.add_argument(_DONOR_FLAG, default=None, metavar="NPZ",
                        help=argparse.SUPPRESS)
    parser.add_argument("--cluster-snapshot", default="",
                        help="write this process's cluster-obs snapshot "
                        "(registry + trace, identity-stamped) here so an "
                        "aggregator can merge the chaos run into the fleet "
                        "view offline (docs/OBSERVABILITY.md § Cluster)")
    parser.add_argument("--push", default="",
                        help="push the snapshot to a running aggregator at "
                        "this host:port over the comm/ ObsPlane instead of "
                        "(or in addition to) writing a file")
    args = parser.parse_args(argv)

    if args.serve_migration_donor is not None:
        _donor_main(args.serve_migration_donor)
        return 0

    # force the virtual-8 CPU mesh BEFORE jax initializes a backend
    from dsml_tpu.utils.platform import configure_platform

    configure_platform("cpu", 8)

    if args.migration:
        report = run_migration_smoke()
        violations = verify_migration(report)
        report["violations"] = violations
        line = json.dumps(report, default=str)
        print(line)
        if args.report:
            with open(args.report, "w") as f:
                f.write(line + "\n")
        for v in violations:
            log.error("migration invariant violated: %s", v)
        return 1 if violations else 0

    want_obs = bool(args.cluster_snapshot or args.push)
    if want_obs:
        # the snapshot is only worth merging if the run recorded itself
        from dsml_tpu import obs as _obs

        _obs.enable(forensics=False)

    env_schedule = config_from_env()
    if env_schedule is not None:
        log.info("DSML_CHAOS schedule: %d events", len(env_schedule.events))
    report = run_smoke(n_steps=args.steps, seeds=tuple(args.seeds),
                       schedule=env_schedule)
    violations = verify(report)
    report["violations"] = violations
    line = json.dumps(report, default=str)
    print(line)
    if args.report:
        with open(args.report, "w") as f:
            f.write(line + "\n")
    if want_obs:
        from dsml_tpu.obs import cluster as _cluster

        if args.cluster_snapshot:
            with open(args.cluster_snapshot, "w") as f:
                json.dump(_cluster.snapshot(role="chaos"), f)
        if args.push:
            try:
                _cluster.push_snapshot(args.push, role="chaos")
            except Exception as e:  # noqa: BLE001 — obs must not fail chaos
                log.warning("cluster push to %s failed: %r", args.push, e)
    for v in violations:
        log.error("chaos invariant violated: %s", v)
    return 1 if violations else 0


if __name__ == "__main__":
    raise SystemExit(_main())
