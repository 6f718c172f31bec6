"""Elastic chaos-survival controller: ride preemptions end-to-end.

The reference marks a communicator permanently dead on first failure
(recovery "none", SURVEY.md §5.3). Every ingredient of the missing half
already exists in this repo — elastic re-plan (``parallel.elastic``),
bit-identical async checkpointing (``checkpoint``), hang/straggler/goodput
signals (``obs``) — but nothing closed the loop: a preemption still killed
the run. This module is the loop:

    detect ──► shrink ──► resume ──► grow
      │          │           │         │
      │          │           │         └─ capacity returns: re-shard live
      │          │           │            state back onto the full fleet at
      │          │           │            the next checkpoint boundary
      │          │           │            ("keep"), or restore the last
      │          │           │            pure-lineage checkpoint and replay
      │          │           │            at full width ("replay" — final
      │          │           │            params bit-identical to a run
      │          │           │            that never failed)
      │          │           └─ rebuild the step function for the new mesh
      │          │              and continue mid-run, data-loader position
      │          │              intact (``batch_provider`` is a pure
      │          │              function of the step index)
      │          └─ ``elastic.reconfigure`` onto the survivors; when the
      │             audit reports torn leaves (an entire tp shard / pp
      │             stage / ZeRO shard died), fall back to
      │             ``elastic.restore_from_checkpoint`` and replay the
      │             steps since the last commit (the "lost work" metric)
      └─ three independent sources: fleet probes (the coordinator health
         verdict), injected ``DeviceLost`` signals (chaos harness, or a
         step raising), and hangwatch deadline expiries (a wedged-but-
         alive device)

Recovery time and lost work land in the obs registry
(``controller_recovery_ms{stage}``, ``controller_lost_steps_total``,
``controller_redone_steps_total``) and the flight recorder throughout, so
a 3am preemption leaves a story, not a mystery. The guarantee is TESTED,
not asserted: ``runtime.chaos`` drives scripted and seeded-random
kill/restore schedules against this loop (and against the serving
``DecodeFleet`` below) — see ``docs/ELASTIC.md`` and ``python -m
dsml_tpu.runtime.chaos``.

On a single host, device loss is simulated by meshes shrinking between
steps (the model multi-host JAX presents when a host drops) — the same
simulation ``parallel.elastic``'s tests use.
"""

from __future__ import annotations

import dataclasses
import os
import time
from collections import deque
from typing import Callable

import numpy as np

from dsml_tpu.obs import (
    GoodputTracker,
    flight_recorder,
    get_registry,
    hangwatch,
    observe_recovery_ms,
)
from dsml_tpu.parallel import elastic
from dsml_tpu.parallel.elastic import ElasticPolicy
from dsml_tpu.parallel.hybrid import init_hybrid, make_hybrid_train_step
from dsml_tpu.parallel.mesh import MeshSpec, build_mesh
from dsml_tpu.utils.config import Config, field
from dsml_tpu.utils.logging import get_logger

__all__ = [
    "DeviceLost",
    "Unrecoverable",
    "StaticFleet",
    "ControllerConfig",
    "ElasticController",
    "DecodeFleet",
]

log = get_logger("controller")


class DeviceLost(RuntimeError):
    """Failure signal: these devices are gone. Raised by a training step on
    a real loss (XLA surfaces device failure as an error from the step),
    injected by the chaos harness, or synthesized from a coordinator
    health verdict."""

    def __init__(self, devices, message: str = ""):
        self.devices = tuple(devices)
        super().__init__(
            message or f"lost {len(self.devices)} device(s): "
            f"{[getattr(d, 'id', d) for d in self.devices]}"
        )


class Unrecoverable(RuntimeError):
    """The job cannot continue: no survivors, or recovery itself failed."""


class StaticFleet:
    """The no-failure fleet view: a fixed device list. Real deployments
    plug in a view backed by ``jax.devices()`` re-resolution or coordinator
    health probes; the chaos harness plugs in ``chaos.VirtualFleet``."""

    def __init__(self, devices):
        self._devices = list(devices)

    def available(self) -> list:
        return list(self._devices)


@dataclasses.dataclass
class ControllerConfig(Config):
    checkpoint_every: int = field(
        8, help="async checkpoint cadence in steps; also the grow-back "
        "boundary — restored capacity is adopted right after a save commits"
    )
    keep_checkpoints: int = field(
        0, help="max checkpoints retained (0 = keep all; replay grow-back "
        "needs the last pure-lineage checkpoint to outlive the outage)"
    )
    growback: str = field(
        "replay", help="grow-back mode: 'replay' restores the last "
        "pure-lineage checkpoint and re-runs the outage window at full "
        "width (final params bit-identical to a never-failed run); 'keep' "
        "re-shards the survivor-width state onto the restored fleet (zero "
        "recompute, mixed-width lineage)"
    )
    detect_every: int = field(
        1, help="probe the fleet view every N steps (injected DeviceLost "
        "signals and hangwatch verdicts are checked every step regardless)"
    )
    recovery_deadline_s: float = field(
        0.0, help="recoveries slower than this warn + dump a postmortem "
        "bundle (0 = DSML_RECOVERY_DEADLINE_S, default 120)"
    )
    batch_per_device: int = field(1, help="forwarded to the elastic re-plan")
    attn_impl: str = field("", help="attention impl for rebuilt steps ('' = "
                           "per-mesh auto: ring2 on cp meshes, ring otherwise "
                           "— a pinned 'ring' on a cp mesh would lose ring2's "
                           "O(S/cp) residual property on every reconfigure)")

    def resolved_recovery_deadline_s(self) -> float:
        if self.recovery_deadline_s > 0:
            return self.recovery_deadline_s
        try:
            return float(os.environ.get("DSML_RECOVERY_DEADLINE_S", 120.0))
        except ValueError:
            return 120.0


class ElasticController:
    """Supervision loop over a hybrid-parallel training run.

    ``batch_provider(step) -> (x, y)`` must be a deterministic function of
    the 1-based step index (``utils.data.shard_batches`` seeded by step is
    exactly this) — that is what makes the data-loader position a single
    integer that rides in every checkpoint manifest, and replay after a
    fallback bit-identical.

    ``step_factory(model, optimizer, mesh) -> step_fn`` defaults to
    ``make_hybrid_train_step``; step functions are cached per topology, so
    growing back onto the original fleet reuses the original compile.
    """

    def __init__(
        self,
        model,
        optimizer,
        batch_provider: Callable[[int], tuple],
        checkpoint_dir: str,
        fleet=None,
        mesh=None,
        spec: MeshSpec | None = None,
        config: ControllerConfig | None = None,
        policy: ElasticPolicy = ElasticPolicy(),
        global_batch: int | None = None,
        seed: int = 0,
        step_factory: Callable | None = None,
        failure_feed: Callable[[], list] | None = None,
        planner_overrides: dict | None = None,
        migrator=None,
        non_addressable=(),
    ):
        from dsml_tpu.checkpoint import CheckpointManager

        self.model = model
        self.optimizer = optimizer
        self.batch_provider = batch_provider
        self.config = config or ControllerConfig()
        self.policy = policy
        self.global_batch = global_batch
        self.seed = seed
        self.planner_overrides = planner_overrides
        # cross-host state motion (docs/ELASTIC.md § Multi-host recovery):
        # with a ShardMigrator wired, a shrink whose pieces survive only on
        # another host pulls them over the P2P streams instead of falling
        # back to a checkpoint; `non_addressable` marks device ids that
        # belong to other hosts (the single-process sim lists local ids)
        self.migrator = migrator
        self.non_addressable = tuple(non_addressable)
        self._step_factory = step_factory or (
            lambda mdl, opt, m: make_hybrid_train_step(
                mdl, opt, m, attn_impl=self.config.attn_impl or None
            )
        )
        self._failure_feed = failure_feed
        self._ckpt = CheckpointManager(
            checkpoint_dir,
            max_to_keep=self.config.keep_checkpoints or None,
        )
        self._registry = get_registry()
        self._recorder = flight_recorder.get_flight_recorder()
        hw_cfg = hangwatch.config_from_env()
        self._hw = hangwatch.get_hangwatch() if hw_cfg is not None else None
        self._hw_deadline = (
            hangwatch.TrailingDeadline.from_config(hw_cfg)
            if hw_cfg is not None else None
        )
        self._hw_fired_seen = len(self._hw.fired) if self._hw is not None else 0

        if fleet is None:
            import jax

            fleet = StaticFleet(jax.devices())
        self.fleet = fleet

        # the FULL topology — the grow-back target. Caller-provided mesh
        # wins (tests pin exact layouts); otherwise the capacity planner
        # picks, exactly as a shrink re-plan would for the same fleet.
        devices = list(fleet.available())
        if not devices:
            raise Unrecoverable("fleet has no available devices")
        if mesh is not None:
            self._full_mesh = mesh
            self._full_spec = spec or self._spec_of(mesh)
        else:
            if spec is not None:
                self._full_mesh = build_mesh(spec, devices)
                self._full_spec = spec.resolved(len(devices))
            else:
                import jax

                # allocation-free count: materializing a full host init
                # just to size the planner would be a transient whole-model
                # allocation at exactly the scale this controller targets
                abstract = jax.eval_shape(lambda: model.init(seed))
                plan, used = elastic._plan_for_survivors(
                    model, model.n_params(abstract), devices,
                    self.config.batch_per_device, global_batch,
                    planner_overrides,
                )
                self._full_mesh = build_mesh(plan.spec, used)
                self._full_spec = plan.spec
        self._full_ids = frozenset(d.id for d in self._full_mesh.devices.flat)

        self.mesh = self._full_mesh
        self.spec = self._full_spec
        self.params, self.opt_state = init_hybrid(
            model, optimizer, self.mesh, seed=seed
        )
        self._n_params = model.n_params(self.params)
        self._step_cache: dict = {}
        self._step_fn = self._get_step_fn(self.mesh, self.spec)

        # bookkeeping: 1-based index of the NEXT step to run; walls of the
        # steps in the CURRENT lineage (a rewind pops the discarded suffix
        # into lost-work); pure = every step since init ran at full width
        self._step = 1
        self._pure = True
        self._lineage_walls: dict[int, float] = {}
        self._lost_work_s = 0.0
        self._redone_steps = 0
        self._injected: deque[DeviceLost] = deque()
        # ids reported lost by a SIGNAL (injected / step-raised DeviceLost)
        # that the fleet view still lists as available: a StaticFleet never
        # stops listing a dead device, so without this quarantine the next
        # grow boundary would re-adopt it and hang the recovery loop. A
        # health-aware fleet clears the quarantine by dropping the device
        # from available() at least once — after that, its reappearance is
        # a genuine restore.
        self._quarantined: set = set()
        self.recoveries: list[dict] = []
        self.losses: dict[int, float] = {}
        self._goodput = GoodputTracker(registry=self._registry)
        self._t0 = time.monotonic()
        self._registry.gauge(
            "controller_fleet_size", "devices in the controller's mesh"
        ).set(len(devices))

    # ---- public surface --------------------------------------------------

    def inject(self, signal: DeviceLost) -> None:
        """Queue a failure signal (the chaos harness's hook; a coordinator
        adapter pushes health verdicts through the same door)."""
        self._injected.append(signal)

    def run(self, n_steps: int,
            on_step: Callable[[int], None] | None = None) -> dict:
        """Drive training to ``n_steps`` completed steps, riding every
        failure the fleet/chaos throws. ``on_step(step)`` fires before each
        step's detection pass (the chaos harness's injection point).
        Returns :meth:`report`."""
        while self._step <= n_steps:
            step = self._step
            if on_step is not None:
                on_step(step)
            self._detect(step)
            x, y = self.batch_provider(step)
            hw_token = None
            if self._hw is not None:
                deadline = self._hw_deadline.timeout_s()
                if deadline is not None:
                    hw_token = self._hw.arm("controller_step", deadline,
                                            step=step)
            t0 = time.perf_counter()
            try:
                self.params, self.opt_state, loss = self._step_fn(
                    self.params, self.opt_state, x, y
                )
                loss.block_until_ready()
            except DeviceLost as e:
                # a real loss surfaces as an error from the step; recover
                # and RETRY the same step index — nothing is skipped
                self._recover(e.devices)
                continue
            finally:
                if hw_token is not None:
                    self._hw.disarm(hw_token)
            wall = time.perf_counter() - t0
            if self._hw is not None:
                self._hw_deadline.observe(wall)
            self._lineage_walls[step] = wall
            self._goodput.add_productive(wall)
            self.losses[step] = float(loss)
            self._recorder.record("controller_step", step=step,
                                  wall_ms=round(wall * 1e3, 3),
                                  width=self.spec.n_devices)
            self._step += 1
            if step % max(self.config.checkpoint_every, 1) == 0:
                self._save(step)
                self._maybe_grow(step)
        return self.report()

    def close(self) -> None:
        self._ckpt.close()

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        self.close()
        return False

    def report(self) -> dict:
        wall = time.monotonic() - self._t0
        productive = sum(self._lineage_walls.values())
        recov_ms = [r["recovery_ms"] for r in self.recoveries]
        out = {
            "steps_completed": self._step - 1,
            "width": self.spec.n_devices,
            "pure_lineage": self._pure,
            "recoveries": list(self.recoveries),
            "n_recoveries": len(self.recoveries),
            "redone_steps": self._redone_steps,
            "lost_work_s": round(self._lost_work_s, 6),
            "wall_s": round(wall, 6),
            "productive_s": round(productive, 6),
            "goodput": round(min(productive / max(wall, 1e-9), 1.0), 4),
        }
        if recov_ms:
            out["recovery_p50_ms"] = round(float(np.percentile(recov_ms, 50)), 3)
            out["recovery_p99_ms"] = round(float(np.percentile(recov_ms, 99)), 3)
        return out

    # ---- internals -------------------------------------------------------

    @staticmethod
    def _spec_of(mesh) -> MeshSpec:
        return MeshSpec.from_mesh(mesh)

    def _get_step_fn(self, mesh, spec: MeshSpec):
        key = (tuple(d.id for d in mesh.devices.flat),
               tuple(sorted(spec.sizes_dict().items())))
        hit = self._step_cache.get(key)
        if hit is not None:
            return hit
        fn = self._step_factory(self.model, self.optimizer, mesh)
        self._step_cache[key] = fn
        return fn

    def _save(self, step: int) -> None:
        t0 = time.perf_counter()
        self._ckpt.save(
            step,
            {"params": self.params, "opt_state": self.opt_state,
             "meta": {"step": step}},
            meta={"step": step,
                  "lineage": "pure" if self._pure else "mixed",
                  "width": self.spec.n_devices,
                  "spec": self.spec.sizes_dict()},
            iterator_state={"step": step},
            wait=False,
        )
        self._recorder.record(
            "controller_checkpoint", step=step,
            lineage="pure" if self._pure else "mixed",
            stall_ms=round((time.perf_counter() - t0) * 1e3, 3),
        )

    def _detect(self, step: int) -> None:
        """Run all three detection sources; recover if any fires."""
        lost: list = []
        seen_ids: set = set()

        def note(devs):
            for d in devs:
                if getattr(d, "id", d) not in seen_ids:
                    seen_ids.add(getattr(d, "id", d))
                    lost.append(d)

        while self._injected:
            note(self._injected.popleft().devices)
        if self._failure_feed is not None:
            feed = self._failure_feed() or []
            # the feed speaks device IDS (coordinator verdicts); match them
            # against the live mesh
            by_id = {d.id: d for d in self.mesh.devices.flat}
            note([by_id[i] for i in feed
                  if isinstance(i, int) and i in by_id]
                 + [d for d in feed if not isinstance(d, int)])
        probe = bool(lost) or step % max(self.config.detect_every, 1) == 0
        if self._hw is not None:
            fired = len(self._hw.fired)
            if fired > self._hw_fired_seen:
                # a deadline expiry is a VERDICT to verify, not a failure by
                # itself: probe the fleet now; a wedged device shows up as
                # unavailable there (a slow-but-healthy step is a false
                # alarm the probe clears)
                self._hw_fired_seen = fired
                self._recorder.record("controller_hang_verdict", step=step)
                probe = True
        if probe:
            avail_ids = {d.id for d in self.fleet.available()}
            # a quarantined id the fleet has stopped reporting is released:
            # the fleet is health-aware, so its NEXT appearance means a
            # genuine restore rather than a stale static listing
            self._quarantined -= {i for i in self._quarantined
                                  if i not in avail_ids}
            note([d for d in self.mesh.devices.flat if d.id not in avail_ids])
        if lost:
            self._recover(lost)

    def _recover(self, lost_devices) -> None:
        """shrink (or checkpoint-fallback) onto the survivors."""
        t0 = time.perf_counter()
        lost_ids = {d.id for d in lost_devices}
        self._quarantined |= lost_ids
        width_before = self.spec.n_devices
        self._goodput.mark("preemption", step=self._step,
                           lost=sorted(lost_ids))
        self._recorder.record("controller_detect", step=self._step,
                              lost=sorted(lost_ids), width=width_before)
        survivors = [d for d in self.fleet.available()
                     if d.id not in lost_ids and d.id not in self._quarantined]
        if not survivors:
            raise Unrecoverable(
                f"no surviving devices after losing {sorted(lost_ids)}"
            )
        lost_in_mesh = [d for d in self.mesh.devices.flat if d.id in lost_ids]
        lost_steps = 0
        extra: dict = {}
        mig_before = None
        if self.migrator is not None:
            # donor death verdicts and cached plans are scoped to ONE
            # recovery: a donor that flaked last outage may be healthy now
            if hasattr(self.migrator, "reset_donors"):
                self.migrator.reset_donors()
            mig_before = dict(self.migrator.stats)
        try:
            state = elastic.reconfigure(
                self.model, self.optimizer, self.params, self.opt_state,
                surviving_devices=survivors, lost_devices=lost_in_mesh,
                policy=self.policy,
                batch_per_device=self.config.batch_per_device,
                global_batch=self.global_batch,
                planner_overrides=self.planner_overrides,
                migrator=self.migrator,
                non_addressable=self.non_addressable,
            )
            kind = "reconfigure"
        except RuntimeError as e:
            if "allow_shrink=False" in str(e):
                raise  # fail-fast policy: the reference's semantics, chosen
            # torn state (or P2P migration undeliverable): the Varuna-style
            # COORDINATED fallback — flush in-flight saves, restore the
            # latest commit onto the survivor plan, and rewind the step
            # counter to it (the replayed steps are the lost work). In a
            # real multi-host fleet every host takes this leg on the step
            # CheckpointManager.newest_common_step agrees on.
            log.warning("live state not recoverable (%s); falling back to "
                        "checkpoint", e)
            extra["fallback_reason"] = str(e)[:200]
            self._ckpt.wait_until_finished()
            try:
                state = elastic.restore_from_checkpoint(
                    self._ckpt, self.model, self.optimizer, survivors,
                    seed=self.seed,
                    batch_per_device=self.config.batch_per_device,
                    global_batch=self.global_batch,
                    planner_overrides=self.planner_overrides,
                )
            except FileNotFoundError as fe:
                raise Unrecoverable(
                    f"state torn and no checkpoint to fall back to: {fe}"
                ) from e
            lost_steps = max((self._step - 1) - state.step, 0)
            self._rewind(state.step)
            kind = "checkpoint_fallback"
        if mig_before is not None:
            stats = self.migrator.stats
            delta = {k: stats[k] - mig_before[k] for k in mig_before}
            if delta.get("pieces") or delta.get("bytes") or \
                    delta.get("integrity_failures") or delta.get("retries"):
                extra.update({
                    "migrated_pieces": delta["pieces"],
                    "migrated_bytes": delta["bytes"],
                    "migration_resumed": delta["resumed"],
                    "migration_integrity_failures": delta["integrity_failures"],
                })
        self._adopt(state)
        self._pure = False
        recovery_ms = (time.perf_counter() - t0) * 1e3
        self._finish_recovery(kind, recovery_ms, width_before, lost_steps,
                              sorted(lost_ids), extra=extra)

    def _adopt(self, state) -> None:
        self.params, self.opt_state = state.params, state.opt_state
        self.mesh, self.spec = state.mesh, state.spec
        self._step_fn = self._get_step_fn(self.mesh, self.spec)
        self._registry.gauge(
            "controller_fleet_size", "devices in the controller's mesh"
        ).set(self.spec.n_devices)

    def _rewind(self, to_step: int) -> None:
        """Discard the lineage suffix past ``to_step`` (it will be redone):
        its walls move from productive to lost work, and the step counter
        returns to the step after the restored one."""
        discarded = [s for s in self._lineage_walls if s > to_step]
        lost_s = sum(self._lineage_walls.pop(s) for s in discarded)
        self._lost_work_s += lost_s
        self._redone_steps += len(discarded)
        self._goodput.add_productive(-lost_s)  # no longer useful work
        for s in discarded:
            self.losses.pop(s, None)
        self._step = to_step + 1

    def _finish_recovery(self, kind: str, recovery_ms: float,
                         width_before: int, lost_steps: int,
                         lost_ids: list, extra: dict | None = None) -> None:
        observe_recovery_ms(kind, recovery_ms)
        self._registry.counter(
            "controller_recoveries_total", "controller recovery actions",
            labels=("kind",),
        ).inc(kind=kind)
        # two DISTINCT counters (docs/OBSERVABILITY.md): lost = work the
        # FAILURE destroyed (fallback rewound past the last commit);
        # redone = work the replay grow-back deliberately discards for a
        # pure lineage. A grow_replay must not inflate the former.
        if lost_steps and kind == "checkpoint_fallback":
            self._registry.counter(
                "controller_lost_steps_total",
                "steps rewound to a checkpoint and replayed",
            ).inc(lost_steps)
        if lost_steps and kind == "grow_replay":
            self._registry.counter(
                "controller_redone_steps_total",
                "steps discarded by a replay grow-back and re-run",
            ).inc(lost_steps)
        self._goodput.mark("restore", kind=kind)
        # ledger watermark at the recovery boundary: the re-sharded state
        # was just re-placed — a postmortem's watermark timeline shows
        # whether a shrink doubled residency (the _place_state
        # double-allocation class) or came back to baseline
        from dsml_tpu.obs.memory import get_memory_ledger

        get_memory_ledger(self._registry).note_step_peak(
            self._step, label=f"recovery:{kind}")
        rec = {
            "kind": kind, "recovery_ms": round(recovery_ms, 3),
            "from_width": width_before, "to_width": self.spec.n_devices,
            "lost_steps": lost_steps, "lost_devices": lost_ids,
            "resume_step": self._step,
        }
        rec.update(extra or {})
        self.recoveries.append(rec)
        self._recorder.record(
            "controller_recovered",
            **{("recovery_kind" if k == "kind" else k): v for k, v in rec.items()},
        )
        log.warning(
            "recovered (%s) in %.0f ms: width %d -> %d, resume at step %d"
            "%s", kind, recovery_ms, width_before, self.spec.n_devices,
            self._step, f", {lost_steps} step(s) to replay" if lost_steps else "",
        )
        deadline_s = self.config.resolved_recovery_deadline_s()
        if recovery_ms > deadline_s * 1e3:
            log.error("recovery exceeded its %.0fs deadline (%.0f ms) — "
                      "dumping postmortem bundle", deadline_s, recovery_ms)
            try:
                self._recorder.dump("slow_recovery", extra=rec)
            except Exception:  # noqa: BLE001 — never mask the recovery
                pass

    def _maybe_grow(self, step: int) -> None:
        """At a checkpoint boundary, adopt restored capacity."""
        avail = [d for d in self.fleet.available()
                 if d.id not in self._quarantined]
        cur_ids = {d.id for d in self.mesh.devices.flat}
        fresh = [d for d in avail if d.id not in cur_ids]
        if not fresh or len(avail) <= self.spec.n_devices:
            return
        back_to_full = {d.id for d in avail} == self._full_ids
        if not back_to_full:
            # would the extra capacity actually be USED? a survivor count
            # whose plan instantiates no wider than today's (batch
            # divisibility idles the extras) must not trigger a state move
            # + recompile per boundary for a zero-chip gain
            plan, _ = elastic._plan_for_survivors(
                self.model, self._n_params, avail,
                self.config.batch_per_device, self.global_batch,
                self.planner_overrides,
            )
            if plan.spec.n_devices <= self.spec.n_devices:
                return
        t0 = time.perf_counter()
        width_before = self.spec.n_devices
        self._goodput.mark("grow", step=step, to=len(avail))
        kind = None
        redone = 0
        # replay grow-back is only meaningful back onto the FULL topology:
        # its whole point is a lineage indistinguishable from a never-failed
        # run, and a partial fleet can't produce full-width bits — partial
        # growth rides the keep path below instead
        if (self.config.growback == "replay" and not self._pure
                and back_to_full):
            # deterministic grow-back: flush + prune the mixed-width
            # lineage (a later fallback must not mix lineages), restore
            # the pure commit onto the full topology, and replay the
            # outage window at full width — the final params carry no
            # trace the outage ever happened. With no pure checkpoint on
            # disk (the failure beat the first save), the deterministic
            # INIT is the pure state at step 0: re-derive it and replay
            # everything.
            # barrier BEFORE the lineage query: an in-flight pure save
            # would otherwise be invisible to latest_step (it scans only
            # committed dirs), then deleted as "mixed" once it lands —
            # replaying the whole run for nothing
            self._ckpt.wait_until_finished()
            pure_step = self._ckpt.latest_step(
                where=lambda m: m.get("lineage") == "pure"
            ) or 0
            self._ckpt.delete_steps(
                [s for s in self._ckpt.all_steps() if s > pure_step]
            )
            t_params, t_opt = init_hybrid(
                self.model, self.optimizer, self._full_mesh, seed=self.seed,
            )
            if pure_step == 0:
                state = elastic.ElasticState(
                    params=t_params, opt_state=t_opt,
                    mesh=self._full_mesh, spec=self._full_spec,
                    reasons=("replay grow-back from the deterministic init "
                             "(no pure checkpoint survived the outage)",),
                    step=0,
                )
            else:
                restored = self._ckpt.restore(
                    pure_step,
                    template={"params": t_params, "opt_state": t_opt},
                    partial=True,
                )
                state = elastic.ElasticState(
                    params=restored["params"],
                    opt_state=restored["opt_state"],
                    mesh=self._full_mesh, spec=self._full_spec,
                    reasons=("replay grow-back onto the original mesh",),
                    step=pure_step,
                )
            redone = (self._step - 1) - pure_step
            self._rewind(pure_step)
            self._adopt(state)
            self._pure = True
            kind = "grow_replay"
        if kind is None:
            # keep mode: live survivor-width state re-shards onto the
            # restored fleet — zero recompute, lineage stays mixed-width
            if back_to_full:
                state = elastic.reshard_onto(
                    self.model, self.optimizer, self.params, self.opt_state,
                    self._full_mesh, self._full_spec,
                    migrator=self.migrator,
                    non_addressable=self.non_addressable,
                )
            else:
                state = elastic.reconfigure(
                    self.model, self.optimizer, self.params, self.opt_state,
                    surviving_devices=avail, lost_devices=(),
                    policy=self.policy,
                    batch_per_device=self.config.batch_per_device,
                    global_batch=self.global_batch,
                    planner_overrides=self.planner_overrides,
                    migrator=self.migrator,
                    non_addressable=self.non_addressable,
                )
            self._adopt(state)
            kind = "grow_keep"
        recovery_ms = (time.perf_counter() - t0) * 1e3
        self._finish_recovery(kind, recovery_ms, width_before, redone,
                              [d.id for d in fresh])


# ---------------------------------------------------------------------------
# Serving: decode-replica fleet with queue-depth autoscaling + chaos survival
# ---------------------------------------------------------------------------


class DecodeFleet:
    """Horizontal decode replicas behind one queue — the serving half of
    the chaos-survival story.

    ``make_replica()`` builds a ``serving.ContinuousBatcher`` (each replica
    owns its own slots/cache; on real hardware each would own a chip).
    Requests enter a fleet-level backlog and dispatch to the least-loaded
    replica each tick; autoscaling is QUEUE-DEPTH-DRIVEN:

    - scale UP: total waiting depth > ``scale_up_queue_depth`` × replicas
      and the fleet is below ``max_replicas``;
    - scale DOWN: a replica has been idle ``scale_down_idle_ticks``
      consecutive ticks and the fleet is above ``min_replicas``.

    :meth:`kill_replica` is the chaos hook: the dead replica's unfinished
    requests (queued, mid-admission, mid-decode) re-enter the backlog and
    re-run from their prompts on the survivors — with greedy decoding the
    retried tokens are identical, so a replica loss costs latency, never
    tokens (pinned in tests). Scale events land in
    ``serving_replica_scale_total{direction}`` /
    ``serving_replica_failures_total`` and the flight recorder."""

    def __init__(
        self,
        make_replica: Callable[[], object],
        min_replicas: int = 1,
        max_replicas: int = 4,
        scale_up_queue_depth: int = 4,
        scale_down_idle_ticks: int = 16,
        devices=None,
        devices_per_replica: int = 1,
    ):
        if min_replicas < 1 or max_replicas < min_replicas:
            raise ValueError(
                f"need 1 <= min_replicas <= max_replicas; got "
                f"{min_replicas}, {max_replicas}"
            )
        # device pool: with `devices` set, each replica SPANS
        # `devices_per_replica` chips — `make_replica(devices_tuple)` builds
        # it (serving.ContinuousBatcher.for_devices is the canonical
        # factory). A killed replica's chips return to the pool, so its
        # respawn — and the requeued work's failover onto survivors —
        # exercises the same multi-device state motion training recovery
        # does. Without `devices`, `make_replica()` keeps the historical
        # zero-arg contract.
        if devices_per_replica < 1:
            raise ValueError(
                f"devices_per_replica must be >= 1, got {devices_per_replica}"
            )
        self._device_pool: list | None = list(devices) if devices is not None else None
        self.devices_per_replica = devices_per_replica
        if self._device_pool is not None:
            capacity = len(self._device_pool) // devices_per_replica
            if capacity < min_replicas:
                raise ValueError(
                    f"{len(self._device_pool)} pooled device(s) cannot back "
                    f"min_replicas={min_replicas} at {devices_per_replica} "
                    "device(s) per replica"
                )
            max_replicas = min(max_replicas, capacity)
        self._replica_devices: dict[int, tuple] = {}
        self._make = make_replica
        self.min_replicas = min_replicas
        self.max_replicas = max_replicas
        self.scale_up_queue_depth = scale_up_queue_depth
        self.scale_down_idle_ticks = scale_down_idle_ticks
        self._obs = get_registry()
        self._replicas: dict[int, object] = {}
        self._idle_ticks: dict[int, int] = {}
        self._next_replica = 0
        self._next_frid = 0
        self._backlog: deque[int] = deque()
        self._spec: dict[int, tuple] = {}       # frid -> (prompt, max_new)
        self._local: dict[tuple, int] = {}      # (replica, local rid) -> frid
        self._placed: dict[int, tuple] = {}     # frid -> (replica, local rid)
        self._results: dict[int, list] = {}
        self.scale_events: list[dict] = []
        for _ in range(min_replicas):
            self._spawn("initial")

    # ---- capacity --------------------------------------------------------

    @property
    def n_replicas(self) -> int:
        return len(self._replicas)

    def _spawn(self, reason: str) -> int:
        rid = self._next_replica
        self._next_replica += 1
        if self._device_pool is not None:
            if len(self._device_pool) < self.devices_per_replica:
                self._next_replica -= 1
                raise RuntimeError(
                    f"device pool exhausted: {len(self._device_pool)} free, "
                    f"{self.devices_per_replica} needed per replica"
                )
            span = tuple(self._device_pool[: self.devices_per_replica])
            del self._device_pool[: self.devices_per_replica]
            self._replica_devices[rid] = span
            try:
                replica = self._replicas[rid] = self._make(span)
            except BaseException:
                # a failed factory must return its chips: nothing will ever
                # retire/kill this rid, so leaking here would permanently
                # shrink fleet capacity one replica-span per failure
                self._release_devices(rid)
                self._next_replica -= 1
                raise
        else:
            replica = self._replicas[rid] = self._make()
        # stamp the replica id into the batcher's serving metrics
        # (admissions / occupancy / queue depth / tokens / sheds) so the
        # cluster aggregator sees per-replica series, not one blended
        # stream; fleet ids are never reused, so a respawn is a NEW series
        if hasattr(replica, "obs_replica"):
            replica.obs_replica = str(rid)
        self._idle_ticks[rid] = 0
        self._note_scale("up", rid, reason)
        return rid

    def _release_devices(self, rid: int) -> None:
        span = self._replica_devices.pop(rid, None)
        if span is not None and self._device_pool is not None:
            self._device_pool.extend(span)

    def _retire(self, rid: int, reason: str) -> None:
        self._replicas.pop(rid)
        self._idle_ticks.pop(rid, None)
        self._release_devices(rid)
        self._note_scale("down", rid, reason)

    def _note_scale(self, direction: str, rid: int, reason: str) -> None:
        self.scale_events.append(
            {"direction": direction, "replica": rid, "reason": reason,
             "n_replicas": len(self._replicas)}
        )
        if self._obs.enabled:
            self._obs.counter(
                "serving_replica_scale_total", "decode replica scale events",
                labels=("direction",),
            ).inc(direction=direction)
            self._obs.gauge(
                "serving_replicas", "live decode replicas",
            ).set(len(self._replicas))
            flight_recorder.record(
                "serving_scale", direction=direction, replica=rid,
                reason=reason, n_replicas=len(self._replicas),
            )

    def kill_replica(self, rid: int | None = None) -> int:
        """Chaos hook: drop a replica (default: the newest). Finished-but-
        uncollected results are harvested first; everything unfinished
        re-enters the backlog at the FRONT (it has waited longest)."""
        if not self._replicas:
            raise RuntimeError("no replicas to kill")
        if rid is None:
            rid = max(self._replicas)
        replica = self._replicas.pop(rid)
        self._idle_ticks.pop(rid, None)
        self._release_devices(rid)
        self._harvest(rid, replica.collect())
        requeued = 0
        for req in reversed(replica.abandon()):
            frid = self._local.pop((rid, req.rid), None)
            if frid is None:
                continue
            self._placed.pop(frid, None)
            self._backlog.appendleft(frid)
            requeued += 1
        if self._obs.enabled:
            self._obs.counter(
                "serving_replica_failures_total", "decode replicas lost",
            ).inc()
            self._obs.counter(
                "serving_requeued_total",
                "requests resubmitted after a replica loss",
            ).inc(requeued)
            self._obs.gauge(
                "serving_replicas", "live decode replicas",
            ).set(len(self._replicas))
            flight_recorder.record(
                "serving_replica_lost", replica=rid, requeued=requeued,
                n_replicas=len(self._replicas),
            )
        self.scale_events.append(
            {"direction": "down", "replica": rid, "reason": "killed",
             "n_replicas": len(self._replicas), "requeued": requeued}
        )
        if not self._replicas and (self._backlog or self._placed):
            # zero capacity with work outstanding: re-arm the minimum fleet
            # now rather than waiting for a tick (the grow-back half)
            for _ in range(self.min_replicas):
                self._spawn("respawn_after_total_loss")
        return requeued

    # ---- requests --------------------------------------------------------

    def submit(self, prompt, max_new_tokens: int) -> int:
        frid = self._next_frid
        self._next_frid += 1
        self._spec[frid] = (np.asarray(prompt, np.int32).reshape(-1),
                            int(max_new_tokens))
        self._backlog.append(frid)
        return frid

    @property
    def outstanding(self) -> int:
        return len(self._backlog) + len(self._placed)

    def queue_depth(self) -> int:
        return len(self._backlog) + sum(
            b.n_queued for b in self._replicas.values()
        )

    def _load(self, replica) -> int:
        return replica.n_queued + replica.n_active + replica.n_pending

    def _harvest(self, rid: int, collected: dict) -> None:
        for lrid, toks in collected.items():
            frid = self._local.pop((rid, lrid), None)
            if frid is not None:
                self._placed.pop(frid, None)
                # the spec (prompt array) exists for requeue-on-failure;
                # once the result is in, keeping it would leak one prompt
                # per lifetime request in a long-lived fleet
                self._spec.pop(frid, None)
                self._results[frid] = toks

    def tick(self) -> None:
        """One fleet scheduler pass: dispatch → autoscale → step replicas
        → harvest."""
        from dsml_tpu.serving import QueueFull

        # dispatch backlog to the least-loaded replica with headroom; a
        # replica at its max_queue cap is only excluded for THIS tick —
        # another replica with room must still receive work (one full
        # queue must not stall the whole backlog)
        capped: set = set()
        while self._backlog and self._replicas:
            open_replicas = [(r, b) for r, b in self._replicas.items()
                             if r not in capped]
            if not open_replicas:
                break
            rid, replica = min(open_replicas, key=lambda kv: self._load(kv[1]))
            if self._load(replica) >= 2 * replica.n_slots:
                break  # the least-loaded is saturated → everyone open is
            frid = self._backlog.popleft()
            prompt, max_new = self._spec[frid]
            try:
                lrid = replica.submit(prompt, max_new)
            except QueueFull:
                self._backlog.appendleft(frid)
                capped.add(rid)
                continue
            self._local[(rid, lrid)] = frid
            self._placed[frid] = (rid, lrid)
        # queue-depth-driven scale-up (one replica per tick)
        if (
            len(self._replicas) < self.max_replicas
            and self.queue_depth()
            > self.scale_up_queue_depth * max(len(self._replicas), 1)
        ):
            self._spawn("queue_depth")
        # drive every replica and harvest retirements
        for rid, replica in list(self._replicas.items()):
            busy = (replica.n_active or replica.n_queued
                    or replica.n_pending)
            if busy:
                self._idle_ticks[rid] = 0
                replica.step()
                self._harvest(rid, replica.collect())
            else:
                self._idle_ticks[rid] += 1
        # idle scale-down (one per tick, never below the floor)
        if len(self._replicas) > self.min_replicas:
            idle = [r for r, t in self._idle_ticks.items()
                    if t >= self.scale_down_idle_ticks]
            if idle:
                self._retire(max(idle), "idle")

    def run(self, max_ticks: int = 100_000) -> dict[int, list]:
        """Drain everything; returns {fleet rid: [tokens]}."""
        for _ in range(max_ticks):
            if not self.outstanding:
                break
            self.tick()
        else:
            raise RuntimeError(f"fleet did not drain within {max_ticks} ticks")
        out = dict(self._results)
        self._results.clear()
        return out
