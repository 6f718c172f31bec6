"""Data-parallel trainer: the reference's training loop, compiled.

Reproduces the observable behavior of the reference client's epoch loop
(``DSML/client/client.go:516-659``: batched SGD, per-epoch "Average Loss /
Accuracy" lines, final test accuracy) with the semantics it intended: the
global batch is sharded across the mesh's ``dp`` axis, gradients all-reduce
on-device, and forward/backward/update run as one donated jitted step.
"""

from __future__ import annotations

import dataclasses
import os
import time

import jax
import jax.numpy  # noqa: F401 (used via jax.numpy.array in warm-start copy)
import numpy as np
import optax

from dsml_tpu.obs import GoodputTracker, StepBreakdown, get_registry
from dsml_tpu.obs import flight_recorder, hangwatch
from dsml_tpu.obs.memory import get_memory_ledger, maybe_dump_oom
from dsml_tpu.obs.sentinels import TrainingSentinels
from dsml_tpu.parallel.dp import make_dp_train_step, make_eval_step
from dsml_tpu.parallel.mesh import data_mesh
from dsml_tpu.utils.config import Config, field
from dsml_tpu.utils.data import Dataset, prefetch_batches, shard_batches
from dsml_tpu.utils.logging import get_logger
from dsml_tpu.utils.metrics import EpochMetrics, MetricsLogger, ProgressBar

log = get_logger("trainer")


@dataclasses.dataclass
class TrainConfig(Config):
    epochs: int = field(10, help="training epochs (reference: 10)")
    batch_size: int = field(64, help="GLOBAL batch size (reference: 64)")
    lr: float = field(0.01, help="SGD learning rate (reference: 0.01)")
    optimizer: str = field("sgd", help="sgd | momentum | adam | adamw")
    lr_schedule: str = field("constant", help="constant | cosine | linear | step | plateau (the adaptive LR the reference README promised but never shipped, SURVEY.md §8.8)")
    warmup_steps: int = field(0, help="linear warmup steps for the schedule")
    plateau_patience: int = field(5, help="plateau schedule: epochs-worth of steps without improvement before decaying")
    plateau_factor: float = field(0.5, help="plateau schedule: lr decay factor")
    algorithm: str = field("xla", help="gradient sync: xla | ring | ring2 | auto | naive | q8 (v1 int8 gather) | q8_ring | q8_ring2 | q4_ring | q4_ring2 (block-quantized ring schedules) | quant (per-dtype via DSML_QUANT)")
    error_feedback: bool = field(False, help="error-feedback residuals for quantized ring sync (q8_ring/q8_ring2/q4_ring/q4_ring2/quant): the per-rank compression error re-enters the next step's gradients; residuals are checkpointable state and ride resume bit-identically")
    bucket_mb: float = field(0.0, help="explicit-sync gradient bucket size in MiB (0 = the DSML_BUCKET_MB default, currently 4; negative = single buffer, the pre-bucketing A/B shape)")
    dp: int = field(0, help="data-parallel devices (0 = all local)")
    seed: int = field(0, help="init + shuffle seed")
    log_metrics: str = field("", help="optional JSONL metrics path")
    checkpoint_dir: str = field("", help="checkpoint directory ('' = no checkpointing; native sharded backend, docs/CHECKPOINT.md)")
    save_every: int = field(1, help="checkpoint every N epochs")
    save_every_steps: int = field(0, help="ALSO checkpoint every N steps mid-epoch (0 = epoch boundaries only); the data-loader position (epoch, consumed batches) rides the manifest so a preempted run resumes mid-epoch bit-identically; step-granularity saves use the global step as the checkpoint id")
    keep_checkpoints: int = field(3, help="max checkpoints retained (older steps garbage-collected)")
    resume: bool = field(False, help="resume from the latest checkpoint in checkpoint_dir")
    progress: bool = field(False, help="draw per-epoch train/eval progress bars on stderr (reference client UX)")
    sync_every: int = field(32, help="device→host loss sync cadence in steps; also the training-health sentinel check point (DSML_SENTINELS — docs/OBSERVABILITY.md)")


# The per-epoch bar is ``utils.metrics.ProgressBar`` (the reference
# client's schollz/progressbar UX, client.go:584-590/467-473): TTY-aware
# — in-place redraws on an interactive stderr, one newline-terminated
# summary line per bar otherwise — and off unless ``TrainConfig.progress``
# (a redraw per batch is host-side noise the compiled step loop doesn't
# need by default).


def _make_optimizer(cfg: TrainConfig, steps_per_epoch: int) -> optax.GradientTransformation:
    from dsml_tpu.utils.schedules import make_schedule, wrap_with_plateau

    total = max(cfg.epochs * steps_per_epoch, 1)
    lr = make_schedule(cfg.lr_schedule, cfg.lr, total, cfg.warmup_steps)
    opt = {
        "sgd": lambda: optax.sgd(lr),
        "momentum": lambda: optax.sgd(lr, momentum=0.9),
        "adam": lambda: optax.adam(lr),
        "adamw": lambda: optax.adamw(lr, weight_decay=1e-4),
    }[cfg.optimizer]()
    if cfg.lr_schedule == "plateau":
        # the reference-documented "adaptive learning rate scheduler":
        # monitor the per-step loss, decay when it stops improving
        # one accumulated loss evaluation per epoch; patience counts epochs
        opt = wrap_with_plateau(
            opt,
            factor=cfg.plateau_factor,
            patience=cfg.plateau_patience,
            accumulation_size=max(steps_per_epoch, 1),
        )
    return opt


class Trainer:
    """Train any model exposing ``init(seed)``, ``loss(params,x,y)``,
    ``apply(params,x)`` data-parallel over a mesh."""

    def __init__(self, model, config: TrainConfig | None = None, mesh=None):
        self.model = model
        self.config = config or TrainConfig()
        self.mesh = mesh if mesh is not None else data_mesh(self.config.dp or None)
        self.metrics = MetricsLogger(self.config.log_metrics or None)
        self._step_fn = None
        self._eval_fn = None
        self._ef_norm_fn = None

    def _build(self, steps_per_epoch: int):
        optimizer = _make_optimizer(self.config, steps_per_epoch)
        # 0 → "auto" (DSML_BUCKET_MB default), < 0 → None (single buffer)
        bucket = self.config.bucket_mb
        self._step_fn = make_dp_train_step(
            self.model.loss, optimizer, self.mesh, algorithm=self.config.algorithm,
            bucket_size_mb="auto" if bucket == 0 else (None if bucket < 0 else bucket),
            error_feedback=self.config.error_feedback,
        )
        self._eval_fn = make_eval_step(self.model, self.mesh)
        return optimizer

    def train(self, data: Dataset, params=None):
        cfg = self.config
        n_dp = self.mesh.shape.get("dp", 1)
        if cfg.batch_size % max(n_dp, 1):
            raise ValueError(f"global batch {cfg.batch_size} not divisible by dp={n_dp}")
        steps_per_epoch = data.n_train // cfg.batch_size
        optimizer = self._build(steps_per_epoch)
        if params is None:
            params = self.model.init(cfg.seed)
        else:
            # The jitted step donates its inputs; copy so the caller's arrays
            # survive the first step.
            params = jax.tree.map(lambda a: jax.numpy.array(a), params)
        opt_state = optimizer.init(params)
        ef = None
        if cfg.error_feedback:
            # per-rank compression residuals (EF-SGD): sharded over dp so
            # each device stores only its own; checkpointable state below
            from dsml_tpu.parallel.bucketing import init_error_feedback

            ef = init_error_feedback(params, self.mesh, "dp")

        ckpt = None
        start_epoch = 1
        resume_skip = 0  # batches already consumed of start_epoch (mid-epoch resume)
        if cfg.checkpoint_dir:
            from dsml_tpu.checkpoint import CheckpointManager

            ckpt = CheckpointManager(cfg.checkpoint_dir,
                                     max_to_keep=cfg.keep_checkpoints)
            if cfg.resume and ckpt.latest_step() is None:
                foreign = [n for n in os.listdir(ckpt.directory) if n.isdigit()]
                if foreign:
                    # digit-named step dirs = the orbax layout the previous
                    # Checkpointer wrote; restarting silently would redo
                    # every completed epoch
                    raise RuntimeError(
                        f"resume=True but {cfg.checkpoint_dir} holds no native "
                        f"checkpoints — found orbax-format step dirs {foreign[:3]}; "
                        "restore them via utils.checkpoint.Checkpointer("
                        "backend='orbax') or start a fresh checkpoint_dir"
                    )
            if cfg.resume and ckpt.latest_step() is not None:
                template = {"params": params, "opt_state": opt_state,
                            "meta": {"epoch": 0}}
                if ef is not None:
                    # EF residuals ride the manifest like any state tree;
                    # restoring them is what keeps a kill-and-resume under
                    # quantized sync bit-identical to the unkilled run
                    template["ef"] = ef
                state = ckpt.restore(template=template)
                params, opt_state = state["params"], state["opt_state"]
                if ef is not None:
                    ef = state["ef"]
                it_state = ckpt.iterator_state() or {}
                if int(it_state.get("consumed", 0)) > 0:
                    # mid-epoch checkpoint (save_every_steps): restart
                    # INSIDE the epoch — shard_batches re-derives the same
                    # shuffle from (seed + epoch), and fast-forwarding past
                    # the consumed prefix makes the remaining batches
                    # bit-identical to the uninterrupted run's
                    start_epoch = int(it_state["epoch"])
                    resume_skip = int(it_state["consumed"])
                    log.info("resumed mid-epoch %d at batch %d",
                             start_epoch, resume_skip)
                else:
                    start_epoch = int(state["meta"]["epoch"]) + 1
                    log.info("resumed from checkpoint at epoch %d", start_epoch - 1)

        # Observability (docs/OBSERVABILITY.md): when the registry is
        # enabled, the loop records a per-step breakdown (data /
        # step_dispatch / loss_sync / checkpoint_stall — the fused jitted
        # step is one program, so there is no fwd-bwd/sync/opt split
        # here) and goodput = productive step time ÷
        # wall across resume/checkpoint events. Disabled: one boolean per
        # step, nothing recorded.
        # Failure forensics (docs/OBSERVABILITY.md § Failure forensics), all
        # opt-in and zero-sync by construction:
        # - sentinels (DSML_SENTINELS) inspect the loss at the EXISTING
        #   loss_sync point — the scalar is already host-ready there, so the
        #   fused step gains no device→host round trips;
        # - hangwatch (DSML_HANGWATCH) arms a deadline per loss-sync window
        #   at k× the trailing-median window wall, once warmed up;
        # - the flight recorder gets one "step" event per batch and one
        #   "loss_sync" per sync.
        obs_reg = get_registry()
        recorder = flight_recorder.get_flight_recorder()
        sentinels = TrainingSentinels.maybe_from_env()
        hw_cfg = hangwatch.config_from_env()
        hw = hangwatch.get_hangwatch() if hw_cfg is not None else None
        measure_act = os.environ.get("DSML_MEASURE_ACT") == "1"
        if sentinels is not None or hw is not None or measure_act:
            # forensic env opt-in IMPLIES observability: a halt bundle with
            # empty event/metric/log sections would defeat the black-box
            # recorder the operator just asked for (and a measured
            # activation claim on a disabled registry would vanish before
            # plan_mesh could read it). Enable the registry and
            # install the crash/SIGTERM dump hooks + the log ring
            # (idempotent; previous hooks are chained, obs.disable restores)
            from dsml_tpu.utils.logging import install_ring_handler

            obs_reg.enable()
            install_ring_handler()
            flight_recorder.install()
        track = obs_reg.enabled
        goodput = GoodputTracker(registry=obs_reg) if track else None
        breakdown = StepBreakdown(registry=obs_reg) if track else None
        ledger = get_memory_ledger(obs_reg)
        if track:
            # memory ledger (docs/OBSERVABILITY.md § Memory ledger):
            # attribute the training state at its allocation site — the
            # per-device resident bytes of params / optimizer state / EF
            # residuals; per-step peak watermarks land at loss syncs below
            ledger.claim_tree("params", params)
            ledger.claim_tree("optimizer", opt_state)
            if ef is not None:
                ledger.claim_tree("error_feedback", ef)
        if measure_act:
            self._measure_activation_footprint(
                params, data.train_x[: cfg.batch_size],
                data.train_y[: cfg.batch_size], ledger, recorder,
            )
        if track and start_epoch > 1:
            goodput.mark("restore", epoch=start_epoch - 1)
        step_deadline = (hangwatch.TrailingDeadline.from_config(hw_cfg)
                         if hw_cfg is not None else None)
        sync_every = max(cfg.sync_every, 1)
        save_every_steps = max(cfg.save_every_steps, 0)
        global_step = (start_epoch - 1) * steps_per_epoch + resume_skip
        recorder.record(
            "train_start", epochs=cfg.epochs, batch_size=cfg.batch_size,
            steps_per_epoch=steps_per_epoch, algorithm=cfg.algorithm,
            start_epoch=start_epoch,
        )

        def save_ckpt(epochs_done: int, it_epoch: int, consumed_now: int,
                      wait: bool = False) -> None:
            """THE checkpoint write, shared by all three call sites
            (mid-epoch, epoch boundary, final) so the id scheme and
            manifest layout cannot drift apart: id = GLOBAL STEP when
            step-granularity saves are on (one monotonic id space), the
            completed-epoch number otherwise; the loader position
            (it_epoch, consumed_now) rides the manifest. With wait=False
            the step loop pays only the synchronous host snapshot +
            enqueue (the commit rides the writer thread and surfaces as
            checkpoint_commit_ms)."""
            t_save = time.perf_counter()
            state = {"params": params, "opt_state": opt_state,
                     "meta": {"epoch": epochs_done}}
            if ef is not None:
                state["ef"] = ef
            ckpt.save(global_step if save_every_steps else epochs_done,
                      state,
                      iterator_state={"epoch": it_epoch,
                                      "consumed": consumed_now},
                      wait=wait)
            if track:
                breakdown.add("checkpoint_stall", time.perf_counter() - t_save)
                goodput.mark("checkpoint_save", epoch=it_epoch,
                             step=global_step)
            recorder.record(
                "checkpoint_save", epoch=it_epoch, step=global_step,
                stall_ms=round((time.perf_counter() - t_save) * 1e3, 3))

        history = []
        t0 = time.monotonic()
        train_body_done = False
        try:
            for epoch in range(start_epoch, cfg.epochs + 1):
                losses = []  # device arrays; synced only every sync_every steps so
                # dispatch of step k+1 overlaps execution of step k without the
                # in-flight queue growing unboundedly
                batches = prefetch_batches(
                    shard_batches(data.train_x, data.train_y, cfg.batch_size, seed=cfg.seed + epoch)
                )
                skip = resume_skip if epoch == start_epoch else 0
                if skip:
                    import itertools

                    # fast-forward the deterministic stream past the consumed
                    # prefix — the prefetcher never over-advances the recorded
                    # position (ResumableIterator's contract, inlined)
                    batches = itertools.islice(batches, skip, None)
                consumed = skip
                bar = ProgressBar(steps_per_epoch - skip,
                                  desc=f"Epoch {epoch}/{cfg.epochs}",
                                  enabled=cfg.progress)
                epoch_t0 = time.monotonic()
                t_prev = time.perf_counter()
                # Hangwatch covers the SYNC WINDOW, not single batches: async
                # dispatch makes 31 of every 32 batch walls sub-ms (only the
                # sync_every-th blocks in block_until_ready), so a per-batch
                # median would collapse the deadline to the floor and fire on
                # every healthy sync. The window wall — sync to sync — is the
                # unimodal quantity a wedged collective actually stretches.
                hw_token = None
                win_t0 = t_prev
                try:
                    for x, y in batches:
                        global_step += 1
                        consumed += 1
                        if hw is not None and hw_token is None:
                            deadline_s = step_deadline.timeout_s()
                            if deadline_s is not None:
                                hw_token = hw.arm("train_sync_window", deadline_s,
                                                  step=global_step, epoch=epoch)
                        if track:
                            t_data = time.perf_counter()
                            breakdown.add("data", t_data - t_prev)
                        if ef is not None:
                            params, opt_state, ef, loss = self._step_fn(
                                params, opt_state, ef, x, y)
                        else:
                            params, opt_state, loss = self._step_fn(params, opt_state, x, y)
                        if track:
                            t_disp = time.perf_counter()
                            breakdown.add("step_dispatch", t_disp - t_data)
                        losses.append(loss)
                        bar.update()
                        if len(losses) % sync_every == 0:
                            losses[-1].block_until_ready()
                            if track:
                                breakdown.add("loss_sync", time.perf_counter() - t_disp)
                                # per-step peak watermark at the existing
                                # sync point (the step already blocked —
                                # no new device round trips; statless
                                # backends record the claimed total)
                                ledger.note_step_peak(global_step)
                            if hw is not None:
                                if hw_token is not None:
                                    hw.disarm(hw_token)
                                    hw_token = None
                                now_sync = time.perf_counter()
                                step_deadline.observe(now_sync - win_t0)
                                win_t0 = now_sync
                            if sentinels is not None or track:
                                # the scalar is already synced; float() is a host read
                                loss_host = float(losses[-1])
                                recorder.record("loss_sync", step=global_step,
                                                epoch=epoch, loss=loss_host)
                                if sentinels is not None:
                                    # halt-policy trips raise SentinelTripped out of
                                    # train() with the postmortem bundle already on disk
                                    sentinels.check(global_step, loss_host)
                            if track and ef is not None:
                                # residual health at the existing sync point
                                # (the step already blocked — one small
                                # jitted norm + host read per sync window)
                                if self._ef_norm_fn is None:
                                    self._ef_norm_fn = jax.jit(optax.global_norm)
                                obs_reg.gauge(
                                    "quant_error_feedback_norm",
                                    "global L2 norm of the error-feedback "
                                    "residual tree (sampled at loss syncs)",
                                ).set(float(self._ef_norm_fn(ef)))
                        if track:
                            now = time.perf_counter()
                            breakdown.note_step_wall(now - t_prev)
                            recorder.record("step", step=global_step, epoch=epoch,
                                            wall_ms=round((now - t_prev) * 1e3, 3))
                            t_prev = now
                        if (ckpt is not None and save_every_steps
                                and consumed < steps_per_epoch
                                and global_step % save_every_steps == 0):
                            # mid-epoch preemption point: resume
                            # fast-forwards past the consumed prefix
                            # bit-identically
                            save_ckpt(epoch - 1, epoch, consumed)
                            if track:
                                t_prev = time.perf_counter()  # save ≠ data time
                finally:
                    # disarm on EVERY exit — a halt/exception (or epoch end with
                    # a partial window) must not leave a deadline that later
                    # fires a spurious hang bundle
                    if hw_token is not None:
                        hw.disarm(hw_token)
                bar.close()
                if track:
                    # productive = time spent driving steps; eval/logging/
                    # checkpoint overhead shows up as the goodput gap
                    goodput.add_productive(time.monotonic() - epoch_t0)
                em = EpochMetrics()
                for loss in losses:
                    em.update(float(loss), 0, cfg.batch_size)
                train_acc = self.evaluate(params, data.train_x, data.train_y)
                # Same log shape as the reference's per-epoch line (client.go:650-652).
                log.info("Epoch %d: Average Loss = %.4f, Accuracy = %.2f%%", epoch, em.avg_loss, train_acc * 100)
                recorder.record("epoch", epoch=epoch, avg_loss=em.avg_loss,
                                train_accuracy=train_acc)
                history.append(
                    self.metrics.log(epoch=epoch, avg_loss=em.avg_loss, train_accuracy=train_acc)
                )
                if ckpt is not None and epoch % max(cfg.save_every, 1) == 0:
                    # async: the write overlaps the next epoch's compute; the
                    # manager's writer barrier (or close()) commits it. Saves
                    # land at epoch boundaries, so the loader position is just
                    # the NEXT epoch's seed — shard_batches re-derives the
                    # shuffle from (cfg.seed + epoch), making resume
                    # bit-identical to the uninterrupted run
                    save_ckpt(epoch, epoch, 0)
            last_epoch = cfg.epochs
            if ckpt is not None:
                # final state must always be persisted, even when epochs isn't a
                # multiple of save_every (otherwise the reported model is lost and
                # resume would redo the last epochs)
                if last_epoch >= start_epoch and last_epoch % max(cfg.save_every, 1) != 0:
                    save_ckpt(last_epoch, last_epoch, 0, wait=True)
            train_body_done = True
        except BaseException as e:
            # a device OOM unwinding through here leaves a postmortem
            # whose memory.json carries the ledger snapshot + watermark
            # timeline (docs/OBSERVABILITY.md § Memory ledger); any other
            # exception passes untouched (the crash hooks own those)
            if track:
                try:
                    maybe_dump_oom(e)
                except Exception:  # noqa: BLE001 — never mask the real crash
                    pass
            raise
        finally:
            if ckpt is not None:
                # ALWAYS flush: a dying run (preemption signal unwinding,
                # sentinel halt) still commits its queued async saves — that
                # checkpoint is exactly what recovery resumes from. A writer
                # error must not mask the original exception.
                try:
                    ckpt.close()
                except Exception:
                    if train_body_done:
                        raise
                    log.warning("checkpoint close failed during exception "
                                "unwind", exc_info=True)
        test_acc = self.evaluate(
            params, data.test_x, data.test_y,
            progress_label="Testing" if cfg.progress else None,
        )
        wall = time.monotonic() - t0
        epochs_run = max(cfg.epochs - start_epoch + 1, 0)  # resume skips earlier epochs
        samples = epochs_run * steps_per_epoch * cfg.batch_size
        log.info("Final Test Accuracy: %.2f%%", test_acc * 100)  # client.go:500-501 shape
        final = {"test_accuracy": test_acc, "wall_time_s": wall,
                 "samples_per_sec": samples / max(wall, 1e-9)}
        if track:
            gsum = goodput.summary()
            obs_reg.gauge("train_goodput", "productive/wall of the last run") \
                .set(gsum["goodput"])
            final["obs_goodput"] = gsum
            final["obs_step_breakdown"] = breakdown.summary()
        self.metrics.log(**final)
        return params, history, test_acc

    def _measure_activation_footprint(self, params, x, y, ledger,
                                      recorder) -> None:
        """``DSML_MEASURE_ACT=1``: measure the train step's XLA temp bytes
        from shapes alone (``parallel.auto.measured_activation_bytes`` —
        compile-only, no data, no execution) and claim them as the
        ledger's ``activations`` subsystem, so the activation-budget
        number ``plan_mesh`` consumes exists without a manual call. The
        extra compile is the opt-in's price; failure logs and trains on —
        a broken measurement must never block the run it instruments."""
        from dsml_tpu.parallel.auto import measured_activation_bytes

        def sds(a):
            return jax.ShapeDtypeStruct(a.shape, a.dtype)

        try:
            measured = measured_activation_bytes(
                self.model.loss, jax.tree.map(sds, params), sds(x), sds(y)
            )
        except Exception:
            log.warning("DSML_MEASURE_ACT: activation measurement failed",
                        exc_info=True)
            return
        if measured is None:
            log.warning(
                "DSML_MEASURE_ACT: backend reports no compiled memory "
                "analysis — activation footprint stays analytic"
            )
            return
        # claim + geometry: plan_mesh rescales per-sample to ITS
        # batch_per_device instead of reusing this absolute number
        ledger.record_activation_measurement(measured, x.shape[0])
        recorder.record("activation_measured", bytes=int(measured),
                        batch=int(x.shape[0]))
        log.info("measured activation footprint: %.2f MB (XLA temp bytes "
                 "of the compiled step)", measured / 1e6)

    def evaluate(self, params, x: np.ndarray, y: np.ndarray, batch_size: int = 2048,
                 progress_label: str | None = None) -> float:
        n_dp = max(self.mesh.shape.get("dp", 1), 1)
        n = x.shape[0]
        usable = n - (n % n_dp)  # each eval batch must split evenly over dp
        bs = max(batch_size - batch_size % n_dp, n_dp)
        bar = ProgressBar((usable + bs - 1) // bs,
                          desc=progress_label or "Testing",
                          enabled=progress_label is not None)
        correct = 0
        for start in range(0, usable, bs):
            xb, yb = x[start : start + bs], y[start : start + bs]
            if xb.shape[0] % n_dp:  # tail: trim to a dp multiple
                cut = xb.shape[0] - xb.shape[0] % n_dp
                xb, yb = xb[:cut], yb[:cut]
            correct += int(self._eval_fn(params, xb, yb))
            bar.update()
        bar.close()
        return correct / max(usable, 1)
