"""End-to-end data-parallel training on the 8-device mesh.

The reference's training code had zero tests (SURVEY.md §4.4). These train
real models (tiny budgets) and assert convergence — including through the
explicit ring-all-reduce gradient path, which the reference's training loop
only pretended to use (§8.4).
"""

import numpy as np
import pytest

from dsml_tpu.models.mlp import MLP
from dsml_tpu.trainer import TrainConfig, Trainer
from dsml_tpu.utils.data import load_mnist, shard_batches, synthetic_classification


@pytest.mark.parametrize("algorithm", ["xla", "ring"])
def test_dp_training_converges_synthetic(dp_mesh8, algorithm):
    data = synthetic_classification(4096, features=32, classes=10, seed=3)
    model = MLP(sizes=(32, 64, 10))
    trainer = Trainer(model, TrainConfig(epochs=3, batch_size=64, lr=0.05, algorithm=algorithm), mesh=dp_mesh8)
    params, history, test_acc = trainer.train(data)
    assert history[-1]["avg_loss"] < history[0]["avg_loss"] * 0.5
    assert test_acc > 0.9


def test_ring_and_xla_gradient_sync_agree(dp_mesh8):
    """Same seed, same data → the explicit ring path and XLA's own all-reduce
    must produce (numerically) the same training trajectory."""
    data = synthetic_classification(1024, features=16, classes=4, seed=1)
    results = {}
    for algorithm in ("xla", "ring"):
        model = MLP(sizes=(16, 32, 4))
        trainer = Trainer(
            model, TrainConfig(epochs=1, batch_size=32, lr=0.05, algorithm=algorithm, seed=7), mesh=dp_mesh8
        )
        params, history, _ = trainer.train(data)
        results[algorithm] = (history[0]["avg_loss"], params)
    assert np.isclose(results["xla"][0], results["ring"][0], rtol=1e-4)
    for k in results["xla"][1]:
        np.testing.assert_allclose(
            np.asarray(results["xla"][1][k]), np.asarray(results["ring"][1][k]), rtol=1e-3, atol=1e-5
        )


def test_mnist_reaches_reference_accuracy(dp_mesh8):
    """MNIST parity: the reference hit 92.89% after 10 epochs on the full
    60k train set (BASELINE.md). The mirror lacks that blob, so this trains
    on the augmented t10k split — 3 epochs must already clear 85%, and the
    full-budget run is ``examples/train_mnist.py``."""
    data = load_mnist()
    model = MLP()  # 784-128-64-10, the documented architecture
    trainer = Trainer(model, TrainConfig(epochs=3, batch_size=64, lr=0.1, optimizer="momentum"), mesh=dp_mesh8)
    _, history, test_acc = trainer.train(data)
    assert test_acc > 0.85, f"got {test_acc:.4f}"


def test_lr_schedule_and_optimizers_build(dp_mesh8):
    data = synthetic_classification(512, features=8, classes=4)
    model = MLP(sizes=(8, 16, 4))
    cfg = TrainConfig(epochs=1, batch_size=32, lr=0.01, optimizer="adamw", lr_schedule="cosine", warmup_steps=2)
    _, history, _ = Trainer(model, cfg, mesh=dp_mesh8).train(data)
    assert len(history) == 1


def test_shard_batches_covers_epoch():
    x = np.arange(100, dtype=np.float32)[:, None]
    y = np.arange(100, dtype=np.int32)
    seen = [xb.shape[0] for xb, _ in shard_batches(x, y, 32, seed=0)]
    assert seen == [32, 32, 32]  # drop_remainder
    all_items = np.concatenate([yb for _, yb in shard_batches(x, y, 50, seed=1)])
    assert len(set(all_items.tolist())) == 100  # shuffled, no duplicates


def test_mid_epoch_save_and_resume_bit_identical(dp_mesh8, tmp_path):
    """save_every_steps: a run preempted MID-EPOCH resumes from the
    step-granularity checkpoint with the data-loader position intact —
    final params bit-identical to the uninterrupted run (the elastic
    controller's resume contract, now at the Trainer level too). Also
    pins the new exception-path flush: the killed run's async saves are
    committed by the time train() has raised."""
    data = synthetic_classification(512, features=16, classes=4, seed=0)
    ck = str(tmp_path / "run")
    cfg = dict(epochs=2, batch_size=64, lr=0.05, seed=3,
               save_every_steps=3, keep_checkpoints=0)
    # synthetic_classification holds out a test split → 448 train rows →
    # steps_per_epoch = 7; kill after 11 completed steps (epoch 2, batch 4)

    class _Preempted(RuntimeError):
        pass

    class _KilledTrainer(Trainer):
        def _build(self, steps_per_epoch):
            optimizer = super()._build(steps_per_epoch)
            inner, calls = self._step_fn, {"n": 0}

            def wrapped(params, opt_state, x, y):
                calls["n"] += 1
                if calls["n"] > 11:
                    raise _Preempted("simulated preemption")
                return inner(params, opt_state, x, y)

            self._step_fn = wrapped
            return optimizer

    model = MLP(sizes=(16, 32, 4))
    uninterrupted, _, _ = Trainer(
        model, TrainConfig(**cfg), mesh=dp_mesh8
    ).train(data)

    with pytest.raises(_Preempted):
        _KilledTrainer(
            model, TrainConfig(checkpoint_dir=ck, **cfg), mesh=dp_mesh8
        ).train(data)
    from dsml_tpu.checkpoint import CheckpointManager

    with CheckpointManager(ck) as m:
        # latest mid-epoch save: global step 9 = epoch 2, 2 batches
        # consumed (7 was the epoch-1 boundary save; the exception-path
        # close flushed the async commit)
        assert m.latest_step() == 9
        assert m.iterator_state() == {"epoch": 2, "consumed": 2}

    resumed, hist, _ = Trainer(
        model, TrainConfig(checkpoint_dir=ck, resume=True, **cfg),
        mesh=dp_mesh8,
    ).train(data)
    assert [h["epoch"] for h in hist] == [2]  # only the resumed epoch
    for k in uninterrupted:
        np.testing.assert_array_equal(
            np.asarray(uninterrupted[k]), np.asarray(resumed[k]), err_msg=k
        )


def test_epoch_boundary_resume_unchanged_by_default(dp_mesh8, tmp_path):
    """save_every_steps=0 (default) keeps the historical epoch-id
    checkpoint scheme byte-for-byte: ids are epoch numbers and resume
    starts at the next epoch."""
    data = synthetic_classification(256, features=8, classes=4, seed=1)
    ck = str(tmp_path / "run")
    model = MLP(sizes=(8, 16, 4))
    Trainer(model, TrainConfig(epochs=2, batch_size=64, lr=0.05,
                               checkpoint_dir=ck, seed=1),
            mesh=dp_mesh8).train(data)
    from dsml_tpu.checkpoint import CheckpointManager

    with CheckpointManager(ck) as m:
        assert m.latest_step() == 2  # epoch ids, not step ids
        assert m.iterator_state() == {"epoch": 2, "consumed": 0}
