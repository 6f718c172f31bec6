"""Example entry points stay runnable (the config-ladder scripts are part of
the framework's public surface, BASELINE.json configs 4-5)."""

import os
import sys

import numpy as np
import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, os.path.join(REPO, "examples"))


@pytest.mark.slow
def test_gpt2_example_trains_and_loss_drops():
    import train_gpt2

    result = train_gpt2.main(
        [
            "--steps", "8",
            "--batch_size", "4",
            "--grad_accum", "2",
            "--dp", "2", "--sp", "2", "--tp", "2",
            "--log_every", "4",
        ]
    )
    assert np.isfinite(result["last_loss"])
    # the step must actually move the params, not just evaluate the loss
    assert result["last_loss"] < result["first_loss"] - 0.05


def test_gpt2_example_adafactor_remat_trains():
    """The XL-on-one-chip recipe's ingredients (adafactor factored state +
    remat) compose with the hybrid step and actually train — the same flag
    path the README recipe uses, at toy scale."""
    import train_gpt2

    result = train_gpt2.main(
        [
            "--steps", "12",
            "--batch_size", "8",
            "--grad_accum", "1",
            "--optimizer", "adafactor",
            "--remat", "true",
            "--seq_len", "64",
            "--warmup_steps", "2",
            "--log_every", "6",
        ]
    )
    assert np.isfinite(result["last_loss"])
    assert result["last_loss"] < result["first_loss"] - 0.02


def test_cifar_example_loads_binary_format(tmp_path):
    import train_cifar_resnet

    # forge two 10-record CIFAR binary batches + a test batch
    rng = np.random.default_rng(0)
    for name in ("data_batch_1.bin", "data_batch_2.bin", "test_batch.bin"):
        rec = np.zeros((10, 3073), np.uint8)
        rec[:, 0] = rng.integers(0, 10, 10)
        rec[:, 1:] = rng.integers(0, 256, (10, 3072))
        rec.tofile(tmp_path / name)
    data = train_cifar_resnet.load_cifar10(str(tmp_path), synth_n=0, seed=0)
    assert data.train_x.shape == (20, 32, 32, 3)
    assert data.test_x.shape == (10, 32, 32, 3)
    assert data.train_x.dtype == np.float32 and data.train_x.max() <= 1.0
    assert data.train_y.dtype == np.int32


def test_cifar_example_synthetic_fallback(tmp_path):
    import train_cifar_resnet

    data = train_cifar_resnet.load_cifar10(str(tmp_path / "missing"), synth_n=128, seed=0)
    assert data.train_x.shape[1:] == (32, 32, 3)


@pytest.mark.slow
def test_llama_family_example_trains():
    import train_gpt2

    result = train_gpt2.main(
        [
            "--family", "llama",
            "--steps", "6",
            "--batch_size", "4",
            "--grad_accum", "2",
            "--dp", "2", "--sp", "1", "--tp", "2",
            "--log_every", "3",
        ]
    )
    assert np.isfinite(result["last_loss"])
    assert result["last_loss"] < result["first_loss"]


@pytest.mark.slow
def test_elastic_example_survives_device_loss():
    import train_elastic

    loss = train_elastic.main(
        ["--devices", "8", "--lose", "3", "--fail_at_step", "2", "--steps", "4"]
    )
    assert np.isfinite(loss)


def test_mnist_example_reaches_reference_band():
    """The reference's own workload end-to-end through the example CLI (ring
    gradient sync on the virtual mesh). Accuracy protocol differs from the
    reference (train blob stripped; SURVEY §8.11) — assert learning happened,
    not a specific headline number."""
    import train_mnist

    # 1 epoch: this test pins the CLI wiring + the ring-sync path learning
    # at all; the reference-band accuracy claim lives in
    # tests/test_trainer.py::test_mnist_reaches_reference_accuracy
    acc = train_mnist.main(["--epochs", "1", "--algorithm", "ring", "--batch_size", "128"])
    assert acc > 0.55, acc


def test_model_by_family_dispatch():
    from dsml_tpu.models import model_by_family
    from dsml_tpu.models.gpt2 import GPT2
    from dsml_tpu.models.llama import Llama

    m, cfg = model_by_family("gpt2", "tiny", vocab_size=128)
    assert type(m) is GPT2 and cfg.vocab_size == 128  # isinstance would pass for Llama (a GPT2 subclass)
    m2, cfg2 = model_by_family("llama", "mixtral_8x7b")
    assert isinstance(m2, Llama) and cfg2.n_experts == 8
    import pytest

    with pytest.raises(ValueError, match="unknown family"):
        model_by_family("mamba", "tiny")
