"""The benchmark's FLOP count equals the program's for both configurations,
and the peaks table refuses a device it does not know."""

import json
from pathlib import Path

import pytest

from benchmarks import flops, harness
from benchmarks.families import gpt2 as family
from dsml_tpu.models.common import transformer_train_flops
from dsml_tpu.models.gpt2 import GPT2Config

CONFIGS = Path(__file__).resolve().parents[1] / "configs"


@pytest.mark.parametrize("config, preset, seq", [
    ("gpt2-small", GPT2Config.small(), 1024),
    ("gpt2-small-8k", GPT2Config.small(), 8192),
    ("gpt2-large", GPT2Config.large(), 1024),
])
def test_train_flops_match_the_programs(config, preset, seq):
    shape = family.shape(json.loads((CONFIGS / f"{config}.json").read_text()))
    n_tokens = 4 * seq
    assert flops.train_flops(shape, n_tokens, seq) == transformer_train_flops(preset, n_tokens, seq)
    attention = flops.attention_train_flops(shape, n_tokens, seq)
    assert 0 < attention < flops.train_flops(shape, n_tokens, seq)
    # the attention term is what train_flops adds when the sequence term is there
    without = transformer_train_flops(preset, n_tokens, 0)
    assert attention == transformer_train_flops(preset, n_tokens, seq) - without


def test_shape_follows_the_published_config():
    large = family.shape(json.loads((CONFIGS / "gpt2-large.json").read_text()))
    preset = GPT2Config.large()
    assert large == {k: getattr(preset, k) for k in large}


def test_roofline_names_the_binding_bound():
    peak = harness.peak_for("TPU v5 lite")
    assert flops.roofline_seconds(197e12, 1.0, peak) == (1.0, "compute")
    assert flops.roofline_seconds(1.0, 819e9, peak) == (1.0, "memory")


def test_unknown_device_kind_raises():
    assert harness.peak_for("TPU v5 lite")["bf16_flops_per_s"] == 197e12
    with pytest.raises(ValueError, match="no peaks known"):
        harness.peak_for("TPU v9 imaginary")
