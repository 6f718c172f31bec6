"""The DeepSeek-V3 family's own arithmetic held to hand arithmetic at
``kanana2-8k``'s shapes, the comparison's watched leaves and the router's limit
of its own, the three new per-layer readers without a trace, and a CPU
rehearsal of the cell end to end."""

import json
import os
import subprocess
import sys
from pathlib import Path

import pytest

sys.path.insert(0, str(Path(__file__).resolve().parents[2]))

from benchmarks.families import deepseek_v3 as family  # noqa: E402
from benchmarks.layer_metrics import latent_flash_roofline, latent_ms, shared_expert_ms  # noqa: E402

ROOT = Path(__file__).resolve().parents[2]
CONFIG = json.loads((ROOT / "benchmarks/configs/kanana-2-30b-a3b.json").read_text())
TOKENS = 8192


@pytest.fixture(scope="module")
def shape():
    return family.shape(CONFIG)


def test_the_cut_is_the_stated_one(shape):
    assert (shape["n_layer"], shape["first_dense"], shape["vocab_size"]) == (5, 1, 64128)
    assert (shape["n_experts"], shape["experts_held"], shape["expert_top_k"]) == (128, (0, 64), 6)
    assert set(CONFIG["reduced"]) == {"num_hidden_layers", "n_routed_experts", "vocab_size"}


def test_attention_counts_the_live_pairs_at_192_and_128(shape):
    pairs = TOKENS * (TOKENS + 1) // 2
    forward = 5 * pairs * 2 * 32 * (192 + 128)
    assert family.attention_train_flops(shape, TOKENS, TOKENS) == 3 * forward
    assert family.attention_train_bytes(shape, TOKENS) == 5 * TOKENS * 32 * (6 * 192 + 6 * 128) * 2


def test_experts_count_the_held_pairs_at_uniform_routing(shape):
    pairs = TOKENS * 6 * 64 / 128
    assert pairs == 24_576
    assert family.expert_train_flops(shape, TOKENS) == 3 * 4 * pairs * 3 * 2 * 2048 * 768
    assert family.train_flops(shape, TOKENS, TOKENS) > (family.expert_train_flops(shape, TOKENS)
                                                         + family.attention_train_flops(shape, TOKENS, TOKENS))


def test_the_watched_view_keeps_the_router_and_cuts_the_experts():
    layer = {"attn": {"wq": 1}, "moe": {"router": 2, "bias": 3, "w_gate": [4, 5], "w_up": [6, 7], "w_down": [8, 9]}}
    like = {"attn": {"wq": 0}, "moe": {"router": 0, "bias": 0, "experts": {1: {}}}}
    view = family.watched_view(layer, like)
    assert view["moe"]["router"] == 2 and view["moe"]["bias"] == 3
    assert view["moe"]["experts"] == {1: {"w_gate": 5, "w_up": 7, "w_down": 9}}
    assert family.watched_view({"mlp": 1}, {"mlp": 0}) == {"mlp": 1}


ROUTER = "[4]['moe']['router']"


@pytest.mark.parametrize("wq,router,correct", [(0.02, 0.2, True), (0.02, 0.3, True), (0.02, 0.31, False),
                                               (0.02, float("nan"), False), (0.02, None, False), (0.11, 0.05, False)],
                         ids=["under", "at", "over", "nan", "absent", "another_leaf_over"])
def test_the_router_is_held_to_its_own_limit(wq, router, correct):
    """``drivers/train_leaf_limits.judge`` under the cell's own check: the
    router's matrix by its own limit alone (0.2 passes though over the 0.1 of
    every other leaf), the rest as ``train_experts`` judges them; a named leaf
    that was not read is not correct."""
    from benchmarks.drivers import train_leaf_limits

    check = json.loads((ROOT / "benchmarks/traffic/train-1x8192-latent.json").read_text())["check"]
    assert check["leaf_limits"] == {"['moe']['router']": 0.3} and check["first_moment_tolerance"] == 0.1
    errors = {"[0]['attn']['wq']": wq, "[4]['moe']['experts'][3]['w_gate']": 0.05,
              **({} if router is None else {ROUTER: router})}
    ok, note = train_leaf_limits.judge(check, errors)
    assert ok is correct and ROUTER not in note["judged"]
    assert note["checks"] == {"first_moment": wq <= 0.1, "leaf_limits": router is not None and router <= 0.3}


@pytest.mark.parametrize("reader", [latent_ms, shared_expert_ms, latent_flash_roofline])
def test_readers_read_nothing_without_a_device_trace(reader):
    assert reader.read(None, {"peak": None}) is None


def test_the_cell_rehearses_end_to_end_on_the_cpu():
    env = {**os.environ, "JAX_PLATFORMS": "cpu"}
    out = subprocess.run([sys.executable, "benchmarks/run.py", "--workload", "kanana2-8k", "--seed", "3900000001",
                          "--seconds", "1", "--trace", "0", "--rehearse"],
                         cwd=ROOT, env=env, capture_output=True, text=True, timeout=600)
    assert out.returncode == 0, out.stderr[-2000:]
    result = json.loads(out.stdout.strip().splitlines()[-1])
    assert result["correct"] and set(result["rehearsal_metrics"]) == {"tok_s_chip", "loss_at_30", "setup_s"}
