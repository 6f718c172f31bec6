"""The Ouro family's own arithmetic held to hand arithmetic at ``ouro-8k``'s
shapes, its parameter count to the program's tree, the watched view and how
the cell's comparison judges the gate, the two new per-layer readers without a
trace, and a CPU rehearsal of the cell end to end."""

import json
import math
import os
import subprocess
import sys
from pathlib import Path

import jax
import jax.numpy as jnp
import pytest

sys.path.insert(0, str(Path(__file__).resolve().parents[2]))

from benchmarks.drivers import train_counted, train_looped  # noqa: E402
from benchmarks.families import ouro as family  # noqa: E402
from benchmarks.layer_metrics import exit_gate_ms, ut_step_ms  # noqa: E402

ROOT = Path(__file__).resolve().parents[2]
CONFIG = json.loads((ROOT / "benchmarks/configs/ouro-2.6b.json").read_text())
TOKENS = 8192


@pytest.fixture(scope="module")
def shape():
    return family.shape(CONFIG)


def test_the_cut_is_the_stated_one(shape):
    assert (shape["n_layer"], shape["total_ut_steps"], shape["vocab_size"]) == (8, 4, 49152)
    assert (shape["d_model"], shape["n_head"], shape["head_dim"], shape["d_ff"]) == (2048, 16, 128, 5632)
    assert set(CONFIG["reduced"]) == {"num_hidden_layers", "layer_types"} and len(CONFIG["layer_types"]) == 8


def test_the_parameter_count_is_the_configurations_and_the_programs(shape):
    layer = 4 * 2048 * 2048 + 3 * 2048 * 5632 + 4 * 2048
    assert layer == 51_388_416
    count = 8 * layer + 2 * 49152 * 2048 + 2048 + 2048 + 1
    assert count == family.parameter_count(shape) == CONFIG["parameters"]["count"] == 612_438_017
    tree = jax.eval_shape(lambda: family.program_model(CONFIG).init(0))
    assert sum(math.prod(leaf.shape) for leaf in jax.tree.leaves(tree)) == count


def test_the_flops_count_four_passes_and_four_heads(shape):
    pairs = TOKENS * (TOKENS + 1) // 2
    attention = 32 * pairs * 2 * 2 * 2048  # q·kᵀ and p·v, 16 heads of 128, 32 block applications
    layers = 32 * 2 * TOKENS * (4 * 2048 * 2048 + 3 * 2048 * 5632)
    heads = 4 * 2 * TOKENS * 2048 * 49152
    gates = 3 * 2 * TOKENS * 2048
    assert family.attention_train_flops(shape, TOKENS, TOKENS) == 3 * attention
    assert family.train_flops(shape, TOKENS, TOKENS) == 3 * (layers + attention + heads + gates)
    assert 126e12 < family.train_flops(shape, TOKENS, TOKENS) < 127e12
    assert family.attention_train_bytes(shape, TOKENS) == 32 * 12 * TOKENS * 2048 * 2


def test_the_watched_view_holds_the_layers_the_gate_and_the_final_norm():
    """The first and last layer, the final norm, and the gate as it is: its
    bias is judged apart from its weight (``drivers/train_looped.py``)."""
    gate = {"w": jnp.arange(4.0).reshape(4, 1), "b": jnp.array([9.0])}
    tree = {"layers": list(range(8)), "exit_gate": gate, "rms_f": {"scale": 3}, "lm_head": 4, "wte": 5}
    view = family.watched_view(tree)
    assert view == {"layers": {0: 0, 7: 7}, "exit_gate": gate, "rms_f": {"scale": 3}}


def _moments(bias, weight_scale=1.0, terms=4.0):
    """A first-moment tree as ``train_looped`` compares it: a gate, one leaf of
    ``MIN_LEAF`` values, and (on the reference's side) the bias's terms."""
    w = jnp.ones((train_counted.MIN_LEAF, 1)) * weight_scale
    return {"exit_gate": {"w": w, "b": jnp.array([bias])}, "rms_f": {"scale": w[:, 0]},
            train_looped.TERMS: jnp.asarray(terms)}


def test_the_gates_bias_is_judged_in_units_of_its_terms():
    """The bias's error is ``|got - want|`` over the magnitudes of its terms, not
    over itself: a bias whose terms cancel to near zero (0.01 of 4) is read to
    the same scale as one whose terms agree, and a state left unchanged reads
    ``|Σ terms| / Σ |terms|``."""
    want = _moments(0.01)
    check = {"first_moment_tolerance": 0.1, "gate_bias_tolerance": 0.05}
    errors = train_looped.moment_errors(_moments(0.11), want)
    assert float(errors[train_looped.BIAS]) == pytest.approx(0.1 / 4.0)
    assert float(errors["['exit_gate']['w']"]) == 0.0 and "['exit_gate']['b']" in errors
    ok, note = train_looped.judge(check, errors)
    assert ok and note["gate_bias"] == {"error": pytest.approx(0.025), "limit": 0.05}
    unchanged = train_looped.moment_errors(_moments(0.0, weight_scale=0.0), want)
    assert float(unchanged[train_looped.BIAS]) == pytest.approx(0.01 / 4.0)
    assert float(unchanged["['exit_gate']['w']"]) == pytest.approx(1.0) and not train_looped.judge(check, unchanged)[0]


@pytest.mark.parametrize("bias,weight_scale", [(0.31, 1.0), (float("nan"), 1.0), (0.01, 1.2)],
                         ids=["bias_over_its_limit", "bias_nan", "weight_over_the_first_moment_limit"])
def test_either_limit_alone_makes_the_step_not_correct(bias, weight_scale):
    check = {"first_moment_tolerance": 0.1, "gate_bias_tolerance": 0.05}
    ok, note = train_looped.judge(check, train_looped.moment_errors(_moments(bias, weight_scale), _moments(0.01)))
    assert not ok
    assert note["checks"]["gate_bias"] == (weight_scale != 1.0)


@pytest.mark.parametrize("reader", [ut_step_ms, exit_gate_ms])
def test_readers_read_nothing_without_a_device_trace(reader):
    assert reader.read(None, {"peak": None}) is None


def test_the_cell_rehearses_end_to_end_on_the_cpu():
    env = {**os.environ, "JAX_PLATFORMS": "cpu"}
    out = subprocess.run([sys.executable, "benchmarks/run.py", "--workload", "ouro-8k", "--seed", "4300000001",
                          "--seconds", "1", "--trace", "0", "--rehearse"],
                         cwd=ROOT, env=env, capture_output=True, text=True, timeout=600)
    assert out.returncode == 0, out.stderr[-2000:]
    result = json.loads(out.stdout.strip().splitlines()[-1])
    assert result["correct"] and set(result["rehearsal_metrics"]) == {"tok_s_chip", "loss_at_30", "setup_s"}
