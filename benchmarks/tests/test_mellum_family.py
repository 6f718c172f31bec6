"""The Mellum family's own arithmetic held to the issue's hand arithmetic, the
five new per-layer readers and their reduction (``benchmarks/moe_reduce.py``)
held to a hand-made trace and to one step of ``mellum2-8k`` recorded on the v5e,
the first-moment watch over the watched experts, and a CPU rehearsal of the cell
end to end."""

import gzip
import json
import os
import subprocess
import sys
from pathlib import Path

import jax
import jax.numpy as jnp
import pytest

sys.path.insert(0, str(Path(__file__).resolve().parents[2]))

from benchmarks import harness, moe_reduce  # noqa: E402
from benchmarks import trace_reduce as tr  # noqa: E402
from benchmarks.families import mellum as family  # noqa: E402

HERE = Path(__file__).parent
ROOT = HERE.parents[1]
CONFIG = json.loads((ROOT / "benchmarks/configs/mellum2-12b-a2.5b.json").read_text())
TOKENS = 8192


@pytest.fixture(scope="module")
def shape():
    return family.shape(CONFIG)


def test_parameter_count_is_the_hand_count(shape):
    attention = 2 * 2304 * 4096 + 2 * 2304 * 512
    router_and_norms = 2304 * 64 + 2 * 2304
    experts = 64 * 3 * 2304 * 896
    assert (attention, router_and_norms, experts) == (21_233_664, 152_064, 396_361_728)
    layer = attention + router_and_norms + experts
    assert layer == 417_747_456 and 4 * layer == 1_670_989_824
    tables = 2 * 24_576 * 2304 + 2304
    assert tables == 113_248_512
    assert family.parameter_count(shape) == 4 * layer + tables == 1_784_238_336


def test_parameter_count_is_the_programs(shape):
    model = family.program_model(CONFIG)
    leaves = jax.tree.leaves(jax.eval_shape(lambda: model.init(0)))
    assert sum(leaf.size for leaf in leaves) == family.parameter_count(shape)
    assert {str(leaf.dtype) for leaf in leaves} == {"bfloat16"} and model.config.remat is True
    assert model.config.layer_types == ("sliding_attention",) * 3 + ("full_attention",)


def test_the_configuration_keeps_every_published_number():
    rows = [json.loads(line) for line in open("/opt/skills/guides/model-configs/architectures.jsonl")] \
        if Path("/opt/skills/guides/model-configs/architectures.jsonl").exists() else []
    row = next((r for r in rows if r["name"] == "Mellum2-12B-A2.5B-Instruct"), None)
    if row is None:
        pytest.skip("the catalog is not on this machine")
    assert CONFIG["source"] == row["source_url"]
    changed = {k for k, v in row["config"].items() if CONFIG[k] != v}
    assert changed == set(CONFIG["reduced"]) == {"num_hidden_layers", "layer_types", "mlp_layer_types", "vocab_size"}
    assert CONFIG["layer_types"] == row["config"]["layer_types"][:4] and CONFIG["vocab_size"] * 4 == row["config"]["vocab_size"]


@pytest.mark.parametrize("seq,window", [(8, 3), (16, 16), (16, 1), (12, 40), (64, 24)])
def test_window_pair_count_by_brute_force(seq, window):
    sliding = sum(0 <= i - j < window for i in range(seq) for j in range(seq))
    full = sum(0 <= i - j for i in range(seq) for j in range(seq))
    assert family.attention_pairs("sliding_attention", seq, window) == sliding
    assert family.attention_pairs("full_attention", seq, window) == full == seq * (seq + 1) // 2


def test_pairs_at_the_cells_length():
    assert family.attention_pairs("sliding_attention", 8192, 1024) == 7_864_832
    assert family.attention_pairs("full_attention", 8192, 1024) == 33_558_528


def test_flops_by_hand(shape):
    expert_a_token = 8 * 3 * 2 * 2304 * 896
    assert expert_a_token == 8 * 6_193_152 * 2 == 99_090_432
    assert family.expert_train_flops(shape, TOKENS) == 3 * 4 * TOKENS * expert_a_token
    pairs = 3 * 7_864_832 + 33_558_528
    assert family.attention_train_flops(shape, TOKENS, TOKENS) == 3 * pairs * 4 * 4096
    projections = 2 * (2 * 2304 * 4096 + 2 * 2304 * 512 + 2304 * 64)
    forward = TOKENS * (4 * (projections + expert_a_token) + 2 * 2304 * 24_576) + pairs * 4 * 4096
    assert family.train_flops(shape, TOKENS, TOKENS) == 3 * forward
    assert 19.4e12 < family.train_flops(shape, TOKENS, TOKENS) < 19.6e12
    # a full-causal count of the window layers would put flash_roofline at 2.4 times its value
    assert 4 * 33_558_528 / pairs == pytest.approx(2.35, abs=0.01)


def test_bytes_by_hand(shape):
    assert family.attention_train_bytes(shape, TOKENS) == 4 * 6 * TOKENS * (4096 + 512) * 2
    weights, rows = 3 * 64 * 3 * 2304 * 896, 5 * TOKENS * 8 * 2304
    assert family.expert_train_bytes(shape, TOKENS) == 4 * (weights + rows) * 2


def test_watched_layers_and_the_view_of_the_experts_the_reference_chose():
    assert family.watched_layers({"layers": [0, 1, 2, 3]}) == (0, 3) and family.WATCHED_EXPERTS == 7
    layer = {"rms_1": {"scale": jnp.ones(4)}, "moe": {"router": jnp.ones((4, 8)), "w_gate": jnp.arange(8.0).reshape(8, 1, 1),
                                                     "w_up": jnp.zeros((8, 1, 1)), "w_down": jnp.zeros((8, 1, 1))}}
    view = family.watched_view(layer, {"moe": {"experts": {2: None, 5: None, 7: None}}})
    assert {e: m["w_gate"].ravel().tolist() for e, m in view["moe"]["experts"].items()} == {
        2: [2.0], 5: [5.0], 7: [7.0]} and view["moe"]["router"].shape == (4, 8)


@pytest.mark.parametrize("op_name,expected", [
    ("jit(step)/jvp()/checkpoint/mlp/experts/jit(_gmm)/gmm_fwd/pallas_call", ("experts", "gmm_fwd")),
    ("jit(step)/transpose(jvp())/checkpoint/mlp/experts/jit(_gmm_dw)/gmm_dw/pallas_call", ("experts", "gmm_dw")),
    ("jit(step)/transpose(jvp())/checkpoint/rematted_computation/mlp/moe_route/sort", ("moe_route",)),
    ("jit(step)/transpose(jvp())/checkpoint/mlp/moe_combine/gather", ("moe_combine",)),
    ("jit(step)/jvp()/checkpoint/attn/attn_window/jit(_flash_fwd)/flash_fwd/pallas_call", ("flash_in_window",)),
    ("jit(step)/jvp()/checkpoint/attn/attn_full/jit(_flash_fwd)/flash_fwd/pallas_call", ()),
    ("jit(step)/jvp()/checkpoint/attn/attn_window/dot_general", ()),
    ("jit(step)/jvp()/mlp/mul;jit(step)/jvp()/mlp/moe_dispatch/gather", ("moe_dispatch",)),
    ("params['layers'][3]['moe']['experts']", ()),  # whole components only
    ("", ()),
])
def test_names_of(op_name, expected):
    assert moe_reduce.names_of(op_name) == expected


_OP_NAMES = {
    "fusion.1": "jit(step)/jvp()/checkpoint/mlp/moe_route/dot_general",
    "gather.1": "jit(step)/jvp()/checkpoint/mlp/moe_dispatch/gather",
    "gmm_fwd.1": "jit(step)/jvp()/checkpoint/mlp/experts/jit(_gmm)/gmm_fwd/pallas_call",
    "fusion.2": "jit(step)/jvp()/checkpoint/mlp/experts/mul",
    "gmm_dw.1": "jit(step)/transpose(jvp())/checkpoint/mlp/experts/jit(_gmm_dw)/gmm_dw/pallas_call",
    "fusion.3": "jit(step)/transpose(jvp())/checkpoint/mlp/moe_combine/mul",
    "flash_fwd.1": "jit(step)/jvp()/checkpoint/attn/attn_window/jit(_flash_fwd)/flash_fwd/pallas_call",
    "flash_fwd.2": "jit(step)/jvp()/checkpoint/attn/attn_full/jit(_flash_fwd)/flash_fwd/pallas_call",
    "fusion.4": "jit(step)/jvp()/checkpoint/attn/dot_general",
}


def _hand_made(dw_ns: int) -> list:
    """One device, ns: the router 10, a gather 20, a forward kernel 40, the gate
    5, the weight-gradient kernel ``dw_ns``, the combine's backward 15, a
    window layer's flash 30, the full layer's 70, a projection 25 (not ours)."""
    kernel = "custom-call:tpu_custom_call"
    return [["fusion.1", "fusion:kOutput", 0, 10], ["gather.1", "fusion:kLoop", 10, 20],
            ["gmm_fwd.1", kernel, 30, 40], ["fusion.2", "fusion:kLoop", 70, 5], ["gmm_dw.1", kernel, 75, dw_ns],
            ["fusion.3", "fusion:kLoop", 200, 15], ["flash_fwd.1", kernel, 215, 30], ["flash_fwd.2", kernel, 245, 70],
            ["fusion.4", "fusion:kOutput", 315, 25]]


def test_hand_made_trace_and_the_five_readers(monkeypatch):
    from benchmarks.layer_metrics import expert_ms, expert_roofline, flash_window_ms, moe_load_max, moe_ms

    events = {"devices": {"/device:TPU:0": _hand_made(90), "/device:TPU:1": _hand_made(110), "/device:TPU:2": []}}
    table = moe_reduce.reduce(events, _OP_NAMES, n_steps=2)

    def ms(ns):
        return pytest.approx(ns / 1e6 / 2)

    assert table == {"moe_route": ms(10), "moe_dispatch": ms(20), "experts": ms(40 + 5 + 100), "moe_combine": ms(15),
                     "gmm_fwd": ms(40), "gmm_dw": ms(100), "flash_in_window": ms(30)}
    monkeypatch.setattr(moe_reduce, "newest", lambda n_steps: table)
    trace = {"n_steps": 2}
    assert moe_ms.read(trace, {}) == ms(10 + 20 + 145 + 15)
    assert expert_ms.read(trace, {}) == ms(140)
    assert flash_window_ms.read(trace, {}) == ms(30)
    # 197 flops at 197e12 flop/s is 1 ps; the kernels took 70 ns a step
    notes = {"peak": harness.peak_for("TPU v5 lite"), "chips": 1, "expert_flops_per_step": 197 * 7000,
             "expert_bytes_per_step": 8, "moe_load_max": 3.5}
    assert expert_roofline.read(trace, notes) == pytest.approx(10.0)
    assert expert_roofline.read(trace, {**notes, "peak": None}) is None
    assert moe_load_max.read(trace, notes) == 3.5 and moe_load_max.read(None, {}) is None
    assert moe_ms.read(None, {}) is None


def test_a_program_without_the_names_reads_nothing(monkeypatch):
    """The parent of the PR that brought the names, and every other cell: an
    empty table, and every reader ``None`` without raising."""
    from benchmarks.layer_metrics import expert_ms, expert_roofline, flash_window_ms, moe_load_max, moe_ms

    assert moe_reduce.reduce({"devices": {"d": _hand_made(90)}}, {}, n_steps=1) == {}
    assert moe_reduce.reduce({"devices": {}}, {}, n_steps=1) == {}
    monkeypatch.setattr(moe_reduce, "newest", lambda n_steps: {})
    notes = {"peak": harness.peak_for("TPU v5 lite"), "chips": 1}
    for reader in (moe_ms, expert_ms, expert_roofline, flash_window_ms, moe_load_max):
        assert reader.read({"n_steps": 5}, notes) is None


@pytest.mark.parametrize("scale,spoiled,correct", [(1.0, (), True), (1.04, (4, 6, 7), True), (1.5, (6,), True),
                                                   (1.5, (4, 6), True), (1.5, (2, 4, 6), False), (0.0, (0, 2, 4), False),
                                                   (float("nan"), (6,), False)])
def test_first_step_watch_judges_the_watched_experts_by_their_median(scale, spoiled, correct):
    """A stand-in step whose adam state holds ``scale`` times the reference's
    moment in the ``spoiled`` watched experts' gate matrices and garbage in an
    expert that is not watched: two of five experts off (a frequent token whose
    routing ties) are still correct, three are not, a NaN never is; the watch
    also keeps the weights the last call returned."""
    import optax

    from benchmarks.drivers import train_counted, train_experts

    optimizer = optax.adamw(3e-4)
    layer = {"moe": {"router": jnp.ones((16, 8)), "w_gate": jnp.ones((8, 16, 8)) * jnp.arange(1.0, 9.0)[:, None, None],
                     "w_up": jnp.ones((8, 16, 8)), "w_down": jnp.ones((8, 8, 16))}}
    chosen = {"moe": {"experts": dict.fromkeys((0, 2, 4, 6, 7))}}
    want = {0: jax.tree.map(lambda a: 0.1 * a, family.watched_view(layer, chosen))}

    def step(params, opt_state, x, y):
        state = optimizer.update({"layers": [layer]}, opt_state, params)[1]
        gate = state[0].mu["layers"][0]["moe"]["w_gate"]
        state[0].mu["layers"][0]["moe"]["w_gate"] = gate.at[jnp.asarray(spoiled, int)].multiply(scale).at[5].set(1e9)  # 5: not chosen
        return {"layers": [x]}, state, jnp.float32(0.0)

    step.lower = None
    params = {"layers": [jax.tree.map(jnp.zeros_like, layer)]}
    watch = train_experts.FirstStepWatch(step, lambda p, x, y: want, family.watched_view)
    _, opt_state, _ = watch(params, optimizer.init(params), "first", None)
    errors = watch.errors
    watch(params, opt_state, "second", None)
    assert watch.errors is errors and watch.params == {"layers": ["second"]}
    ok, note = train_experts.judge({"first_moment_tolerance": 0.05}, errors)
    assert ok is correct and note["checks"] == {"first_moment": correct}
    if scale in (1.5, 0.0):
        assert note["largest_single_expert"] == pytest.approx(abs(scale - 1.0), rel=1e-3)
    assert note["judged"]["[0]['moe']['experts'][median]['w_up']"] == pytest.approx(0.0, abs=1e-6)
    assert sorted(note["judged"]) == ["[0]['moe']['experts'][median]['w_down']", "[0]['moe']['experts'][median]['w_gate']",
                                      "[0]['moe']['experts'][median]['w_up']", "[0]['moe']['router']"]


def test_rehearsal_of_the_cell_prints_rehearsal_metrics():
    env = {**os.environ, "JAX_PLATFORMS": "cpu"}
    done = subprocess.run(
        [sys.executable, "benchmarks/run.py", "--workload", "mellum2-8k", "--seed", "3500000301",
         "--seconds", "1", "--trace", "1", "--rehearse"],
        cwd=ROOT, env=env, capture_output=True, text=True, timeout=900)
    assert done.returncode == 0, done.stderr[-2000:]
    lines = [json.loads(line) for line in done.stdout.strip().splitlines() if line.startswith("{")]
    result, notes = lines[-1], {line["phase"]: line for line in lines[:-1] if "phase" in line}
    assert "rehearsal_metrics" in result and "metrics" not in result and result["device"]["platform"] == "cpu"
    assert result["rehearsal_metrics"]["moe_load_max"]["value"] >= 1.0
    assert sum(notes["routing"]["pairs_per_expert"]) == 2 * 128 * 2  # rows x seq x top-k, none dropped
    assert set(notes["check_first_step"]["judged"]) >= {"[0]['moe']['router']", "[3]['moe']['experts'][median]['w_down']",
                                                       "[3]['attn']['wq']"}
    assert "[3]['moe']['experts'][7]['w_down']" in notes["check_first_step"]["errors"]


def test_recorded_v5e_mellum_step():
    """The first of the five traced steps of ``mellum2-8k`` on the v5e (PR 35,
    seed 3500000011, tile 512): the one device's events as ``trace_reduce.load``
    returned them and the op name of every instruction among them as
    ``hlo_modules`` read it from the same file. A layer runs its three forward
    grouped matmuls twice (whole-block remat), three ``gmm_dx`` and six ``gmm_dw``
    (two column blocks a leaf); a sliding layer its flash forward twice and the
    one backward kernel."""
    with gzip.open(HERE / "v5e_mellum_one_step.json.gz", "rt") as f:
        recorded = json.load(f)
    op_names = recorded["op_names"]
    kernels = [moe_reduce.names_of(op) for op in op_names.values() if "pallas_call" in op]
    assert sorted(k for k in kernels if k) == sorted(
        4 * (6 * [("experts", "gmm_fwd")] + 3 * [("experts", "gmm_dx")] + 6 * [("experts", "gmm_dw")])
        + 3 * 3 * [("flash_in_window",)])
    assert kernels.count(()) == 3  # the full layer's flash calls
    table = moe_reduce.reduce(recorded, op_names, n_steps=1)
    assert table == pytest.approx({"experts": 122.065229, "flash_in_window": 45.137255, "gmm_dw": 28.316313,
                                   "gmm_dx": 25.424334, "gmm_fwd": 50.675164, "moe_combine": 35.053142,
                                   "moe_dispatch": 17.388489, "moe_route": 5.360547})
    # trace_reduce calls every Mosaic kernel "flash": the grouped matmuls and flash's two are its sum
    kinds = tr.reduce({"devices": recorded["devices"], "host": []}, 1)["kind_ms_per_step"]
    gmm = sum(table[k] for k in moe_reduce.KERNELS)
    assert gmm == pytest.approx(104.415811)
    assert 67.0 < kinds["flash"] - gmm < 68.5 and table["flash_in_window"] / (kinds["flash"] - gmm) == pytest.approx(0.666, abs=0.01)
