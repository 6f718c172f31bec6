"""The Jamba family's own arithmetic held to the issue's hand arithmetic, the
three new per-layer readers and their reduction (``benchmarks/ssm_reduce.py``)
held to a hand-made trace and to one step of ``jamba2-3b-8k`` recorded on the
v5e, and a CPU rehearsal of the cell end to end."""

import gzip
import json
import os
import subprocess
import sys
from pathlib import Path

import jax
import pytest

sys.path.insert(0, str(Path(__file__).resolve().parents[2]))

from benchmarks import harness, ssm_reduce  # noqa: E402
from benchmarks import trace_reduce as tr  # noqa: E402
from benchmarks.families import jamba as family  # noqa: E402

HERE = Path(__file__).parent
ROOT = HERE.parents[1]
TOKENS = 8192


@pytest.fixture(scope="module")
def shape():
    return family.shape(json.loads((ROOT / "benchmarks/configs/jamba2-3b.json").read_text()))


def test_parameter_count_is_the_hand_count(shape):
    mamba_mixer = 26_214_400 + 25_600 + 983_040 + 824_320 + 81_920 + 5_120 + 13_107_200 + 192
    mlp, attention_mixer = 62_914_560, 2 * 6_553_600 + 2 * 327_680
    assert mamba_mixer == 41_241_792
    period = 13 * (mamba_mixer + mlp + 2 * 2560) + (attention_mixer + mlp + 2 * 2560)
    assert period == 1_430_781_376
    assert family.parameter_count(shape) == period + 65_536 * 2560 + 2560 == 1_598_556_096


def test_parameter_count_is_the_programs(shape):
    import jax

    model = family.program_model(json.loads((ROOT / "benchmarks/configs/jamba2-3b.json").read_text()))
    leaves = jax.tree.leaves(jax.eval_shape(lambda: model.init(0)))
    assert sum(leaf.size for leaf in leaves) == family.parameter_count(shape)
    assert {str(leaf.dtype) for leaf in leaves} == {"bfloat16"} and model.config.remat is True


def test_train_flops_are_the_hand_count(shape):
    """13 Mamba layers of 208.08M, the attention layer's 195.30M at 8k and the
    head's 335.54M forward a token; backward twice that."""
    assert family.forward_flops_per_token(shape, TOKENS) == (
        13 * 208_076_800 + 195_297_280 + 335_544_320) == 3_235_840_000
    assert family.train_flops(shape, TOKENS, TOKENS) == 3 * 3_235_840_000 * TOKENS
    # one attention layer, not fourteen: q.k^T and p.v over 20 heads of 128, causal
    assert family.attention_train_flops(shape, TOKENS, TOKENS) == 3 * TOKENS * 2 * TOKENS * 2560
    # six arrays as wide as the query heads and six as wide as the one key-value head
    assert family.attention_train_bytes(shape, TOKENS) == 6 * TOKENS * (2560 + 128) * 2
    # eight [tokens, 5120] bf16 arrays a Mamba layer
    assert family.scan_train_bytes(shape, TOKENS) == 13 * 8 * TOKENS * 5120 * 2


@pytest.mark.parametrize("op_name,expected", [
    ("jit(step)/jvp()/ssm/ssm_scan_fwd/pallas_call", (("ssm_scan_fwd", "fwd"), ("ssm", "fwd"))),
    ("jit(step)/transpose(jvp())/ssm/ssm_scan_bwd/pallas_call", (("ssm_scan_bwd", "bwd"), ("ssm", "bwd"))),
    ("jit(step)/transpose(jvp())/checkpoint/rematted_computation/ssm/ssm_scan_fwd/pallas_call",
     (("ssm_scan_fwd", "bwd"), ("ssm", "bwd"))),
    ("jit(step)/jvp()/checkpoint/ssm/dot_general", (("ssm", "fwd"),)),
    ("jit(step)/transpose(jvp())/mlp/mul;jit(step)/transpose(jvp())/ssm/mul", (("ssm", "bwd"),)),
    ("jit(step)/jvp()/attn/flash_fwd/pallas_call", ()),
    ("params['layers'][3]['ssm']['w_in']", ()),  # whole components only
    ("jit(step)/jvp()/ssm_scan_fwd_helper/mul", ()),
    ("", ()),
])
def test_names_of(op_name, expected):
    assert ssm_reduce.names_of(op_name) == expected


_OP_NAMES = {
    "fusion.1": "jit(step)/jvp()/checkpoint/ssm/dot_general",
    "ssm_scan_fwd.1": "jit(step)/jvp()/checkpoint/ssm/ssm_scan_fwd/pallas_call",
    "fusion.2": "jit(step)/jvp()/checkpoint/mlp/dot_general",
    "ssm_scan_fwd.2": "jit(step)/transpose(jvp())/checkpoint/rematted_computation/ssm/ssm_scan_fwd/pallas_call",
    "ssm_scan_bwd.1": "jit(step)/transpose(jvp())/checkpoint/ssm/ssm_scan_bwd/pallas_call",
    "fusion.3": "jit(step)/transpose(jvp())/checkpoint/ssm/transpose",
    "flash_fwd.1": "jit(step)/jvp()/attn/flash_fwd/pallas_call",
}


def _hand_made(bwd_ns: int) -> list:
    """One device, ns: a mixer's matmul 100, its forward kernel 40, an MLP matmul
    60 (not the mixer's), the recomputed forward kernel 40, the backward kernel
    ``bwd_ns``, a mixer's backward op 30, a flash kernel 20 (not ours)."""
    kernel = "custom-call:tpu_custom_call"
    return [["fusion.1", "fusion:kOutput", 0, 100], ["ssm_scan_fwd.1", kernel, 100, 40],
            ["fusion.2", "fusion:kOutput", 140, 60], ["ssm_scan_fwd.2", kernel, 200, 40],
            ["ssm_scan_bwd.1", kernel, 240, bwd_ns], ["fusion.3", "fusion:kLoop", 400, 30],
            ["flash_fwd.1", kernel, 430, 20]]


def test_hand_made_trace_and_the_three_readers(monkeypatch):
    from benchmarks.layer_metrics import scan_ms, scan_roofline, ssm_ms

    events = {"devices": {"/device:TPU:0": _hand_made(90), "/device:TPU:1": _hand_made(110),
                          "/device:TPU:2": []}}
    table = ssm_reduce.reduce(events, _OP_NAMES, n_steps=2)

    def ms(ns):
        return pytest.approx(ns / 1e6 / 2)

    assert table == {"ssm": {"fwd": ms(140), "bwd": ms(40 + 100 + 30)},
                     "ssm_scan_fwd": {"fwd": ms(40), "bwd": ms(40)},
                     "ssm_scan_bwd": {"fwd": 0.0, "bwd": ms(100)}}
    monkeypatch.setattr(ssm_reduce, "newest", lambda n_steps: table)
    trace = {"n_steps": 2}
    assert ssm_ms.read(trace, {}) == ms(310)
    assert scan_ms.read(trace, {}) == ms(180)
    # 819 bytes at 819e9 bytes/s is 1 ns; the kernels took 90 ns a step
    notes = {"peak": harness.peak_for("TPU v5 lite"), "chips": 1, "scan_bytes_per_step": 819 * 9}
    assert scan_roofline.read(trace, notes) == pytest.approx(10.0)
    assert scan_roofline.read(trace, {**notes, "peak": None}) is None
    assert ssm_ms.read(None, {}) is None


def test_a_program_without_the_names_reads_nothing(monkeypatch):
    """The parent of the PR that brought the names, and every GPT-2 cell: an
    empty table, and every reader ``None`` without raising."""
    from benchmarks.layer_metrics import scan_ms, scan_roofline, ssm_ms

    assert ssm_reduce.reduce({"devices": {"d": _hand_made(90)}}, {}, n_steps=1) == {}
    assert ssm_reduce.reduce({"devices": {}}, {}, n_steps=1) == {}
    monkeypatch.setattr(ssm_reduce, "newest", lambda n_steps: {})
    notes = {"peak": harness.peak_for("TPU v5 lite"), "chips": 1}
    for reader in (ssm_ms, scan_ms, scan_roofline):
        assert reader.read({"n_steps": 5}, notes) is None


def test_recorded_v5e_jamba_step():
    """The first of the five traced steps of ``jamba2-3b-8k`` on the v5e (PR
    27, seed 2700000012): the one device's events as ``trace_reduce.load``
    returned them and the op name of every instruction among them as
    ``hlo_modules`` read it from the same file. Recorded before whole-block
    remat kept the forward kernel's outputs: here it runs once forward and once
    more, recomputed, in the backward pass, 13 calls each way."""
    with gzip.open(HERE / "v5e_jamba_one_step_ssm.json.gz", "rt") as f:
        recorded = json.load(f)
    op_names = recorded["op_names"]
    kernels = {name: op for name, op in op_names.items() if "pallas_call" in op and "/ssm/" in op}
    assert sorted(ssm_reduce.names_of(op) for op in kernels.values()) == sorted(
        13 * [(("ssm_scan_fwd", "fwd"), ("ssm", "fwd")), (("ssm_scan_fwd", "bwd"), ("ssm", "bwd")),
              (("ssm_scan_bwd", "bwd"), ("ssm", "bwd"))])
    table = ssm_reduce.reduce(recorded, op_names, n_steps=1)
    assert table == {"ssm": pytest.approx({"fwd": 97.196019, "bwd": 345.17797}),
                     "ssm_scan_fwd": pytest.approx({"fwd": 30.903811, "bwd": 30.804609}),
                     "ssm_scan_bwd": pytest.approx({"fwd": 0.0, "bwd": 76.651477})}
    # trace_reduce calls every Mosaic kernel "flash": the scan kernels and flash's three are its sum
    kinds = tr.reduce({"devices": recorded["devices"], "host": []}, 1)["kind_ms_per_step"]
    scan = sum(table[k]["fwd"] + table[k]["bwd"] for k in ssm_reduce.KERNELS)
    assert scan == pytest.approx(138.359897)
    assert 17.0 < kinds["flash"] - scan < 18.0  # flash_fwd twice, flash_dq, flash_dkv at head 128


def test_watched_layers_are_the_lowest_of_each_kind(shape):
    layers = [{"attn": 0} if i % shape["attn_layer_period"] == shape["attn_layer_offset"] else {"ssm": 0}
              for i in range(shape["n_layer"])]
    assert family.watched_layers({"layers": layers}) == (0, 7)


@pytest.mark.parametrize("scale,correct", [(1.0, True), (1.04, True), (1.5, False), (0.0, False),
                                           (float("nan"), False)])
def test_first_step_watch_reads_the_first_moment_and_judges_it(scale, correct):
    """A stand-in step whose adam state holds ``scale`` times the reference's
    moment in one leaf: within the limit or not, a state left unchanged (0)
    reads 1, a NaN is not correct; later calls pass through unread."""
    import jax.numpy as jnp
    import optax

    from benchmarks.drivers import train_counted

    optimizer = optax.adamw(3e-4)
    layer = {"w": jnp.arange(1.0, 257.0), "v": jnp.ones(128), "tiny": jnp.ones(16)}
    want = {0: jax.tree.map(lambda a: 0.1 * a, layer)}
    calls = []

    def step(params, opt_state, x, y):
        calls.append(x)
        state = optimizer.update({"layers": [layer]}, opt_state, params)[1]
        state[0].mu["layers"][0]["v"] = scale * state[0].mu["layers"][0]["v"]
        state[0].mu["layers"][0]["tiny"] = 2.0 * state[0].mu["layers"][0]["tiny"]  # under MIN_LEAF: not read
        return params, state, jnp.float32(0.0)

    step.lower = None
    params = {"layers": [jax.tree.map(jnp.zeros_like, layer)]}
    watch = train_counted.FirstStepWatch(step, lambda p, x, y: want)
    _, opt_state, _ = watch(params, optimizer.init(params), 1, None)
    errors = watch.errors
    watch(params, opt_state, 2, None)
    assert calls == [1, 2] and watch.errors is errors
    ok, note = train_counted.judge({"first_moment_tolerance": 0.05}, errors)
    assert ok is correct and note["checks"] == {"first_moment": correct}
    assert note["worst_leaf"] == "[0]['v']"
    if scale == 0.0:
        assert note["first_moment_error"] == pytest.approx(1.0)
    assert note["errors"]["[0]['w']"] == pytest.approx(0.0, abs=1e-6) and sorted(note["errors"]) == ["[0]['v']", "[0]['w']"]


def test_rehearsal_of_the_cell_prints_rehearsal_metrics():
    env = {**os.environ, "JAX_PLATFORMS": "cpu"}
    done = subprocess.run(
        [sys.executable, "benchmarks/run.py", "--workload", "jamba2-3b-8k", "--seed", "2700000301",
         "--seconds", "1", "--trace", "1", "--rehearse"],
        cwd=ROOT, env=env, capture_output=True, text=True, timeout=600)
    assert done.returncode == 0, done.stderr[-2000:]
    lines = [json.loads(line) for line in done.stdout.strip().splitlines() if line.startswith("{")]
    last = lines[-1]
    assert last["correct"] is True and last["failed"] == 0
    first_step = [line for line in lines if line.get("phase") == "check_first_step"]
    assert len(first_step) == 1 and first_step[0]["checks"] == {"first_moment": True}
    assert 0 < first_step[0]["first_moment_error"] <= first_step[0]["limit"]
    assert "metrics" not in last and "step_ms_p50" in last["rehearsal_metrics"]
