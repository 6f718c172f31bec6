"""``benchmarks/name_reduce.py`` held to the op names the TPU's compiler gave
``mellum2-8k``'s step (the recorded steps of PR 35 and PR 37), to the readers it
should agree with, to the fused computations of a trace file recorded in the
sandbox, and to a hand-made program with a fusion whose body holds two names."""

import gzip
import importlib
import io
import json
import sys
from pathlib import Path

import pytest

sys.path.insert(0, str(Path(__file__).resolve().parents[2]))

from benchmarks import moe_reduce, name_reduce as nr  # noqa: E402
from benchmarks import scope_reduce as sr  # noqa: E402

HERE = Path(__file__).parent
NEW_METRICS = ("remat_ms", "moe_gather_ms", "kv_repeat_ms", "norm_ms", "rope_ms", "ssm_conv_ms")


def _recorded(name: str) -> dict:
    with gzip.open(HERE / name, "rt") as f:
        return json.load(f)


@pytest.mark.parametrize("op_name,expected", [
    # as the TPU's compiler names them in `mellum2-8k`'s step (v5e_mellum_one_step.json.gz)
    ("jit(step)/jvp()/checkpoint/attn/dot_general",
     {"attn": "fwd", "checkpoint": "fwd", "dot_general": "fwd"}),
    ("jit(step)/transpose(jvp())/checkpoint/rematted_computation/attn/rsqrt",
     {"attn": "remat", "rematted_computation": "remat"}),
    ("jit(step)/transpose(jvp())/checkpoint/mlp/moe_combine/gather",
     {"mlp": "bwd", "moe_combine": "bwd"}),
    ("jit(step)/transpose(jvp())/checkpoint/rematted_computation/mlp/experts/gmm_fwd/pallas_call",
     {"mlp": "remat", "experts": "remat", "gmm_fwd": "remat"}),
    ("jit(step)/transpose(jvp())/checkpoint/attn/attn_window/flash_dkv/pallas_call",
     {"attn": "bwd", "attn_window": "bwd", "flash_dkv": "bwd"}),
    # as this PR's names nest
    ("jit(step)/transpose(jvp())/checkpoint/rematted_computation/attn/kv_repeat/broadcast_in_dim",
     {"attn": "remat", "kv_repeat": "remat"}),
    ("jit(step)/jvp()/loss_head/normalize/mul", {"loss_head": "fwd", "normalize": "fwd"}),
    ("jit(step)/optimizer/add", {"optimizer": "fwd"}),
    # a toy step holds the scope inside the wrapper; what precedes the transpose is not backward
    ("jit(step)/transpose(jvp(block))/mlp/mul", {"jit": "fwd", "step": "fwd", "block": "bwd", "mlp": "bwd"}),
    # instructions XLA merged: for each name the first part that holds it decides
    ("jit(step)/transpose(jvp())/mlp/mul;jit(step)/optimizer/add;jit(step)/jvp()/mlp/mul",
     {"mlp": "bwd", "optimizer": "fwd", "add": "fwd"}),
])
def test_directions_of(op_name, expected):
    found = dict(nr.directions_of(op_name))
    assert {name: found.get(name) for name in expected} == expected


@pytest.mark.parametrize("op_name", [
    "params['layers'][7]['attn']['wo']",        # an argument's name is one component
    "jit(step)/jvp()/attention/normalized/mul",  # whole components only
    "",
])
def test_whole_components_only(op_name):
    assert not {"attn", "normalize", "layers"}.intersection(dict(nr.directions_of(op_name)))


def test_the_three_directions_sum_to_what_moe_reduce_reads():
    """The recorded step of PR 35: every name of ``moe_reduce`` reads the same here,
    and the directions split it (the combine is kept, not computed again: its
    backward gathers need the plan's integers alone)."""
    recorded = _recorded("v5e_mellum_one_step.json.gz")
    table = nr.reduce(recorded, recorded["op_names"], {}, n_steps=1)
    old = moe_reduce.reduce(recorded, recorded["op_names"], n_steps=1)
    for name in (*moe_reduce.SCOPES, *moe_reduce.KERNELS):
        row = table[name]
        assert row["fwd"] + row["remat"] + row["bwd"] == pytest.approx(old[name], abs=1e-9)
        assert row["own"] == pytest.approx(old[name], abs=1e-9) and row["guest"] == 0.0
    assert table["moe_combine"]["remat"] == 0.0 and table["moe_dispatch"]["remat"] == pytest.approx(2.793229)
    assert table["gmm_fwd"] == pytest.approx({"fwd": 25.3365, "remat": 25.338664, "bwd": 0.0,
                                              "own": 50.675164, "guest": 0.0})
    assert table["gmm_dx"]["bwd"] == table["gmm_dx"]["own"]
    # a kernel counts under its own name and under every scope round it; scope_reduce takes it out
    scopes = sr.reduce(recorded, recorded["op_names"], n_steps=1)["scope_ms_per_step"]
    kernels = sum(table[k]["own"] for k in ("flash_fwd", "flash_dkv"))
    assert table["attn"]["own"] - kernels == pytest.approx(scopes["attn"]["fwd"] + scopes["attn"]["bwd"])
    assert table["mlp"]["own"] == pytest.approx(scopes["mlp"]["fwd"] + scopes["mlp"]["bwd"])
    # the recomputed forward, every instruction of it: what `remat_ms` reads
    remat = table[nr.REMAT]
    assert remat["remat"] == remat["own"] == pytest.approx(65.367307) and remat["fwd"] == remat["bwd"] == 0.0
    assert remat["own"] == pytest.approx(table["attn"]["remat"] + table["mlp"]["remat"])


def test_fusion_bodies_from_an_xplane_file():
    """``cpu_toy_step.xplane.pb.gz`` (``test_scope_reduce.py`` says what it holds):
    the walk finds each fusion's body by ``called_computation_ids``, in
    ``hlo_modules``'s order, and the body's instructions keep their own op names:
    the weight gradient's fusion, rooted in ``mlp``, took the loss's backward in."""
    data = gzip.decompress((HERE / "cpu_toy_step.xplane.pb.gz").read_bytes())
    modules, bodies = sr.hlo_modules(data), nr.fusion_bodies(data)
    assert [name for name, _ in bodies] == [name for name, _ in modules] and len(bodies) == 4
    op_names, step = dict(modules)["jit_step"], dict(bodies)["jit_step"]
    assert sorted(step) == ["multiply_add_fusion", "multiply_subtract_fusion", "reduce_multiply_fusion",
                            "wrapped_multiply", "wrapped_reduce-window", "wrapped_tanh"]
    assert step["multiply_subtract_fusion"] == ["param_0.3", "param_1.4", "constant.4", "mul.1", "mul.0", "sub.0"]
    assert {op_names[member] for member in step["multiply_subtract_fusion"]} == {
        "", "jit(step)/optimizer/mul", "jit(step)/optimizer/sub"}
    guests = nr.guest_names(op_names, step)
    assert guests["multiply_subtract_fusion"] == {"mul"}  # `optimizer` is the root's own: no guest
    assert "loss_head" in guests["multiply_add_fusion"] and "mlp" not in guests["multiply_add_fusion"]
    events = {"devices": {"d": [["multiply_add_fusion", "fusion:kLoop", 0, 1]]}}
    assert nr.step_program(data, events) == (op_names, step)
    assert nr.step_program(b"", events) == ({}, {})


_OP_NAMES = {
    "fusion.1": "jit(step)/jvp()/attn/dot_general",                     # a matmul that took the norm's scale in
    "mul.1": "jit(step)/jvp()/attn/normalize/mul", "dot.1": "jit(step)/jvp()/attn/dot_general",
    "fusion.2": "jit(step)/jvp()/attn/normalize/reduce_sum",            # the norm's own reduction
    "sum.1": "jit(step)/jvp()/attn/normalize/reduce_sum",
    "fusion.3": "jit(step)/transpose(jvp())/rematted_computation/attn/kv_repeat/broadcast_in_dim",
    "bcast.1": "jit(step)/transpose(jvp())/rematted_computation/attn/kv_repeat/broadcast_in_dim",
    "fusion.4": "jit(step)/transpose(jvp())/attn/dot_general",          # a backward matmul holding two guests
    "rot.1": "jit(step)/transpose(jvp())/rematted_computation/attn/rope/mul",
    "sum.2": "jit(step)/transpose(jvp())/attn/kv_repeat/reduce_sum", "dot.2": "jit(step)/transpose(jvp())/attn/dot_general",
    "flash_fwd.1": "jit(step)/jvp()/attn/flash_fwd/pallas_call",
}
_BODIES = {"fusion.1": ["mul.1", "dot.1"], "fusion.2": ["sum.1"], "fusion.3": ["bcast.1"],
           "fusion.4": ["rot.1", "sum.2", "dot.2"]}


def _hand_made(matmul_ns: int) -> list:
    """One device's ``[name, category, start, duration]``: fusion.1 ``matmul_ns``,
    fusion.2 40, flash_fwd.1 50, fusion.3 30, fusion.4 200, copy.1 (no op name) 10."""
    rows = [["fusion.1", "fusion:kOutput", matmul_ns], ["fusion.2", "fusion:kLoop", 40],
            ["flash_fwd.1", "custom-call:tpu_custom_call", 50], ["fusion.3", "fusion:kLoop", 30],
            ["fusion.4", "fusion:kOutput", 200], ["copy.1", "copy", 10]]
    out, at = [], 0
    for name, category, ns in rows:
        out.append([name, category, at, ns])
        at += ns
    return out


def test_own_and_guest_time_on_a_hand_made_program(monkeypatch):
    """Two devices that differ in the first matmul (90 and 110 ns: the median is
    100), two steps. ``normalize`` owns its reduction and is a guest of the whole
    matmul that took its scale in; ``fusion.4`` holds two names and each gets it
    whole; a name that is only ever a guest (``rope``) reads 0, not nothing."""
    events = {"devices": {"/device:TPU:0": _hand_made(90), "/device:TPU:1": _hand_made(110),
                          "/device:TPU:2": []}, "host": []}
    table = nr.reduce(events, _OP_NAMES, _BODIES, n_steps=2)

    def row(fwd=0, remat=0, bwd=0, guest=0):
        return pytest.approx({"fwd": fwd / 2e6, "remat": remat / 2e6, "bwd": bwd / 2e6,
                              "own": (fwd + remat + bwd) / 2e6, "guest": guest / 2e6})

    assert table["normalize"] == row(fwd=40, guest=100)
    assert table["kv_repeat"] == row(remat=30, guest=200)
    assert table["rope"] == row(guest=200)
    assert table["attn"] == row(fwd=100 + 40 + 50, remat=30, bwd=200)  # never a guest: every root holds it
    assert table["flash_fwd"] == row(fwd=50)
    assert table[nr.REMAT] == row(remat=30, guest=200)
    assert "copy" not in table and "layers" not in table
    swallowed = nr.hosts(events, _OP_NAMES, _BODIES, n_steps=2)
    assert swallowed["normalize"] == [["fusion", "jit(step)/jvp()/attn/dot_general", pytest.approx(100 / 2e6)]]
    assert sorted(swallowed) == ["kv_repeat", "mul", "normalize", "reduce_sum", "rematted_computation", "rope"]

    monkeypatch.setattr(nr, "newest", lambda n_steps: table)
    trace = {"n_steps": 2}
    assert nr.ms(trace, ("normalize",)) == pytest.approx(40 / 2e6)
    assert nr.ms(trace, ("normalize", "kv_repeat")) == pytest.approx(70 / 2e6)
    assert nr.ms(trace, ("attn",), "remat") == pytest.approx(30 / 2e6)
    assert nr.ms(trace, ("rope",)) == 0.0
    assert nr.ms(trace, ("ssm_conv",)) is None and nr.ms(trace, ("ssm_conv", "rope")) == 0.0
    assert nr.ms(None, ("normalize",)) is None


@pytest.mark.parametrize("metric", NEW_METRICS)
def test_a_reader_says_nothing_without_its_name_or_without_a_device_trace(monkeypatch, metric):
    """The parent of this PR under this PR's benchmark files: its program sets none
    of the four names, so four readers return ``None`` on its trace, and all six
    without a device trace (the CPU rehearsal); ``remat_ms`` and ``moe_gather_ms``
    read names the parent's program already holds."""
    reader = importlib.import_module(f"benchmarks.layer_metrics.{metric}")
    assert reader.read(None, {}) is None
    assert nr.reduce({"devices": {}}, {}, {}, n_steps=1) == {}
    monkeypatch.setattr(nr, "newest", lambda n_steps: {})
    assert reader.read({"n_steps": 1}, {}) is None
    recorded = _recorded("v5e_mellum_one_step.json.gz")  # PR 35's step: the names of PR 35, not this PR's
    table = nr.reduce(recorded, recorded["op_names"], {}, n_steps=1)
    monkeypatch.setattr(nr, "newest", lambda n_steps: table)
    expected = {"remat_ms": 65.367307, "moe_gather_ms": 35.053142 + 17.388489}.get(metric)
    assert reader.read({"n_steps": 1}, {}) == (expected and pytest.approx(expected))


def test_recorded_v5e_mellum_steps_read_what_the_chip_run_printed(monkeypatch):
    """The five traced steps of ``mellum2-8k`` on the v5e (PR 37, seed 3700000012):
    the one device's events as ``trace_reduce.load`` returned them, the op name of
    every instruction among them and of every named instruction inside their
    fusions, and each fusion's body, as ``step_program`` read them from the same
    file. The values are the run's own result line and ``name_reduce`` note."""
    recorded = _recorded("v5e_mellum_five_steps_names.json.gz")
    table = nr.reduce(recorded, recorded["op_names"], recorded["bodies"], recorded["n_steps"])
    monkeypatch.setattr(nr, "newest", lambda n_steps: table)
    trace = {"n_steps": recorded["n_steps"]}
    printed = {"remat_ms": 56.1693836, "moe_gather_ms": 52.4292432, "kv_repeat_ms": 2.2444566,
               "norm_ms": 1.7122618, "rope_ms": 9.4544734, "ssm_conv_ms": None}
    for metric, value in printed.items():
        read = importlib.import_module(f"benchmarks.layer_metrics.{metric}").read(trace, {})
        assert read == (value and pytest.approx(value, abs=1e-7)), metric
    # own and guest as the note gave them: the norms and half the rotation run inside their neighbours
    assert table["normalize"] == pytest.approx({"fwd": 0.2896636, "remat": 0.0822534, "bwd": 1.3403448,
                                                "own": 1.7122618, "guest": 34.4156886})
    assert table["rope"] == pytest.approx({"fwd": 2.710446, "remat": 2.713983, "bwd": 4.0300444,
                                           "own": 9.4544734, "guest": 8.8931542})
    assert table["kv_repeat"]["guest"] == 0.0 and table[nr.REMAT]["guest"] == pytest.approx(10.5296428)
    assert [host[:2] for host in nr.hosts(recorded, recorded["op_names"], recorded["bodies"], 5)["normalize"]] == [
        ["fusion", "jit(step)/jvp()/loss_head/while/body/closed_call/dot_general"],  # the head's logits matmul
        ["fusion", "jit(step)/jvp()/attn/dot_general"], ["convert_bitcast_fusion", "jit(step)/jvp()/attn/dot_general"]]
    # the accepted readers' sums, from the new one: `moe_ms` by its four names, the kernels by theirs
    old = moe_reduce.reduce(recorded, recorded["op_names"], recorded["n_steps"])
    assert sum(table[name]["own"] for name in moe_reduce.SCOPES) == pytest.approx(179.8544684, abs=1e-6)
    assert printed["moe_gather_ms"] + table["moe_route"]["own"] + table["experts"]["own"] == pytest.approx(
        sum(old[name] for name in moe_reduce.SCOPES), abs=0.01)
    assert sum(table[name]["own"] for name in moe_reduce.KERNELS) == pytest.approx(104.416775, abs=1e-6)
    # every direction of the recomputed forward is `remat`, and it is the two scopes' recomputed parts
    assert table[nr.REMAT]["own"] == pytest.approx(table["attn"]["remat"] + table["mlp"]["remat"])


def test_describe_prints_the_table(tmp_path, monkeypatch):
    events = {"devices": {"/device:TPU:0": _hand_made(100)}, "host": []}
    monkeypatch.setattr(nr, "_parse", lambda trace_dir: (events, _OP_NAMES, _BODIES))
    printed = io.StringIO()
    nr.describe(str(tmp_path), 1, ["normalize", "ssm_conv"], out=printed)
    out = printed.getvalue().splitlines()
    assert out[0].split() == ["name", "fwd", "ms", "remat", "ms", "bwd", "ms", "guest", "ms"]
    assert out[1].split() == ["normalize", "0.000", "0.000", "0.000", "0.000"]  # 40 ns: under the print's digits
    assert out[2].split()[:2] == ["guest", "of"] and out[2].split()[-2:] == ["fusion", "jit(step)/jvp()/attn/dot_general"]
    assert out[3].startswith("ssm_conv") and "no op name" in out[3]
    printed = io.StringIO()
    nr.describe(str(tmp_path), 1, out=printed)
    assert [line.split()[0] for line in printed.getvalue().splitlines()[1:] if not line.startswith("  ")] == [
        "attn", "kv_repeat", "rope", "normalize", nr.REMAT, "flash_fwd"]
