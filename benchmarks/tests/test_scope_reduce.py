"""``benchmarks/scope_reduce.py`` held to a trace file recorded in the sandbox,
to the op names the TPU's compiler gave the cells' steps, to a hand-made
trace, and to one step of ``gpt2l-1k-dp4`` recorded on the v5e."""

import gzip
import json
import sys
from pathlib import Path

import pytest

sys.path.insert(0, str(Path(__file__).resolve().parents[2]))

from benchmarks import scope_reduce as sr  # noqa: E402
from benchmarks import trace_reduce as tr  # noqa: E402

HERE = Path(__file__).parent


def test_wire_walk_reads_the_hlo_from_an_xplane_file():
    """``cpu_toy_step.xplane.pb.gz``: two calls of a jitted toy step under
    ``jax.profiler.start_trace`` on the sandbox's CPU (jax 0.9.0). The step is
    ``value_and_grad`` of ``mean(tanh(x @ w) ** 2)`` with the matmul and tanh
    under ``named_scope("mlp")``, the mean under ``"loss_head"`` and
    ``w - 0.1 * g`` under ``"optimizer"``. The file's ``/host:metadata`` plane
    holds one ``Hlo Proto`` a program: the step and three transfer programs."""
    modules = sr.hlo_modules(gzip.decompress((HERE / "cpu_toy_step.xplane.pb.gz").read_bytes()))
    assert [name for name, _ in modules].count("jit_step") == 1
    assert len(modules) == 4
    step = dict(modules)["jit_step"]
    assert len(step) == 46
    assert step["dot_general.2"] == "jit(step)/jvp(mlp)/dot_general"
    assert step["multiply_add_fusion"] == "jit(step)/transpose(jvp(mlp))/add_any"
    assert step["multiply_subtract_fusion"] == "jit(step)/optimizer/sub"
    assert step["broadcast.1"] == ("jit(step)/transpose(jvp(loss_head))/mul;"
                                   "jit(step)/transpose(jvp(loss_head))/broadcast_in_dim")
    assert step["tuple.1"] == ""  # an instruction with no metadata
    assert sr.scope_of(step["dot"]) == ("mlp", "bwd")
    # the step is the program that covers the events, whatever else the file holds
    events = {"devices": {"d": [["dot_general.2", "", 0, 1], ["wrapped_tanh", "", 1, 1],
                                ["param_0", "", 2, 1]]}}  # param_0 is a transfer program's
    assert sr.pick_module(modules, events) is step


def test_no_metadata_plane_gives_no_module():
    assert sr.hlo_modules(b"") == []
    assert sr.pick_module([], {"devices": {}}) == {}


@pytest.mark.parametrize("op_name,expected", [
    # as the TPU's compiler names them in the cells' steps (described v5e:2x2)
    ("jit(step)/jvp()/attn/flash_fwd/pallas_call", ("flash_fwd", "fwd")),
    ("jit(step)/transpose(jvp())/attn/flash_dq/pallas_call", ("flash_dq", "bwd")),
    ("jit(step)/transpose(jvp())/shard_map/attn/flash_dkv/pallas_call", ("flash_dkv", "bwd")),
    ("jit(step)/optimizer/add", ("optimizer", "fwd")),
    ("jit(step)/jvp()/loss_head/while/body/closed_call/dot_general", ("loss_head", "fwd")),
    ("jit(step)/transpose(jvp())/loss_head/while/body/closed_call/dot_general", ("loss_head", "bwd")),
    ("jit(step)/transpose(jvp())/mlp/dot_general", ("mlp", "bwd")),
    ("jit(step)/transpose(jvp())/attn/bsd,dke->bske/dot_general", ("attn", "bwd")),
    ("jit(step)/transpose(jvp())/embed/scatter-add", ("embed", "bwd")),
    ("jit(step)/jvp()/checkpoint/rematted_computation/mlp/mul", ("mlp", "fwd")),
    # a toy step holds the scope inside the wrapper
    ("jit(step)/jvp(loss_head)/while", ("loss_head", "fwd")),
    ("jit(step)/transpose(jvp(block))/mlp/mul", ("mlp", "bwd")),
    # instructions XLA merged: the first name that holds a scope decides
    ("jit(step)/transpose(jvp())/mlp/mul;jit(step)/optimizer/add", ("mlp", "bwd")),
    ("jit(step)/reduce_sum;jit(step)/optimizer/add", ("optimizer", "fwd")),
    # whole components only: an argument's name is one component
    ("params['layers'][7]['attn']['wo']", None),
    ("jit(step)/transpose(jvp())/shard_map/psum", None),
    ("jit(step)/jvp()/attention/mul", None),
    ("", None),
])
def test_scope_of(op_name, expected):
    assert sr.scope_of(op_name) == expected


_OP_NAMES = {
    "fusion.1": "jit(step)/jvp()/attn/bsd,dke->bske/dot_general",
    "flash_fwd.1": "jit(step)/jvp()/attn/flash_fwd/pallas_call",
    "while.1": "jit(step)/jvp()/loss_head/while",
    "fusion.2": "jit(step)/jvp()/loss_head/while/body/closed_call/dot_general",
    "fusion.3": "jit(step)/transpose(jvp())/loss_head/while/body/closed_call/dot_general",
    "flash_dq.1": "jit(step)/transpose(jvp())/attn/flash_dq/pallas_call",
    "flash_dkv.1": "jit(step)/transpose(jvp())/attn/flash_dkv/pallas_call",
    "fusion.4": "params['layers'][7]['attn']['wo']",
    "fusion.5": "jit(step)/transpose(jvp())/mlp/mul;jit(step)/optimizer/add",
    "fusion.6": "jit(step)/reduce_sum;jit(step)/optimizer/add",
    "all-reduce.1": "jit(step)/transpose(jvp())/shard_map/psum",
}


def _hand_made(dkv_ns: int, shift: int) -> list:
    """One device's ``[name, category, start, duration]`` in ns::

        0    fusion.1 100        attn fwd
        100  flash_fwd.1 50      flash_fwd (nests inside attn: the kernel's name wins)
        150  while.1 250         loss_head fwd; self time 250 - 60 - 30 - 80 = 80
        160    fusion.2 60       loss_head fwd (the loop's body carries its own names)
        230    copy.1 30         no op name at all: unscoped
        300    fusion.3 80       loss_head bwd
        400  flash_dq.1 70       flash_dq
        470  flash_dkv.1 dkv_ns  flash_dkv
        600  fusion.4 40         named after a parameter: unscoped
        640  fusion.5 50         merged, mlp bwd named first
        690  fusion.6 50         merged, the first name holds no scope: optimizer
        740  all-reduce.1 30     the shard_map's psum: unscoped
    """
    rows = [["fusion.1", "fusion:kOutput", 0, 100], ["flash_fwd.1", "custom-call:tpu_custom_call", 100, 50],
            ["while.1", "while", 150, 250], ["fusion.2", "fusion:kOutput", 160, 60],
            ["copy.1", "copy", 230, 30], ["fusion.3", "fusion:kOutput", 300, 80],
            ["flash_dq.1", "custom-call:tpu_custom_call", 400, 70],
            ["flash_dkv.1", "custom-call:tpu_custom_call", 470, dkv_ns],
            ["fusion.4", "fusion:kLoop", 600, 40], ["fusion.5", "fusion:kLoop", 640, 50],
            ["fusion.6", "fusion:kLoop", 690, 50], ["all-reduce.1", "all-reduce", 740, 30]]
    return [[n, c, s + shift, d] for n, c, s, d in rows]


def test_hand_made_trace():
    """Two devices that differ in the dkv kernel alone (90 and 110 ns: the
    median of two is 100), two steps in the trace."""
    events = {"devices": {"/device:TPU:0": _hand_made(90, 0), "/device:TPU:1": _hand_made(110, 7),
                          "/device:TPU:2": []}, "host": []}
    out = sr.reduce(events, _OP_NAMES, n_steps=2)

    def ms(ns):
        return pytest.approx(ns / 1e6 / 2)

    assert out["scope_ms_per_step"] == {
        "attn": {"fwd": ms(100), "bwd": 0.0},
        "flash_fwd": {"fwd": ms(50), "bwd": 0.0},
        "loss_head": {"fwd": ms(80 + 60), "bwd": ms(80)},
        "flash_dq": {"fwd": 0.0, "bwd": ms(70)},
        "flash_dkv": {"fwd": 0.0, "bwd": ms(100)},
        "mlp": {"fwd": 0.0, "bwd": ms(50)},
        "optimizer": {"fwd": ms(50), "bwd": 0.0},
    }
    assert out["unscoped_ms_per_step"] == ms(30 + 40 + 30)
    assert out["busy_ms_per_step"] == ms(100 + 50 + 250 + 70 + 100 + 40 + 50 + 50 + 30)
    assert out["top_unscoped"] == [["fusion", ms(40)], ["copy", ms(30)], ["all-reduce", ms(30)]]


def test_a_program_without_names_is_all_unscoped():
    out = sr.reduce({"devices": {"d": _hand_made(90, 0)}}, {}, n_steps=1)
    assert out["scope_ms_per_step"] == {}
    assert out["unscoped_ms_per_step"] == pytest.approx(out["busy_ms_per_step"])
    with pytest.raises(ValueError, match="no device operation"):
        sr.reduce({"devices": {"d": []}}, {}, n_steps=1)


def test_scoped_pct_reads_zero_where_the_join_finds_no_name(monkeypatch):
    """A device trace whose program (or whose file) carries no name at all: the
    five readers by scope say nothing, the guard says 0 and not nothing. Only a
    run with no device trace (the CPU rehearsal) leaves the guard out."""
    from benchmarks.layer_metrics import scoped_pct, xent_ms

    table = sr.reduce({"devices": {"d": _hand_made(90, 0)}}, {}, n_steps=1)
    monkeypatch.setattr(sr, "newest", lambda n_steps: table)
    assert scoped_pct.read({"n_steps": 1}, []) == 0.0
    assert xent_ms.read({"n_steps": 1}, []) is None
    assert scoped_pct.read(None, []) is None
    named = sr.reduce({"devices": {"d": _hand_made(90, 0)}}, _OP_NAMES, n_steps=1)
    monkeypatch.setattr(sr, "newest", lambda n_steps: named)
    assert scoped_pct.read({"n_steps": 1}, []) == pytest.approx(100 * (1 - 100 / 730))


def test_recorded_v5e_dp4_step():
    """One step of two of the four devices of ``gpt2l-1k-dp4``, recorded on the
    v5e (PR 25): the events as ``trace_reduce.load`` returned them, and the op
    name of every instruction among them as ``hlo_modules`` read it from the
    same file. Under the shard_map the wrappers are empty (``jvp()``) and the
    scope follows a ``shard_map`` component."""
    with gzip.open(HERE / "v5e_dp4_one_step_scopes.json.gz", "rt") as f:
        recorded = json.load(f)
    op_names = recorded["op_names"]
    assert op_names["flash_dq.36"] == "jit(step)/transpose(jvp())/shard_map/attn/flash_dq/pallas_call"
    assert op_names["all-reduce.110"] == "jit(step)/transpose(jvp())/shard_map/psum"
    out = sr.reduce(recorded, op_names, n_steps=1)
    scopes = out["scope_ms_per_step"]
    assert {k: v["fwd"] + v["bwd"] for k, v in scopes.items()} == pytest.approx({
        "attn": 42.63624, "embed": 0.813375, "mlp": 66.4768995, "loss_head": 15.308232,
        "optimizer": 9.640784, "flash_fwd": 21.9877945, "flash_dq": 12.2569495,
        "flash_dkv": 17.5696995})
    assert scopes["flash_fwd"]["bwd"] == scopes["flash_dq"]["fwd"] == scopes["optimizer"]["bwd"] == 0.0
    assert scopes["loss_head"] == pytest.approx({"fwd": 4.887469, "bwd": 10.420763})
    assert out["busy_ms_per_step"] == pytest.approx(226.723361)
    assert out["unscoped_ms_per_step"] == pytest.approx(40.033387)
    # the two reductions time the same kernels: by name here, by custom_call_target there
    kinds = tr.reduce({"devices": recorded["devices"], "host": []}, 1)["kind_ms_per_step"]
    assert sum(scopes[k]["fwd"] + scopes[k]["bwd"] for k in sr.KERNELS) == pytest.approx(kinds["flash"])
    # what no scope holds: the shard_map transpose's all-reduces, and XLA's own async copies
    top = dict(out["top_unscoped"])
    assert top["all-reduce"] + top["psum"] == pytest.approx(kinds["collective"])
    assert list(top)[:4] == ["all-reduce", "copy-done", "slice-done", "psum"]
    assert sum(list(top.values())[:4]) == pytest.approx(out["unscoped_ms_per_step"], rel=1e-3)
