"""``trace_reduce.reduce`` on a hand-made trace whose answers can be worked out
on paper, and on a small trace recorded on the v5e."""

import gzip
import json
from pathlib import Path

import pytest

from benchmarks import trace_reduce as tr

US = 1_000.0  # the traces are in nanoseconds


def _hand_made():
    """One device, two steps, times in microseconds:

        0-100   fusion.1       output fusion               matmul
        100-150 jvp__.2        tpu_custom_call             flash
        150-160 all-reduce-start.1                         collective (async)
        160-200 fusion.3       loop fusion                 other, hides the all-reduce
        200-260 all-reduce-done.1                          collective, exposed
        260-300 (nothing: the host is in bench.fetch)      idle
        300-400 while.4        contains fusion.5 310-350 and all-reduce.6 350-390
        400-405 (nothing, under 20 us)                     idle between ops
        405-500 convolution_add_fusion.7                   matmul
    """
    ops = [
        ["fusion.1", "fusion:kOutput", 0, 100],
        ["jvp__.2", "custom-call:tpu_custom_call", 100, 50],
        ["all-reduce-start.1", "all-reduce-start", 150, 10],
        ["fusion.3", "fusion:kLoop", 160, 40],
        ["all-reduce-done.1", "all-reduce-done", 200, 60],
        ["while.4", "while", 300, 100],
        ["fusion.5", "fusion:kLoop", 310, 40],
        ["all-reduce.6", "all-reduce", 350, 40],
        ["convolution_add_fusion.7", "fusion:kOutput", 405, 95],
    ]
    host = [["bench.dispatch", 240, 15], ["bench.fetch", 255, 60], ["bench.batch", 400, 3]]
    return {
        "devices": {"/device:TPU:0": [[n, c, s * US, d * US] for n, c, s, d in ops]},
        "host": [[n, s * US, d * US] for n, s, d in host],
    }


def test_classify_by_what_xla_names():
    fusion = ("%bitcast_dynamic-update-slice_fusion.2 = bf16[7,8192,768]{2,1,0:T(8,128)(2,1)} "
              "fusion(bf16[7,8192,768]{2,1,0:T(8,128)(2,1)} %get-tuple-element.1076), "
              "kind=kOutput, calls=%fused_computation.33.clone.clone")
    assert tr.parse_op(fusion) == ("bitcast_dynamic-update-slice_fusion.2", "fusion:kOutput")
    assert tr.classify(*tr.parse_op(fusion)) == "matmul"
    kernel = ('%jvp__.17 = (bf16[384,1024,64]{2,1,0:T(8,128)(2,1)}, f32[384,8,1024]{2,1,0:T(8,128)}) '
              'custom-call(s32[1]{0:T(128)} %constant.101), custom_call_target="tpu_custom_call", '
              'operand_layout_constraints={s32[1]{0}}')
    assert tr.parse_op(kernel) == ("jvp__.17", "custom-call:tpu_custom_call")
    assert tr.classify(*tr.parse_op(kernel)) == "flash"
    loop = "%while.4 = (s32[]{:T(128)}, f32[32768,768]{1,0:T(8,128)}) while((s32[]{:T(128)}) %tuple.605), condition=%c, body=%b"
    assert tr.parse_op(loop) == ("while.4", "while")
    assert tr.classify("all-reduce-start.1", "all-reduce-start") == "collective"
    assert tr.classify("reduce-scatter.9", "") == "collective"
    assert tr.classify("fusion.7", "fusion:kLoop") == "other"
    assert tr.classify("custom-call.1", "custom-call:SomethingElse") == "other"
    assert tr.classify("copy.2", "copy") == "other"
    assert tr.stem("%all-reduce-done.11") == "all-reduce-done"


def test_interval_arithmetic():
    assert tr.union([[5, 7], [0, 2], [1, 3], [7, 8]]) == [[0, 3], [5, 8]]
    assert tr.subtract([[0, 10]], [[2, 3], [5, 12]]) == [[0, 2], [3, 5]]
    assert tr.subtract([[0, 4], [6, 9]], [[3, 7]]) == [[0, 3], [7, 9]]
    assert tr.length([[0, 3], [5, 8]]) == 6


def test_hand_made_trace():
    out = tr.reduce(_hand_made(), n_steps=2)
    assert out["window_s"] == pytest.approx(500e-6)
    assert out["busy_s"] == pytest.approx(455e-6)  # all but 260-300 and 400-405
    assert out["idle_pct"] == pytest.approx(9.0)
    per_step = out["kind_ms_per_step"]
    assert per_step["matmul"] == pytest.approx(0.195 / 2)
    assert per_step["flash"] == pytest.approx(0.050 / 2)
    # start 10 + done 60 + the all-reduce inside the while 40
    assert per_step["collective"] == pytest.approx(0.110 / 2)
    # fusion.3 40 + fusion.5 40 + the while's own 20, its body not counted twice
    assert per_step["other"] == pytest.approx(0.100 / 2)
    # in flight 150-260 and 350-390; fusion.3 hides 160-200 of it, the while hides nothing
    assert out["coll_ms_per_step"] == pytest.approx(0.150 / 2)
    assert out["coll_exposed_ms_per_step"] == pytest.approx(0.110 / 2)
    gaps = dict(out["breakdown"]["idle_gaps"])
    assert gaps == {"bench.fetch": pytest.approx(40e-6), "between_ops_under_20us": pytest.approx(5e-6)}
    ops = dict(out["breakdown"]["device_ops"])
    assert ops["matmul:fusion"] == pytest.approx(100e-6)
    assert ops["matmul:convolution_add_fusion"] == pytest.approx(95e-6)
    assert len(out["breakdown"]["device_ops"]) <= 10


def test_no_device_operation_is_an_error():
    with pytest.raises(ValueError, match="no device operation"):
        tr.reduce({"devices": {}, "host": []}, n_steps=1)


def test_recorded_v5e_trace():
    """One step of two of the four devices of ``gpt2l-1k-dp4``, recorded on the
    v5e (PR 23) and kept as ``trace_reduce.load`` returned it."""
    path = Path(__file__).with_name("v5e_dp4_one_step.json.gz")
    with gzip.open(path, "rt") as f:
        events = json.load(f)
    assert sorted(events["devices"]) == ["/device:TPU:0", "/device:TPU:1"]
    out = tr.reduce(events, n_steps=1)
    assert out["n_devices"] == 2
    assert out["window_s"] == pytest.approx(0.226747399)
    assert out["idle_pct"] == pytest.approx(0.0105, abs=1e-3)
    kinds = out["kind_ms_per_step"]
    assert kinds == pytest.approx(
        {"matmul": 111.2994955, "flash": 51.8132615, "collective": 26.94907, "other": 36.661723})
    # self times split the busy time between the kinds: nothing is counted twice or dropped
    assert sum(kinds.values()) == pytest.approx(out["busy_s"] * 1e3, rel=1e-3)
    # XLA left the gradient all-reduces synchronous: nothing computes while they run
    assert out["coll_ms_per_step"] == pytest.approx(26.94907)
    assert out["coll_exposed_ms_per_step"] == pytest.approx(out["coll_ms_per_step"])
    ops = out["breakdown"]["device_ops"]
    assert ops[0][0] == "matmul:fusion" and ops[1][0] == "flash:shard_map"
    assert ops[2] == ["collective:all-reduce", pytest.approx(0.0246887735)]
    assert len(ops) == 10 and out["breakdown"]["idle_gaps"][0][0] == "between_ops_under_20us"
