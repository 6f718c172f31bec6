"""Perf-regression gate: extraction over every (messy) record shape a
driver's capture can take, noise-band math pinned against numpy, and the CLI
contract — an injected >=20% step-time slowdown exits nonzero, an unchanged
history exits zero.
"""

import json

import numpy as np
import pytest

from dsml_tpu.obs import regress
from dsml_tpu.obs.regress import (
    compare,
    export_profile,
    extract_metrics,
    metric_direction,
    noise_band,
)

# ---------------------------------------------------------------------------
# extraction: every artifact shape a capture can take
# ---------------------------------------------------------------------------


def test_extracts_full_record_with_parsed_payload(bench_history):
    m = extract_metrics(str(bench_history / "BENCH_r01.json"))
    assert m["mnist_samples_per_sec_per_chip"] == pytest.approx(1200.5)
    assert m["allreduce_ring_p50_ms"] == pytest.approx(0.5)
    assert "cmd" not in m and "rc" not in m  # record structure is not a metric


def test_extracts_truncated_tail_with_null_parsed(bench_history):
    # r03's tail is cut mid-JSON on BOTH ends and parsed is null — a strict
    # json.loads would yield nothing; the scanner must recover the numeric
    # pairs anyway
    m = extract_metrics(str(bench_history / "BENCH_r03.json"))
    assert len(m) >= 10
    assert m["allreduce_ring_p50_ms"] == pytest.approx(0.52)
    assert m["gpt2_realtext_eval_ppl"] == pytest.approx(13.72)


def test_timeout_record_yields_nothing_not_garbage(bench_history):
    # r04 timed out (rc=124) before emitting any metrics line
    assert extract_metrics(str(bench_history / "BENCH_r04.json")) == {}


def test_extracts_headline_metric_from_raw_stdout():
    text = ('noise\n{"metric": "gpt2_tokens_per_sec", "value": 123.5, '
            '"extras": {"gpt2_step_ms": 55.0}}\n')
    m = extract_metrics(text)
    assert m["gpt2_tokens_per_sec"] == 123.5
    assert m["gpt2_step_ms"] == 55.0


def test_extracts_nested_dict_leaves():
    m = extract_metrics({"rows": {"a_ms": 1.5, "inner": {"b_ms": 2.5}},
                         "flag": True})
    assert m == {"a_ms": 1.5, "b_ms": 2.5}  # bools are not metrics


def test_headline_value_binds_to_preceding_metric_only():
    """A truncated multi-record tail can cut the LAST record's value off;
    the earlier record's value must stay with ITS metric name, never get
    handed to the later headline (review finding: last-headline-wins
    misattributed one section's throughput to another)."""
    text = ('{"metric": "mnist_samples_per_sec", "value": 500.0, "x": 1}\n'
            '{"metric": "gpt2_tokens_per_sec", "val')  # value truncated away
    m = extract_metrics(text)
    assert m.get("mnist_samples_per_sec") == 500.0
    assert "gpt2_tokens_per_sec" not in m


def test_truncated_trailing_number_is_rejected():
    # the tail boundary cuts a number in half: "…step_ms": 188 (really
    # 1887.62) — the lookahead must refuse the orphan rather than record
    # a fabricated 10x-off value
    m = extract_metrics('{"a_ms": 3.0, "b_ms": 188')
    assert m == {"a_ms": 3.0}


# ---------------------------------------------------------------------------
# direction table + noise bands
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("name,want", [
    ("gpt2_tokens_per_sec", "higher"),
    ("mnist_samples_per_sec_per_chip", "higher"),
    ("gpt2_seq32k_mfu", "higher"),
    ("mnist_test_accuracy", "higher"),
    ("chaos_goodput", "higher"),
    ("gpt2_step_ms", "lower"),
    ("checkpoint_save_ms", "lower"),
    ("obs_disabled_overhead_pct", "lower"),
    ("gpt2_realtext_eval_loss", "lower"),
    ("gpt2_realtext_eval_ppl", "lower"),
    ("allreduce_devices", None),       # config, never gated
    ("mnist_batch", None),
    ("reference_samples_per_sec", None),
    ("gpt2_seq32k_remat", None),
    # request_tracing: the per-request bill + tick walls gate down-good;
    # verdict flags, burn status, tail attribution, and the per-class
    # burst-schedule accounting (incl. its p99 thresholds) never gate
    ("request_tracing_per_request_trace_us", "lower"),
    ("request_tracing_trace_overhead_pct", "lower"),
    ("request_tracing_decode_tick_ms", "lower"),
    ("request_tracing_tick_ms_enabled", "lower"),
    ("request_tracing_tick_ms_disabled", "lower"),
    ("request_tracing_ttft_exemplar_ok", None),
    ("request_tracing_interactive_burn_status", None),
    ("request_tracing_interactive_dominant_stage", None),
    ("request_tracing_interactive_p99_ms", None),
    ("request_tracing_batch_goodput_requests", None),
    # "_trace_us" is scoped so forensics' single-shot µs row stays ungated
    ("forensics_enabled_bundle_us", None),
    # memory section (ISSUE 15): peak watermarks and the unattributed
    # residual gate DOWN-GOOD despite the generic "_bytes" exemption
    # (a peak is a measurement, not a schedule count); bytes_limit is
    # the chip, claimed-taxonomy rows are attribution bookkeeping, and
    # availability flags are structure — never gated
    ("memory_step_peak_bytes", "lower"),
    ("hbm_peak_bytes_in_use", "lower"),
    ("memory_unattributed_bytes", "lower"),
    ("memory_disabled_overhead_pct", "lower"),
    ("hbm_bytes_limit", None),
    ("memory_claimed_params_bytes", None),
    ("memory_stats_available", None),
    ("memory_rung2048_measured_temp_bytes", None),  # compiler count
    ("memory_selfcheck_expected_residual_bytes", None),
    ("memory_oom_watermarks", None),
    ("memory_fleet_unattributed_rows", None),  # process count, not drift
])
def test_direction_table(name, want):
    assert metric_direction(name) == want


def test_noise_band_median_mad_pinned_against_numpy():
    vals = [100.0, 103.0, 97.0, 104.0, 99.0, 250.0]  # one outlier round
    band = noise_band(vals, k=5.0, rel_floor=0.0)
    med = float(np.median(vals))
    mad = float(np.median(np.abs(np.asarray(vals) - med)))
    assert band["median"] == pytest.approx(med)
    assert band["mad"] == pytest.approx(mad)
    assert band["hi"] == pytest.approx(med + 5.0 * mad)
    # the outlier widened MAD but did not drag the center
    assert band["median"] < 110.0


def test_rel_floor_prevents_zero_width_band():
    band = noise_band([100.0, 100.0, 100.0], k=5.0, rel_floor=0.10)
    assert band["lo"] == pytest.approx(90.0)
    assert band["hi"] == pytest.approx(110.0)


def test_compare_statuses():
    hist = [{"a_step_ms": v, "b_tokens_per_sec": 1000.0 + i,
             "noisy_ms": [1.0, 100.0, 10000.0][i]}
            for i, v in enumerate((100.0, 101.0, 99.0))]
    rep = compare({"a_step_ms": 130.0,       # 30% slower -> regression
                   "b_tokens_per_sec": 1500.0,  # faster -> improved
                   "new_ms": 5.0,            # no history
                   "some_batch": 32.0,       # not a perf metric
                   "noisy_ms": 50.0},        # MAD/median >> ceiling
                  hist)
    m = rep["metrics"]
    assert m["a_step_ms"]["status"] == "regression"
    assert m["b_tokens_per_sec"]["status"] == "improved"
    assert m["new_ms"]["status"] == "insufficient_history"
    assert m["some_batch"]["status"] == "not_gated"
    assert m["noisy_ms"]["status"] == "too_noisy"
    assert rep["regressions"] == ["a_step_ms"]


# ---------------------------------------------------------------------------
# the CLI contract (ISSUE 7 acceptance)
# ---------------------------------------------------------------------------


def _write_history(tmp_path, step_values):
    paths = []
    for i, v in enumerate(step_values):
        p = tmp_path / f"BENCH_t{i:02d}.json"
        p.write_text(json.dumps({
            "n": i, "rc": 0,
            "tail": json.dumps({"metric": "gpt2_tokens_per_sec",
                                "value": 2048000.0 / v,
                                "extras": {"gpt2_step_ms": v}}),
            "parsed": None,
        }))
        paths.append(str(p))
    return paths


def test_injected_20pct_slowdown_exits_nonzero(tmp_path):
    hist = _write_history(tmp_path, [100.0, 102.0, 98.0, 101.0, 99.0])
    fresh = tmp_path / "fresh.json"
    fresh.write_text(json.dumps(
        {"metric": "gpt2_tokens_per_sec", "value": 2048000.0 / 120.0,
         "extras": {"gpt2_step_ms": 120.0}}))
    report = tmp_path / "report.json"
    rc = regress.main(["--fresh", str(fresh), "--history", *hist,
                       "--report", str(report)])
    assert rc == 1
    rep = json.loads(report.read_text())
    assert rep["schema"] == "dsml.obs.regress_report/1"
    assert "gpt2_step_ms" in rep["regressions"]
    assert "gpt2_tokens_per_sec" in rep["regressions"]
    row = rep["metrics"]["gpt2_step_ms"]
    assert row["fresh"] == 120.0 and row["direction"] == "lower"


def test_unchanged_history_exits_zero(tmp_path):
    hist = _write_history(tmp_path, [100.0, 102.0, 98.0, 101.0, 99.0])
    rc = regress.main(["--history", *hist])  # self-check: fresh = newest
    assert rc == 0


def test_report_only_mode_always_exits_zero(tmp_path):
    hist = _write_history(tmp_path, [100.0, 102.0, 98.0, 101.0, 99.0])
    fresh = tmp_path / "fresh.json"
    fresh.write_text(json.dumps({"extras": {"gpt2_step_ms": 200.0}}))
    report = tmp_path / "report.json"
    rc = regress.main(["--fresh", str(fresh), "--history", *hist,
                       "--report-only", "--report", str(report)])
    assert rc == 0
    rep = json.loads(report.read_text())
    assert rep["regressions"] == ["gpt2_step_ms"]  # verdict still recorded
    assert rep["report_only"] is True


def test_bench_history_self_check_exits_zero(bench_history):
    """Self-check mode: the gate over BENCH_r01..r05 with the newest record
    as the fresh sample must be clean — a history is not a regression
    against itself, whatever shapes its records take."""
    rc = regress.main(["--history", str(bench_history / "BENCH_r*.json")])
    assert rc == 0


def test_unparseable_history_exits_2(tmp_path):
    rc = regress.main(["--history", str(tmp_path / "nope*.json")])
    assert rc == 2


# ---------------------------------------------------------------------------
# calibrated collective profile (cost-model planner input)
# ---------------------------------------------------------------------------


def test_profile_exports_collective_constants_from_history(bench_history):
    history = [extract_metrics(str(bench_history / f"BENCH_r{i:02d}.json"))
               for i in range(1, 6)]
    history = [h for h in history if h]
    fresh = history[-1]
    prof = export_profile(fresh, history)
    assert prof["schema"] == "dsml.obs.collective_profile/1"
    ring = prof["constants"]["allreduce_ring_p50_ms"]
    assert ring["n"] >= 3 and ring["median"] > 0
    # derived constants the planner consumes directly
    assert prof["derived"]["ring_ms_per_mb"] == pytest.approx(
        ring["median"] / prof["constants"]["allreduce_payload_mb"]["median"])
    assert prof["derived"]["wire_overhead_ms"] >= 0.0
    json.dumps(prof)


def test_profile_from_merged_cluster_snapshots():
    from dsml_tpu.obs.cluster import merge_snapshots
    from dsml_tpu.obs.registry import Registry
    from dsml_tpu.obs.regress import profile_from_merged

    def build(reg):
        h = reg.histogram("collective_latency_ms",
                          labels=("algorithm", "axis"))
        for v in (1.0, 2.0, 3.0):
            h.observe(v, algorithm="ring", axis="wire")

    snaps = []
    for pid in (1, 2):
        reg = Registry(enabled=True)
        build(reg)
        snaps.append({"schema": "dsml.obs.cluster/1", "host": "h",
                      "pid": pid, "role": "coordinator", "wall_s": 0.0,
                      "mono_us": 0.0, "enabled": True,
                      "metrics": reg.collect()})
    prof = profile_from_merged(merge_snapshots(snaps))
    entry = prof["constants"]["collective_ring_wire"]
    assert entry["count"] == 6
    assert entry["mean_ms"] == pytest.approx(2.0)
    assert entry["p50_ms"] is not None and entry["p50_ms"] > 0
