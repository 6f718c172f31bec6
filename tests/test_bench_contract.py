"""The bench section contracts: each ``bench.py`` section the tier-1 suite
can afford returns the rows its issue's acceptance bars read, and the
helpers the benchmark PR will build on fail loudly (an unknown
``device_kind`` has no peak FLOP/s — it is an error, not a default).

Sections run in-process on the conftest's 8-device CPU mesh, or — for the
ones that force their own virtual-8 mesh — in the subprocess the section
itself starts.
"""

from __future__ import annotations

import json
import os
import sys
import types

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def test_peak_flops_raises_on_unknown_device_kind():
    sys.path.insert(0, REPO)
    import bench

    assert bench._peak_flops(types.SimpleNamespace(device_kind="TPU v5 lite")) == 197e12
    with pytest.raises(ValueError, match="no peak bf16 FLOP/s known.*'cpu'"):
        bench._peak_flops(types.SimpleNamespace(device_kind="cpu"))


def test_main_exits_nonzero_when_a_section_raises(monkeypatch, capsys):
    """``main()`` runs on whatever ``jax.devices()`` gives, keeps the other
    sections' rows, prints the one JSON line naming the device, and returns
    nonzero because a section raised — no fallback, no exit 0."""
    sys.path.insert(0, REPO)
    import bench

    def boom():
        raise RuntimeError("flagship failed")

    monkeypatch.setattr(bench, "bench_gpt2", boom)
    monkeypatch.setattr(bench, "_MAIN_SECTIONS", (("mnist", lambda: {"mnist_rows": 1}, 0),))
    assert bench.main() == 1
    head = json.loads(capsys.readouterr().out.strip().splitlines()[-1])
    assert head["value"] is None
    ex = head["extras"]
    assert "flagship failed" in ex["errors"]["gpt2"]
    assert ex["mnist_rows"] == 1
    assert (ex["platform"], ex["device_kind"]) == ("cpu", "cpu")

    monkeypatch.setattr(bench, "bench_gpt2", lambda: {})
    assert bench.main() == 0


def test_obs_section_schema():
    """The BENCH `obs` section's contract (ISSUE 4 acceptance): per-
    algorithm collective-latency histograms, a step-time breakdown whose
    components sum to within 5% of the measured step wall, and the
    disabled-registry overhead guard. Run in-process — the test conftest
    already provides the 8-device CPU mesh the section measures on."""
    sys.path.insert(0, REPO)
    import bench

    rows = bench.bench_obs()

    # (a) per-algorithm latency histograms: every explicit algorithm has
    # p50/p90 + sample count, and the cumulative histogram is monotone
    # with its +Inf bucket equal to the count
    for alg in ("ring", "ring2", "naive", "q8"):
        assert f"obs_collective_{alg}_error" not in rows, rows
        assert rows[f"obs_collective_{alg}_n"] > 0
        assert rows[f"obs_collective_{alg}_p90_ms"] >= rows[f"obs_collective_{alg}_p50_ms"]
        hist = rows["obs_collective_latency_hist"][alg]
        counts = list(hist.values())
        assert counts == sorted(counts)  # cumulative
        assert hist["+Inf"] == rows[f"obs_collective_{alg}_n"]

    # (b) step breakdown: the five canonical phases, summing to within 5%
    # of the measured wall
    breakdown = rows["obs_step_breakdown_ms"]
    assert set(breakdown) == {
        "data", "forward_backward", "grad_sync", "optimizer", "checkpoint_stall"
    }
    assert rows["obs_step_wall_ms"] > 0
    assert rows["obs_step_coverage_pct"] >= 95.0

    # (c) disabled-mode overhead guard: the acceptance bar is < 1% of a
    # fused step (measured as bundle cost ÷ step time — see bench_obs)
    assert rows["obs_disabled_overhead_pct"] < 1.0


def test_forensics_section_schema():
    """The BENCH `forensics` section's contract (ISSUE 5 acceptance):
    sentinel/hangwatch per-step overhead stays under the 1% bar BOTH
    disabled and enabled, and the injected-NaN row reports a detection
    latency bounded by the sync cadence plus a complete bundle."""
    sys.path.insert(0, REPO)
    import bench

    rows = bench.bench_forensics()

    # (a)+(b) overhead guards — the same <1%-of-a-fused-step bar as obs
    assert rows["forensics_disabled_overhead_pct"] < 1.0
    assert rows["forensics_enabled_overhead_pct"] < 1.0
    assert rows["forensics_disabled_bundle_ns"] > 0
    assert rows["forensics_enabled_bundle_us"] > 0

    # (c) injected-NaN detection: the sentinel only looks at sync points,
    # so detection lands within one sync window of the injection
    assert "forensics_nan_error" not in rows, rows
    assert rows["forensics_nan_trip_step"] >= rows["forensics_nan_inject_step"]
    assert rows["forensics_nan_detect_steps"] <= rows["forensics_nan_sync_every"]
    assert rows["forensics_nan_detect_ms"] > 0
    # the halt left a complete bundle behind
    assert rows["forensics_bundle_events"] > 0
    assert {"events.jsonl", "registry.json", "stacks.txt",
            "trace.json"} <= set(rows["forensics_bundle_files"])


def test_cluster_section_schema(bench_history, monkeypatch):
    """The BENCH `cluster` section's contract (ISSUE 7 acceptance): the
    aggregation plane's DISABLED per-step overhead stays under the 1% bar,
    the merge/scrape/stitch micro-rows are present and sane, and the
    regress gate self-check over a (synthetic) history exits 0 with a
    calibrated collective profile written."""
    sys.path.insert(0, REPO)
    import bench

    # run in the scratch cwd that holds the synthetic history, so the
    # profile artifact doesn't land in the repo
    monkeypatch.chdir(bench_history)
    rows = bench.bench_cluster()

    # (a) the acceptance bar: aggregation disabled-overhead < 1% per step
    assert rows["cluster_disabled_overhead_pct"] < 1.0
    assert rows["cluster_disabled_instrument_ns"] > 0
    assert rows["cluster_step_wall_ms"] > 0

    # (b) live hammering actually happened and was measured
    assert rows["cluster_scrape_hammer_count"] > 0
    assert rows["cluster_scrape_overhead_pct"] >= 0.0

    # (c) plane micro-costs
    assert rows["cluster_merge_ms"] > 0
    assert rows["cluster_scrape_roundtrip_ms"] > 0
    assert rows["cluster_stitch_events"] > 0

    # (d) the history gates itself clean, and the profile JSON
    # for the cost-model planner was written with derived constants
    assert rows["cluster_regress_selfcheck_rc"] == 0
    assert rows["cluster_profile_constants"] > 0
    assert rows["cluster_profile_ring_ms_per_mb"] > 0
    with open(bench_history / "collective_profile.json") as f:
        prof = json.load(f)
    assert prof["schema"] == "dsml.obs.collective_profile/1"


def test_quant_sweep_section_schema(monkeypatch):
    """The BENCH `quant_sweep` section's contract (ISSUE 9 acceptance):
    the (bucket × scheme × algorithm) grid reports per-cell sync ms +
    analytic wire bytes, the quantized ring's wire-byte reduction vs the
    fp32 ring at equal bucket size is ≥ 2× (int8 ~4×, int4 ~8× — a
    counting argument over the schedule, not a CPU-timing claim), and the
    q8+EF loss trajectory stays within the stated tolerance of the fp32
    sync. Runs the TINY grid (the same one the CI smoke step uses)."""
    sys.path.insert(0, REPO)
    import bench

    monkeypatch.setenv("DSML_QUANT_SWEEP_TINY", "1")
    rows = bench.bench_quant_sweep()

    assert "quant_sweep_error" not in rows, rows

    # (a) grid cells: per-sync ms + bucket counts for every tiny-grid cell
    for alg in ("ring", "q8_ring"):
        assert rows[f"quant_sweep_{alg}_4mb_ms"] >= 0
        assert rows[f"quant_sweep_{alg}_4mb_buckets"] >= 1

    # (b) the acceptance bar: quantized ring ships ≥2× fewer wire bytes
    # than the fp32 ring at equal bucket size (analytic, static shapes)
    assert rows["quant_sweep_int8_ring_wire_reduction"] >= 2.0
    assert rows["quant_sweep_int8_ring2_wire_reduction"] >= 2.0
    assert rows["quant_sweep_int4_ring_wire_reduction"] >= 4.0
    assert rows["quant_sweep_fp32_ring_wire_bytes_per_bucket"] > \
        rows["quant_sweep_q8_ring_wire_bytes_per_bucket"]

    # (c) q8+EF parity: measured loss trajectory within the stated
    # tolerance of the fp32 ring sync, and the verdict row says so
    assert rows["quant_sweep_parity_q8_ef_rel_dev"] <= \
        rows["quant_sweep_parity_tolerance"]
    assert rows["quant_sweep_parity_q8_ef_ok"] is True
    assert rows["quant_sweep_parity_steps"] > 0


@pytest.mark.slow
def test_serving_fleet_section_schema(monkeypatch):
    """The BENCH `serving_fleet` section's contract (ISSUE 10 acceptance):
    the disaggregated fleet and the equal-chip monolithic pool both carry
    p50/p99 TTFT, per-token latency, and goodput-per-chip under BOTH
    arrival processes; under the bursty schedule the fleet's decode p99
    per-token latency beats the monolithic pool's (burst isolation), and
    under uniform Poisson the fleet keeps ≥ 0.9× the pool's tokens/sec.
    Runs the TINY A/B (the same one the CI smoke step uses) — slow tier:
    the subprocess compiles four serving stacks."""
    sys.path.insert(0, REPO)
    import bench

    monkeypatch.setenv("DSML_SERVING_FLEET_TINY", "1")
    rows = bench.bench_serving_fleet()

    assert "serving_fleet_error" not in rows, rows
    # equal chip count by construction
    assert (rows["serving_fleet_prefill_workers"]
            + rows["serving_fleet_decode_workers"]
            == rows["serving_fleet_mono_workers"]
            == rows["serving_fleet_chips"])
    # both variants × both workloads carry the full latency/goodput row
    for wl in ("poisson", "bursty"):
        for var in ("disagg", "mono"):
            for m in ("ttft_p50_ms", "ttft_p99_ms", "tpot_p50_ms",
                      "tpot_p99_ms", "decode_gap_p99_ms", "tokens_per_sec",
                      "goodput_per_chip"):
                assert rows[f"serving_fleet_{wl}_{var}_{m}"] > 0
    # the acceptance bars: burst isolation + Poisson throughput parity
    assert rows["serving_fleet_burst_isolation_speedup"] > 1.0
    assert rows["serving_fleet_poisson_throughput_ratio"] >= 0.9


@pytest.mark.slow
def test_request_tracing_section_schema(monkeypatch):
    """The BENCH `request_tracing` section's contract (ISSUE 13
    acceptance): the FULL per-request tracing bill (TraceContext mint +
    spans + flows + SLO record + exemplar) stays under 1% of the measured
    serving-representative decode tick (asserted here with 1.5x headroom
    for CPU wall noise — the artifact row carries the raw pct the <1%
    acceptance reads); the burst schedule yields a per-class burn status
    and a p99 tail attribution naming a dominant stage with a trace_id
    exemplar; a tail-bucket serving_ttft_ms exemplar resolves to a real
    retired request; and the request flow chains are fully linked
    (start → steps → end). Runs the TINY leg (the CI smoke step's) —
    slow tier: the subprocess compiles two serving stacks."""
    sys.path.insert(0, REPO)
    import bench

    monkeypatch.setenv("DSML_REQUEST_TRACING_TINY", "1")
    rows = bench.bench_request_tracing()

    assert "request_tracing_error" not in rows, rows
    # the overhead bar: per-request bill vs a decode tick
    assert rows["request_tracing_decode_tick_ms"] > 0
    assert rows["request_tracing_per_request_trace_us"] > 0
    assert rows["request_tracing_trace_overhead_pct"] < 1.5
    # tracing on vs off: same tick count through the identical schedule
    assert rows["request_tracing_ticks_enabled"] > 0
    assert rows["request_tracing_tick_ms_disabled"] > 0
    # SLO accounting rows per class: burn status + tail attribution
    for cls in ("interactive", "batch"):
        assert rows[f"request_tracing_{cls}_requests"] > 0
        assert rows[f"request_tracing_{cls}_burn_status"] in (
            "ok", "warn", "page"
        )
        assert rows[f"request_tracing_{cls}_dominant_stage"] in (
            "queue", "prefill", "handoff", "first_decode", "decode"
        )
        assert rows[f"request_tracing_{cls}_tail_trace_id"]
    # the verdicts: exemplar resolution + fully linked flow chains
    assert rows["request_tracing_tail_attribution_ok"] == 1
    assert rows["request_tracing_ttft_exemplar_ok"] == 1
    assert rows["request_tracing_flow_links_ok"] == 1
    assert rows["request_tracing_flow_linked_requests"] > 0


@pytest.mark.slow
def test_paged_kv_section_schema(monkeypatch):
    """The BENCH `paged_kv` section's contract (ISSUE 11 acceptance): at
    EQUAL analytic HBM budget the paged int4 pool holds ≥4× the dense
    batcher's concurrent sequences (analytic accounting AND the measured
    virtual-8 leg), greedy tokens are BIT-IDENTICAL to the dense batcher
    running the same int4 codec, and the PR 10 burst schedule's p99
    decode gap stays in the dense cache's band (the gather adds no tail
    on this workload — 1.5× headroom for CPU wall noise; the real-chip
    bar lives in the evidence capture). Runs the TINY A/B (the CI smoke
    step's) — slow tier: the subprocess compiles several serving stacks."""
    sys.path.insert(0, REPO)
    import bench

    monkeypatch.setenv("DSML_PAGED_KV_TINY", "1")
    rows = bench.bench_paged_kv()

    assert "paged_kv_error" not in rows, rows
    # analytic accounting is exact: budget = dense slots × dense bytes,
    # and the int4 page rows are what buy the capacity ratio
    assert rows["paged_kv_hbm_budget_bytes"] == (
        rows["paged_kv_dense_slots"] * rows["paged_kv_dense_slot_bytes_f32"]
    )
    assert rows["paged_kv_capacity_ratio_analytic"] >= 4.0
    # the measured leg: the paged pool actually held >=4x in flight
    assert rows["paged_kv_measured_concurrency_ratio"] >= 4.0
    assert rows["paged_kv_paged_peak_concurrent"] >= \
        4 * rows["paged_kv_dense_peak_concurrent"]
    # greedy tokens bit-identical to the dense int4 batcher
    assert rows["paged_kv_greedy_bit_identical"] == 1
    # burst p99 decode gap: no worse than dense (CPU-noise headroom)
    assert rows["paged_kv_burst_gap_p99_ratio"] <= 1.5
    # page-size sweep rows exist for the TUNING.md defaults
    for ps in (8, 16):
        assert rows[f"paged_kv_sweep_page{ps}_tick_p50_ms"] > 0
        assert rows[f"paged_kv_sweep_page{ps}_capacity_tokens"] > 0


@pytest.mark.slow
def test_paged_attention_section_schema(monkeypatch):
    """The BENCH `paged_attention` section's contract (ISSUE 14
    acceptance): the analytic per-tick HBM table shows the Pallas
    kernel's bill EXACTLY linear in live pages (cross-checked against
    ``paged_hbm_bytes`` here) while the XLA gather's never moves, greedy
    tokens are bit-identical kernel-vs-gather AND tp2-vs-single-device,
    the tp=2 per-chip capacity ratio clears the ≥4× bar, and the
    eviction-preemption leg evicts at least once, resumes with identical
    tokens, and leaks nothing. Runs the TINY A/B (the CI smoke step's) —
    slow tier: the subprocess compiles several serving stacks."""
    sys.path.insert(0, REPO)
    import bench
    from dsml_tpu.ops.paged_attention import paged_hbm_bytes

    monkeypatch.setenv("DSML_PAGED_ATTENTION_TINY", "1")
    rows = bench.bench_paged_attention()

    assert "paged_attention_error" not in rows, rows
    # the analytic A/B is exact — recompute one cell from the accounting
    # function so the table can't drift from the program structure
    n_slots = rows["paged_attention_n_slots"]
    ps = rows["paged_attention_page_size"]
    n_pt = 256 // ps
    live25 = max(n_slots * n_pt * 25 // 100, 1)
    assert rows["paged_attention_hbm_pallas_bytes_live25"] == paged_hbm_bytes(
        n_slots=n_slots, n_pt=n_pt, page_size=ps, n_kv_head=4, head_dim=16,
        mode="int4", live_pages=live25, impl="pallas",
    )
    # live-shaped vs table-shaped: the headline claim, as verdicts
    assert rows["paged_attention_hbm_pallas_live_shaped_ok"] == 1
    assert rows["paged_attention_hbm_xla_table_shaped_ok"] == 1
    # a quarter-live pool reads >4x less HBM through the kernel
    assert rows["paged_attention_hbm_reduction_at_live25"] >= 4.0
    # bit-identity: kernel vs gather, and tp=2 sharded pool vs single
    assert rows["paged_attention_pallas_parity_ok"] == 1
    assert rows["paged_attention_tp2_tokens_identical_ok"] == 1
    # the capacity story survives TP: >=4x per chip at the dense budget
    assert rows["paged_attention_tp2_capacity_ratio"] >= 4.0
    # eviction preemption: exercised, token-pure, leak-free
    assert rows["paged_attention_preempt_eviction_events"] >= 1
    assert rows["paged_attention_preempt_tokens_identical_ok"] == 1
    assert rows["paged_attention_preempt_no_leak_ok"] == 1
    # measured walls exist for the live-fraction ladder
    for frac in (25, 100):
        assert rows[f"paged_attention_tick_p50_ms_live{frac}"] > 0


@pytest.mark.slow
def test_long_context_section_schema(monkeypatch):
    """The BENCH `long_context` section's contract (ISSUE 12 acceptance):
    the cp=8 ring-attention ladder names 128k as its target rung, every
    attempted rung carries EXACT per-hop KV wire-byte accounting (cross-
    checked here against the counting model), the GPT-2-small headroom
    table shows selective remat + cp dividing the 128k activation
    footprint, and the ring2-vs-flash parity verdicts (fwd AND grads, odd
    length included) hold. Runs the TINY ladder (the CI smoke step's) —
    slow tier: the subprocess compiles a train step per rung."""
    sys.path.insert(0, REPO)
    import bench

    monkeypatch.setenv("DSML_LONG_CONTEXT_TINY", "1")
    rows = bench.bench_long_context()

    assert "long_context_error" not in rows, rows
    # the ladder's target is the 128k rung (the full run climbs to it; the
    # tiny CI ladder stops early but must COMPLETE its planned rungs)
    assert rows["long_context_ladder_target_tokens"] == 131072
    assert rows["long_context_cp"] == 8
    rungs = rows["long_context_rungs_planned"]
    assert rows["long_context_max_tokens"] == rungs[-1], rows

    # exact wire accounting on every attempted rung — re-derive one: per
    # hop both directions together carry the full resident KV shard (K+V)
    from dsml_tpu.ops.ring_attention import ring_kv_wire_bytes

    s_local = rungs[0] // 8
    assert rows[f"long_context_seq{rungs[0]}_kv_wire_bytes_per_hop"] == \
        ring_kv_wire_bytes(s_local, 8, 2, 16) // 7
    assert rows[f"long_context_seq{rungs[0]}_kv_wire_bytes_bwd"] > \
        rows[f"long_context_seq{rungs[0]}_kv_wire_bytes_fwd"]
    # measured rung rows present for every completed rung
    for seq in rungs:
        assert rows[f"long_context_seq{seq}_step_ms"] > 0
        assert rows[f"long_context_seq{seq}_tokens_per_sec"] > 0

    # the headroom argument: at 128k, selective remat shrinks the single-
    # chip footprint, and cp=8 divides what remains by the ring size
    single = rows["long_context_gpt2s_131072_act_gb_single"]
    remat = rows["long_context_gpt2s_131072_act_gb_single_remat_mlp"]
    cp8 = rows["long_context_gpt2s_131072_act_gb_cp8_remat_mlp"]
    assert single > remat > cp8
    assert abs(remat / cp8 - 8.0) < 0.1  # cp divides resident tokens

    # MFU-vs-single-chip at the shared rung: MFU normalizes by peak, so the
    # cp=8 row is the throughput scaling ÷ 8 — both emitted, both positive
    assert rows["long_context_mfu_vs_single_chip"] > 0
    assert rows["long_context_throughput_vs_single_chip"] == pytest.approx(
        rows["long_context_mfu_vs_single_chip"] * 8, rel=0.02)
    assert rows["long_context_parity_ok"] is True
    assert rows["long_context_parity_fwd_max_err"] < 5e-4
    assert rows["long_context_parity_grad_max_err"] < 2e-3


@pytest.mark.slow
def test_memory_section_schema(monkeypatch):
    """The BENCH `memory` section's contract (ISSUE 15 acceptance): ledger
    attribution pins exactly against hand-counted per-device bytes, the
    injected-stats reconciliation self-check's residual math is exact, the
    disabled-mode ledger bundle stays under the 1% bar, an injected
    RESOURCE_EXHAUSTED leaves a postmortem whose memory.json carries the
    snapshot + watermark timeline, the analytic-vs-compiler-measured rung
    cross-check is monotone, and the fleet merge orders headroom
    min/mean/max. Runs the TINY ladder (the CI smoke step's) — slow tier:
    the subprocess compiles a step per rung."""
    sys.path.insert(0, REPO)
    import bench

    monkeypatch.setenv("DSML_MEMORY_TINY", "1")
    rows = bench.bench_memory()

    assert "memory_error" not in rows, rows

    # (a) attribution math pinned: claims == hand-counted per-device bytes
    assert rows["memory_attribution_params_ok"] == 1
    assert rows["memory_attribution_optimizer_ok"] == 1
    assert rows["memory_claimed_params_bytes"] > 0
    # adam m/v double the param bytes (plus replicated scalars)
    assert rows["memory_claimed_optimizer_bytes"] >= \
        2 * rows["memory_claimed_params_bytes"]
    # the wrapped hybrid step recorded one watermark per step, source-
    # stamped (CPU backends report no stats → "claimed" provenance)
    assert rows["memory_step_watermarks"] == 3
    assert rows["memory_step_peak_bytes"] > 0
    assert rows["memory_watermark_source"] in ("claimed", "memory_stats")

    # (b) reconciliation: the self-check's residual math is EXACT, and on
    # stats-reporting backends the live residual honors the documented
    # bound (CPU: provenance says unavailable, the row is absent)
    assert rows["memory_selfcheck_ok"] == 1
    assert rows["memory_selfcheck_residual_bytes"] == \
        rows["memory_selfcheck_expected_residual_bytes"]
    if rows["memory_stats_available"]:
        assert rows["memory_reconcile_residual_pct"] <= \
            rows["memory_reconcile_bound_pct"]

    # (c) analytic-vs-measured rung cross-check: both columns exist per
    # rung and the compiler-measured temps grow with the rung
    assert rows["memory_rung_monotonic_ok"] == 1
    assert rows["memory_rung1024_analytic_act_bytes"] > 0
    assert rows["memory_rung1024_measured_temp_bytes"] > 0
    assert rows["memory_rung1024_measured_over_analytic"] > 0

    # (d) the disabled-mode bar — same <1%-of-a-fused-step contract as
    # every other obs subsystem
    assert rows["memory_disabled_overhead_pct"] < 1.0
    assert rows["memory_disabled_bundle_ns"] > 0

    # (e) OOM forensics: the bundle names the reason and carries a
    # complete ledger snapshot + the watermark timeline
    assert rows["memory_oom_reason_ok"] == 1
    assert rows["memory_oom_snapshot_ok"] == 1
    assert rows["memory_oom_watermarks"] >= 3
    assert {"memory.json", "registry.json", "events.jsonl",
            "stacks.txt"} <= set(rows["memory_oom_bundle_files"])

    # (f) fleet merge: headroom min/mean/max over both synthetic hosts
    assert rows["memory_fleet_headroom_ok"] == 1
    assert rows["memory_fleet_headroom_min_gb"] < \
        rows["memory_fleet_headroom_max_gb"]
    assert rows["memory_fleet_unattributed_rows"] == 2


@pytest.mark.slow
def test_kernel_fusion_section_schema(monkeypatch):
    """The BENCH `kernel_fusion` section's contract (ISSUE 16
    acceptance): fused-vs-unfused A/B rows exist for all three fusions
    with explicit CPU provenance labels (interpret-mode walls hide the
    DMA overlap — the labels are what keep the rows honest off-TPU),
    the bit-identity verdicts hold, and the weight-byte compression
    rows clear the 3.9x (int8) / 7.8x (int4) floors. Runs the TINY A/B
    (the CI smoke step's) — slow tier: the subprocess compiles several
    serving stacks and interprets the paged kernels."""
    sys.path.insert(0, REPO)
    import bench

    monkeypatch.setenv("DSML_KERNEL_FUSION_TINY", "1")
    rows = bench.bench_kernel_fusion()

    assert "kernel_fusion_error" not in rows, rows
    # (1) paged double buffering: tick p50 A/B rows for both schedules,
    # provenance says the walls are interpreted (DMAs synchronous)
    assert rows["kernel_fusion_tick_p50_ms_live25_single"] > 0
    assert rows["kernel_fusion_tick_p50_ms_live25_pipelined"] > 0
    assert rows["kernel_fusion_dma_overlap_provenance"] == "interpret"
    # both kernels' working sets carry the VMEM-budget sizing rows
    assert rows["kernel_fusion_paged_vmem_pipelined_bytes"] > 0
    # (2) in-ring fused hop: per-hop walls both schedules, bit-identity,
    # and the analytic idle fraction the fusion closes on chips
    assert rows["kernel_fusion_ring_hop_ms_unfused"] > 0
    assert rows["kernel_fusion_ring_hop_ms_fused"] > 0
    assert rows["kernel_fusion_ring_fused_bit_identical_ok"] == 1
    assert rows["kernel_fusion_ring_hop_provenance"] == "analytic"
    assert 0 < rows["kernel_fusion_ring_mxu_idle_frac_unfused_analytic"] < 1
    assert rows["kernel_fusion_ring_mxu_idle_frac_fused_analytic"] == 0.0
    # (3) dequant-fused weights: the acceptance compression floors at
    # real dims, kernel-vs-oracle parity
    assert rows["kernel_fusion_weight_compression_int8"] >= 3.9
    assert rows["kernel_fusion_weight_compression_int4"] >= 7.8
    assert rows["kernel_fusion_weight_fused_parity_ok"] == 1
    # regress-gate wiring: the wall rows gate down-good, the compression
    # and analytic rows never gate
    from dsml_tpu.obs.regress import metric_direction

    assert metric_direction(
        "kernel_fusion_tick_p50_ms_live25_pipelined") == "lower"
    assert metric_direction("kernel_fusion_ring_hop_ms_fused") == "lower"
    assert metric_direction("kernel_fusion_weight_compression_int4") is None
    assert metric_direction(
        "kernel_fusion_ring_mxu_idle_frac_unfused_analytic") is None
