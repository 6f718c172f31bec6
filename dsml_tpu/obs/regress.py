"""Perf-regression gate over a history of ``BENCH_r*.json`` bench records.

"Did this change make the benches worse?" as a machine-checkable answer
over a driver's captures of the pre-chip harness's output (removed in
PR 29; nothing in the tree writes such records now, ROADMAP Design 13):

- :func:`extract_metrics` — best-effort metric extraction from every
  record shape a capture can take: full bench records with ``parsed``
  payloads, rc=124 timeouts with bare tails, and 2000-byte tail
  TRUNCATIONS that cut the final JSON line mid-record — a strict parser
  would call those empty;
- :func:`compare` — per-metric noise bands (median ± k·MAD over the
  history, with a relative floor so an all-identical history doesn't
  produce a zero-width band) and a direction table (tokens/s up is good,
  step-ms up is bad; config constants like batch sizes are never gated);
- :func:`export_profile` — the calibrated collective-latency constants
  (ring/naive p50, e2e wire path, payload) as a machine-readable profile
  JSON for the ROADMAP's SCALE-Sim-style cost-model planner, sourced
  from the bench history and/or an aggregated cluster snapshot's
  ``collective_latency_ms`` histograms;
- ``python -m dsml_tpu.obs.regress`` — the CI gate: exits nonzero on a
  regression, 0 clean, 2 when nothing was parseable; ``--report-only``
  always exits 0 but still writes the report artifact.

Thresholds and the direction table are documented in
``docs/OBSERVABILITY.md`` § Perf-regression gate.
"""

from __future__ import annotations

import glob
import json
import os
import re

__all__ = [
    "compare",
    "export_profile",
    "extract_metrics",
    "main",
    "metric_direction",
    "noise_band",
    "profile_from_merged",
]

REPORT_SCHEMA = "dsml.obs.regress_report/1"
PROFILE_SCHEMA = "dsml.obs.collective_profile/1"

# defaults; the CLI exposes all three
DEFAULT_K = 5.0          # band half-width in MADs
DEFAULT_REL_FLOOR = 0.10  # ... but never narrower than ±10% of |median|
DEFAULT_MIN_HISTORY = 3   # fewer samples -> "insufficient_history", not gated

# a history this noisy carries no regression signal: MAD/|median| above
# this ratio marks the metric "too_noisy" and exempts it from gating
# (one warm-cache row can be 270x its successors — a band wide enough to
# admit that spread would admit anything)
NOISE_CEILING = 0.5


# ---------------------------------------------------------------------------
# extraction
# ---------------------------------------------------------------------------

# one positional token stream over (possibly truncated) JSON text:
# headline names ('"metric": "NAME"') and numeric '"key": value' pairs —
# the trailing lookahead rejects a number cut off by the tail boundary
_TOKEN_RE = re.compile(
    r'"metric":\s*"([A-Za-z_][A-Za-z0-9_]*)"'
    r'|"([A-Za-z_][A-Za-z0-9_]*)":\s*(-?\d+(?:\.\d+)?(?:[eE][-+]?\d+)?)'
    r"(?=\s*[,}\]])"
)
# bookkeeping keys that are record structure, not metrics
_STRUCTURE_KEYS = frozenset({"n", "rc", "time", "value"})


def _scan_text(text: str, out: dict) -> None:
    """Fold numeric pairs from (possibly truncated) JSON text into ``out``
    — later occurrences win, matching "the final emitted line is the
    record". A ``"value": V`` maps onto the most recent PRECEDING
    ``"metric": NAME`` only: a truncated multi-record tail can cut one
    record's value off entirely, and last-headline-wins would then hand
    another record's value to the wrong metric."""
    headline = None
    for m in _TOKEN_RE.finditer(text):
        name, key, num = m.groups()
        if name is not None:
            headline = name
            continue
        if key == "value":
            if headline is not None:
                out[headline] = float(num)
                headline = None  # one headline, one value
            continue
        if key in _STRUCTURE_KEYS:
            continue
        out[key] = float(num)


def _flatten_numeric(obj, out: dict) -> None:
    """Collect numeric leaves of a nested dict keyed by their LEAF name
    (bench extras are flat and uniquely named; nested wrappers like the
    evidence file's rows just add structure)."""
    if isinstance(obj, dict):
        for k, v in obj.items():
            if isinstance(v, bool):
                continue
            if isinstance(v, (int, float)):
                if isinstance(k, str) and k not in _STRUCTURE_KEYS:
                    out[str(k)] = float(v)
            elif isinstance(v, (dict, list)):
                _flatten_numeric(v, out)
    elif isinstance(obj, list):
        for v in obj:
            _flatten_numeric(v, out)


def extract_metrics(source) -> dict[str, float]:
    """{metric name: value} from a bench artifact.

    Accepts: a BENCH record dict (``{n, cmd, rc, tail, parsed}``), a
    ``{"metric":..., "extras": {...}}`` headline dict, any nested dict of
    numbers (a ``--section`` rows dict), raw bench stdout text, or a
    path to a JSON/text file holding any of those."""
    if isinstance(source, str):
        if os.path.exists(source):
            with open(source) as f:
                text = f.read()
            try:
                source = json.loads(text)
            except ValueError:
                source = text
        # fall through with text or the decoded object
    out: dict[str, float] = {}
    if isinstance(source, str):
        _scan_text(source, out)
        return out
    if isinstance(source, dict) and ("tail" in source or "parsed" in source):
        # BENCH record: tail first (truncated, older), parsed wins (complete)
        if isinstance(source.get("tail"), str):
            _scan_text(source["tail"], out)
        parsed = source.get("parsed")
        if isinstance(parsed, dict):
            _flatten_numeric(parsed.get("extras", {}), out)
            if isinstance(parsed.get("metric"), str) and \
                    isinstance(parsed.get("value"), (int, float)):
                out[parsed["metric"]] = float(parsed["value"])
        return out
    if isinstance(source, dict):
        if isinstance(source.get("metric"), str) and \
                isinstance(source.get("value"), (int, float)):
            out[source["metric"]] = float(source["value"])
        _flatten_numeric(source.get("extras", source), out)
        return out
    raise TypeError(f"cannot extract metrics from {type(source).__name__}")


# ---------------------------------------------------------------------------
# direction table
# ---------------------------------------------------------------------------

# (predicate order matters: first hit wins)
_NOT_A_METRIC = (
    "reference_", "_devices", "_batch", "batch", "_epochs", "epochs_",
    "_steps", "steps_per", "_seed", "_vocab", "_payload", "payload_",
    "_bytes", "_mb", "_requests", "n_requests", "_quantum", "_window",
    "_events", "_count", "capture_", "_buckets", "_replicas", "timed_",
    "warmup_", "_remat",
    # quant_sweep section: parity rows are correctness verdicts against a
    # stated tolerance (never perf-gated — a "regression" there is a test
    # failure, not a noise-band question), wire reductions and tolerances
    # are analytic constants. The grid's quant `_ms` cells stay gated
    # down-good via the `_ms` suffix rule below.
    "parity", "_reduction", "_tolerance",
    # serving_fleet section: worker/slot/chunk counts are configuration,
    # not measurements
    "_workers", "_slots", "_chunk",
    # paged_kv section: pool sizing and page geometry are configuration,
    # bit-identity is a verdict the contract test asserts (never a noise
    # band), peak-concurrent counts ride the gated concurrency RATIO, and
    # acceptance/window telemetry is workload-dependent
    "pages_at_budget", "page_size", "bit_identical", "_peak_concurrent",
    "capacity_tokens", "windows_used", "accept_rate", "ticks_per_token",
    # request_tracing section: verdict rows (`_ok` 0/1 flags), burn-rate
    # status/shares, tail attributions, and the per-class burst-schedule
    # accounting (requests/goodput/p99-threshold rows — tail stats over a
    # few dozen scripted requests, SLO accounting not a perf signal) are
    # never perf-gated; per_request_trace_us stays gated via the
    # "_trace_us" suffix and the tick walls via the "tick_ms" contains
    # rule
    "_ok", "dominant", "_burn", "tracing_interactive_", "tracing_batch_",
    # paged_attention section: the analytic HBM A/B rows are EXACT
    # program-structure counts (the "_bytes" rule above exempts them; a
    # changed count is a schedule change the contract test pins), the
    # live-shaped/table-shaped/parity/no-leak rows are `_ok` verdicts,
    # and eviction counts are workload constants via "_events". The
    # tick_p50_ms_live* walls gate down-good via the "tick_p50" contains
    # rule below, tp2_capacity_ratio up-good via "capacity_ratio", and
    # the preemption-vs-reservation throughput rows up-good via
    # "tokens_per_sec".
    # memory section: availability/provenance flags, device/watermark
    # counts, and the injected self-check's expectation constants are
    # structure, not perf (the residual/overhead rows gate through the
    # explicit memory rules in metric_direction below)
    "stats_available", "_watermarks", "memory_oom_", "expected_",
    # kernel_fusion section: the weight-byte compression ratios are
    # analytic codec constants the contract test pins against the
    # acceptance floors (a moved ratio is a codec change, not a
    # noise-band question), the MXU-idle fractions are analytic labels
    # (no rule matches them — ungated by default), and the provenance
    # rows are strings the flattener never sees. The
    # tick_p50_ms_live*_{single,pipelined} walls gate down-good via the
    # "tick_p50" contains rule and the ring_hop_ms_{fused,unfused}
    # walls via the "hop_ms" contains rule below (the _ms SUFFIX rule
    # misses the trailing schedule tag).
    "compression",
    # long_context section: ladder geometry + analytic accounting rows.
    # The KV wire-byte rows are EXACT schedule counts (the generic "_bytes"
    # rule above already exempts them — a changed count is a schedule
    # change the contract test pins, not a noise-band question) and the
    # _act_gb headroom table is analytic; rung `_ms` cells stay gated
    # down-good via the `_ms` suffix rule, `_mfu`/`max_tokens` up-good.
    "rungs_planned", "ladder_target", "keep_fraction", "_act_gb",
)
_HIGHER_BETTER = (
    "samples_per_sec", "tokens_per_sec", "tokens_per_s", "goodput",
    "accuracy", "mfu", "speedup", "coverage_pct",
    # paged_kv: concurrent-sequence capacity per HBM byte — the headline
    "capacity_ratio", "concurrency_ratio",
    # long_context: the highest sequence rung a train step COMPLETED
    "max_tokens",
)
_LOWER_BETTER_SUFFIX = ("_ms", "_s", "_sec", "_trace_us", "_pct", "_ppl")
# "_trace_us" (not bare "_us"): gates request_tracing's per-request bill
# down-good WITHOUT flipping forensics_enabled_bundle_us — a single-shot
# µs wall sample that was deliberately never gated.
# "ttft"/"tpot": the serving_fleet section's time-to-first-token and
# per-token-latency rows gate down-good (their `_ms` suffix already says
# so; the explicit tokens make the intent survive a unit rename), while
# `goodput_per_chip`/`tokens_per_sec` ride the up-good table above and
# `burst_isolation_speedup` the "speedup" rule.
_LOWER_BETTER_CONTAINS = ("loss", "overhead", "stall", "latency", "ttft",
                          # "tick_ms": the request_tracing fleet tick
                          # walls end in _enabled/_disabled, so the _ms
                          # SUFFIX rule misses them — the enabled-vs-
                          # disabled A/B is the end-to-end cost this
                          # section exists to watch
                          "tpot", "tick_ms",
                          # "tick_p50": the paged_attention section's
                          # per-live-fraction decode-tick walls
                          # (tick_p50_ms_live25/...): the _ms SUFFIX rule
                          # misses the trailing fraction tag
                          "tick_p50",
                          # "hop_ms": the kernel_fusion section's per-hop
                          # ring walls (ring_hop_ms_fused/_unfused): the
                          # _ms SUFFIX rule misses the trailing schedule
                          # tag
                          "hop_ms")


# memory-ledger rows (ISSUE 15): peak-byte watermarks and the
# unattributed residual gate DOWN-GOOD even though the generic "_bytes"
# rule above exempts byte rows (those are analytic schedule counts; a
# PEAK is a measurement — more resident bytes at the same workload is a
# memory regression exactly like a slower step is a latency regression).
# Capacity/provenance rows stay ungated: bytes_limit is the chip, not
# the code, and the claimed-taxonomy rows are attribution bookkeeping
# whose "regressions" are the contract test's business.
_MEMORY_NEVER_GATED = ("bytes_limit", "claimed_", "hbm_source")
# "unattributed_bytes"/"_gb", not bare "unattributed": the fleet-merge
# structure row memory_fleet_unattributed_rows is a process COUNT
_MEMORY_DOWN_GOOD = ("peak_bytes", "peak_gb", "unattributed_bytes",
                     "unattributed_gb")


def metric_direction(name: str) -> str | None:
    """"higher" / "lower" = which way is GOOD; None = not a perf metric
    (config constants, provenance counts) — never gated."""
    low = name.lower()
    if any(t in low for t in _MEMORY_NEVER_GATED):
        return None
    if any(t in low for t in _MEMORY_DOWN_GOOD):
        return "lower"
    if any(t in low for t in _NOT_A_METRIC):
        return None
    if any(t in low for t in _HIGHER_BETTER):
        return "higher"
    if any(t in low for t in _LOWER_BETTER_CONTAINS):
        return "lower"
    if low.endswith(_LOWER_BETTER_SUFFIX):
        return "lower"
    return None


# ---------------------------------------------------------------------------
# noise bands + comparison
# ---------------------------------------------------------------------------


def _median(vals: list[float]) -> float:
    s = sorted(vals)
    n = len(s)
    mid = n // 2
    return s[mid] if n % 2 else (s[mid - 1] + s[mid]) / 2.0


def noise_band(history: list[float], k: float = DEFAULT_K,
               rel_floor: float = DEFAULT_REL_FLOOR) -> dict:
    """median ± max(k·MAD, rel_floor·|median|) — MAD is robust to the
    history's outlier rounds (one off-platform run must not drag the
    center), the relative floor keeps an all-identical history from
    flagging any measurement jitter as a regression."""
    med = _median(history)
    mad = _median([abs(v - med) for v in history])
    half = max(k * mad, rel_floor * abs(med))
    return {
        "median": med, "mad": mad, "half_width": half,
        "lo": med - half, "hi": med + half, "n": len(history),
        "noise_ratio": (mad / abs(med)) if med else None,
    }


def compare(fresh: dict[str, float], history: list[dict[str, float]],
            k: float = DEFAULT_K, rel_floor: float = DEFAULT_REL_FLOOR,
            min_history: int = DEFAULT_MIN_HISTORY) -> dict:
    """Gate ``fresh`` against per-metric noise bands over ``history``.

    Per metric: ``regression`` (fresh beyond the band on the BAD side),
    ``improved`` (beyond on the good side), ``ok`` (inside),
    ``insufficient_history`` (< min_history samples), ``too_noisy``
    (MAD/|median| > NOISE_CEILING — no signal), ``not_gated`` (no
    direction). The report is the artifact; ``regressions`` is the exit
    verdict."""
    rows: dict[str, dict] = {}
    regressions: list[str] = []
    for name in sorted(fresh):
        value = fresh[name]
        samples = [h[name] for h in history if name in h]
        direction = metric_direction(name)
        row: dict = {"fresh": value, "direction": direction,
                     "n_history": len(samples)}
        if direction is None:
            row["status"] = "not_gated"
        elif len(samples) < min_history:
            row["status"] = "insufficient_history"
        else:
            band = noise_band(samples, k=k, rel_floor=rel_floor)
            row.update(band)
            ratio = band["noise_ratio"]
            if ratio is not None and ratio > NOISE_CEILING:
                row["status"] = "too_noisy"
            elif direction == "higher" and value < band["lo"]:
                row["status"] = "regression"
            elif direction == "lower" and value > band["hi"]:
                row["status"] = "regression"
            elif direction == "higher" and value > band["hi"]:
                row["status"] = "improved"
            elif direction == "lower" and value < band["lo"]:
                row["status"] = "improved"
            else:
                row["status"] = "ok"
        if row["status"] == "regression":
            regressions.append(name)
        rows[name] = row
    counts: dict[str, int] = {}
    for row in rows.values():
        counts[row["status"]] = counts.get(row["status"], 0) + 1
    return {
        "schema": REPORT_SCHEMA,
        "params": {"k": k, "rel_floor": rel_floor,
                   "min_history": min_history,
                   "noise_ceiling": NOISE_CEILING},
        "n_history_records": len(history),
        "metrics": rows,
        "counts": counts,
        "regressions": regressions,
    }


# ---------------------------------------------------------------------------
# calibrated collective-latency profile (cost-model planner input)
# ---------------------------------------------------------------------------

# bench keys that ARE calibration constants for the planner's cost model
_PROFILE_PREFIXES = ("allreduce_", "bucket_sweep_", "v8_")
_PROFILE_EXACT = ("serving_host_rtt_ms",)
_PROFILE_SUFFIXES = ("_step_ms",)


def _is_profile_key(name: str) -> bool:
    return (name.startswith(_PROFILE_PREFIXES)
            or name in _PROFILE_EXACT
            or name.endswith(_PROFILE_SUFFIXES))


def export_profile(fresh: dict[str, float],
                   history: list[dict[str, float]]) -> dict:
    """The measured collective/step-time constants, centered by history
    median (robust to outlier rounds) with the fresh sample alongside —
    the calibration input the ROADMAP's auto-parallel planner consumes
    instead of re-measuring."""
    constants: dict[str, dict] = {}
    names = {n for n in fresh if _is_profile_key(n)}
    for h in history:
        names.update(n for n in h if _is_profile_key(n))
    for name in sorted(names):
        samples = [h[name] for h in history if name in h]
        entry: dict = {}
        if name in fresh:
            entry["fresh"] = fresh[name]
        if samples:
            entry["median"] = _median(samples)
            entry["mad"] = _median(
                [abs(v - entry["median"]) for v in samples]
            )
            entry["n"] = len(samples)
        constants[name] = entry
    derived: dict[str, float] = {}
    ring = constants.get("allreduce_ring_p50_ms", {}).get("median")
    payload = constants.get("allreduce_payload_mb", {}).get("median")
    e2e = constants.get("allreduce_e2e_p50_ms", {}).get("median")
    if ring is not None and payload:
        derived["ring_ms_per_mb"] = ring / payload
    if e2e is not None and ring is not None:
        # wire-path fixed cost: gRPC hops + host staging beyond the
        # on-mesh reduction itself
        derived["wire_overhead_ms"] = max(e2e - ring, 0.0)
    return {"schema": PROFILE_SCHEMA, "constants": constants,
            "derived": derived}


def profile_from_merged(merged) -> dict:
    """Calibration constants from an AGGREGATED cluster view's
    ``collective_latency_ms{algorithm,axis}`` fleet histograms — the
    cross-process measurement path (ISSUE: the cost model "must be
    calibrated from aggregated measured collective-latency histograms")."""
    from dsml_tpu.obs.cluster import estimate_quantile

    constants: dict[str, dict] = {}
    for rec in merged.collect():
        if rec["name"] != "collective_latency_ms:fleet":
            continue
        labels = rec["labels"]
        bounds = tuple(b for b in rec["buckets"] if b != "+Inf")
        key = "collective_{algorithm}_{axis}".format(
            algorithm=labels.get("algorithm", "unknown"),
            axis=labels.get("axis", "unknown"),
        )
        constants[key] = {
            "count": rec["count"],
            "mean_ms": (rec["sum"] / rec["count"]) if rec["count"] else None,
            "p50_ms": estimate_quantile(bounds, rec["buckets"], 0.5),
            "p90_ms": estimate_quantile(bounds, rec["buckets"], 0.9),
        }
    return {"schema": PROFILE_SCHEMA, "constants": constants, "derived": {}}


# ---------------------------------------------------------------------------
# CLI
# ---------------------------------------------------------------------------


def _load_history(patterns: list[str]) -> tuple[list[str], list[dict]]:
    paths: list[str] = []
    for pat in patterns:
        hits = sorted(glob.glob(pat))
        paths.extend(hits if hits else ([pat] if os.path.exists(pat) else []))
    records = []
    used = []
    for p in paths:
        metrics = extract_metrics(p)
        if metrics:
            records.append(metrics)
            used.append(p)
    return used, records


def main(argv=None) -> int:
    import argparse

    ap = argparse.ArgumentParser(
        prog="python -m dsml_tpu.obs.regress",
        description="compare a fresh bench record against the BENCH_r*.json "
        "history with per-metric noise bands; exit 1 on regression",
    )
    ap.add_argument("--fresh", default=None,
                    help="fresh bench artifact (JSON record or raw stdout); "
                    "default: the newest history file (self-check mode)")
    ap.add_argument("--history", nargs="*", default=["BENCH_r*.json"],
                    help="history files/globs (default: BENCH_r*.json)")
    ap.add_argument("--k", type=float, default=DEFAULT_K,
                    help=f"band half-width in MADs (default {DEFAULT_K})")
    ap.add_argument("--rel-floor", type=float, default=DEFAULT_REL_FLOOR,
                    help="minimum band half-width as a fraction of |median| "
                    f"(default {DEFAULT_REL_FLOOR})")
    ap.add_argument("--min-history", type=int, default=DEFAULT_MIN_HISTORY,
                    help="samples required before a metric is gated "
                    f"(default {DEFAULT_MIN_HISTORY})")
    ap.add_argument("--report", default=None,
                    help="write the full comparison report JSON here")
    ap.add_argument("--profile", default=None,
                    help="write the calibrated collective-latency profile "
                    "JSON here (cost-model planner input)")
    ap.add_argument("--report-only", action="store_true",
                    help="always exit 0 (CI advisory mode); the report still "
                    "records the verdict")
    args = ap.parse_args(argv)

    used, history = _load_history(args.history)
    if not history:
        print(f"regress: no parseable history from {args.history}")
        return 2
    if args.fresh is not None:
        fresh = extract_metrics(args.fresh)
        fresh_src = args.fresh
    else:
        fresh = history[-1]
        fresh_src = used[-1] + " (self-check)"
    if not fresh:
        print(f"regress: nothing parseable in fresh artifact {fresh_src}")
        return 2

    report = compare(fresh, history, k=args.k, rel_floor=args.rel_floor,
                     min_history=args.min_history)
    report["fresh_source"] = fresh_src
    report["history_sources"] = used
    report["report_only"] = bool(args.report_only)
    if args.report:
        with open(args.report, "w") as f:
            json.dump(report, f, indent=2, sort_keys=True)
    if args.profile:
        with open(args.profile, "w") as f:
            json.dump(export_profile(fresh, history), f, indent=2,
                      sort_keys=True)

    counts = report["counts"]
    print(f"regress: {len(fresh)} fresh metrics vs {len(history)} history "
          f"records ({used[0]}..{used[-1]}): "
          + ", ".join(f"{k}={v}" for k, v in sorted(counts.items())))
    for name in report["regressions"]:
        row = report["metrics"][name]
        print(f"  REGRESSION {name}: fresh={row['fresh']:g} outside "
              f"[{row['lo']:g}, {row['hi']:g}] (median={row['median']:g}, "
              f"direction={row['direction']})")
    if report["regressions"] and not args.report_only:
        return 1
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
