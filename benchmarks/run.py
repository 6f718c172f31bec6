"""One run of one cell: load, warm up, measure, print one JSON line, exit.

    python3 benchmarks/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

Everything that belongs to one cell is data this file finds by name and never
names itself: the cell is an entry of ``BENCHMARK.json``'s ``workloads``; its
``config`` names ``benchmarks/configs/<config>.json``, its ``traffic``
``benchmarks/traffic/<traffic>.json``; the traffic's ``kind`` names the loop
``benchmarks/drivers/<kind>.py``; each per-layer metric ``BENCHMARK.json`` lists
for the cell is read by ``benchmarks/layer_metrics/<metric>.py``. A new cell,
configuration, traffic mix, metric or kind of workload is new files plus
``BENCHMARK.json`` entries.

The last line of standard output is the result; everything before it is notes.
``--trace 0`` reports the cell's end-to-end metrics, ``--trace 1`` its per-layer
metrics and the breakdown. Without a TPU, or with fewer chips than the cell
asks for, the run fails: there is no CPU fallback. ``--rehearse`` is the
sandbox's switch (``JAX_PLATFORMS=cpu``): tiny sizes through the same code, and
its last line carries ``rehearsal_metrics`` in place of ``metrics`` so that no
reader can take a CPU number for a device metric.
"""

from __future__ import annotations

import time

T_PROCESS_START = time.perf_counter()

import argparse  # noqa: E402
import importlib  # noqa: E402
import json  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

sys.path.insert(0, str(Path(__file__).resolve().parents[1]))

from benchmarks import harness  # noqa: E402  (imports jax only inside its functions)


def _applies(metric: dict, cell: str) -> bool:
    return cell in metric.get("workloads", [cell])


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--rehearse", action="store_true",
                    help="CPU rehearsal at tiny sizes; reports no device metric")
    args = ap.parse_args(argv)

    bench, cell, config, traffic = harness.resolve(args.workload)

    import jax

    cache_dir = harness.configure_compile_cache()
    compile_watch = harness.CompileWatch()
    devices = jax.devices()
    device = {"platform": devices[0].platform, "kind": devices[0].device_kind, "count": len(devices)}
    if device["platform"] != "tpu" and not args.rehearse:
        print(f"no TPU: jax reports {device}; the benchmark runs on the chip only", file=sys.stderr)
        return 1
    if len(devices) < cell["chips"]:
        print(f"cell {cell['name']} needs {cell['chips']} chips, jax reports {device}", file=sys.stderr)
        return 1
    harness.note(phase="start", cell=cell["name"], config=cell["config"], traffic=cell["traffic"],
                 seed=args.seed, seconds=args.seconds, trace=args.trace, device=device,
                 jax=jax.__version__, compile_cache_dir=cache_dir,
                 import_s=time.perf_counter() - T_PROCESS_START)

    driver = importlib.import_module(f"benchmarks.drivers.{traffic['kind']}")
    out = driver.run(harness.Run(
        config=config, traffic=traffic, seed=args.seed, seconds=args.seconds,
        trace=bool(args.trace), rehearse=args.rehearse, t_process_start=T_PROCESS_START,
        compile_watch=compile_watch, trace_dir=str(harness.ROOT / ".bench_trace" / cell["name"])))

    metrics = {}
    if args.trace:
        for metric in bench["per_layer"]:
            if not _applies(metric, cell["name"]):
                continue
            reader = importlib.import_module(f"benchmarks.layer_metrics.{metric['name']}")
            value = reader.read(out["trace"], out["notes"])
            if value is not None:
                metrics[metric["name"]] = {"value": value, "unit": metric["unit"]}
    else:
        for metric in bench["end_to_end"]:
            if _applies(metric, cell["name"]):
                metrics[metric["name"]] = {"value": out["end_to_end"][metric["name"]],
                                           "unit": metric["unit"]}

    device["memory_peak_bytes"] = out["memory_peak_bytes"]
    result = {"correct": out["correct"], "attempted": out["attempted"], "failed": out["failed"],
              "rehearsal_metrics" if args.rehearse else "metrics": metrics, "device": device}
    if out["trace"]:
        device["busy_s"], device["window_s"] = out["trace"]["busy_s"], out["trace"]["window_s"]
        result["breakdown"] = out["trace"]["breakdown"]
    harness.note(phase="end", total_s=time.perf_counter() - T_PROCESS_START,
                 memory_stats_peak_bytes=out["memory_stats_peak_bytes"])
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
