"""What every driver needs and no cell owns: the compile cache and its
counters, the table of peaks, the device report, notes on earlier lines."""

from __future__ import annotations

import collections
import dataclasses
import json
import os
from pathlib import Path

ROOT = Path(__file__).resolve().parents[1]
BENCH_DIR = Path(__file__).resolve().parent

# what jax's persistent compile cache did, counted from its own events:
# `requests` compiles consulted it, `hits` were served from it, `writes` were
# stored in it; `backend_compiles` counts every XLA compile, cached or not
_CACHE_EVENTS = {
    "/jax/compilation_cache/compile_requests_use_cache": "requests",
    "/jax/compilation_cache/cache_hits": "hits",
    "/jax/compilation_cache/cache_misses": "writes",
}
_BACKEND_COMPILE = "/jax/core/compile/backend_compile_duration"


class CompileWatch:
    """Counts compile-cache traffic and backend compiles from ``jax.monitoring``."""

    def __init__(self):
        import jax

        self.counts: collections.Counter = collections.Counter()
        jax.monitoring.register_event_listener(self._on_event)
        jax.monitoring.register_event_duration_secs_listener(self._on_duration)

    def _on_event(self, event: str, **_) -> None:
        if event in _CACHE_EVENTS:
            self.counts[_CACHE_EVENTS[event]] += 1

    def _on_duration(self, event: str, _duration: float, **_) -> None:
        if event == _BACKEND_COMPILE:
            self.counts["backend_compiles"] += 1

    def snapshot(self) -> dict:
        return {k: self.counts[k] for k in ("requests", "hits", "writes", "backend_compiles")}

    def since(self, before: dict) -> dict:
        return {k: v - before[k] for k, v in self.snapshot().items()}


def configure_compile_cache() -> str:
    """``JAX_COMPILATION_CACHE_DIR`` if the environment sets it (jax reads it
    itself), else ``<checkout>/.jax_cache``: a fixed path, because the path is
    part of the key of a step that holds a Pallas kernel (PERF.md §6, PR 22).
    Every program is kept, not only those that took a second to compile: a run
    makes a dozen small ones (the draw, the optimizer's init, the reference's
    block) and each run of each later check would compile them again."""
    import jax

    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
    env_dir = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env_dir:
        return env_dir
    cache_dir = str(ROOT / ".jax_cache")
    jax.config.update("jax_compilation_cache_dir", cache_dir)
    return cache_dir


def peak_for(device_kind: str) -> dict:
    table = json.loads((BENCH_DIR / "peaks.json").read_text())["peaks"]
    for key, peak in table.items():
        if key in device_kind.lower():
            return peak
    raise ValueError(f"no peaks known for device_kind {device_kind!r}; add a row with its "
                     f"source to benchmarks/peaks.json (known: {sorted(table)})")


def resolve(workload: str) -> tuple[dict, dict, dict, dict]:
    """``BENCHMARK.json``, the cell named ``workload``, its configuration file and
    its traffic file. The cell's ``config`` and ``traffic`` are the only names
    that lead to files; nothing else knows a cell."""
    bench = json.loads((ROOT / "BENCHMARK.json").read_text())

    def by_name(entries: list, name: str, what: str) -> dict:
        for entry in entries:
            if entry["name"] == name:
                return entry
        raise SystemExit(f"BENCHMARK.json has no {what} named {name!r}; "
                         f"it has {[e['name'] for e in entries]}")

    cell = by_name(bench["workloads"], workload, "workload")
    config = json.loads((ROOT / by_name(bench["configs"], cell["config"], "config")["file"]).read_text())
    traffic = json.loads((BENCH_DIR / "traffic" / f"{cell['traffic']}.json").read_text())
    if traffic["chips"] != cell["chips"]:
        raise SystemExit(f"cell {cell['name']} asks for {cell['chips']} chips, "
                         f"its traffic file for {traffic['chips']}")
    return bench, cell, config, traffic


def note(**fields) -> None:
    """One JSON line of notes, on an earlier line than the result."""
    print(json.dumps(fields), flush=True)


@dataclasses.dataclass
class Run:
    """One run of one cell: what ``run.py`` resolved and hands to the driver."""

    config: dict        # the configuration file
    traffic: dict       # the traffic file
    seed: int
    seconds: float
    trace: bool
    rehearse: bool
    t_process_start: float   # time.perf_counter() at the top of run.py
    compile_watch: CompileWatch
    trace_dir: str
