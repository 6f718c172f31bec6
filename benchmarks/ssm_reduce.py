"""Device time under the names the state-space mixer brought: the scope ``ssm``
(``models/jamba.py``: the whole Mamba mixer) and the kernels ``ssm_scan_fwd``
and ``ssm_scan_bwd`` (``ops/selective_scan.py``), which ``scope_reduce.KERNELS``
/ ``SCOPES`` (fixed tuples) do not know.

The same join as ``scope_reduce.py``, with its pieces: ``hlo_modules`` and
``pick_module`` give ``{instruction: op_name}`` of the step from the trace
file's metadata plane, ``trace_reduce.load`` / ``self_times`` the events. Whole
path components only; ``bwd`` where a ``transpose`` precedes the name. The
kernels nest in the scope: an instruction counts under its kernel's name and,
kernel or not, under ``ssm``. A program without these names (the parent of the
PR that brought them, every GPT-2 cell) gives an empty table and every reader
``None``. A shim: ROADMAP Design has the names become data of one reduction.
"""

from __future__ import annotations

import collections
import functools
import re
import statistics
import time
from pathlib import Path

from benchmarks import harness, scope_reduce, trace_reduce

KERNELS = ("ssm_scan_fwd", "ssm_scan_bwd")
SCOPE = "ssm"


@functools.lru_cache(maxsize=None)  # asked once an event, answered once an instruction
def names_of(op_name: str) -> tuple:
    """``((name, "fwd" | "bwd"), ...)``: the kernel, if the op name holds one,
    then the scope, if it holds it; of instructions XLA merged (``;``) the first
    part that holds either decides."""
    for one in op_name.split(";"):
        tokens = re.split(r"[/()]", one)
        found = []
        for wanted in (KERNELS, (SCOPE,)):  # kernels before the scope they nest in
            at = next((i for i, token in enumerate(tokens) if token in wanted), None)
            if at is not None:
                found.append((tokens[at], "bwd" if "transpose" in tokens[:at] else "fwd"))
        if found:
            return tuple(found)
    return ()


def reduce(events: dict, op_names: dict, n_steps: int) -> dict:
    """``{name: {"fwd": ms, "bwd": ms}}`` a step, the median over devices of
    the self times of each device's ``XLA Ops`` events."""
    per_device = []
    for evs in events["devices"].values():
        if not evs:
            continue
        by_name = collections.Counter()
        for name, _category, _start, _end, self_ns, _leaf in trace_reduce.self_times(evs):
            for found in names_of(op_names.get(name, "")):
                by_name[found] += self_ns
        per_device.append(by_name)
    table: dict = {}
    for name, direction in sorted({k for d in per_device for k in d}):
        table.setdefault(name, {"fwd": 0.0, "bwd": 0.0})[direction] = statistics.median(
            d[(name, direction)] for d in per_device) / 1e6 / n_steps
    return table


def read_dir(trace_dir: str, n_steps: int) -> dict:
    """The newest ``*.xplane.pb`` under ``trace_dir``, joined and reduced."""
    events = trace_reduce.load(trace_dir)
    modules = scope_reduce.hlo_modules(Path(trace_reduce.newest_xplane(trace_dir)).read_bytes())
    return reduce(events, scope_reduce.pick_module(modules, events), n_steps)


@functools.lru_cache(maxsize=None)
def newest(n_steps: int) -> dict:
    """The run's own trace (the driver has just rewritten the cell's directory
    under ``.bench_trace``), parsed once a process."""
    t0 = time.perf_counter()
    table = read_dir(str(harness.ROOT / ".bench_trace"), n_steps)
    harness.note(phase="ssm_reduce", seconds=time.perf_counter() - t0, ms_per_step=table)
    return table


def name_ms(trace, *names: str):
    """What a per-layer reader returns: ms a step under ``names`` together,
    forward and backward, or ``None`` where the run has no device trace or the
    program none of these names."""
    if not trace:
        return None
    table = newest(trace["n_steps"])
    found = [table[name] for name in names if name in table]
    return sum(ms["fwd"] + ms["bwd"] for ms in found) if found else None
