"""Device time under the names the expert layer and the window brought: the
scopes ``moe_route``, ``moe_dispatch``, ``experts`` and ``moe_combine``
(``models/experts.py``, all inside ``mlp``), the kernels ``gmm_fwd``, ``gmm_dx``
and ``gmm_dw`` (``ops/grouped_matmul.py``, inside ``experts``), and the flash
kernels where they run inside the scope ``attn_window`` (``models/mellum.py``:
the sliding layers' calls), which ``scope_reduce.KERNELS`` / ``SCOPES`` (fixed
tuples) do not know.

The same join as ``ssm_reduce.py``, with ``scope_reduce``'s pieces:
``hlo_modules`` and ``pick_module`` give ``{instruction: op_name}`` of the step
from the trace file's metadata plane, ``trace_reduce.load`` / ``self_times`` the
events. Whole path components only. An instruction counts under every one of
the names its op name holds (a kernel under its own name and under ``experts``),
forward, recomputed forward and backward together: the readers want sums. A
program without these names (the parent of the PR that brought them, every
other cell) gives an empty table and every reader ``None``. A shim: ROADMAP
Design 17 has the names become data of one reduction.
"""

from __future__ import annotations

import collections
import functools
import re
import statistics
import time
from pathlib import Path

from benchmarks import harness, scope_reduce, trace_reduce

SCOPES = ("moe_route", "moe_dispatch", "experts", "moe_combine")
KERNELS = ("gmm_fwd", "gmm_dx", "gmm_dw")
WINDOW_SCOPE, FLASH_IN_WINDOW = "attn_window", "flash_in_window"


@functools.lru_cache(maxsize=None)  # asked once an event, answered once an instruction
def names_of(op_name: str) -> tuple:
    """The names of ``SCOPES`` and ``KERNELS`` among the op name's components,
    and ``FLASH_IN_WINDOW`` for a flash kernel under ``attn_window``; of
    instructions XLA merged (``;``) the first part that holds any decides."""
    for one in op_name.split(";"):
        tokens = set(re.split(r"[/()]", one))
        found = [name for name in (*SCOPES, *KERNELS) if name in tokens]
        if WINDOW_SCOPE in tokens and tokens.intersection(scope_reduce.KERNELS):
            found.append(FLASH_IN_WINDOW)
        if found:
            return tuple(found)
    return ()


def reduce(events: dict, op_names: dict, n_steps: int) -> dict:
    """``{name: ms a step}``, the median over devices of the self times of each
    device's ``XLA Ops`` events."""
    per_device = []
    for evs in events["devices"].values():
        if not evs:
            continue
        by_name = collections.Counter()
        for name, _category, _start, _end, self_ns, _leaf in trace_reduce.self_times(evs):
            for found in names_of(op_names.get(name, "")):
                by_name[found] += self_ns
        per_device.append(by_name)
    return {name: statistics.median(d[name] for d in per_device) / 1e6 / n_steps
            for name in sorted({k for d in per_device for k in d})}


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
    harness.note(phase="moe_reduce", seconds=time.perf_counter() - t0, ms_per_step=table)
    return table


def name_ms(trace, *names: str):
    """What a per-layer reader returns: ms a step under ``names`` together, or
    ``None`` where the run has no device trace or the program none of these
    names."""
    if not trace:
        return None
    table = newest(trace["n_steps"])
    found = [table[name] for name in names if name in table]
    return sum(found) if found else None
