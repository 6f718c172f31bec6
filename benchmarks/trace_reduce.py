"""From a ``jax.profiler`` trace to numbers: the benchmark's one reduction.

Two steps, kept apart so the second can be checked on a recorded trace
(``benchmarks/tests/``):

``load(trace_dir)`` reads the newest ``*.xplane.pb`` under a profiler
directory with ``jax.profiler.ProfileData`` and keeps what the reduction
needs, as plain lists: for each device plane (``/device:TPU:n``) the events of
its ``XLA Ops`` line as ``[name, category, start_ns, duration_ns]``
(``parse_op`` takes both from the HLO instruction that names the event), the
collectives of its ``Async XLA Ops`` line (in flight beside the ops), and from
the host planes the benchmark's own ``TraceAnnotation`` spans (names starting
``bench.``) as ``[name, start_ns, duration_ns]``. Device and host events are
on one clock.

``reduce(events, n_steps)`` classifies each device op by what XLA names it
(``classify``), takes self times where ops nest (the body of a ``while`` lies
inside the ``while`` event), and returns per-step milliseconds by kind, the
busy/idle union, collective time and the part of it no compute covers, the ops
that took most time, and the idle time by what the host was doing. Numbers
that are per device are the median over devices; ``busy_s`` is the mean.

``python benchmarks/trace_reduce.py <trace_dir>`` prints what a trace holds
(planes, lines, the commonest events with their stats): read one by hand
before trusting a rule below on a new kind of program.
"""

from __future__ import annotations

import collections
import glob
import os
import re
import statistics
import sys

OPS_LINE = "XLA Ops"
ASYNC_LINE = "Async XLA Ops"
HOST_SPAN_PREFIX = "bench."
COLLECTIVES = ("all-reduce", "all-gather", "reduce-scatter", "all-to-all",
               "collective-permute", "collective-broadcast")
# an idle gap shorter than this lies between two ops of one program and is
# the device's own business; a longer one is set beside what the host was doing
HOST_GAP_NS = 20_000
KINDS = ("matmul", "flash", "collective", "other")


def newest_xplane(trace_dir: str) -> str:
    paths = glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"), recursive=True)
    if not paths:
        raise FileNotFoundError(f"no *.xplane.pb under {trace_dir}")
    return max(paths, key=os.path.getmtime)


def load(trace_dir: str) -> dict:
    from jax.profiler import ProfileData

    profile = ProfileData.from_file(newest_xplane(trace_dir))
    devices, in_flight, host = {}, {}, []
    for plane in profile.planes:
        if plane.name.startswith("/device:TPU:") and plane.name.split(":")[-1].isdigit():
            for line in plane.lines:
                if line.name not in (OPS_LINE, ASYNC_LINE):
                    continue
                events = [[*parse_op(ev.name), float(ev.start_ns), float(ev.duration_ns)]
                          for ev in line.events]
                if line.name == OPS_LINE:
                    devices[plane.name] = events
                else:  # copies and collectives in flight beside the ops; the copies are not read
                    in_flight[plane.name] = [e for e in events if classify(e[0], e[1]) == "collective"]
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                host.extend([ev.name, float(ev.start_ns), float(ev.duration_ns)]
                            for ev in line.events if ev.name.startswith(HOST_SPAN_PREFIX))
    return {"devices": devices, "in_flight": in_flight, "host": host}


_OPCODE = re.compile(r"\s([a-z][a-z0-9\-]*)\(")
_FUSION_KIND = re.compile(r"kind=(k\w+)")
_CALL_TARGET = re.compile(r'custom_call_target="([^"]+)"')


def parse_op(text: str) -> tuple[str, str]:
    """An ``XLA Ops`` event is named by its whole HLO instruction,
    ``%fusion.7 = bf16[..] fusion(..), kind=kOutput, calls=..``. Returns the
    instruction's name (``fusion.7``) and a category: the opcode, with the
    fusion kind (``fusion:kOutput``) or the custom call's target
    (``custom-call:tpu_custom_call``) after a colon."""
    head, _, rest = text.partition(" = ")
    found = _OPCODE.search(rest)
    category = found.group(1) if found else ""
    detail = {"fusion": _FUSION_KIND, "custom-call": _CALL_TARGET}.get(category)
    if detail and (found := detail.search(rest)):
        category += ":" + found.group(1)
    return head.lstrip("%"), category


def stem(name: str) -> str:
    """``fusion.123`` -> ``fusion``: an instruction's name without its serial."""
    return re.sub(r"[.\d]+$", "", name.lstrip("%"))


def classify(name: str, category: str) -> str:
    """matmul | flash | collective | other. A Pallas kernel is a custom call
    to ``tpu_custom_call``. XLA lowers every dot to a convolution; the fusion
    around one is an output fusion (``kind=kOutput``), whatever root op names
    it (the head's weight gradient is a ``bitcast_dynamic-update-slice_fusion``)."""
    opcode = category.split(":")[0] or stem(name)
    if opcode.startswith(COLLECTIVES):
        return "collective"
    if category == "custom-call:tpu_custom_call":
        return "flash"
    if opcode in ("convolution", "dot") or category == "fusion:kOutput" or "convolution" in name:
        return "matmul"
    return "other"


def union(intervals: list) -> list:
    """Sorted disjoint ``[start, end]`` covering the same points."""
    out = []
    for start, end in sorted(intervals):
        if out and start <= out[-1][1]:
            out[-1][1] = max(out[-1][1], end)
        else:
            out.append([start, end])
    return out


def length(intervals: list) -> float:
    return sum(end - start for start, end in intervals)


def subtract(a: list, b: list) -> list:
    """The part of disjoint sorted ``a`` that disjoint sorted ``b`` does not cover."""
    out, j = [], 0
    for start, end in a:
        while j < len(b) and b[j][1] <= start:
            j += 1
        k, at = j, start
        while k < len(b) and b[k][0] < end:
            if b[k][0] > at:
                out.append([at, b[k][0]])
            at = max(at, b[k][1])
            k += 1
        if at < end:
            out.append([at, end])
    return out


def self_times(events: list) -> list:
    """``[name, category, start, end, self_ns, is_leaf]`` per event: ``self_ns``
    is its duration less the events nested directly inside it."""
    rows = [[n, c, s, s + d, d, True] for n, c, s, d in sorted(events, key=lambda e: (e[2], -e[3]))]
    stack = []
    for row in rows:
        while stack and stack[-1][3] <= row[2]:
            stack.pop()
        if stack:
            stack[-1][4] -= row[3] - row[2]
            stack[-1][5] = False
        stack.append(row)
    return rows


def collective_spans(rows: list) -> list:
    """One ``[start, end]`` per collective: the event itself, or for an
    asynchronous pair from the start of ``x-start`` to the end of ``x-done``
    (paired first-in first-out within a kind)."""
    spans, open_starts = [], collections.defaultdict(collections.deque)
    for name, category, start, end, _, _ in rows:
        if classify(name, category) != "collective":
            continue
        s = stem(name)
        if s.endswith("-start"):
            open_starts[s[:-len("-start")]].append(start)
        elif s.endswith("-done") and open_starts[s[:-len("-done")]]:
            spans.append([open_starts[s[:-len("-done")]].popleft(), end])
        else:
            spans.append([start, end])
    return spans


def _host_label(gap: list, host: list) -> str:
    best, label = 0.0, "no_benchmark_span"
    for name, start, dur in host:
        overlap = min(gap[1], start + dur) - max(gap[0], start)
        if overlap > best:
            best, label = overlap, name
    return label


def _device(events: list, in_flight: list, host: list) -> dict:
    rows = self_times(events)
    busy = union([[r[2], r[3]] for r in rows])
    window = [busy[0][0], busy[-1][1]]
    by_kind = dict.fromkeys(KINDS, 0.0)
    by_op = collections.Counter()
    for name, category, _, _, self_ns, _ in rows:
        kind = classify(name, category)
        by_kind[kind] += self_ns
        by_op[f"{kind}:{stem(name)}"] += self_ns
    coll = union(collective_spans(rows) + [[e[2], e[2] + e[3]] for e in in_flight])
    # a while or conditional spans its body, collectives included: only leaves compute
    compute = union([[r[2], r[3]] for r in rows if r[5] and classify(r[0], r[1]) != "collective"])
    gaps = collections.Counter()
    for gap in subtract([window], busy):
        short = gap[1] - gap[0] < HOST_GAP_NS
        gaps["between_ops_under_20us" if short else _host_label(gap, host)] += gap[1] - gap[0]
    return {"window_ns": window[1] - window[0], "busy_ns": length(busy), "kind_ns": by_kind,
            "coll_ns": length(coll), "coll_exposed_ns": length(subtract(coll, compute)),
            "op_ns": by_op, "gap_ns": gaps}


def reduce(events: dict, n_steps: int) -> dict:
    """See the module docstring. Raises where no operation ran on a device."""
    per_device = [_device(evs, events.get("in_flight", {}).get(plane, []), events["host"])
                  for plane, evs in events["devices"].items() if evs]
    if not per_device:
        raise ValueError("the trace holds no device operation (no /device:TPU:n 'XLA Ops' events)")

    def median_ms_per_step(get) -> float:
        return statistics.median(get(d) for d in per_device) / 1e6 / n_steps

    def top(key: str) -> list:
        total = collections.Counter()
        for d in per_device:
            total.update(d[key])
        return [[name, ns / 1e9 / len(per_device)] for name, ns in total.most_common(10)]

    window_s = statistics.mean(d["window_ns"] for d in per_device) / 1e9
    busy_s = statistics.mean(d["busy_ns"] for d in per_device) / 1e9
    return {
        "n_steps": n_steps, "n_devices": len(per_device), "window_s": window_s, "busy_s": busy_s,
        "idle_pct": 100.0 * (1.0 - busy_s / window_s),
        "kind_ms_per_step": {k: median_ms_per_step(lambda d, k=k: d["kind_ns"][k]) for k in KINDS},
        "coll_ms_per_step": median_ms_per_step(lambda d: d["coll_ns"]),
        "coll_exposed_ms_per_step": median_ms_per_step(lambda d: d["coll_exposed_ns"]),
        "breakdown": {"device_ops": top("op_ns"), "idle_gaps": top("gap_ns")},
    }


def describe(trace_dir: str, out=sys.stdout) -> None:
    """What a trace holds, for reading by hand."""
    from jax.profiler import ProfileData

    profile = ProfileData.from_file(newest_xplane(trace_dir))
    for plane in profile.planes:
        print(f"PLANE {plane.name!r}", file=out)
        for line in plane.lines:
            events = list(line.events)
            print(f"  LINE {line.name!r}: {len(events)} events", file=out)
            total = collections.Counter()
            sample = {}
            for ev in events:
                key = stem(parse_op(ev.name)[0])
                total[key] += ev.duration_ns
                sample.setdefault(key, ev)
            for name, ns in total.most_common(12):
                ev = sample[name]
                stats = {k: str(v)[:80] for k, v in ev.stats}
                print(f"    {ns / 1e6:10.3f} ms  {name[:60]!r}  e.g. {ev.name[:400]!r} stats={stats}", file=out)


if __name__ == "__main__":
    describe(sys.argv[1])
