"""From the trace file alone to time by the program's own names.

The program names its work (``jax.named_scope`` on ``embed``, ``attn``,
``mlp``, ``loss_head`` and ``optimizer``; ``name=`` on the Pallas kernels
``flash_fwd``, ``flash_dq``, ``flash_dkv``). XLA carries those names as the
``op_name`` of each instruction's metadata,
``jit(step)/transpose(jvp())/attn/flash_dq/pallas_call``, and the profiler
writes each program's ``HloProto`` into the ``/host:metadata`` plane of the
same ``*.xplane.pb`` that holds the op events. So the join needs nothing but
that file:

``hlo_modules(data)`` walks the protobuf wire format (no TensorFlow, no
``xprof``) down to ``{instruction name: op_name}``, one dictionary a program;
``scope_of(op_name)`` finds the scope and the direction in an op name by whole
path components; ``reduce(events, op_names, n_steps)`` sums
``trace_reduce.self_times`` of each device's ``XLA Ops`` events by scope and
direction (a ``while`` keeps its own self time, its body's events carry their
own names) and takes the median over devices, in ms a step. What falls under
no scope is ``unscoped`` and is listed by instruction name.

``python benchmarks/scope_reduce.py <trace_dir> [n_steps]`` prints the table
(``n_steps`` is 5 unless given: what ``drivers/train.py`` traces).
"""

from __future__ import annotations

import collections
import functools
import re
import statistics
import sys
import time
from pathlib import Path

if __name__ == "__main__":
    sys.path.insert(0, str(Path(__file__).resolve().parents[1]))

from benchmarks import harness, trace_reduce  # noqa: E402

KERNELS = ("flash_fwd", "flash_dq", "flash_dkv")  # looked for first: they nest inside attn
SCOPES = ("embed", "attn", "mlp", "loss_head", "optimizer")
METADATA_PLANE = "/host:metadata"
HLO_STAT = "Hlo Proto"


# -- the protobuf wire format, as far as the walk needs it -------------------

def _varint(buf, at: int) -> tuple[int, int]:
    value = shift = 0
    while True:
        byte = buf[at]
        at += 1
        value |= (byte & 0x7F) << shift
        if byte < 0x80:
            return value, at
        shift += 7


def fields(buf):
    """``(field number, value)`` of each field of one message: an ``int`` for a
    varint, a ``memoryview`` for a length-delimited field; fixed 32- and
    64-bit fields are skipped."""
    at, end = 0, len(buf)
    while at < end:
        key, at = _varint(buf, at)
        number, wire = key >> 3, key & 7
        if wire == 0:
            value, at = _varint(buf, at)
            yield number, value
        elif wire == 2:
            size, at = _varint(buf, at)
            yield number, buf[at:at + size]
            at += size
        elif wire in (1, 5):
            at += 8 if wire == 1 else 4
        else:
            raise ValueError(f"wire type {wire} at byte {at}: not a protobuf message")


def _sub(buf, number: int):
    return (value for n, value in fields(buf) if n == number)


def _text(buf, number: int) -> str:
    return next((bytes(v).decode() for v in _sub(buf, number)), "")


def _map_values(buf, number: int):
    """The values of a ``map<int64, Message>`` field (entries hold key=1, value=2)."""
    for entry in _sub(buf, number):
        yield from _sub(entry, 2)


def hlo_modules(data: bytes) -> list[tuple[str, dict]]:
    """``[(module name, {instruction name: op_name})]`` from an xplane file's
    bytes: XSpace.planes=1, XPlane.name=2 / .event_metadata=4 / .stat_metadata=5,
    XEventMetadata.stats=5, XStat.metadata_id=1 / .bytes_value=6,
    HloProto.hlo_module=1, HloModuleProto.name=1 / .computations=3,
    HloComputationProto.instructions=2, HloInstructionProto.name=1 / .metadata=7,
    OpMetadata.op_name=2. Empty where the file has no such plane."""
    modules = []
    for plane in _sub(memoryview(data), 1):
        if _text(plane, 2) != METADATA_PLANE:
            continue
        hlo_stat_ids = {next(_sub(meta, 1), 0) for meta in _map_values(plane, 5)
                        if _text(meta, 2) == HLO_STAT}
        for event_meta in _map_values(plane, 4):
            for stat in _sub(event_meta, 5):
                if next(_sub(stat, 1), 0) not in hlo_stat_ids:
                    continue
                for hlo_proto in _sub(stat, 6):
                    for module in _sub(hlo_proto, 1):
                        modules.append((_text(module, 1), _module_op_names(module)))
    return modules


def _module_op_names(module) -> dict:
    names = {}
    for computation in _sub(module, 3):
        for instruction in _sub(computation, 2):
            metadata = next(_sub(instruction, 7), None)
            names[_text(instruction, 1)] = _text(metadata, 2) if metadata is not None else ""
    return names


# -- from an op name to a scope ----------------------------------------------

@functools.lru_cache(maxsize=None)  # asked once an event, answered once an instruction
def scope_of(op_name: str) -> tuple[str, str] | None:
    """``(scope, "fwd" | "bwd")`` or ``None``. An op name is a path,
    ``jit(step)/transpose(jvp())/attn/flash_dq/pallas_call``; instructions XLA
    merged carry several joined by ``;`` and the first that names a scope
    decides. Whole components only: ``params['layers'][7]['attn']['wo']`` is
    one component and names no scope. ``bwd`` where a ``transpose`` precedes
    the scope."""
    for one in op_name.split(";"):
        tokens = re.split(r"[/()]", one)
        for wanted in (KERNELS, SCOPES):
            at = next((i for i, token in enumerate(tokens) if token in wanted), None)
            if at is not None:
                return tokens[at], "bwd" if "transpose" in tokens[:at] else "fwd"
    return None


# -- from events to milliseconds by scope ------------------------------------

def pick_module(modules: list, events: dict) -> dict:
    """The program whose instruction names cover the most op events: the step
    (the batch's transfer programs are tiny)."""
    seen = collections.Counter(e[0] for evs in events["devices"].values() for e in evs)
    covered = [sum(n for name, n in seen.items() if name in op_names) for _, op_names in modules]
    return modules[covered.index(max(covered))][1] if modules else {}


def reduce(events: dict, op_names: dict, n_steps: int) -> dict:
    """See the module docstring. ``events`` is what ``trace_reduce.load`` returns."""
    per_device, unscoped_ops = [], collections.Counter()
    for evs in events["devices"].values():
        if not evs:
            continue
        by_scope = collections.Counter()
        for name, _category, _start, _end, self_ns, _leaf in trace_reduce.self_times(evs):
            found = scope_of(op_names.get(name, ""))
            by_scope[found] += self_ns
            if found is None:
                unscoped_ops[trace_reduce.stem(name)] += self_ns
        per_device.append(by_scope)
    if not per_device:
        raise ValueError("the trace holds no device operation (no /device:TPU:n 'XLA Ops' events)")

    def median_ms(get) -> float:
        return statistics.median(get(d) for d in per_device) / 1e6 / n_steps

    scope_ms: dict = {}
    for scope, direction in sorted({k for d in per_device for k in d if k}):
        scope_ms.setdefault(scope, {"fwd": 0.0, "bwd": 0.0})[direction] = median_ms(
            lambda d: d[(scope, direction)])
    return {
        "scope_ms_per_step": scope_ms,
        "unscoped_ms_per_step": median_ms(lambda d: d[None]),
        "busy_ms_per_step": median_ms(lambda d: sum(d.values())),
        "top_unscoped": [[name, ns / 1e6 / n_steps / len(per_device)]
                         for name, ns in unscoped_ops.most_common(10)],
    }


def read_dir(trace_dir: str, n_steps: int) -> dict:
    """The newest ``*.xplane.pb`` under ``trace_dir``, joined and reduced."""
    events = trace_reduce.load(trace_dir)
    modules = hlo_modules(Path(trace_reduce.newest_xplane(trace_dir)).read_bytes())
    return reduce(events, pick_module(modules, events), n_steps)


@functools.lru_cache(maxsize=None)
def newest(n_steps: int) -> dict:
    """The run's own trace: the driver has just removed and rewritten the
    cell's directory under ``.bench_trace``. Parsed once a process; every
    reader of ``layer_metrics`` that goes by scope calls this."""
    t0 = time.perf_counter()
    out = read_dir(str(harness.ROOT / ".bench_trace"), n_steps)
    harness.note(phase="scope_reduce", seconds=time.perf_counter() - t0, **out)
    return out


def scope_ms(trace, scope: str):
    """What a per-layer reader returns: ms a step under ``scope``, forward and
    backward, or ``None`` where the run has no device trace or the program no
    such name (the parent of the PR that brought the names)."""
    if not trace:
        return None
    found = newest(trace["n_steps"])["scope_ms_per_step"].get(scope)
    return found and found["fwd"] + found["bwd"]


def describe(trace_dir: str, n_steps: int, out=sys.stdout) -> None:
    table = read_dir(trace_dir, n_steps)
    busy = table["busy_ms_per_step"]
    print(f"{'scope':<12}{'fwd ms':>10}{'bwd ms':>10}{'share':>8}", file=out)
    for scope, ms in table["scope_ms_per_step"].items():
        share = 100 * (ms["fwd"] + ms["bwd"]) / busy
        print(f"{scope:<12}{ms['fwd']:>10.3f}{ms['bwd']:>10.3f}{share:>7.1f}%", file=out)
    unscoped = table["unscoped_ms_per_step"]
    print(f"{'unscoped':<12}{unscoped:>20.3f}{100 * unscoped / busy:>7.1f}%", file=out)
    print(f"{'busy':<12}{busy:>20.3f}", file=out)
    for name, ms in table["top_unscoped"]:
        print(f"  unscoped {ms:>9.3f} ms  {name}", file=out)


if __name__ == "__main__":
    describe(sys.argv[1], int(sys.argv[2]) if len(sys.argv) > 2 else 5)
