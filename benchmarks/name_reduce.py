"""Device time by any name an op name holds, in three directions, with what
leaked into a neighbour's fusion beside it: the one reader that takes names as
data.

The join is ``scope_reduce.py``'s (its ``hlo_modules`` / ``pick_module`` give
``{instruction: op_name}`` of the step from the trace file's metadata plane,
``trace_reduce.load`` / ``self_times`` the events), the table is wider:

- **every whole component is a name**: ``jit(step)/transpose(jvp())/checkpoint/
  rematted_computation/attn/kv_repeat/broadcast_in_dim`` counts under ``attn``,
  under ``kv_repeat`` and under ``rematted_computation`` (as in
  ``moe_reduce.py``, a kernel under its own name and under its scope's). Nothing
  here lists the names: a reader asks for the ones it wants, so a name a later
  PR sets in the program needs a ``layer_metrics`` file and no new parser;
- **a direction** for each: ``remat`` where ``rematted_computation`` (what
  ``jax.checkpoint`` writes round the forward it computes again) is among the
  components, else ``bwd`` where a ``transpose`` precedes the name, else
  ``fwd``. Of instructions XLA merged (``;``) the first part that holds the name
  decides. ``fwd + remat + bwd`` of a name is what ``scope_reduce`` /
  ``ssm_reduce`` / ``moe_reduce`` read for it;
- **own and guest time**. An instruction carries one op name, its root's, so a
  norm's multiply that XLA fused into the matmul that consumes it is timed under
  the matmul. The ``HloProto`` keeps each fused computation's instructions with
  their own op names (``fusion_bodies``): ``own`` is the time of instructions
  whose own op name holds the name, ``guest`` the WHOLE time of fusions whose op
  name does not but whose body does: an upper bound on what leaked, loose by
  construction (the matmul is counted whole for the one multiply).

``python benchmarks/name_reduce.py <trace_dir> [n_steps] [name ...]`` prints name
x (fwd, remat, bwd, guest) for the names the program sets (``NAMES``) or the ones
given, and under each the fusions that hold most of its guest time (``n_steps`` is
5 unless given: what ``drivers/train.py`` traces).
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

from benchmarks import harness, scope_reduce, trace_reduce  # noqa: E402

REMAT = "rematted_computation"
DIRECTIONS = ("fwd", "remat", "bwd")
# What the program names as of PR 37, for the table a person reads (the command,
# the run's notes). Readers do not go through it: `ms` takes any component.
NAMES = ("embed", "attn", "attn_window", "attn_full", "kv_repeat", "rope", "mlp", "moe_route",
         "moe_dispatch", "experts", "moe_combine", "ssm", "ssm_conv", "normalize", "loss_head",
         "optimizer", REMAT, "flash_fwd", "flash_dq", "flash_dkv", "ssm_scan_fwd", "ssm_scan_bwd",
         "gmm_fwd", "gmm_dx", "gmm_dw")


# -- from the trace file to {fusion: its body's instructions} ------------------

def _packed(value) -> list[int]:
    """A ``repeated int64`` field's values: one varint, or several packed."""
    if isinstance(value, int):
        return [value]
    out, at = [], 0
    while at < len(value):
        one, at = scope_reduce._varint(value, at)
        out.append(one)
    return out


def fusion_bodies(data: bytes) -> list[tuple[str, dict]]:
    """``[(module name, {fusion instruction: [its body's instruction names]})]``,
    one entry a program in ``scope_reduce.hlo_modules``'s order (the same walk;
    see its docstring for the field numbers down to the module), from
    HloComputationProto.id=5 / .instructions=2 and HloInstructionProto.name=1 /
    .opcode=2 / .called_computation_ids=38. Instruction names are unique in a
    module, so the body's op names are in ``hlo_modules``'s dictionary."""
    sub, text = scope_reduce._sub, scope_reduce._text
    modules = []
    for plane in sub(memoryview(data), 1):
        if text(plane, 2) != scope_reduce.METADATA_PLANE:
            continue
        hlo_stat_ids = {next(sub(meta, 1), 0) for meta in scope_reduce._map_values(plane, 5)
                        if text(meta, 2) == scope_reduce.HLO_STAT}
        for event_meta in scope_reduce._map_values(plane, 4):
            for stat in sub(event_meta, 5):
                if next(sub(stat, 1), 0) not in hlo_stat_ids:
                    continue
                for hlo_proto in sub(stat, 6):
                    for module in sub(hlo_proto, 1):
                        modules.append((text(module, 1), _module_fusion_bodies(module)))
    return modules


def _module_fusion_bodies(module) -> dict:
    sub, text = scope_reduce._sub, scope_reduce._text
    members, calls = {}, {}
    for computation in sub(module, 3):
        instructions = list(sub(computation, 2))
        members[next(sub(computation, 5), 0)] = [text(i, 1) for i in instructions]
        for instruction in instructions:
            if text(instruction, 2) == "fusion":  # name and opcode lead the message: only a fusion is read through
                calls[text(instruction, 1)] = [
                    called for value in sub(instruction, 38) for called in _packed(value)]
    return {name: [member for called in ids for member in members.get(called, [])]
            for name, ids in calls.items()}


def step_program(data: bytes, events: dict) -> tuple[dict, dict]:
    """``({instruction: op_name}, {fusion: body instructions})`` of the program
    whose instructions cover the most op events (``scope_reduce.pick_module``)."""
    modules = scope_reduce.hlo_modules(data)
    op_names = scope_reduce.pick_module(modules, events)
    bodies = next((found for (_, ops), (_, found) in zip(modules, fusion_bodies(data)) if ops is op_names), {})
    return op_names, bodies


# -- from an op name to names and directions -----------------------------------

@functools.lru_cache(maxsize=None)  # asked once an instruction a device
def directions_of(op_name: str) -> tuple:
    """``((component, "fwd" | "remat" | "bwd"), ...)``, every whole component of
    the op name once (``params['layers'][7]['attn']['wo']`` is one component and
    not ``attn``)."""
    found = {}
    for one in op_name.split(";"):
        tokens = re.split(r"[/()]", one)
        recomputed = REMAT in tokens
        transposed = tokens.index("transpose") if "transpose" in tokens else len(tokens)
        for at, token in enumerate(tokens):
            if token and token not in found:
                found[token] = "remat" if recomputed else "bwd" if at > transposed else "fwd"
    return tuple(found.items())


def guest_names(op_names: dict, bodies: dict) -> dict:
    """``{fusion: the components its body holds and its own op name does not}``."""
    def components(instruction: str) -> set:
        return {name for name, _ in directions_of(op_names.get(instruction, ""))}

    return {fusion: set().union(*map(components, members)) - components(fusion)
            for fusion, members in bodies.items()}


# -- from events to milliseconds by name ---------------------------------------

def _by_instruction(events: dict) -> list:
    """Self time by instruction name, one counter a device that ran anything."""
    per_device = []
    for evs in events["devices"].values():
        if evs:
            by_instruction = collections.Counter()
            for name, _category, _start, _end, self_ns, _leaf in trace_reduce.self_times(evs):
                by_instruction[name] += self_ns
            per_device.append(by_instruction)
    return per_device


def reduce(events: dict, op_names: dict, bodies: dict, n_steps: int) -> dict:
    """``{name: {"fwd", "remat", "bwd", "own", "guest"}}`` in ms a step, for
    every component of every op name among the events or inside their fusions;
    the median over devices of the self times of each device's ``XLA Ops``
    events. Empty where no device ran anything."""
    guests, per_device = guest_names(op_names, bodies), []
    for by_instruction in _by_instruction(events):
        by_name = collections.Counter()
        for instruction, self_ns in by_instruction.items():
            for name, direction in directions_of(op_names.get(instruction, "")):
                by_name[name, direction] += self_ns
            for name in guests.get(instruction, ()):
                by_name[name, "guest"] += self_ns
        per_device.append(by_name)
    table: dict = {}
    for name, column in sorted({key for d in per_device for key in d}):
        row = table.setdefault(name, dict.fromkeys((*DIRECTIONS, "own", "guest"), 0.0))
        row[column] = statistics.median(d[name, column] for d in per_device) / 1e6 / n_steps
    for row in table.values():
        row["own"] = sum(row[direction] for direction in DIRECTIONS)
    return table


def hosts(events: dict, op_names: dict, bodies: dict, n_steps: int, most: int = 3) -> dict:
    """``{name: [[fusion's stem, its own op name, ms a step], ...]}``: for each
    name the fusions that hold most of its guest time, summed by stem and op
    name, the mean over devices: what swallowed the work its own time lacks."""
    guests, per_device = guest_names(op_names, bodies), _by_instruction(events)
    total = collections.defaultdict(collections.Counter)
    for by_instruction in per_device:
        for instruction, self_ns in by_instruction.items():
            for name in guests.get(instruction, ()):
                total[name][trace_reduce.stem(instruction), op_names.get(instruction, "")] += self_ns
    return {name: [[stem, op_name, ns / 1e6 / n_steps / len(per_device)]
                   for (stem, op_name), ns in by_host.most_common(most)]
            for name, by_host in total.items()}


def _parse(trace_dir: str) -> tuple[dict, dict, dict]:
    events = trace_reduce.load(trace_dir)
    return (events, *step_program(Path(trace_reduce.newest_xplane(trace_dir)).read_bytes(), events))


def read_dir(trace_dir: str, n_steps: int) -> dict:
    """The newest ``*.xplane.pb`` under ``trace_dir``, joined and reduced."""
    return reduce(*_parse(trace_dir), n_steps)


@functools.lru_cache(maxsize=None)
def newest(n_steps: int) -> dict:
    """The run's own trace (the driver has just rewritten the cell's directory
    under ``.bench_trace``), parsed once a process; the rows of ``NAMES`` go into
    the run's notes."""
    t0 = time.perf_counter()
    table = read_dir(str(harness.ROOT / ".bench_trace"), n_steps)
    harness.note(phase="name_reduce", seconds=time.perf_counter() - t0,
                 ms_per_step={name: table[name] for name in NAMES if name in table})
    return table


def ms(trace, names, direction: str | None = None):
    """What a per-layer reader returns: own ms a step under ``names`` together
    (each a whole component; ask for names that do not nest, or their time counts
    twice), in one direction or all three. ``None`` where the run has no device
    trace or no op name of the step, an instruction's or inside a fusion, holds
    any of them; a name only fusions' bodies hold reads 0, not nothing."""
    if not trace:
        return None
    table = newest(trace["n_steps"])
    found = [table[name] for name in names if name in table]
    return sum(row[direction or "own"] for row in found) if found else None


def describe(trace_dir: str, n_steps: int, names=(), out=sys.stdout) -> None:
    events, op_names, bodies = _parse(trace_dir)
    table, swallowed = reduce(events, op_names, bodies, n_steps), hosts(events, op_names, bodies, n_steps)
    print(f"{'name':<22}{'fwd ms':>10}{'remat ms':>10}{'bwd ms':>10}{'guest ms':>10}", file=out)
    for name in names or [known for known in NAMES if known in table]:
        row = table.get(name)
        if row is None:
            print(f"{name:<22}{'no op name of the step holds it':>40}", file=out)
            continue
        print(f"{name:<22}{row['fwd']:>10.3f}{row['remat']:>10.3f}{row['bwd']:>10.3f}{row['guest']:>10.3f}",
              file=out)
        for stem, op_name, guest_ms in swallowed.get(name, ()):
            print(f"  guest of {guest_ms:>9.3f} ms  {stem}  {op_name}", file=out)


if __name__ == "__main__":
    steps = int(sys.argv[2]) if len(sys.argv) > 2 else 5
    describe(sys.argv[1], steps, sys.argv[3:])
