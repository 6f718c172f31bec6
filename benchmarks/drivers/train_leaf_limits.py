"""The loop for cells of kind ``train_leaf_limits``: ``drivers/train_experts.py``'s
loop and comparison, with leaves that are each held to a limit of their own.

The traffic file's ``check.leaf_limits`` maps the end of a leaf's path (as
``train_experts.moment_errors`` writes it, ``"['moe']['router']"``) to that
leaf's limit on ``|program - reference| / |reference|`` of adam's first moment.
A leaf whose path ends so is held to its own limit and to nothing else; every
other leaf is judged as ``train_experts.judge`` judges it, under
``check.first_moment_tolerance``. A limit of its own is for a leaf whose
reading by its nature lies apart from the others': a sigmoid router's gradient
reaches the chosen experts' columns alone, so a token whose last chosen and
first unchosen scores lie closer than the program's bfloat16 rounding gives it
to another column on each side (PERF.md §4). One limit for all would either
leave that leaf unjudged or loosen the limit of every other.

``train_experts`` has no seam for this, so its ``judge`` is swapped while the
loop runs, as ``train_experts`` swaps ``train.build_step``.
"""

from __future__ import annotations

from benchmarks import harness
from benchmarks.drivers import train_experts

_judge_rest = train_experts.judge


def judge(check: dict, errors: dict) -> tuple[bool, dict]:
    """Whether every leaf is within its limit, and the note that says why: the
    leaves ``check["leaf_limits"]`` names by their own limits (``leaf_limits``
    in the note, each leaf's error beside its limit), the rest by
    ``train_experts.judge``. A NaN is not correct."""
    limits = check["leaf_limits"]

    def limit_of(path: str):
        return next((limit for end, limit in limits.items() if path.endswith(end)), None)

    own = {path: {"error": float(e), "limit": limit_of(path)} for path, e in errors.items()
           if limit_of(path) is not None}
    ok, note = _judge_rest(check, {path: e for path, e in errors.items() if path not in own})
    # every named leaf read (a view that dropped one would judge nothing), each within its limit; a NaN compares false
    by_leaf = (all(any(path.endswith(end) for path in own) for end in limits)
               and all(leaf["error"] <= leaf["limit"] for leaf in own.values()))
    note["checks"]["leaf_limits"] = by_leaf
    note["leaf_limits"] = own
    return ok and by_leaf, note


def run(r: harness.Run) -> dict:
    train_experts.judge = judge
    try:
        return train_experts.run(r)
    finally:
        train_experts.judge = _judge_rest
