"""What the compiled step holds on one device at its worst moment: arguments + temp + outputs -
aliased, from ``compiled.memory_analysis()`` (``memory_stats`` leaves the temp out on this
runtime: PERF.md §5).
"""


def read(trace, notes):
    return notes["step_memory_bytes"]["live"] / 1e9
