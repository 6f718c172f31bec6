"""Share of the device's busy time that falls under one of the program's names (the five
scopes and the three kernels). The guard on the join itself: a dropped scope shows here as a
lower share, and a program or a trace file that carries no name at all (the parent of the PR
that brought the names, a renamed metadata plane, a compiler that drops op names) as 0, not
as six metrics gone without a sound. Nothing only where the run has no device trace.
"""

from benchmarks import scope_reduce


def read(trace, notes):
    if not trace:
        return None
    table = scope_reduce.newest(trace["n_steps"])
    return 100.0 * (1.0 - table["unscoped_ms_per_step"] / table["busy_ms_per_step"])
