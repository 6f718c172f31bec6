"""Device time of the backward kernel that makes dq (it recomputes q·kᵀ), found by the
kernel's own name (``name="flash_dq"`` on its ``pl.pallas_call``) in the op name. ms a step.
"""

from benchmarks import scope_reduce


def read(trace, notes):
    return scope_reduce.scope_ms(trace, "flash_dq")
