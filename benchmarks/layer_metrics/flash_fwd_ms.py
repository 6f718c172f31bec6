"""Device time of the forward flash kernel, found by the kernel's own name
(``name="flash_fwd"`` on its ``pl.pallas_call``) in the op name. ms a step.
"""

from benchmarks import scope_reduce


def read(trace, notes):
    return scope_reduce.scope_ms(trace, "flash_fwd")
