"""Device time of the two selective-scan kernels, found by their own names
(``name="ssm_scan_fwd"`` / ``"ssm_scan_bwd"`` on the ``pl.pallas_call``s): every
call of a step, the forward's recomputation under remat included. ms a step.
"""

from benchmarks import ssm_reduce


def read(trace, notes):
    return ssm_reduce.name_ms(trace, *ssm_reduce.KERNELS)
