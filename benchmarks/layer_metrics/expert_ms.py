"""Device time of the grouped-matmul kernels, found by their own names
(``name="gmm_fwd"`` / ``"gmm_dx"`` / ``"gmm_dw"`` on the ``pl.pallas_call``s):
every call of a step, the forward's recomputation under remat included. ms a
step.
"""

from benchmarks import moe_reduce


def read(trace, notes):
    return moe_reduce.name_ms(trace, *moe_reduce.KERNELS)
