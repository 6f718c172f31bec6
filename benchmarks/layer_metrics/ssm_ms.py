"""Device time under the program's ``ssm`` scope, forward and backward (under
whole-block remat the recomputed forward too): the whole Mamba mixer of every
such layer, from its norm through ``W_in``, the convolution, ``W_x``, the three
small norms, ``W_dt``, the scan kernels, the gate and ``W_out`` to the residual
add. ms a step.
"""

from benchmarks import ssm_reduce


def read(trace, notes):
    return ssm_reduce.name_ms(trace, ssm_reduce.SCOPE)
