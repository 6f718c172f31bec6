"""Device time under the program's ``ssm_conv`` scope (``models/jamba.py::
_ssm_block``): the Mamba mixer's depthwise causal convolution and its SiLU; all
directions. Own time (``guest`` in the run's ``name_reduce`` note bounds what
fused into a neighbour). Part of ``ssm_ms``. ms a step.
"""

from benchmarks import name_reduce


def read(trace, notes):
    return name_reduce.ms(trace, ("ssm_conv",))
