"""Device time of the forward computed again in the backward: every instruction
whose op name holds ``rematted_computation``, the component ``jax.checkpoint``
writes round the recomputation (kernels included; what the policy keeps, the scan
kernel's outputs in ``jamba2-3b-8k``, is not made again and not here). Own time:
an instruction of the backward proper whose fusion took a recomputed one in is in
``guest`` of the run's ``name_reduce`` note, not here. ms a step.
"""

from benchmarks import name_reduce


def read(trace, notes):
    return name_reduce.ms(trace, (name_reduce.REMAT,))
