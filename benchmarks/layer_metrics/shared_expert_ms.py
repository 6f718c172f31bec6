"""Device time under the program's ``shared_expert`` name (``models/deepseek_v3.py``:
the shared experts' gated SiLU MLP that every token runs beside its routed
experts, and the sum of the two): forward, recomputed forward and backward.
Own time (``benchmarks/name_reduce.py``); ``None`` where no op name of the step
holds the name. ms a step.
"""

from benchmarks import name_reduce


def read(trace, notes):
    return name_reduce.ms(trace, ("shared_expert",))
