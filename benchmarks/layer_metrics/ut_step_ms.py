"""Device time under the program's ``ut_step`` name (``models/stack.py``'s walk,
where a family applies its layers more than once a step: each pass of
``models/ouro.py``'s looped stack, its blocks' attention, kernels, MLP and norms
and the final norm that ends the pass): forward, recomputed forward and
backward. The recurrent stack's share of the step, by the rules of
``benchmarks/name_reduce.py`` (own time); ``None`` where no op name of the step
holds the name. ms a step.
"""

from benchmarks import name_reduce


def read(trace, notes):
    return name_reduce.ms(trace, ("ut_step",))
