"""Device time of the expert layers whole: the program's scopes
``moe_route`` (router, top-k, the sort and the plan), ``moe_dispatch`` (rows
gathered into the buffer), ``experts`` (the grouped matmuls and the gate) and
``moe_combine`` (rows gathered back and summed), forward, recomputed forward and
backward, of every layer. ms a step.
"""

from benchmarks import moe_reduce


def read(trace, notes):
    return moe_reduce.name_ms(trace, *moe_reduce.SCOPES)
