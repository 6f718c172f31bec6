"""Device time of the rows' way into the experts' buffer and back: the program's
scopes ``moe_dispatch`` + ``moe_combine`` (``models/experts.py``: gathers both
ways), forward, recomputed forward and backward. With ``moe_route`` and
``experts`` it is ``moe_ms``. ms a step.
"""

from benchmarks import name_reduce


def read(trace, notes):
    return name_reduce.ms(trace, ("moe_dispatch", "moe_combine"))
