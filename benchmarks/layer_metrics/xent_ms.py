"""Device time under the program's ``loss_head`` scope, forward and backward: the final norm,
the head matmul of each vocabulary chunk, the softmax, and both ``while`` loops of
``ops/xent.py``. ms a step.
"""

from benchmarks import scope_reduce


def read(trace, notes):
    return scope_reduce.scope_ms(trace, "loss_head")
