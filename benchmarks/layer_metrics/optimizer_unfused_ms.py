"""Device time of the instructions named by the program's ``optimizer`` scope: adamw's moments
and the weight write of every leaf whose update is an instruction of its own (embeddings,
norms, biases; under dp the block weights too). Not the optimizer's cost: where XLA fuses a
block weight's update into the output fusion that makes its gradient, that fusion carries its
root's name (``attn`` or ``mlp``, backward) and is not counted here, so a fusion decision
moves this number and the optimizer's own work may not. ms a step.
"""

from benchmarks import scope_reduce


def read(trace, notes):
    return scope_reduce.scope_ms(trace, "optimizer")
