"""Device time under the program's ``kv_repeat`` scope (``models/llama.py::
_qkv_gqa``): the key-value heads repeated to the query heads and, in the
backward, the sum over the repeats; all directions. Own time: where XLA fused
the repeat into a consumer the run's ``name_reduce`` note has it as ``guest``.
ms a step.
"""

from benchmarks import name_reduce


def read(trace, notes):
    return name_reduce.ms(trace, ("kv_repeat",))
