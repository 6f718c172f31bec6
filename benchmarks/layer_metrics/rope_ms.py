"""Device time under the program's ``rope`` scope (``_rotate`` of
``models/llama.py`` and ``models/mellum.py``): the rotation of q and k by the
layer's table; all directions. Own time (``guest`` in the run's ``name_reduce``
note bounds what fused into a neighbour). ms a step.
"""

from benchmarks import name_reduce


def read(trace, notes):
    return name_reduce.ms(trace, ("rope",))
