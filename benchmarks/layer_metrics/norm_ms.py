"""Device time under the program's ``normalize`` scope (``models/llama.py::
_rms_norm``, ``models/gpt2.py::_layer_norm``): every norm of the step, the
blocks' two, the final one, the Mamba mixer's three small ones; all directions.
Own time, and a floor: a norm's scale that XLA fused into the matmul that
consumes it is timed under the matmul (``guest`` in the run's ``name_reduce``
note, the matmul whole). ms a step.
"""

from benchmarks import name_reduce


def read(trace, notes):
    return name_reduce.ms(trace, ("normalize",))
