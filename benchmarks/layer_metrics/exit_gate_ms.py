"""Device time under the program's ``exit_gate`` name (``models/ouro.py``: the
gate on each pass's state, the exits' distribution, the entropy term and the
term that carries the gate's gradient), forward and backward: the exits' work
outside the head, whose four sweeps ``xent_ms`` reads under ``loss_head``. Own
time (``benchmarks/name_reduce.py``); ``None`` where no op name of the step
holds the name. ms a step.
"""

from benchmarks import name_reduce


def read(trace, notes):
    return name_reduce.ms(trace, ("exit_gate",))
