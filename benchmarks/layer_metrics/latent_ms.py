"""Device time under the program's ``mla_latent`` name (``models/deepseek_v3.py``:
latent attention's down projection to the latent and the rotary key, the
latent's norm, the up projection to each head's key part and value, and the
rotary key's broadcast to the heads): forward, recomputed forward and backward.
Own time, as ``norm_ms`` reads it (``benchmarks/name_reduce.py``); ``None``
where no op name of the step holds the name. ms a step.
"""

from benchmarks import name_reduce


def read(trace, notes):
    return name_reduce.ms(trace, ("mla_latent",))
