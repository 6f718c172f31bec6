"""Summed device time of the Pallas flash kernels (``tpu_custom_call``: forward, dq, dkv), ms a
step.
"""


def read(trace, notes):
    return trace and trace["kind_ms_per_step"]["flash"]
