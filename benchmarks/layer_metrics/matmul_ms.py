"""Device time in fusions that hold a dot (XLA names them convolution fusions), ms a step."""


def read(trace, notes):
    return trace and trace["kind_ms_per_step"]["matmul"]
