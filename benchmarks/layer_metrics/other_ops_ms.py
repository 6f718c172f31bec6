"""Device time in neither matmul, kernel nor collective: norms, GELU, cross entropy, the
optimizer, copies. ms a step.
"""


def read(trace, notes):
    return trace and trace["kind_ms_per_step"]["other"]
