"""The largest expert's share of layer 0's (token, expert) pairs over the mean
share, on the ``loss_step`` batch under the weights the run ended with, counted
by the program (``Mellum.expert_load``) after the timed window: 1 is uniform
routing, ``experts / top_k`` every token on the same ``top_k``. The work of the
step does not follow it (PERF.md §6, PR 35); the metric shows how far the router
has collapsed while the cell's other numbers were taken.
"""


def read(trace, notes):
    return notes.get("moe_load_max")
