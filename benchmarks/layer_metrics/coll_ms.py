"""Time with a collective in flight on the median device (for an asynchronous pair, from the
start of ``-start`` to the end of ``-done``), ms a step. Nothing where the program holds no
collective.
"""


def read(trace, notes):
    return (trace and trace["coll_ms_per_step"]) or None
