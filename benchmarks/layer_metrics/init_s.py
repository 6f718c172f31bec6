"""Host clock around ``init_hybrid`` (weights drawn on the device from the seed, placed on the
mesh, optimizer state built).
"""


def read(trace, notes):
    return notes["init_s"]
