"""Host clock around ``step.lower(...)``: Python tracing of the unrolled layers and lowering to
StableHLO. Paid by every run, cached or not.
"""


def read(trace, notes):
    return notes["lower_s"]
