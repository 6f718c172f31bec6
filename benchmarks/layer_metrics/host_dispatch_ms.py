"""Host clock around the ``step(...)`` call alone (the enqueue, not the wait): median over the
window, ms a step.
"""

import statistics


def read(trace, notes):
    return statistics.median(notes["dispatch_ms"])
