"""Host clock around making one batch (the input pipeline), median over the window. It runs
while the device works on the step before, so it costs throughput only where it nears
``step_ms_p50``.
"""

import statistics


def read(trace, notes):
    return statistics.median(notes["batch_ms"])
