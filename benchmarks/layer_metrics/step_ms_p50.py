"""Median gap between consecutive loss-ready times in the window."""

import statistics


def read(trace, notes):
    return statistics.median(notes["step_ms"])
