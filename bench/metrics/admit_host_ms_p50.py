"""Median duration of the program's ``serve.admit`` span in the traced
window: the host time of one admit, from the slot's padding to the
dispatch of the admit program, on the profiler's clock."""
from harness import common, spans


def read(rec):
    d = [dur for _, _, dur, _ in spans.within(rec, "serve.admit")]
    return None if not d else common.percentile(d, 50) / 1e6
