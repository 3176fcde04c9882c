"""Median ``engine/put`` span of the window's steps, in ms: the host to
device copy of the batch chunk, with the runtime's layout change.  None
when the program recorded no spans."""
from bench.harness.spans import median_ms, window_spans


def read(run):
    return median_ms(window_spans(run, "engine/put"))
