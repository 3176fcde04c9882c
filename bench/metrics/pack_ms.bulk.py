"""Median ``scheduler/pack`` span of the window's steps, in ms: the queue
walk and the filling of the batch chunk on the host.  None when the
program recorded no spans."""
from bench.harness.spans import median_ms, window_spans


def read(run):
    return median_ms(window_spans(run, "scheduler/pack"))
