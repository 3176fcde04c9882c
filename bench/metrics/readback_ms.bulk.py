"""Median, over the window's steps, of the end of ``scheduler/fetch`` less
the end of that step's classify execution on the device, in ms, with the
program's span stamps put on the trace's clock: the wait for the result
after the device is done, and its copy back.  None when the program
recorded no spans or the trace has no session start."""
import statistics

from bench.harness.spans import match, on_trace_clock


def read(run):
    spans = on_trace_clock(run)
    if spans is None:
        return None
    back = [(fetch.end - ex[2]) * 1e-6 for ex, _, fetch in match(spans, run.trace)]
    return statistics.median(back) if back else None
