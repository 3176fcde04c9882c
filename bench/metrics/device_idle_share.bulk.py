"""Share of the measured window in which no op ran on the device:
1 - (union of device-op intervals) / window, in percent."""
from bench.harness.trace import busy_s


def read(run):
    if run.trace is None:
        return None
    return 100.0 * (1.0 - busy_s(run.trace) / run.window_s)
