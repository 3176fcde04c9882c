"""Least time of the forward's linears over the device time of the MXInt
linear kernels (``mxint_matmul``, ``mxint_ln_matmul``), in percent, over
the classify executions of the traced window.

Least time, per linear: the larger of 2*M*K*N at the int8 peak and its
operands and result at MXInt width at the HBM bandwidth
(``bench/work``).  Each execution serves the engine's whole batch."""
import bisect

from bench.harness.manifest import work
from bench.harness.trace import kernel_kind

KERNELS = ("mxint_matmul", "mxint_ln_matmul")


def read(run):
    if run.trace is None:
        return None
    tr = run.trace
    execs = tr.modules[0]
    if not execs:
        return None
    by_name = {}
    for m in execs:
        by_name.setdefault(m[0], []).append(m)
    execs = max(by_name.values(), key=len)
    spans = sorted((s, e) for _, s, e in execs)
    starts = [a for a, _ in spans]
    kernel_ns = 0.0
    for name, s, e in tr.ops[0]:
        i = bisect.bisect_right(starts, s) - 1
        if i >= 0 and e <= spans[i][1] and kernel_kind(name) in KERNELS:
            kernel_ns += e - s
    if not kernel_ns:
        return None
    cfg = run.cell.config
    least = len(execs) * work(cfg["family"]).linear_least_s(
        cfg, run.batch, run.peaks)
    return 100.0 * least / (kernel_ns * 1e-9)
