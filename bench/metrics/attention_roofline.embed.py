"""Least time of the global layers' attention over the device time of the
flash attention kernel (``mxint_flash_attention``), in percent, over the
classify executions of the traced window.

Least time: the larger of the scores', relative-position terms' and
p @ V's FLOPs at the bf16 peak and q, k, v and the output at MXInt8
width at the HBM bandwidth (``bench/work/<family>.py``
``global_attention_least_s``).  Each execution serves the engine's whole
batch.  None where the family counts no such attention or the trace
holds no such kernel."""
import bisect

from bench.harness.manifest import work
from bench.harness.trace import kernel_kind

KERNELS = ("mxint_flash_attention",)


def read(run):
    if run.trace is None:
        return None
    cfg = run.cell.config
    least_s = getattr(work(cfg["family"]), "global_attention_least_s", None)
    if least_s is None:
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
    least = len(execs) * least_s(cfg, run.batch, run.peaks)
    return 100.0 * least / (kernel_ns * 1e-9)
