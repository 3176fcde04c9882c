"""Device time of the attention kernels, the flash kernel
(``mxint_flash_attention``, global layers) and the whole-row softmax
kernel (``mxint_softmax``, window layers), as a share of the device's
busy time in the traced window, in percent.  None where the trace holds
neither kernel."""
from bench.harness.trace import busy_s, kernel_kind, leaves

KERNELS = ("mxint_flash_attention", "mxint_softmax")


def read(run):
    if run.trace is None:
        return None
    ns = sum(e - s for ops in run.trace.ops for name, s, e in leaves(ops)
             if kernel_kind(name) in KERNELS) / len(run.trace.ops)
    if not ns:
        return None
    return 100.0 * ns * 1e-9 / busy_s(run.trace)
