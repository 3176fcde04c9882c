"""Profiler trace of the measured window, reduced to device intervals.

The JAX profiler writes an ``.xplane.pb``; ``jax.profiler.ProfileData``
reads it.  On a TPU the device plane ``/device:TPU:<n>`` has a line
``XLA Ops`` (one event per executed HLO instruction, named by the
instruction's text, e.g. ``%mxint_matmul.10 = f32[...] custom-call(...)``)
and a line ``XLA Modules`` (one event per program execution).

The host tracer is off (``host_tracer_level`` 0).  At level 1 it records
about 1e5 events per DeiT step from the runtime's host-side transpose of
the image batch, which slows a step 2.5-fold; the window would then no
longer be the one an untraced run measures.  So the trace holds device
events only.  The trace starts before the window's first step and stops
after its last step has returned, and device work happens only inside
steps, so every device event lies in the window; the window's length is
the host clock's, as ``images_per_s`` takes it.

The trace directory is made under ``TMPDIR`` and deleted once read.
"""
from __future__ import annotations

import contextlib
import dataclasses
import glob
import os
import shutil
import sys
import tempfile
import time


@dataclasses.dataclass
class Trace:
    """Device intervals in ns on the trace's clock, each (name, start, end)."""
    ops: list        # device XLA ops, one list per device
    modules: list    # device program executions, one list per device


def union_ns(intervals, lo: float, hi: float) -> float:
    """Length of the union of (name, start, end) intervals inside [lo, hi]."""
    total, cur_s, cur_e = 0.0, None, None
    for _, s, e in sorted(intervals, key=lambda x: x[1]):
        s, e = max(s, lo), min(e, hi)
        if e <= s:
            continue
        if cur_e is None or s > cur_e:
            if cur_e is not None:
                total += cur_e - cur_s
            cur_s, cur_e = s, e
        else:
            cur_e = max(cur_e, e)
    if cur_e is not None:
        total += cur_e - cur_s
    return total


def leaves(events):
    """Drop container events (an XLA ``while`` holds its body's ops): an
    event that another event of the same line starts inside."""
    ev = sorted(events, key=lambda x: (x[1], -x[2]))
    return [e for i, e in enumerate(ev)
            if not (i + 1 < len(ev) and ev[i + 1][1] < e[2])]


def busy_s(trace: Trace) -> float:
    """Seconds with an op running on the device, averaged over devices."""
    inf = float("inf")
    per = [union_ns(ops, -inf, inf) for ops in trace.ops]
    return sum(per) / len(per) * 1e-9


def short_name(event_name: str) -> str:
    """``%mxint_matmul.10 = f32[...] ...`` -> ``mxint_matmul.10``."""
    return event_name.split(" = ", 1)[0].lstrip("%")


def kernel_kind(event_name: str) -> str:
    """``mxint_matmul.10`` -> ``mxint_matmul``."""
    return short_name(event_name).rsplit(".", 1)[0]


def module_name(event_name: str) -> str:
    """``jit_logits(15301814030787476212)`` -> ``jit_logits``."""
    return event_name.split("(", 1)[0]


def idle_gaps(trace: Trace, top: int = 10):
    """The longest gaps between device ops, each labelled by where it lies:
    ``in <program>`` inside a program execution (the device waits on
    itself), ``between executions`` outside one (the host holds the
    device back: results read back, the next batch packed and copied)."""
    ops = sorted(trace.ops[0], key=lambda x: x[1])
    gaps, t = [], None
    for _, s, e in ops:
        if t is not None and s > t:
            gaps.append((t, s))
        t = e if t is None else max(t, e)
    mods = trace.modules[0]
    out = []
    for s, e in sorted(gaps, key=lambda g: g[0] - g[1])[:top]:
        inside = [m for m in mods if m[1] <= s and e <= m[2]]
        label = (f"in {module_name(inside[0][0])}" if inside
                 else "between executions")
        out.append([label, (e - s) * 1e-9])
    return out


def top_ops(trace: Trace, top: int = 10):
    """The device ops that took most time, by name."""
    tot = {}
    for ops in trace.ops:
        for name, s, e in leaves(ops):
            k = short_name(name)
            tot[k] = tot.get(k, 0.0) + (e - s) * 1e-9
    return [[k, v] for k, v in sorted(tot.items(), key=lambda kv: -kv[1])[:top]]


def read(path: str) -> Trace:
    import jax
    pd = jax.profiler.ProfileData.from_file(path)
    ops, modules = [], []
    for plane in pd.planes:
        if plane.name.startswith("/device:TPU:"):
            lines = {ln.name: ln for ln in plane.lines}
            ops.append([(e.name, e.start_ns, e.end_ns)
                        for e in lines["XLA Ops"].events])
            modules.append([(e.name, e.start_ns, e.end_ns)
                            for e in lines["XLA Modules"].events])
    if not ops or not all(ops):
        raise RuntimeError(f"trace has {len(ops)} TPU planes, "
                           f"{sum(map(bool, ops))} with device ops")
    return Trace(ops=ops, modules=modules)


@contextlib.contextmanager
def capture(out: list):
    """Trace the device in the body; appends the reduced ``Trace`` to
    ``out``."""
    import jax
    d = tempfile.mkdtemp(prefix="bench_trace_")
    opts = jax.profiler.ProfileOptions()
    opts.host_tracer_level = 0
    opts.python_tracer_level = 0
    opts.enable_hlo_proto = False
    try:
        jax.profiler.start_trace(d, profiler_options=opts)
        try:
            yield
        finally:
            jax.profiler.stop_trace()
        files = glob.glob(f"{d}/**/*.xplane.pb", recursive=True)
        if len(files) != 1:
            raise RuntimeError(f"profiler wrote {len(files)} trace files")
        t = time.perf_counter()
        out.append(read(files[0]))
        print(f"bench: trace {os.path.getsize(files[0])} bytes, read in "
              f"{time.perf_counter() - t:.3f} s", file=sys.stderr)
    finally:
        shutil.rmtree(d, ignore_errors=True)
