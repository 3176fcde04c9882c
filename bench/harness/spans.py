"""The program's own spans, joined to the device trace on one clock.

Inside ``repro.telemetry.recording()`` the program keeps a record of each
span it closes: name, id, parent, and start and end stamps on the host's
``perf_counter_ns``; the recorder converts a stamp to ns since the epoch.
The profiler gives each device event's start in ns from the start of the
profiling session, and that start in epoch ns as the stat
``profile_start_time`` of the plane ``Task Environment``.  So a span
stamp on the trace's clock is ``epoch_ns(stamp) - profile_start_time``.

The spans of one ``ClassifyScheduler`` step (``scheduler/step`` with
``pack``, ``classify_step`` holding ``engine/put``, ``engine/dispatch``
and ``scheduler/fetch``, then ``deliver``) and the collector's
``python/gc`` spans say what the host did at each moment.  Request
lifetimes (``scheduler/request``, ``scheduler/queue_wait``) overlap
everything and name nothing.

``window`` runs ``load.window``'s traced closed loop with the recorder
on (kept on through the drain, so every request sent in the window has
its queue wait); a program without the recorder gives ``spans`` None,
and every reader here then gives what it gives without spans.
"""
from __future__ import annotations

import bisect
import contextlib
import dataclasses
import glob
import os
import shutil
import statistics
import sys
import tempfile
import time

import numpy as np

from bench.harness import load, program
from bench.harness import trace as TR

REQUEST_SPANS = ("scheduler/request", "scheduler/queue_wait")


@dataclasses.dataclass
class SpanTrace(TR.Trace):
    """A ``Trace`` with the session's start in epoch ns (None if the
    profiler did not write it)."""
    profile_start_time: int | None = None


@dataclasses.dataclass
class SpanRun(load.Run):
    """A ``Run`` with the program's span recorder (None without one)."""
    spans: object = None


def profile_start_ns(profile) -> int | None:
    """``profile_start_time`` of a ``jax.profiler.ProfileData``."""
    for plane in profile.planes:
        if plane.name == "Task Environment":
            for key, value in plane.stats:
                if key == "profile_start_time":
                    return int(value)
    return None


def read(path: str) -> SpanTrace:
    import jax
    tr = TR.read(path)
    start = profile_start_ns(jax.profiler.ProfileData.from_file(path))
    return SpanTrace(ops=tr.ops, modules=tr.modules, profile_start_time=start)


@contextlib.contextmanager
def capture(out: list):
    """``trace.capture``, keeping the session's start: appends a
    ``SpanTrace`` to ``out``."""
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


def recorder(on: bool):
    """The program's ``telemetry.recording()`` when ``on`` and the program
    has one, else a block that yields None."""
    from repro import telemetry as T
    rec = getattr(T, "recording", None)
    return rec() if on and rec is not None else contextlib.nullcontext()


def window(s: load.Setup, seconds: float, peaks: dict,
           record: bool) -> SpanRun:
    """``load.window`` traced, with the span recorder on when ``record``."""
    rng = np.random.default_rng([s.seed, 2])
    s.sched.finished.clear()
    srv = load._Server(s)
    if s.cell.traffic["loop"] != "closed":
        raise ValueError(f"unknown loop {s.cell.traffic['loop']!r}")
    before = program.counters()
    traces: list = []
    with recorder(record) as rec:
        with capture(traces):
            t0, t1, sent = load.closed_loop(srv, seconds, rng)
        after = program.counters()
        srv.drain()
    return SpanRun(cell=s.cell, batch=s.batch, setup_s=s.setup_s, t0=t0,
                   t1=t1,
                   steps=[x for x in srv.steps if x[0] >= t0 and x[1] <= t1],
                   requests=srv.requests,
                   counters={k: after[k] - before[k] for k in after},
                   peaks=peaks, trace=traces[0], in_window=sent, spans=rec)


# --- reading the spans ------------------------------------------------------

def window_spans(run, name: str) -> list:
    """Records of ``name`` that lie in the window (host clock), or []."""
    rec = getattr(run, "spans", None)
    if rec is None:
        return []
    lo, hi = run.t0 * 1e9, run.t1 * 1e9
    return [r for r in rec.spans
            if r.name == name and lo <= r.start and r.end <= hi]


def median_ms(records) -> float | None:
    return (statistics.median((r.end - r.start) * 1e-6 for r in records)
            if records else None)


def on_trace_clock(run) -> list | None:
    """Every record with its stamps on the trace's clock, or None when the
    run has no spans or the trace no session start."""
    rec = getattr(run, "spans", None)
    start = getattr(run.trace, "profile_start_time", None)
    if rec is None or start is None:
        return None
    return [r._replace(start=rec.epoch_ns(r.start) - start,
                       end=rec.epoch_ns(r.end) - start) for r in rec.spans]


def executions(trace) -> list:
    """Executions of the classify program on device 0 (the program that
    ran most often), sorted by start."""
    by_name: dict = {}
    for m in trace.modules[0]:
        by_name.setdefault(m[0], []).append(m)
    return sorted(max(by_name.values(), key=len, default=[]),
                  key=lambda m: m[1])


def match(spans: list, trace) -> list:
    """(execution, its ``scheduler/classify_step`` span, that span's
    ``scheduler/fetch``) for each classify execution whose midpoint a
    ``classify_step`` span holds."""
    steps = sorted((r for r in spans if r.name == "scheduler/classify_step"),
                   key=lambda r: r.start)
    fetch = {r.parent: r for r in spans if r.name == "scheduler/fetch"}
    starts = [r.start for r in steps]
    out = []
    for ex in executions(trace):
        mid = (ex[1] + ex[2]) / 2
        i = bisect.bisect_right(starts, mid) - 1
        if i >= 0 and mid <= steps[i].end and steps[i].id in fetch:
            out.append((ex, steps[i], fetch[steps[i].id]))
    return out


def offset_ns(ex, cs) -> float:
    """How far an execution reaches outside its ``classify_step`` span."""
    return max(0.0, cs.start - ex[1], ex[2] - cs.end)


def gaps(trace) -> list:
    """Every gap between device ops on device 0, as (start, end, name of
    the program execution holding it or None), as ``trace.idle_gaps``
    finds them."""
    ops = sorted(trace.ops[0], key=lambda x: x[1])
    found, t = [], None
    for _, s, e in ops:
        if t is not None and s > t:
            found.append((t, s))
        t = e if t is None else max(t, e)
    mods = sorted(trace.modules[0], key=lambda m: m[1])
    starts = [m[1] for m in mods]
    out = []
    for s, e in found:
        i = bisect.bisect_right(starts, s) - 1
        inside = i >= 0 and e <= mods[i][2]
        out.append((s, e, mods[i][0] if inside else None))
    return out


class HostTime:
    """What the host was doing over an interval of the trace's clock: the
    innermost host span (any but the request spans) covering each part."""

    def __init__(self, spans: list):
        self.spans = sorted((r for r in spans if r.name not in REQUEST_SPANS),
                            key=lambda r: r.start)
        self.starts = [r.start for r in self.spans]
        self.longest = max((r.end - r.start for r in self.spans), default=0)

    def segments(self, lo: float, hi: float) -> list:
        """[lo, hi] in time order as (innermost span or None, ns), one
        entry per stretch under the same span."""
        i = bisect.bisect_left(self.starts, lo - self.longest)
        j = bisect.bisect_right(self.starts, hi)
        near = [r for r in self.spans[i:j] if r.end > lo and r.start < hi]
        cuts = sorted({lo, hi} | {t for r in near for t in (r.start, r.end)
                                  if lo < t < hi})
        out: list = []
        for a, b in zip(cuts, cuts[1:]):
            cover = [r for r in near if r.start <= a and b <= r.end]
            inner = min(cover, key=lambda r: r.end - r.start) if cover else None
            if out and out[-1][0] is inner:
                out[-1][1] += b - a
            else:
                out.append([inner, b - a])
        return [tuple(x) for x in out]

    def split(self, lo: float, hi: float) -> dict:
        """ns of [lo, hi] by span name; None for the part no span covers."""
        out: dict = {}
        for r, ns in self.segments(lo, hi):
            name = r.name if r is not None else None
            out[name] = out.get(name, 0.0) + ns
        return out


def idle_gaps(trace, spans: list | None, top: int = 10) -> list:
    """``trace.idle_gaps`` with each gap between executions named by the
    span that covers most of it (``between executions: scheduler/pack``);
    it stays ``between executions`` where the uncovered part is the
    largest, and everywhere when there are no spans."""
    host = HostTime(spans) if spans else None
    out = []
    for s, e, inside in sorted(gaps(trace), key=lambda g: g[0] - g[1])[:top]:
        if inside is not None:
            label = f"in {TR.module_name(inside)}"
        else:
            label = "between executions"
            if host is not None:
                split = host.split(s, e)
                name = max(split, key=split.get)
                if name is not None:
                    label = f"{label}: {name}"
        out.append([label, (e - s) * 1e-9])
    return out


def idle_split(trace, spans: list) -> dict:
    """Seconds of device idle between executions by host span name, None
    for the part no span covers."""
    host = HostTime(spans)
    out: dict = {}
    for s, e, inside in gaps(trace):
        if inside is None:
            for name, ns in host.split(s, e).items():
                out[name] = out.get(name, 0.0) + ns * 1e-9
    return out


def report(run) -> dict | None:
    """The join of one traced run with spans: idle between executions by
    span (ms per step of the window), the share covered, the clock check,
    the median time from the end of ``engine/dispatch`` to the start of its
    execution, the labelled gaps, and the three longest gaps between
    executions as (span, ms) in time order; None without spans on the
    trace's clock."""
    spans = on_trace_clock(run)
    if spans is None:
        return None
    split = idle_split(run.trace, spans)
    idle = sum(split.values())
    steps = max(len(run.steps), 1)
    pairs = match(spans, run.trace)
    dispatch = {r.parent: r for r in spans if r.name == "engine/dispatch"}
    start = [ex[1] - dispatch[cs.id].end for ex, cs, _ in pairs
             if cs.id in dispatch]
    host = HostTime(spans)
    longest = sorted((g for g in gaps(run.trace) if g[2] is None),
                     key=lambda g: g[0] - g[1])[:3]
    return {
        "idle_ms_per_step": {name or "(none)": v * 1e3 / steps
                             for name, v in sorted(
                                 split.items(), key=lambda kv: -kv[1])},
        "idle_between_s": idle,
        "covered": 1.0 - split.get(None, 0.0) / idle if idle else None,
        "executions": len(executions(run.trace)),
        "matched": len(pairs),
        "max_offset_ms": max((offset_ns(ex, cs) * 1e-6
                              for ex, cs, _ in pairs), default=None),
        "start_after_dispatch_ms": (statistics.median(start) * 1e-6
                                    if start else None),
        "dropped": run.spans.dropped,
        "idle_gaps": idle_gaps(run.trace, spans),
        "longest_gaps": [[[r.name if r is not None else None, ns * 1e-6]
                          for r, ns in host.segments(s, e)]
                         for s, e, _ in longest],
    }


def describe(cell: str, rep: dict) -> str:
    """The report as one line for standard error."""
    parts = ", ".join(f"{k} {v:.3f}" for k, v in rep["idle_ms_per_step"].items())
    covered = ("n/a" if rep["covered"] is None
               else f"{100 * rep['covered']:.1f}%")
    offset = ("n/a" if rep["max_offset_ms"] is None
              else f"{rep['max_offset_ms']:.3f} ms")
    return (f"bench: {cell} idle between executions by span, ms per step: "
            f"{parts}; covered {covered} of {rep['idle_between_s']:.3f} s; "
            f"{rep['matched']} of {rep['executions']} executions inside "
            f"their classify_step, largest offset {offset}")
