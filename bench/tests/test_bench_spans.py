"""CPU checks of the join of the program's spans with the device trace.

    PYTHONPATH=src JAX_PLATFORMS=cpu python -m pytest -q bench/tests
"""
from __future__ import annotations

import contextlib
import glob
import sys
import time
from pathlib import Path
from types import SimpleNamespace

import pytest

sys.path.insert(0, str(Path(__file__).resolve().parent))

import tiny  # noqa: E402
from bench.harness import load, manifest, spans  # noqa: E402
from bench.harness.trace import Trace, idle_gaps  # noqa: E402
from repro.telemetry.tracing import SpanRecord  # noqa: E402

MS = 1_000_000                       # ns
EPOCH = 1_700_000_000 * 10 ** 9      # the session's start, epoch ns
SPAN_METRICS = ("pack_ms.bulk", "put_ms.bulk", "readback_ms.bulk",
                "queue_wait_ms.bulk")


def read(metric, run):
    return manifest.metric_reader(metric)(run)


def rec(name, id, parent, start, end, **attrs):
    return SpanRecord(name, id, parent, start * MS, end * MS, attrs)


class FakeRecorder:
    """Host stamps 5 s ahead of the trace's clock: the records below,
    given on the trace's clock, plus 5 s."""
    dropped = 0

    def __init__(self, records):
        self.spans = [r._replace(start=r.start + 5000 * MS,
                                 end=r.end + 5000 * MS) for r in records]

    @staticmethod
    def epoch_ns(stamp):
        return stamp - 5000 * MS + EPOCH


# Two steps; times in ms.  Step 1 packs in [0, 10] and its execution runs
# [9, 85]; the host is outside any step in [96, 110]; step 2 packs in
# [110, 140], with a collection in [115, 125], and executes [149, 186].
# A third step, after the window, packs in [210, 220].
RECORDS = [
    rec("scheduler/step", 1, None, 0, 96, step=1, images=32),
    rec("scheduler/pack", 2, 1, 0, 10),
    rec("scheduler/classify_step", 3, 1, 10, 90, images=32),
    rec("engine/put", 4, 3, 10, 20),
    rec("engine/dispatch", 5, 3, 20, 25),
    rec("scheduler/fetch", 6, 3, 25, 90),
    rec("scheduler/deliver", 7, 1, 90, 96),
    rec("scheduler/step", 8, None, 110, 200, step=2, images=32),
    rec("scheduler/pack", 9, 8, 110, 140),
    rec("python/gc", 10, 9, 115, 125, generation=0),
    rec("scheduler/classify_step", 11, 8, 140, 190, images=32),
    rec("engine/put", 12, 11, 140, 148),
    rec("engine/dispatch", 13, 11, 148, 150),
    rec("scheduler/fetch", 14, 11, 150, 190),
    rec("scheduler/deliver", 15, 8, 190, 200),
    rec("scheduler/pack", 16, None, 210, 220),
    rec("scheduler/queue_wait", 17, 0, -5, 0),
    rec("scheduler/queue_wait", 18, 1, 50, 110),
    rec("scheduler/queue_wait", 19, 2, 150, 300),
    rec("scheduler/request", 0, None, -5, 150, images=20, first_step=1),
]
# an execution before the recording, then the two steps' executions
OPS = [(n, s * MS, e * MS) for n, s, e in (
    ("%fusion.1", -100, -60), ("%mxint_matmul.1", 9, 50),
    ("%mxint_gelu.1", 55, 85), ("%mxint_matmul.1", 149, 186))]
MODULES = [("jit_logits(7)", s * MS, e * MS)
           for s, e in ((-100, -60), (9, 85), (149, 186))]


def span_run(spans_on=True, start=EPOCH):
    trace = spans.SpanTrace(ops=[OPS], modules=[MODULES],
                            profile_start_time=start)
    return SimpleNamespace(
        trace=trace, t0=5.0, t1=5.2,
        steps=[(5.0, 5.096, 32), (5.11, 5.2, 32)],
        requests=[SimpleNamespace(uid=u) for u in range(3)], in_window=2,
        spans=FakeRecorder(RECORDS) if spans_on else None)


def test_span_readers_against_known_numbers():
    run = span_run()
    # the window [5.0, 5.2] s on the host clock holds two packs: 10 and 30
    assert read("pack_ms.bulk", run) == pytest.approx(20.0)
    assert read("put_ms.bulk", run) == pytest.approx(9.0)
    # fetch ends 5 and 4 ms after the executions it waits for
    assert read("readback_ms.bulk", run) == pytest.approx(4.5)
    # requests 0 and 1 were sent in the window: waits 5 and 60 ms
    assert read("queue_wait_ms.bulk", run) == pytest.approx(32.5)


def test_span_readers_find_nothing_without_spans():
    for run in (span_run(spans_on=False),
                SimpleNamespace(trace=None, t0=0.0, t1=1.0, requests=[],
                                in_window=0)):
        for m in SPAN_METRICS:
            assert read(m, run) is None
        assert spans.report(run) is None
    # spans, but a trace without the session's start: no shared clock
    run = span_run(start=None)
    assert read("readback_ms.bulk", run) is None
    assert read("pack_ms.bulk", run) == pytest.approx(20.0)


def test_idle_split_and_gap_labels():
    run = span_run()
    tr = spans.on_trace_clock(run)
    assert [r.start for r in tr[:2]] == [0, 0]
    split = {k: v * 1e3 for k, v in spans.idle_split(run.trace, tr).items()}
    # gap [-60, 9]: no span until 0, then step 1's pack;
    # gap [85, 149]: fetch, deliver, nothing, pack around a collection, put,
    # dispatch.  The 5 ms gap inside the first execution is not counted.
    assert split == pytest.approx({
        None: 74.0, "scheduler/pack": 29.0, "python/gc": 10.0,
        "engine/put": 8.0, "engine/dispatch": 1.0, "scheduler/fetch": 5.0,
        "scheduler/deliver": 6.0})
    assert spans.idle_gaps(run.trace, tr) == [
        ["between executions", pytest.approx(0.069)],
        ["between executions: scheduler/pack", pytest.approx(0.064)],
        ["in jit_logits", pytest.approx(0.005)]]
    rep = spans.report(run)
    assert rep["covered"] == pytest.approx(1 - 74 / 133)
    assert rep["idle_between_s"] == pytest.approx(0.133)
    assert rep["idle_ms_per_step"]["scheduler/pack"] == pytest.approx(29 / 2)
    assert list(rep["idle_ms_per_step"])[0] == "(none)"
    assert (rep["executions"], rep["matched"]) == (3, 2)
    assert rep["max_offset_ms"] == pytest.approx(1.0)   # starts 1 ms early
    assert rep["idle_gaps"] == spans.idle_gaps(run.trace, tr)
    # executions start at 9 and 149, dispatches end at 25 and 150
    assert rep["start_after_dispatch_ms"] == pytest.approx(-8.5)
    assert rep["longest_gaps"] == [
        [[None, pytest.approx(60.0)], ["scheduler/pack", pytest.approx(9.0)]],
        [["scheduler/fetch", pytest.approx(5.0)],
         ["scheduler/deliver", pytest.approx(6.0)],
         [None, pytest.approx(14.0)], ["scheduler/pack", pytest.approx(5.0)],
         ["python/gc", pytest.approx(10.0)],
         ["scheduler/pack", pytest.approx(15.0)],
         ["engine/put", pytest.approx(8.0)],
         ["engine/dispatch", pytest.approx(1.0)]]]
    assert "covered 44.4%" in spans.describe("c", rep)


def test_gap_labels_without_spans_are_the_traces_own():
    tr = Trace(ops=[OPS], modules=[MODULES])
    assert spans.idle_gaps(tr, None) == idle_gaps(tr)
    assert spans.idle_gaps(tr, []) == idle_gaps(tr)


def test_profile_start_puts_host_events_on_the_epoch_clock(tmp_path):
    """What the join rests on, on the CPU: the session's start in epoch ns
    plus an event's ``start_ns`` is the event's epoch time."""
    import jax
    opts = jax.profiler.ProfileOptions()
    opts.host_tracer_level = 1
    opts.python_tracer_level = 0
    jax.profiler.start_trace(str(tmp_path), profiler_options=opts)
    try:
        lo = time.time_ns()
        with jax.profiler.TraceAnnotation("bench_clock_probe"):
            time.sleep(2e-3)
        hi = time.time_ns()
    finally:
        jax.profiler.stop_trace()
    (path,) = glob.glob(f"{tmp_path}/**/*.xplane.pb", recursive=True)
    pd = jax.profiler.ProfileData.from_file(path)
    start = spans.profile_start_ns(pd)
    (ev,) = [e for p in pd.planes for ln in p.lines for e in ln.events
             if e.name == "bench_clock_probe"]
    assert lo <= start + ev.start_ns
    assert start + ev.start_ns + ev.duration_ns <= hi
    assert ev.duration_ns >= 2e6


def test_window_records_the_programs_spans(monkeypatch):
    """A window of the tiny cell on the CPU, the trace left out: with the
    recorder on, every step of the window has its tree and the host-clock
    readers read it; with it off, nothing is kept."""
    @contextlib.contextmanager
    def no_trace(out):
        yield
        out.append(spans.SpanTrace(ops=[[]], modules=[[]]))

    monkeypatch.setattr(spans, "capture", no_trace)
    s = load.setup(tiny.cell("bulk"), 2 ** 33 + 5, time.perf_counter())
    run = spans.window(s, 1.0, tiny.PEAKS, record=True)
    assert run.spans.dropped == 0
    assert len(spans.window_spans(run, "scheduler/step")) == len(run.steps)
    for m in ("pack_ms.bulk", "put_ms.bulk", "queue_wait_ms.bulk"):
        assert read(m, run) > 0
    waits = [r for r in run.spans.spans if r.name == "scheduler/queue_wait"]
    assert sorted(r.parent for r in waits) == list(range(len(run.requests)))
    assert read("readback_ms.bulk", run) is None       # no session start
    off = spans.window(s, 1.0, tiny.PEAKS, record=False)
    assert off.spans is None and off.steps
    for m in SPAN_METRICS:
        assert read(m, off) is None
