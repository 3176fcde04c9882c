"""Wall-clock spans over the metrics registry (DESIGN.md §15).

``span(name, **attrs)`` is a nestable context manager:

* on exit it records the elapsed wall-clock into the histogram
  ``span/<name>/ms`` (and each numeric ``attr`` into
  ``span/<name>/<attr>`` with size buckets) in the target registry;
* while open it forwards to ``jax.profiler.TraceAnnotation`` when jax
  is importable, so host spans line up with device traces in a profiler
  UI — telemetry itself stays dependency-free;
* nesting is tracked per thread (``current_span()``), and the elapsed
  time is exposed as ``.elapsed_s``/``.elapsed_ms`` after exit, so
  callers that used to keep their own ``t0 = time.perf_counter()``
  bookkeeping read the span instead.

``span.interval(name, start_ns, end_ns, ...)`` records the same for an
interval that is not a ``with`` block (a request's lifetime), from
stamps taken with :func:`now_ns`.

Inside ``with recording() as rec:`` every span exit and interval also
appends a :class:`SpanRecord` (name, id, parent, start, end, attrs) to
``rec.spans``, and each garbage collection a ``python/gc`` record with
its ``generation``.  Stamps are ``perf_counter_ns``;
``rec.epoch_ns(stamp)`` puts one on the epoch clock the JAX profiler's
``profile_start_time`` uses.  Outside a recording a span costs an id
and one check more than its histograms.

This module is the ONLY place in ``src/`` allowed to call
``time.time()``/``time.perf_counter()`` — the ``no-adhoc-timing``
lint rule (DESIGN.md §13) fails anything else.  For plain wall-clock
*timestamps* (heartbeats, checkpoint metadata) use :func:`walltime`.
"""
from __future__ import annotations

import contextlib
import gc
import itertools
import threading
import time
from typing import NamedTuple, Optional

from repro.telemetry import metrics
from repro.telemetry.metrics import (DEFAULT_MS_BUCKETS,
                                     DEFAULT_SIZE_BUCKETS, Registry)

_local = threading.local()
_ids = itertools.count(1)          # span ids, unique in the process

_TRACE_ANNOTATION = None
_TRACE_TRIED = False


def _trace_annotation_cls():
    """jax.profiler.TraceAnnotation, resolved once, None without jax."""
    global _TRACE_ANNOTATION, _TRACE_TRIED
    if not _TRACE_TRIED:
        _TRACE_TRIED = True
        try:
            from jax.profiler import TraceAnnotation
            _TRACE_ANNOTATION = TraceAnnotation
        except Exception:          # pragma: no cover - no-jax environments
            _TRACE_ANNOTATION = None
    return _TRACE_ANNOTATION


def walltime() -> float:
    """Epoch-seconds timestamp (the sanctioned ``time.time()``).

    For *metadata* — heartbeat files, checkpoint manifests, request
    submit stamps.  Durations go through :class:`span`, never through
    subtracting two ``walltime()`` calls."""
    return time.time()


def now_ns() -> int:
    """A span stamp (``perf_counter_ns``), for :meth:`span.interval`."""
    return time.perf_counter_ns()


class SpanRecord(NamedTuple):
    """One recorded span; ``start``/``end`` are ``perf_counter_ns``."""
    name: str
    id: int
    parent: Optional[int]
    start: int
    end: int
    attrs: dict


class Recorder:
    """The spans of one :func:`recording` block, in the order they end.

    Keeps the first ``capacity`` records and counts the rest in
    ``dropped``."""

    def __init__(self, capacity: int):
        self.capacity = capacity
        self.spans: list[SpanRecord] = []
        self.dropped = 0
        before, epoch = time.perf_counter_ns(), time.time_ns()
        self._anchor = ((before + time.perf_counter_ns()) // 2, epoch)
        self._gc_start: Optional[int] = None

    def add(self, rec: SpanRecord) -> None:
        if len(self.spans) < self.capacity:
            self.spans.append(rec)
        else:
            self.dropped += 1

    def epoch_ns(self, stamp: int) -> int:
        """A ``perf_counter_ns`` stamp as ns since the epoch."""
        counter, epoch = self._anchor
        return stamp - counter + epoch

    def _on_gc(self, phase: str, info: dict) -> None:
        """``gc.callbacks`` hook: one ``python/gc`` record per collection,
        a child of the span open on the collecting thread."""
        if phase == "start":
            self._gc_start = time.perf_counter_ns()
        elif self._gc_start is not None:
            parent = current_span()
            self.add(SpanRecord(
                "python/gc", next(_ids), parent.id if parent else None,
                self._gc_start, time.perf_counter_ns(),
                {"generation": info["generation"]}))
            self._gc_start = None


_recorder: Optional[Recorder] = None


@contextlib.contextmanager
def recording(capacity: int = 1 << 18):
    """``with recording() as rec: ...`` keeps the spans that end inside
    the block in ``rec.spans`` (see the module docstring).  One recording
    at a time per process."""
    global _recorder
    if _recorder is not None:
        raise RuntimeError("a span recording is already open")
    rec = Recorder(capacity)
    _recorder = rec
    gc.callbacks.append(rec._on_gc)
    try:
        yield rec
    finally:
        gc.callbacks.remove(rec._on_gc)
        _recorder = None


def _finish(name: str, registry: Registry, attrs: dict, span_id: int,
            parent: Optional[int], start: int, end: int) -> None:
    registry.histogram(f"span/{name}/ms",
                       DEFAULT_MS_BUCKETS).record((end - start) * 1e-6)
    for key, val in attrs.items():
        registry.histogram(f"span/{name}/{key}",
                           DEFAULT_SIZE_BUCKETS).record(val)
    rec = _recorder
    if rec is not None:
        rec.add(SpanRecord(name, span_id, parent, start, end, dict(attrs)))


class span:
    """``with span("serving/classify", images=n): ...``

    Records ``span/serving/classify/ms`` (latency histogram) and
    ``span/serving/classify/images`` (size histogram) on exit.  Attrs
    must be host scalars — jax tracers raise (the registry's jit-safety
    contract, DESIGN.md §15); ``attrs`` may be filled in while the span
    is open.
    """

    __slots__ = ("name", "attrs", "registry", "elapsed_s", "start_ns", "id",
                 "_ta")

    def __init__(self, name: str, registry: Optional[Registry] = None,
                 **attrs):
        self.name = name
        self.attrs = attrs
        self.registry = registry or metrics.default_registry()
        self.elapsed_s: Optional[float] = None
        self.start_ns: Optional[int] = None
        self.id = next(_ids)
        self._ta = None

    @property
    def elapsed_ms(self) -> Optional[float]:
        return None if self.elapsed_s is None else self.elapsed_s * 1e3

    def __enter__(self) -> "span":
        stack = getattr(_local, "stack", None)
        if stack is None:
            stack = _local.stack = []
        stack.append(self)
        cls = _trace_annotation_cls()
        if cls is not None:
            try:
                self._ta = cls(self.name)
                self._ta.__enter__()
            except Exception:      # profiler unavailable mid-run: fine
                self._ta = None
        self.start_ns = time.perf_counter_ns()
        return self

    def __exit__(self, exc_type, exc, tb) -> bool:
        end = time.perf_counter_ns()
        self.elapsed_s = (end - self.start_ns) * 1e-9
        if self._ta is not None:
            try:
                self._ta.__exit__(exc_type, exc, tb)
            except Exception:
                pass
        stack = _local.stack
        stack.pop()
        _finish(self.name, self.registry, self.attrs, self.id,
                stack[-1].id if stack else None, self.start_ns, end)
        return False

    @staticmethod
    def interval(name: str, start_ns: int, end_ns: int,
                 id: Optional[int] = None, parent: Optional[int] = None,
                 registry: Optional[Registry] = None, **attrs) -> None:
        """Record a span that is not a ``with`` block, from two
        :func:`now_ns` stamps: histograms as on a span's exit, and a
        record while recording.  ``id`` defaults to a fresh span id; a
        caller may give its own (a request's uid), unique among the
        spans of ``name``."""
        _finish(name, registry or metrics.default_registry(), attrs,
                next(_ids) if id is None else id, parent, start_ns, end_ns)


def current_span() -> Optional[span]:
    """Innermost open span on this thread, or None."""
    stack = getattr(_local, "stack", None)
    return stack[-1] if stack else None


def span_stats(name: str, registry: Optional[Registry] = None):
    """(count, mean_ms) of a recorded span — the one-line read most
    report dicts need after replacing hand-rolled perf_counter math."""
    reg = registry or metrics.default_registry()
    h = reg.histogram(f"span/{name}/ms", DEFAULT_MS_BUCKETS)
    return h.count, h.mean
