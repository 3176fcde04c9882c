"""repro.telemetry — runtime metrics, spans, and the predicted-vs-
measured roofline for the serving stack (DESIGN.md §15).

The measured half of the repo's performance story: the static analysis
layer predicts per-kernel FLOPs/bytes (DESIGN.md §14); this package
records what the engines, schedulers, kernels, and loops actually did
— request latency, batch/prefill shape histograms, queue/slot gauges,
recompile and fallback counters — in one process-local registry, and
exports it as JSON, Prometheus text, or the predicted-vs-measured join.

Convenience surface (all over the default registry)::

    from repro import telemetry as T

    T.counter("serving/requests").inc()
    T.gauge("scheduler/queue_depth").set(len(queue))
    with T.span("serving/classify", images=n):
        ...                          # -> span/serving/classify/ms + /images
    with T.recording() as rec:       # keep each span's start/end/parent
        ...                          # -> rec.spans (SpanRecord list)
    snap = T.snapshot()              # coherent dict copy
    T.reset()                        # drop everything (tests)

jit-safety contract: every recording call coerces to a host scalar, so
a jax tracer raises — telemetry lives at trace boundaries only (record
after ``block_until_ready``, around jitted calls, never inside them).
"""
from repro.telemetry.metrics import (DEFAULT_MS_BUCKETS,       # noqa: F401
                                     DEFAULT_SIZE_BUCKETS, Counter, Gauge,
                                     Histogram, Registry, default_registry)
from repro.telemetry.tracing import (Recorder, SpanRecord,     # noqa: F401
                                     current_span, now_ns, recording,
                                     span, span_stats, walltime)


def counter(name: str) -> Counter:
    return default_registry().counter(name)


def gauge(name: str) -> Gauge:
    return default_registry().gauge(name)


def histogram(name: str, buckets=None) -> Histogram:
    return default_registry().histogram(name, buckets)


def snapshot() -> dict:
    return default_registry().snapshot()


def reset(prefix: str | None = None) -> None:
    default_registry().reset(prefix)
