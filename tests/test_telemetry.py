"""repro.telemetry unit tests: registry semantics, span recording,
exporters, thread-safety, and the predicted-vs-measured join
(DESIGN.md §15)."""
import gc
import json
import threading
import time

import pytest

from repro import telemetry as T
from repro.telemetry.export import (json_snapshot, predicted_vs_measured,
                                    prometheus_text)
from repro.telemetry.metrics import Registry
from repro.telemetry.tracing import (current_span, now_ns, recording, span,
                                     span_stats)


@pytest.fixture
def reg():
    return Registry()


# ---------------------------------------------------------------------------
# registry
# ---------------------------------------------------------------------------
class TestMetrics:
    def test_counter_get_or_create_and_inc(self, reg):
        c = reg.counter("a/b")
        c.inc()
        c.inc(3)
        assert reg.counter("a/b").value == 4

    def test_counter_rejects_negative(self, reg):
        with pytest.raises(ValueError):
            reg.counter("c").inc(-1)

    def test_gauge_set_add(self, reg):
        g = reg.gauge("g")
        g.set(2.5)
        g.add(0.5)
        assert g.value == 3.0

    def test_histogram_bucketing(self, reg):
        h = reg.histogram("h", (1.0, 10.0, 100.0))
        for v in (0.5, 5.0, 50.0, 500.0):
            h.record(v)
        snap = h.snapshot()
        assert snap["counts"] == [1, 1, 1, 1]       # one per bucket + inf
        assert snap["count"] == 4
        assert snap["min"] == 0.5 and snap["max"] == 500.0
        assert snap["mean"] == pytest.approx(555.5 / 4)

    def test_histogram_boundary_goes_low(self, reg):
        h = reg.histogram("h", (1.0, 10.0))
        h.record(1.0)                               # le semantics: v <= bound
        assert h.snapshot()["counts"] == [1, 0, 0]

    def test_histogram_conflicting_buckets_raise(self, reg):
        reg.histogram("h", (1.0, 2.0))
        reg.histogram("h")                          # None = keep existing
        with pytest.raises(ValueError):
            reg.histogram("h", (1.0, 3.0))

    def test_histogram_bad_buckets_raise(self, reg):
        with pytest.raises(ValueError):
            reg.histogram("h", (2.0, 1.0))
        # empty buckets through the registry mean "use the defaults"
        assert reg.histogram("h2", ()).buckets == T.DEFAULT_MS_BUCKETS

    def test_snapshot_shape_and_isolation(self, reg):
        reg.counter("c").inc()
        reg.gauge("g").set(1)
        reg.histogram("h", (1.0,)).record(0.5)
        snap = reg.snapshot()
        assert set(snap) == {"counters", "gauges", "histograms"}
        snap["counters"]["c"] = 999                 # mutating a copy
        assert reg.counter("c").value == 1

    def test_reset_prefix_removes(self, reg):
        reg.counter("x/a").inc()
        reg.counter("x/b").inc()
        reg.counter("y/a").inc()
        reg.reset("x/")
        snap = reg.snapshot()
        assert list(snap["counters"]) == ["y/a"]
        # handle after reset is detached; re-fetch starts at zero
        assert reg.counter("x/a").value == 0

    def test_counters_with_prefix_drops_zero(self, reg):
        reg.counter("f/head_dim").inc()
        reg.counter("f/other")                      # created, never inc'd
        assert reg.counters_with_prefix("f/") == {"head_dim": 1}

    def test_jit_safety_tracer_raises(self, reg):
        jax = pytest.importorskip("jax")

        def traced(x):
            reg.counter("bad").inc(x)
            return x

        with pytest.raises(Exception) as ei:
            jax.jit(traced)(1)
        assert "trace boundaries" in str(ei.value)
        assert reg.counter("bad").value == 0

    def test_thread_safety_exact_totals(self, reg):
        n_threads, per_thread = 8, 2000

        def work():
            for i in range(per_thread):
                reg.counter("thr").inc()
                reg.histogram("thr_ms", (1.0, 10.0)).record(i % 20)

        threads = [threading.Thread(target=work) for _ in range(n_threads)]
        for t in threads:
            t.start()
        for t in threads:
            t.join()
        assert reg.counter("thr").value == n_threads * per_thread
        h = reg.histogram("thr_ms").snapshot()
        assert h["count"] == n_threads * per_thread
        assert sum(h["counts"]) == h["count"]


# ---------------------------------------------------------------------------
# spans
# ---------------------------------------------------------------------------
class TestSpans:
    def test_span_records_ms_and_attrs(self, reg):
        with span("op", registry=reg, items=7) as sp:
            pass
        assert sp.elapsed_s is not None and sp.elapsed_ms >= 0
        snap = reg.snapshot()["histograms"]
        assert snap["span/op/ms"]["count"] == 1
        assert snap["span/op/items"]["count"] == 1
        assert snap["span/op/items"]["sum"] == 7.0

    def test_span_nesting_and_current(self, reg):
        assert current_span() is None
        with span("outer", registry=reg) as so:
            assert current_span() is so
            with span("inner", registry=reg) as si:
                assert current_span() is si
            assert current_span() is so
        assert current_span() is None

    def test_span_records_on_exception(self, reg):
        with pytest.raises(RuntimeError):
            with span("boom", registry=reg):
                raise RuntimeError("x")
        assert reg.histogram("span/boom/ms").count == 1

    def test_span_stats(self, reg):
        for _ in range(3):
            with span("s", registry=reg):
                pass
        n, mean_ms = span_stats("s", registry=reg)
        assert n == 3 and mean_ms >= 0


class TestRecording:
    @pytest.fixture(autouse=True)
    def _collect_only_when_asked(self):
        """Automatic collections would add ``python/gc`` records at
        random points; the tests that want one call ``gc.collect()``."""
        was = gc.isenabled()
        gc.disable()
        yield
        if was:
            gc.enable()

    def test_nothing_kept_outside_a_recording(self, reg):
        with span("before", registry=reg):
            pass
        with recording() as rec:
            pass
        with span("after", registry=reg):
            pass
        span.interval("later", now_ns(), now_ns(), registry=reg)
        assert rec.spans == [] and rec.dropped == 0
        assert reg.histogram("span/after/ms").count == 1

    def test_nested_ids_parents_stamps_and_attrs(self, reg):
        with recording() as rec:
            with span("outer", registry=reg, n=2) as so:
                with span("inner", registry=reg) as si:
                    pass
                with span("inner", registry=reg) as sj:
                    pass
        inner, inner2, outer = rec.spans          # in the order they end
        assert [r.name for r in rec.spans] == ["inner", "inner", "outer"]
        assert (outer.id, inner.id, inner2.id) == (so.id, si.id, sj.id)
        assert len({so.id, si.id, sj.id}) == 3
        assert outer.parent is None
        assert inner.parent == inner2.parent == so.id
        assert outer.attrs == {"n": 2} and inner.attrs == {}
        assert outer.start <= inner.start <= inner.end <= inner2.start \
            <= inner2.end <= outer.end
        assert outer.start == so.start_ns
        assert (outer.end - outer.start) * 1e-9 == pytest.approx(so.elapsed_s)

    def test_interval_carries_the_callers_id(self, reg):
        a = now_ns()
        b = now_ns()
        with recording() as rec:
            span.interval("scheduler/request", a, b, id=42, registry=reg,
                          images=3)
            span.interval("scheduler/queue_wait", a, b, parent=42,
                          registry=reg)
        req, wait = rec.spans
        assert (req.name, req.id, req.parent, req.start, req.end) == \
            ("scheduler/request", 42, None, a, b)
        assert req.attrs == {"images": 3}
        assert wait.parent == 42 and wait.id != 42
        assert reg.histogram("span/scheduler/request/ms").count == 1
        assert reg.histogram("span/scheduler/request/images").sum == 3

    def test_epoch_ns_lies_between_wall_clock_reads(self):
        with recording() as rec:
            for _ in range(3):
                lo = time.time_ns()
                time.sleep(1e-3)
                stamp = now_ns()
                time.sleep(1e-3)
                hi = time.time_ns()
                assert lo < rec.epoch_ns(stamp) < hi

    def test_capacity_and_dropped(self, reg):
        with recording(capacity=2) as rec:
            for i in range(5):
                with span("s", registry=reg, i=i):
                    pass
        assert [r.attrs["i"] for r in rec.spans] == [0, 1]
        assert rec.dropped == 3

    def test_gc_collections_are_spans(self, reg):
        with recording() as rec:
            with span("outer", registry=reg) as so:
                gc.collect()
            assert rec._on_gc in gc.callbacks
        assert rec._on_gc not in gc.callbacks
        collections = [r for r in rec.spans if r.name == "python/gc"]
        assert any(r.attrs == {"generation": 2} and r.parent == so.id
                   and so.start_ns <= r.start <= r.end
                   for r in collections)
        n = len(rec.spans)
        gc.collect()                              # hook gone: not kept
        assert len(rec.spans) == n

    def test_one_recording_at_a_time(self):
        with recording():
            with pytest.raises(RuntimeError):
                with recording():
                    pass
        with recording() as rec:                  # released on exit
            pass
        assert rec.spans == []


# ---------------------------------------------------------------------------
# exporters
# ---------------------------------------------------------------------------
class TestExport:
    def _snap(self, reg):
        reg.counter("req/total").inc(2)
        reg.gauge("q depth").set(3)
        h = reg.histogram("lat", (1.0, 10.0))
        for v in (0.5, 5.0, 50.0):
            h.record(v)
        return reg.snapshot()

    def test_prometheus_text(self, reg):
        txt = prometheus_text(self._snap(reg))
        assert "repro_req_total_total 2" in txt
        assert "repro_q_depth 3.0" in txt
        # cumulative le buckets ending in +Inf == count
        assert 'repro_lat_bucket{le="1.0"} 1' in txt
        assert 'repro_lat_bucket{le="10.0"} 2' in txt
        assert 'repro_lat_bucket{le="+Inf"} 3' in txt
        assert "repro_lat_count 3" in txt

    def test_json_snapshot_writes_and_merges(self, reg, tmp_path):
        out = tmp_path / "m.json"
        payload = json_snapshot(self._snap(reg), path=out,
                                extra={"tag": "t1"})
        assert payload["tag"] == "t1"
        on_disk = json.loads(out.read_text())
        assert on_disk["counters"]["req/total"] == 2
        assert on_disk["tag"] == "t1"

    def test_predicted_vs_measured_join(self, reg):
        # two measured kernel spans; only one has a static row
        for label, ms in (("matmul-deit", 2.0), ("mystery", 1.0)):
            reg.histogram(f"span/kernel:{label}/ms",
                          T.DEFAULT_MS_BUCKETS).record(ms)
        rows = [{"label": "matmul-deit", "kernel": "mxint_matmul",
                 "flops": 2 * 400 * 192 * 256,
                 "hbm_bytes": 400 * 192 * 4 + 192 * 256 + 6 * 256,
                 "intensity": 7.9}]
        rep = predicted_vs_measured(reg.snapshot(), rows)
        assert rep["unmatched"] == ["mystery"]
        (k,) = rep["kernels"]
        assert k["label"] == "matmul-deit"
        assert k["kernel"] == "mxint_matmul"
        assert k["samples"] == 1
        assert k["measured_ms"] == pytest.approx(2.0)
        # predicted = max(flops/peak, bytes/bw); join math is exact
        peaks = rep["peaks"]
        want = max(k["flops"] / peaks["flops_per_s"],
                   k["hbm_bytes"] / peaks["hbm_bytes_per_s"]) * 1e3
        assert k["predicted_ms"] == pytest.approx(want, abs=1e-6)
        assert k["achieved_fraction"] == pytest.approx(want / 2.0, abs=1e-6)
        assert k["bottleneck"] in ("compute", "memory")

    def test_predicted_vs_measured_skips_empty_histograms(self, reg):
        reg.histogram("span/kernel:idle/ms", T.DEFAULT_MS_BUCKETS)
        rep = predicted_vs_measured(reg.snapshot(), [])
        assert rep["kernels"] == [] and rep["unmatched"] == []


# ---------------------------------------------------------------------------
# default-registry conveniences + the ops.FALLBACKS compat view
# ---------------------------------------------------------------------------
class TestDefaultRegistry:
    def test_module_level_api(self):
        T.reset("tmod/")
        T.counter("tmod/c").inc()
        T.gauge("tmod/g").set(1)
        snap = T.snapshot()
        assert snap["counters"]["tmod/c"] == 1
        T.reset("tmod/")
        assert "tmod/c" not in T.snapshot()["counters"]

    def test_fallback_view_counter_semantics(self):
        from repro.kernels import ops

        ops.reset_attention_fallbacks()
        assert ops.attention_fallback_counts() == {}
        assert ops.FALLBACKS == {}
        with pytest.warns(UserWarning, match="fell back"):
            ops._count_fallback("head_dim", "test")
        assert ops.FALLBACKS["head_dim"] == 1
        assert "head_dim" in ops.FALLBACKS
        assert dict(ops.FALLBACKS.items()) == {"head_dim": 1}
        assert ops.attention_fallback_counts() == {"head_dim": 1}
        # the same counts live in the telemetry snapshot
        assert T.snapshot()["counters"][
            "kernels/attention_fallback/head_dim"] == 1
        ops.reset_attention_fallbacks()
        assert ops.FALLBACKS == {}
