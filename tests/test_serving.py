"""Serving substrate: packed weights, engine generate, batch scheduler."""
import dataclasses
from types import SimpleNamespace

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import telemetry as T
from repro.configs import smoke_config
from repro.core.mx_types import MXINT8_WEIGHT, MXFormat, QuantConfig
from repro.core.quantize import MXTensor
from repro.models import build_model
from repro.models.model_api import is_param, unwrap
from repro.serving.engine import (ServeConfig, ServingEngine,
                                  ViTServingEngine, pack_params_mxint)
from repro.serving.scheduler import (BatchScheduler, ClassifyRequest,
                                     ClassifyScheduler, Request)


@pytest.fixture(scope="module")
def dense_model():
    cfg = smoke_config("llama3_8b")
    model = build_model(cfg)
    params = model.init(jax.random.key(0))
    return cfg, model, params


class TestPackedWeights:
    def test_pack_marks_large_kernels_only(self, dense_model):
        cfg, model, params = dense_model
        packed = pack_params_mxint(params, MXINT8_WEIGHT)
        n_mx = n_plain = 0
        for leaf in jax.tree_util.tree_leaves(
                packed, is_leaf=lambda l: isinstance(l, MXTensor)):
            if isinstance(leaf, MXTensor):
                n_mx += 1
            else:
                n_plain += 1
        assert n_mx > 0 and n_plain > 0   # kernels packed, norms not

    def test_packed_bytes_shrink(self, dense_model):
        from repro.core.quantize import packed_bytes
        cfg, model, params = dense_model
        raw = unwrap(params)
        base = sum(l.size * l.dtype.itemsize
                   for l in jax.tree_util.tree_leaves(raw))
        packed = pack_params_mxint(params, MXFormat(6, 256))
        got = packed_bytes(unwrap(packed))
        assert got < base * 0.45           # f32 -> ~6.03 bits on kernels

    def test_packed_forward_close_to_float(self, dense_model):
        cfg, model, params = dense_model
        toks = jnp.asarray(
            np.random.default_rng(0).integers(0, cfg.vocab, (2, 16)),
            jnp.int32)
        ref = model.loss(params, {"tokens": toks})
        packed = pack_params_mxint(params, MXINT8_WEIGHT)
        got = model.loss(packed, {"tokens": toks})
        assert abs(float(got) - float(ref)) < 0.15, (float(got), float(ref))

    def test_abstract_pack_matches_concrete_shapes(self, dense_model):
        cfg, model, params = dense_model
        ab = jax.eval_shape(lambda: model.init(jax.random.key(0)))
        pa = pack_params_mxint(ab, MXINT8_WEIGHT, abstract=True)
        pc = pack_params_mxint(params, MXINT8_WEIGHT)
        sa = jax.tree_util.tree_map(lambda x: x.shape,
                                    jax.tree_util.tree_leaves(unwrap(pa)))
        sc = jax.tree_util.tree_map(lambda x: x.shape,
                                    jax.tree_util.tree_leaves(unwrap(pc)))
        assert sa == sc


class TestEngine:
    def test_generate_greedy_deterministic(self, dense_model):
        cfg, model, params = dense_model
        eng = ServingEngine(model, params, ServeConfig(max_len=64, batch=2))
        toks = jnp.asarray(
            np.random.default_rng(1).integers(0, cfg.vocab, (2, 8)),
            jnp.int32)
        a = eng.generate({"tokens": toks}, max_new_tokens=6)
        b = eng.generate({"tokens": toks}, max_new_tokens=6)
        assert a.shape == (2, 6)
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))

    def test_packed_engine_generates(self, dense_model):
        cfg, model, params = dense_model
        eng = ServingEngine(model, params,
                            ServeConfig(max_len=64, batch=2,
                                        pack_weights=True,
                                        weight_fmt=MXINT8_WEIGHT))
        toks = jnp.asarray([[5, 6, 7, 8]], jnp.int32)
        out = eng.generate({"tokens": toks}, max_new_tokens=4)
        assert out.shape == (1, 4)

    def test_decode_matches_parallel_forward(self, dense_model):
        """Prefill+decode must agree with the teacher-forced forward pass
        (KV-cache correctness)."""
        cfg, model, params = dense_model
        rng = np.random.default_rng(2)
        toks = jnp.asarray(rng.integers(0, cfg.vocab, (1, 12)), jnp.int32)
        # parallel logits for positions 0..11
        x = model._embed_inputs(params, toks, None)
        pos = jnp.arange(12)[None, :]
        h, _, _ = model._run_stack(params, x, positions=pos, cache=None,
                                   cache_index=None, decode=False)
        full_logits = model.logits(params, h)
        # incremental: prefill 8, decode 4
        cache = model.cache_init(1, 32)
        lg, cache = model.prefill(params, toks[:, :8], cache)
        np.testing.assert_allclose(np.asarray(lg[0, 0]),
                                   np.asarray(full_logits[0, 7]),
                                   rtol=2e-3, atol=2e-3)
        for t in range(8, 12):
            lg, cache = model.decode_step(params, toks[:, t:t + 1], cache)
            if t < 11:
                np.testing.assert_allclose(np.asarray(lg[0, 0]),
                                           np.asarray(full_logits[0, t]),
                                           rtol=2e-3, atol=2e-3)


class TestKernelModeServingDecode:
    """BatchScheduler decode in mode='kernel' exercises the Pallas decode
    path (ISSUE 3): scoring + Eq. 14-20 softmax + p @ V fused in one
    kernel over the cache ring — no XLA L.softmax in the decode step."""

    @pytest.fixture(scope="class")
    def kernel_engine(self):
        cfg = dataclasses.replace(
            smoke_config("llama3_8b"), n_layers=1,
            quant=QuantConfig(mode="kernel", quantize_nonlinear=True))
        model = build_model(cfg)
        params = model.init(jax.random.key(0))
        return ServingEngine(model, params,
                             ServeConfig(max_len=32, batch=2,
                                         pack_weights=True,
                                         weight_fmt=MXINT8_WEIGHT))

    def test_scheduler_generates_through_pallas_decode(self, kernel_engine):
        from repro.models import layers as L
        eng = kernel_engine
        # the decode step's traced program carries the Pallas kernel and
        # never routes scores through L.softmax
        cache = eng.model.cache_init(2, eng.cfg.max_len)
        tok = jnp.zeros((2, 1), jnp.int32)
        calls = []
        orig = L.softmax
        L.softmax = lambda *a, **k: (calls.append(1), orig(*a, **k))[1]
        try:
            jaxpr = jax.make_jaxpr(
                lambda t, c: eng._decode.__wrapped__(eng.params, t, c)
            )(tok, cache)
        finally:
            L.softmax = orig
        assert not calls
        assert "pallas_call" in str(jaxpr)

        sched = BatchScheduler(eng, batch_size=2)
        rng = np.random.default_rng(0)
        for uid in range(3):                       # 3 requests, 2 slots
            sched.submit(Request(uid=uid,
                                 prompt=rng.integers(1, 512, uid + 2),
                                 max_new_tokens=2))
        done = sched.run()
        assert len(done) == 3
        assert all(len(r.generated) == 2 for r in done)

    def test_slot_admission_no_per_slot_recompiles(self, kernel_engine):
        """Kernel mode: a ragged stream through the slot scheduler keeps
        the decode + slot-prefill jit caches flat after warmup — slot
        index / per-row lengths are traced values, never specialization
        keys (the ClassifyScheduler zero-recompile contract, ported to
        the token path)."""
        eng = kernel_engine
        rng = np.random.default_rng(1)

        def stream(uids, plens):
            sched = BatchScheduler(eng, batch_size=2, prefill_len=8)
            for uid, n in zip(uids, plens):
                sched.submit(Request(uid=uid,
                                     prompt=rng.integers(1, 512, n),
                                     max_new_tokens=2))
            return sched.run()

        stream([0, 1], [3, 5])                     # warm both jits
        base = eng.jit_cache_size()
        done = stream([2, 3, 4], [7, 2, 4])        # new slots + lengths
        assert len(done) == 3
        if base >= 0:
            assert eng.jit_cache_size() == base    # zero recompiles


# ---------------------------------------------------------------------------
# scripted stub engine: slot prefill emits the LAST real prompt token and
# decode counts up from it (+1, +2, ...), so EOS timing is controlled
# exactly by the prompt contents (no model in the loop)
# ---------------------------------------------------------------------------
class _StubModel:
    def cache_init(self, batch, max_len):
        return jnp.zeros((batch,), jnp.int32)


class _StubEngine:
    cfg = ServeConfig(max_len=32, batch=2)
    model = _StubModel()
    params = None

    def _prefill_slot(self, params, tokens, length, slot, cache):
        toks = np.asarray(tokens)
        tok = jnp.asarray([toks[0, int(length) - 1]], jnp.int32)
        return tok, cache

    def _decode(self, params, tok, cache):
        return tok + 1, cache


class TestSchedulerEdgeCases:
    def _mk(self, batch=2, eos=None, admission="slot"):
        return BatchScheduler(_StubEngine(), batch_size=batch, eos_id=eos,
                              admission=admission)

    def test_empty_queue_step_is_noop(self):
        sched = self._mk()
        assert sched.step() == 0
        assert sched.run(max_steps=4) == []

    def test_submit_beyond_capacity_drains(self):
        sched = self._mk(batch=2)
        for uid in range(5):                       # > 2x capacity
            sched.submit(Request(uid=uid, prompt=np.asarray([uid + 1]),
                                 max_new_tokens=3))
        done = sched.run()
        assert len(done) == 5 and all(r.done for r in done)
        for r in done:                             # scripted: last, +1, +2
            assert r.generated == [r.uid + 1, r.uid + 2, r.uid + 3]

    def test_freed_slot_refilled_next_step_under_load(self):
        """Regression (ISSUE 7): a slot freed at step t serves a queued
        request at step t+1 — eviction used to fire only at wave
        boundaries, idling freed slots until the whole batch drained —
        while the surviving row's stream is untouched."""
        eos = 12
        sched = self._mk(batch=2, eos=eos)
        a = Request(uid=0, prompt=np.asarray([10]), max_new_tokens=6)
        b = Request(uid=1, prompt=np.asarray([20]), max_new_tokens=8)
        c = Request(uid=2, prompt=np.asarray([30]), max_new_tokens=2)
        sched.submit(a)
        sched.submit(b)
        sched.step()                               # admit A:10 B:20; +1
        assert a.generated == [10, 11] and b.generated == [20, 21]
        sched.submit(c)
        sched.step()                               # A:12 (EOS) B:22
        assert a.done and a.generated == [10, 11, 12]
        sched.step()                               # A evicted, C admitted NOW
        assert c.generated == [30, 31]             # prefill + 1 decode
        assert a in sched.finished
        done = sched.run()
        # B's stream never saw the eviction or the admission
        assert b.generated == [20, 21, 22, 23, 24, 25, 26, 27]
        assert c.done and c.generated == [30, 31]
        assert {r.uid for r in done} == {0, 1, 2}

    def test_run_cannot_starve_queued_request(self):
        """A long-running slot must not starve the queue: every freed
        slot is refilled FIFO on the next step, so all short requests
        complete while the long one is still decoding."""
        sched = self._mk(batch=2)
        long = Request(uid=0, prompt=np.asarray([1]), max_new_tokens=40)
        sched.submit(long)
        shorts = [Request(uid=1 + i, prompt=np.asarray([2 + i]),
                          max_new_tokens=2) for i in range(6)]
        for r in shorts:
            sched.submit(r)
        # enough steps for the shorts only if freed slots recycle per-step
        for _ in range(16):
            sched.step()
        assert all(r.done for r in shorts)
        assert not long.done                       # still occupying its slot
        done = sched.run()
        assert {r.uid for r in done} == {r.uid for r in shorts} | {0}

    def test_wave_admission_defers_until_batch_drains(self):
        """admission='wave' retains the old policy (the kernel_bench
        baseline): no admission while any slot is active."""
        sched = self._mk(batch=2, admission="wave")
        a = Request(uid=0, prompt=np.asarray([10]), max_new_tokens=2)
        b = Request(uid=1, prompt=np.asarray([20]), max_new_tokens=4)
        c = Request(uid=2, prompt=np.asarray([30]), max_new_tokens=2)
        sched.submit(a)
        sched.submit(b)
        sched.step()                               # admit wave {A, B}
        sched.submit(c)
        sched.step()                               # A done; B alive
        assert a.done
        sched.step()                               # slot must idle
        assert c.generated == []                   # deferred admission
        done = sched.run()
        assert c.done and c.generated == [30, 31]  # admitted after drain
        assert {r.uid for r in done} == {0, 1, 2}

    def test_eos_request_evicted_to_finished(self):
        sched = self._mk(batch=1, eos=12)
        sched.submit(Request(uid=0, prompt=np.asarray([11]),
                             max_new_tokens=8))
        sched.submit(Request(uid=1, prompt=np.asarray([5]),
                             max_new_tokens=2))
        done = sched.run()
        assert [r.uid for r in done] == [0, 1]
        assert done[0].generated == [11, 12]       # EOS on first decode

    def test_prompt_longer_than_prefill_len_rejected(self):
        sched = BatchScheduler(_StubEngine(), batch_size=2, prefill_len=4)
        with pytest.raises(ValueError):
            sched.submit(Request(uid=0, prompt=np.asarray([1] * 5)))


class TestClassifyScheduler:
    @pytest.fixture(scope="class")
    def vit_engine(self):
        from repro.configs.deit import DEIT_MICRO
        cfg = dataclasses.replace(DEIT_MICRO, n_layers=2, quant=QuantConfig())
        model = build_model(cfg)
        params = model.init(jax.random.key(0))
        return cfg, ViTServingEngine(model, params, ServeConfig(batch=4))

    def _images(self, n, size, seed):
        rng = np.random.default_rng(seed)
        return rng.normal(size=(n, size, size, 3)).astype(np.float32)

    def test_mixed_sizes_match_direct_classify(self, vit_engine):
        cfg, eng = vit_engine
        sched = ClassifyScheduler(eng)
        sizes = (3, 6, 1, 2)                       # 12 images, batch 4
        reqs = [ClassifyRequest(uid=i, images=self._images(n, cfg.image_size,
                                                           seed=i))
                for i, n in enumerate(sizes)]
        for r in reqs:
            sched.submit(r)
        done = sched.run()
        assert len(done) == len(sizes) and all(r.done for r in done)
        for r in done:
            want_labels, want_logits = eng.classify(r.images)
            np.testing.assert_array_equal(r.labels, np.asarray(want_labels))
            np.testing.assert_array_equal(r.logits, np.asarray(want_logits))

    def test_fixed_shape_jit_stays_warm(self, vit_engine):
        cfg, eng = vit_engine
        eng.classify(self._images(4, cfg.image_size, seed=99))   # warm
        base = eng.jit_cache_size()
        sched = ClassifyScheduler(eng)
        for i, n in enumerate((5, 1, 7, 3, 4)):
            sched.submit(ClassifyRequest(
                uid=i, images=self._images(n, cfg.image_size, seed=10 + i)))
        done = sched.run()
        assert len(done) == 5
        if base >= 0:                              # cache stats available
            assert eng.jit_cache_size() == base    # zero recompiles

    def test_zero_image_request_keeps_order_and_shapes(self, vit_engine):
        """Empty requests complete in FIFO order with (0, n_classes)
        results, so position-based concatenation stays aligned."""
        cfg, eng = vit_engine
        sched = ClassifyScheduler(eng)
        sched.submit(ClassifyRequest(uid=0, images=self._images(
            0, cfg.image_size, seed=0)))
        assert sched.step() == 0                   # evicted, nothing to run
        assert len(sched.finished) == 1 and sched.finished[0].done
        sched.submit(ClassifyRequest(uid=1, images=self._images(
            2, cfg.image_size, seed=1)))
        sched.submit(ClassifyRequest(uid=2, images=self._images(
            0, cfg.image_size, seed=2)))
        sched.submit(ClassifyRequest(uid=3, images=self._images(
            1, cfg.image_size, seed=3)))
        done = sched.run()
        assert [r.uid for r in done] == [0, 1, 2, 3]   # FIFO completion
        for r in done:
            assert r.logits.shape[1] == cfg.n_classes
        # the serve-example aggregation pattern must not trip on empties
        agg = np.concatenate([r.logits for r in done])
        assert agg.shape == (3, cfg.n_classes)

    def test_step_counts_images_not_requests(self, vit_engine):
        cfg, eng = vit_engine
        sched = ClassifyScheduler(eng)
        for i in range(3):                         # 3 x 2 images, batch 4
            sched.submit(ClassifyRequest(
                uid=i, images=self._images(2, cfg.image_size, seed=20 + i)))
        assert sched.step() == 4                   # spans request boundary
        assert sched.step() == 2                   # remainder, zero-padded
        assert sched.step() == 0


class _MeanPixelModel:
    """A stand-in classifier: each image's mean pixel through a fixed
    (3, n_classes) matrix, so the real engine runs a trivial jit."""
    cfg = SimpleNamespace(n_classes=5)

    def logits(self, params, images):
        return images.mean(axis=(1, 2)) @ params


class _GridOutputModel:
    """A stand-in image model whose output per image is a (2, 2, 3) grid
    (each 2 x 2 quadrant's mean pixel), not a class vector: the shape of
    an embedding model such as the SAM encoder."""
    cfg = SimpleNamespace(n_classes=0)
    out_shape = (2, 2, 3)

    def logits(self, params, images):
        b, h, w, c = images.shape
        q = images.reshape(b, 2, h // 2, 2, w // 2, c)
        return q.mean(axis=(2, 4)) * params


class TestClassifySchedulerGridOutput:
    """``ClassifyScheduler`` carries any per-image output: request outputs
    keep the model's per-image shape, a zero-image request gets an empty
    one of that shape, and no labels are taken from a non-vector output."""

    @pytest.fixture(scope="class")
    def engine(self):
        return ViTServingEngine(_GridOutputModel(), jnp.float32(2.0),
                                ServeConfig(batch=4))

    def test_outputs_keep_their_shape(self, engine):
        rng = np.random.default_rng(0)
        sched = ClassifyScheduler(engine)
        reqs = [ClassifyRequest(uid=i, images=rng.normal(
            size=(n, 4, 4, 3)).astype(np.float32))
            for i, n in enumerate((3, 0, 6))]
        for r in reqs:
            sched.submit(r)
        done = sched.run()
        assert [r.uid for r in done] == [0, 1, 2]
        for r in done:
            n = r.images.shape[0]
            assert r.logits.shape == (n, 2, 2, 3)
            assert r.labels is None
            want = r.images.reshape(n, 2, 2, 2, 2, 3).mean(axis=(2, 4)) * 2
            np.testing.assert_allclose(r.logits, want, rtol=1e-6)
        labels, out = engine.classify(reqs[0].images)
        assert labels is None and out.shape == (3, 2, 2, 3)


class TestClassifySchedulerSpans:
    """The span tree of ``ClassifyScheduler.step`` and the request spans,
    through a real ``ViTServingEngine`` around a stand-in model."""

    STEP_TREE = {"scheduler/step": None,
                 "scheduler/pack": "scheduler/step",
                 "scheduler/classify_step": "scheduler/step",
                 "engine/put": "scheduler/classify_step",
                 "engine/dispatch": "scheduler/classify_step",
                 "scheduler/fetch": "scheduler/classify_step",
                 "scheduler/deliver": "scheduler/step"}

    @pytest.fixture(scope="class")
    def engine(self):
        params = jnp.arange(15, dtype=jnp.float32).reshape(3, 5)
        return ViTServingEngine(_MeanPixelModel(), params,
                                ServeConfig(batch=4))

    @staticmethod
    def _request(uid, n):
        images = np.full((n, 2, 2, 3), float(uid), np.float32)
        return ClassifyRequest(uid=uid, images=images)

    @staticmethod
    def _host(spans):
        return [r for r in spans if r.name != "python/gc"]

    def test_one_step_is_one_tree(self, engine):
        sched = ClassifyScheduler(engine)
        sched.submit(self._request(0, 3))
        sched.submit(self._request(1, 2))
        with T.recording() as rec:
            assert sched.step() == 4
        spans = self._host(rec.spans)
        tree = [r for r in spans if r.name in self.STEP_TREE]
        assert sorted(r.name for r in tree) == sorted(self.STEP_TREE)
        by_id = {r.id: r for r in tree}
        for r in tree:
            parent = by_id[r.parent].name if r.parent in by_id else None
            assert parent == self.STEP_TREE[r.name], r
            if parent is not None:
                p = by_id[r.parent]
                assert p.start <= r.start <= r.end <= p.end
        (step,) = [r for r in tree if r.name == "scheduler/step"]
        assert step.attrs == {"step": 1, "images": 4}
        (cs,) = [r for r in tree if r.name == "scheduler/classify_step"]
        assert cs.attrs == {"images": 4}
        # both requests were packed; request 0 (3 images) is done
        waits = [r for r in spans if r.name == "scheduler/queue_wait"]
        assert sorted(r.parent for r in waits) == [0, 1]
        assert all(r.end == step.start for r in waits)
        (req,) = [r for r in spans if r.name == "scheduler/request"]
        assert req.id == 0 and req.attrs == {"images": 3, "first_step": 1}
        assert len(spans) == len(tree) + 3

    def test_every_request_has_one_request_and_one_wait(self, engine):
        sched = ClassifyScheduler(engine)
        sizes = (3, 6, 1, 2, 5, 4)
        with T.recording() as rec:
            for uid, n in enumerate(sizes):
                sched.submit(self._request(uid, n))
            done = sched.run()
        assert len(done) == len(sizes)
        spans = self._host(rec.spans)
        reqs = {r.id: r for r in spans if r.name == "scheduler/request"}
        waits = {r.parent: r for r in spans
                 if r.name == "scheduler/queue_wait"}
        assert sorted(reqs) == sorted(waits) == list(range(len(sizes)))
        assert len([r for r in spans if r.name == "scheduler/request"]) \
            == len([r for r in spans if r.name == "scheduler/queue_wait"]) \
            == len(sizes)
        steps = {r.attrs["step"]: r for r in spans
                 if r.name == "scheduler/step"}
        assert len(steps) == sched.steps == 6          # 21 images, batch 4
        for uid, n in enumerate(sizes):
            req, wait = reqs[uid], waits[uid]
            assert req.attrs["images"] == n
            first = steps[req.attrs["first_step"]]
            assert req.start == wait.start
            assert wait.end == first.start             # packed in that step
            assert first.start < req.end
        assert sum(s.attrs["images"] for s in steps.values()) == sum(sizes)


class TestScheduler:
    def test_continuous_batching(self, dense_model):
        cfg, model, params = dense_model
        eng = ServingEngine(model, params, ServeConfig(max_len=64, batch=2))
        sched = BatchScheduler(eng, batch_size=2)
        rng = np.random.default_rng(3)
        for uid in range(4):
            sched.submit(Request(uid=uid,
                                 prompt=rng.integers(
                                     0, cfg.vocab, 6).astype(np.int32),
                                 max_new_tokens=4))
        done = sched.run(max_steps=64)
        finished = [r for r in done if r.done]
        assert len(finished) >= 2
        for r in finished:
            assert len(r.generated) == 4
