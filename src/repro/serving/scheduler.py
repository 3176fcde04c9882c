"""Continuous-batching request schedulers (host side; DESIGN.md §7).

Two schedulers share the same contract — a FIXED batch shape feeds one
jit specialization forever, while mixed-size request streams are packed
into it at step boundaries:

``BatchScheduler`` (token engines): maintains a fixed-width decode
batch with SLOT-level admission: the KV cache carries a per-row
``cache['index']`` vector, so a finished row is evicted and the next
queued request prefilled into that slot immediately (one batch-1
prefill scattered into the live cache — ``make_slot_prefill_step``)
while the other rows keep decoding.  No wave barrier: a slot freed at
step t serves a new request at step t+1.

``ClassifyScheduler`` (ViT engines): classification is stateless, so
admission is fully continuous — each step packs up to ``batch`` images
from the queue front, ACROSS request boundaries, zero-padding only the
final partial chunk.  A request's images may span several steps; the
request completes when its last image is classified.  Because every
step runs the same (batch, H, W, 3) shape, the jit cache never grows
past one entry regardless of the request-size mix (asserted via
``engine.jit_cache_size()`` in tests/test_sharded_serving.py).

Both schedulers publish to ``repro.telemetry`` (DESIGN.md §15):
``scheduler/submitted``/``completed``/``admissions`` counters,
``queue_depth``/``slots_active``/``in_flight`` gauges (conserving
``submitted == completed + in_flight`` at step boundaries), and the
``serving/recompiles`` counter via the engine's jit-cache delta.
``BatchScheduler`` adds a ``request_latency_ms`` histogram and a
``tokens_per_s`` gauge.  ``ClassifyScheduler`` opens a span at each
layer boundary of a step, so a ``telemetry.recording()`` holds the tree
of every step and the life of every request::

    scheduler/step              one step (>= 1 image); attrs images, step
    ├─ scheduler/pack           queue walk + filling the batch chunk
    ├─ scheduler/classify_step  engine call + read-back; attr images
    │  ├─ engine/put            host -> device copy of the chunk
    │  ├─ engine/dispatch       the jitted call, returning unready
    │  └─ scheduler/fetch       wait for the device + copy back
    └─ scheduler/deliver        logits into requests, gauges, eviction
    scheduler/request           submit -> done; id = uid; attrs images,
    └─ scheduler/queue_wait       first_step; submit -> start of the
                                  step that packs its first image
"""
from __future__ import annotations

import dataclasses
from collections import deque
from typing import Dict, List, Optional

import jax.numpy as jnp
import numpy as np

from repro import telemetry as T
from repro.serving.engine import _note_recompiles


@dataclasses.dataclass
class Request:
    """One token-generation request.

    prompt: (s,) int32 token ids; generated: filled by the scheduler;
    done: set on EOS or when ``max_new_tokens`` is reached."""
    uid: int
    prompt: np.ndarray                 # (s,) int32
    max_new_tokens: int = 16
    generated: List[int] = dataclasses.field(default_factory=list)
    done: bool = False
    _submit_ts: Optional[float] = None  # set by the scheduler at submit


class BatchScheduler:
    """Slot-level continuous batching around a token engine.

    engine: a ``ServingEngine`` (needs ``_prefill_slot``/``_decode``/
    ``params`` and ``model.cache_init``).  batch_size: fixed decode
    width.  eos_id: optional stop token.

    prefill_len: fixed (1, P) slot-prefill shape; prompts are
    RIGHT-padded to it (a longer prompt raises at ``submit``).  ``None``
    buckets each prompt to the next power of two — one jit
    specialization per bucket ever seen, flat after warmup.

    A queued request is admitted into every freed slot at each step.

    Token contract: a request's first generated token comes from its
    prefill logits (recorded at admission), the rest from decode steps
    — identical to running ``ServingEngine.generate`` on that request
    alone (property-tested against the unbatched oracle in
    tests/test_scheduler_properties.py).
    """

    def __init__(self, engine, batch_size: int, eos_id: Optional[int] = None,
                 prefill_len: Optional[int] = None):
        self.engine = engine
        self.batch = batch_size
        self.eos = eos_id
        self.prefill_len = prefill_len
        self.queue: deque[Request] = deque()
        self.active: List[Optional[Request]] = [None] * batch_size
        self.finished: List[Request] = []
        self._tok = None               # (batch, 1) int32 numpy
        self._cache = None

    def submit(self, req: Request):
        """Enqueue; admitted into the next freed slot (FIFO).  There is
        no capacity limit — the queue absorbs any submit burst."""
        if self.prefill_len is not None and \
                len(req.prompt) > self.prefill_len:
            raise ValueError(
                f"prompt length {len(req.prompt)} > prefill_len "
                f"{self.prefill_len}")
        req._submit_ts = T.walltime()
        self.queue.append(req)
        T.counter("scheduler/submitted").inc()
        self._update_gauges()

    def _bucket(self, n: int) -> int:
        """Slot-prefill pad length for an ``n``-token prompt: the fixed
        ``prefill_len``, or the next power of two (>= 8) — each bucket
        is one jit specialization, so the cache stays flat once every
        bucket in the workload has been seen."""
        if self.prefill_len is not None:
            return self.prefill_len
        p = 8
        while p < n:
            p *= 2
        return p

    def _record(self, req: Request, tok: int):
        req.generated.append(tok)
        if (self.eos is not None and tok == self.eos) or \
                len(req.generated) >= req.max_new_tokens:
            req.done = True

    def _update_gauges(self):
        """Publish the queue/slot occupancy gauges.  The conservation
        invariant asserted by tests/test_scheduler_properties.py:
        ``scheduler/submitted == scheduler/completed +
        scheduler/in_flight`` at every step boundary (a done-but-not-
        evicted slot still counts as in flight — it completes at
        eviction)."""
        slots = sum(1 for r in self.active if r is not None)
        T.gauge("scheduler/queue_depth").set(len(self.queue))
        T.gauge("scheduler/slots_active").set(slots)
        T.gauge("scheduler/in_flight").set(len(self.queue) + slots)

    def _evict(self):
        """Move done requests out of their slots: each slot is freed
        the step after its request finishes."""
        for i, r in enumerate(self.active):
            if r is not None and r.done:
                self.finished.append(r)
                self.active[i] = None
                T.counter("scheduler/completed").inc()
                if r._submit_ts is not None:
                    T.histogram("scheduler/request_latency_ms",
                                T.DEFAULT_MS_BUCKETS).record(
                        (T.walltime() - r._submit_ts) * 1e3)
        self._update_gauges()

    def _admit(self):
        """Fill free slots from the queue front, one batch-1 slot
        prefill each — the live rows' cache state is untouched (per-row
        index contract, DESIGN.md §7)."""
        if not self.queue:
            return
        for i in range(self.batch):
            if not self.queue or self.active[i] is not None:
                continue
            req = self.queue.popleft()
            if self._cache is None:
                self._cache = self.engine.model.cache_init(
                    self.batch, self.engine.cfg.max_len)
                self._tok = np.zeros((self.batch, 1), np.int32)
            n = len(req.prompt)
            P = self._bucket(n)
            tokens = np.zeros((1, P), np.int32)
            tokens[0, :n] = req.prompt
            T.histogram("serving/prefill_len",
                        T.DEFAULT_SIZE_BUCKETS).record(P)
            with T.span("scheduler/slot_prefill"):
                tok, self._cache = self.engine._prefill_slot(
                    self.engine.params, jnp.asarray(tokens), jnp.int32(n),
                    jnp.int32(i), self._cache)
                t = int(np.asarray(tok)[0])
            T.counter("scheduler/admissions").inc()
            self.active[i] = req
            self._record(req, t)
            self._tok[i, 0] = t

    def step(self) -> int:
        """Evict, admit, then one decode step across the batch; returns
        #live requests.

        Empty queue + empty batch is a no-op returning 0 (safe to call
        in a drain loop).  Done-but-not-yet-evicted rows and empty slots
        keep decoding as padding; their output is discarded.
        """
        self._evict()
        self._admit()
        live = [r for r in self.active if r is not None and not r.done]
        if not live:
            self._update_gauges()
            return 0
        with T.span("scheduler/decode_step", live=len(live)) as sp:
            tok, self._cache = self.engine._decode(
                self.engine.params, jnp.asarray(self._tok), self._cache)
            self._tok = np.array(tok)      # writable host copy
        ntok = 0
        for i, r in enumerate(self.active):
            if r is None or r.done:
                continue
            self._record(r, int(self._tok[i, 0]))
            ntok += 1
        T.counter("scheduler/tokens_generated").inc(ntok)
        if sp.elapsed_s:
            T.gauge("scheduler/tokens_per_s").set(ntok / sp.elapsed_s)
        _note_recompiles(self.engine)
        self._update_gauges()
        return sum(1 for r in self.active if r is not None and not r.done)

    def run(self, max_steps: int = 1024) -> List[Request]:
        """Drain queue + batch; returns every request seen (finished
        first, then the residual active slots).  Slot-level admission
        means a queued request can never starve behind long-running
        slots: every freed slot is refilled from the queue front on the
        very next step."""
        for _ in range(max_steps):
            if self.step() == 0 and not self.queue:
                break
        self._evict()
        return self.finished + [r for r in self.active if r is not None]


# ---------------------------------------------------------------------------
# classification-side continuous batching (the ViT serving path)
# ---------------------------------------------------------------------------
@dataclasses.dataclass
class ClassifyRequest:
    """One classification request of ``images.shape[0]`` images.

    images: (n, H, W, 3) float; logits: (n, *out) numpy, the model's
    output per image, whatever its shape (class logits (n, classes) for
    a classifier, an embedding (n, g, g, c) for the SAM encoder); labels:
    (n,) argmax labels, for a classifier only (None otherwise).  Both
    are filled incrementally as the scheduler packs this request's
    images into fixed-shape batches; done: set when all n are served.
    """
    uid: int
    images: np.ndarray
    logits: Optional[np.ndarray] = None
    labels: Optional[np.ndarray] = None
    done: bool = False
    _next: int = 0                     # images admitted so far
    _submit_ns: Optional[int] = None   # span stamp, set at submit
    _first_step: Optional[int] = None  # step that packed its first image


class ClassifyScheduler:
    """Continuous batching for stateless classification.

    Port of the token-engine ``BatchScheduler`` to the classify side:
    because a classifier holds no per-request state, admission needs no
    wave barrier — every ``step()`` packs up to ``batch`` images from
    the FRONT of the queue, spanning request boundaries, and zero-pads
    only when the queue runs dry mid-chunk.  All steps reuse the one
    (batch, H, W, 3) jit specialization of ``engine._logits`` (sharded
    or not), so mixed request sizes never recompile.

    engine: a ``ViTServingEngine``; batch_size defaults to the engine's
    ``ServeConfig.batch``.  The per-image output shape is the model's
    ``out_shape`` (``(n_classes,)`` for a model without one); a vector
    output is a classifier's, and only then are labels taken.
    """

    def __init__(self, engine, batch_size: Optional[int] = None):
        self.engine = engine
        self.batch = batch_size or engine.cfg.batch
        self.n_classes = int(getattr(engine.model.cfg, "n_classes", 0))
        self.out_shape = tuple(getattr(engine.model, "out_shape",
                                       (self.n_classes,)))
        self.classifier = len(self.out_shape) == 1
        self.queue: deque[ClassifyRequest] = deque()
        self.finished: List[ClassifyRequest] = []
        self.steps = 0                     # steps that classified images

    def submit(self, req: ClassifyRequest):
        """Enqueue a request; its images are admitted (possibly split
        across steps) in FIFO order.  A zero-image request completes in
        queue order too (with correctly shaped empty results), so
        position-based result/label pairing stays aligned."""
        req._submit_ns = T.now_ns()
        self.queue.append(req)
        T.counter("scheduler/submitted").inc()
        self._update_gauges()

    def _update_gauges(self):
        """Classification holds no slots: in-flight is just the queue
        (same conservation invariant as ``BatchScheduler``)."""
        T.gauge("scheduler/queue_depth").set(len(self.queue))
        T.gauge("scheduler/in_flight").set(len(self.queue))

    def jit_cache_size(self) -> int:
        """Specialization count of the underlying jitted forward (see
        ``ViTServingEngine.jit_cache_size``)."""
        return self.engine.jit_cache_size()

    def _evict_completed(self):
        """Pop front requests whose images are all classified (including
        zero-image requests) to ``finished``, preserving FIFO order, and
        close each one's ``scheduler/request`` span."""
        while self.queue and self.queue[0]._next >= \
                self.queue[0].images.shape[0]:
            req = self.queue.popleft()
            if req.logits is None:             # zero-image request
                req.logits = np.zeros((0,) + self.out_shape, np.float32)
                if self.classifier:
                    req.labels = np.zeros((0,), np.int64)
            req.done = True
            self.finished.append(req)
            T.counter("scheduler/completed").inc()
            attrs = {"images": req.images.shape[0]}
            if req._first_step is not None:
                attrs["first_step"] = req._first_step
            T.span.interval("scheduler/request", req._submit_ns, T.now_ns(),
                            id=req.uid, **attrs)
        self._update_gauges()

    def _pack(self, step_start_ns: int):
        """Take up to ``batch`` images off the queue front into a fresh
        zero-padded chunk; returns (chunk, [(request, image index)]).
        Closes the ``scheduler/queue_wait`` span of each request whose
        first image is taken."""
        take: List[tuple] = []                 # (request, image index)
        for req in self.queue:
            if req._next == 0 and req.images.shape[0]:
                req._first_step = self.steps
                T.span.interval("scheduler/queue_wait", req._submit_ns,
                                step_start_ns, parent=req.uid)
            while len(take) < self.batch and \
                    req._next < req.images.shape[0]:
                take.append((req, req._next))
                req._next += 1
            if len(take) >= self.batch:
                break
        img = take[0][0].images
        chunk = np.zeros((self.batch,) + img.shape[1:], img.dtype)
        for j, (req, i) in enumerate(take):
            chunk[j] = req.images[i]
        return chunk, take

    def _deliver(self, logits: np.ndarray, take: List[tuple]):
        """Scatter a step's outputs (and a classifier's labels) into its
        requests, publish the step's counters, and evict the completed
        requests."""
        # slot occupancy for a stateless batch = filled rows this step
        # (the rest of the fixed shape is zero padding)
        T.gauge("scheduler/slots_active").set(len(take))
        T.counter("scheduler/images_classified").inc(len(take))
        _note_recompiles(self.engine)
        for j, (req, i) in enumerate(take):
            if req.logits is None:
                n = req.images.shape[0]
                req.logits = np.zeros((n,) + logits.shape[1:], logits.dtype)
                if self.classifier:
                    req.labels = np.zeros((n,), np.int64)
            req.logits[i] = logits[j]
            if self.classifier:
                req.labels[i] = int(np.argmax(logits[j]))
        self._evict_completed()

    def step(self) -> int:
        """Classify up to ``batch`` images off the queue front; returns
        the number of images classified (0 when the queue is empty)."""
        self._evict_completed()
        if not self.queue:      # else the front request has images left
            return 0
        self.steps += 1
        with T.span("scheduler/step", step=self.steps) as sp:
            with T.span("scheduler/pack"):
                chunk, take = self._pack(sp.start_ns)
            sp.attrs["images"] = len(take)
            with T.span("scheduler/classify_step", images=len(take)):
                logits = self.engine.logits_batch(chunk)
                with T.span("scheduler/fetch"):
                    logits = np.asarray(logits)
            with T.span("scheduler/deliver"):
                self._deliver(logits, take)
        return len(take)

    def run(self, max_steps: int = 4096) -> List[ClassifyRequest]:
        """Drain the queue; returns the finished requests in completion
        order."""
        for _ in range(max_steps):
            if self.step() == 0:
                break
        return self.finished
