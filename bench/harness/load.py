"""Set-up, the measured window, and what a run recorded.

One process, one thread: the load generator and the scheduler share it,
as a client library calling the server in-process would.  Times are the
host's ``perf_counter``.
"""
from __future__ import annotations

import dataclasses
import time

import numpy as np

from bench.harness import program, traffic, weights as W
from bench.harness.trace import capture

POOL_IMAGES = 128


@dataclasses.dataclass
class Request:
    uid: int
    arrival: float          # when its caller sent it
    start: int              # first image's row in the image pool
    n: int
    sent: float = 0.0
    finish: float | None = None
    output: np.ndarray | None = None   # what the program answered


@dataclasses.dataclass
class Setup:
    cell: object
    seed: int
    batch: int
    weights: dict
    engine: object
    sched: object
    pool: np.ndarray        # (POOL_IMAGES + largest request, H, W, 3)
    setup_s: float


@dataclasses.dataclass
class Run:
    """What one measured window recorded; the metric readers read this."""
    cell: object
    batch: int
    setup_s: float
    t0: float
    t1: float
    steps: list             # (start, end, images) of every step
    requests: list          # Request, in send order
    counters: dict          # program counters over the window
    peaks: dict
    trace: object = None
    in_window: int = 0      # requests sent inside the window

    @property
    def window_s(self) -> float:
        return self.t1 - self.t0


def image_pool(cfg: dict, seed: int, largest: int) -> np.ndarray:
    """Distinct N(0, 1) images from the seed; the first ``largest`` rows
    repeat at the end so every request is one contiguous slice."""
    s = cfg["image_size"]
    rng = np.random.default_rng([seed, 1])
    pool = rng.standard_normal((POOL_IMAGES, s, s, 3), dtype=np.float32)
    return np.concatenate([pool, pool[:largest]])


def setup(cell, seed: int, t_start: float) -> Setup:
    """Weights on the device, the engine and scheduler, the image pool,
    and the one batch shape warmed up through the scheduler."""
    import jax
    cfg = cell.config
    batch = cell.workload["batch"]
    w = W.make(cfg, seed)
    jax.block_until_ready(w)
    engine, sched = program.scheduler(cfg, w, batch)
    pool = image_pool(cfg, seed, cell.traffic["sizes"]["max"])
    for uid in range(2):
        sched.submit(program.request(-1 - uid, pool[:batch]))
        sched.run()
    sched.finished.clear()
    return Setup(cell, seed, batch, w, engine, sched, pool,
                 time.perf_counter() - t_start)


class _Server:
    """Sends requests into the scheduler and steps it, recording both."""

    def __init__(self, s: Setup):
        self.s = s
        self.requests: list[Request] = []
        self.steps: list = []
        self.cursor = 0
        self.done = 0

    def send(self, n: int, arrival: float) -> Request:
        r = Request(uid=len(self.requests), arrival=arrival,
                    start=self.cursor % POOL_IMAGES, n=n)
        self.cursor += n
        self.s.sched.submit(program.request(
            r.uid, self.s.pool[r.start:r.start + n]))
        r.sent = time.perf_counter()
        self.requests.append(r)
        return r

    def step(self) -> list:
        """One scheduler step; returns the requests it completed."""
        t = time.perf_counter()
        n = self.s.sched.step()
        e = time.perf_counter()
        self.steps.append((t, e, n))
        fin = self.s.sched.finished
        out = []
        for req in fin[self.done:]:
            r = self.requests[req.uid]
            r.finish, r.output = e, req.logits
            out.append(r)
        self.done = len(fin)
        return out

    def drain(self):
        while self.s.sched.queue:
            self.step()


def closed_loop(srv: _Server, seconds: float, rng) -> tuple:
    """``clients`` callers with one request each outstanding; the window
    opens at the first step and closes at the end of the first step that
    ends ``seconds`` later.  Returns (t0, t1, requests sent in it); the
    requests still outstanding are drained after the window."""
    sizes = traffic.size_stream(srv.s.cell.traffic["sizes"], rng)
    for _ in range(srv.s.cell.traffic["clients"]):
        srv.send(next(sizes), time.perf_counter())
    t0 = None
    while True:
        done = srv.step()
        t0 = t0 if t0 is not None else srv.steps[-1][0]
        t1 = srv.steps[-1][1]
        if t1 - t0 >= seconds:
            break
        for _ in done:
            srv.send(next(sizes), time.perf_counter())
    return t0, t1, len(srv.requests)


def window(s: Setup, seconds: float, trace: bool, peaks: dict) -> Run:
    """The measured window, traced when asked."""
    rng = np.random.default_rng([s.seed, 2])
    s.sched.finished.clear()
    srv = _Server(s)
    if s.cell.traffic["loop"] != "closed":
        raise ValueError(f"unknown loop {s.cell.traffic['loop']!r}")
    before = program.counters()
    traces: list = []
    if trace:
        with capture(traces):
            t0, t1, sent = closed_loop(srv, seconds, rng)
    else:
        t0, t1, sent = closed_loop(srv, seconds, rng)
    after = program.counters()
    srv.drain()
    return Run(cell=s.cell, batch=s.batch, setup_s=s.setup_s, t0=t0, t1=t1,
               steps=[x for x in srv.steps if x[0] >= t0 and x[1] <= t1],
               requests=srv.requests,
               counters={k: after[k] - before[k] for k in after},
               peaks=peaks, trace=traces[0] if traces else None,
               in_window=sent)
