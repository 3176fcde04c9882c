"""The one traffic generator; every mix is a data file it reads.

A mix (``bench/traffic/<name>.json``) states the loop and the request
sizes.  ``"loop": "closed"``: ``clients`` callers, each with one request
outstanding; a caller sends its next request when the last completes.
Sizes (images per request) are ``{"dist": "log_uniform", "min", "max"}``.

Every seed gets the same work in another order: sizes are the
distribution's quantiles at evenly spaced probabilities, which the seed
only shuffles.  So two seeds differ by the order of requests, not by
how many images a run holds.
"""
from __future__ import annotations

import numpy as np


def size_quantiles(spec: dict, n: int) -> np.ndarray:
    """``n`` request sizes at evenly spaced quantiles of ``spec``."""
    if spec["dist"] != "log_uniform":
        raise ValueError(f"unknown size distribution {spec['dist']!r}")
    lo, hi = int(spec["min"]), int(spec["max"])
    u = (np.arange(n) + 0.5) / n
    # continuous log-uniform on [lo, hi + 1), floored to an integer
    x = np.exp(np.log(lo) + u * (np.log(hi + 1) - np.log(lo)))
    return np.minimum(np.floor(x), hi).astype(np.int64)


def size_stream(spec: dict, rng: np.random.Generator, n: int = 4096):
    """Endless request sizes: the quantile set, reshuffled every pass."""
    base = size_quantiles(spec, n)
    while True:
        for s in rng.permutation(base):
            yield int(s)
