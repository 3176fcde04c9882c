"""The comparison that decides ``correct``.

Two numbers, each with a limit from the cell's file:

* the family's output number (``OUTPUT`` of ``bench/families/<family>.py``;
  ``logits_rel_rms`` for ``vit``): what the window served against the
  family's plain reference forward at ``"highest"``, on a sample of
  finished requests drawn from the seed, the longest among them.  Per
  image, the RMS of the difference over the RMS of the reference, taken
  over all of that image's output elements; the worst image counts.  It
  catches a wrong weight, layer, image or row; for ``vit`` it cannot
  tell float32 from bfloat16, because this datapath parts any two float32
  implementations by a few percent at the logits.
* ``ops_rel_rms``: each op case of the family (for ``vit``: fused
  LayerNorm -> linear, attention, linear, GELU, LayerNorm) through the
  program's kernels at the cell's widths and batch, against the reference
  op on the same seeded inputs; the worst op counts.  This is the number
  a lower precision fails.

The reference runs after the window, once the program's state is freed,
in blocks of images.
"""
from __future__ import annotations

import jax.numpy as jnp
import numpy as np

from bench.harness import manifest

REF_BLOCK = 8


def sample(requests, rng, images: int) -> list:
    """Finished requests drawn from the seed, the longest first, until
    they hold at least ``images`` images."""
    done = [r for r in requests if r.output is not None]
    if not done:
        return []
    longest = max(done, key=lambda r: r.n)
    out, total = [longest], longest.n
    for i in rng.permutation(len(done)):
        if total >= images:
            break
        if done[i] is not longest:
            out.append(done[i])
            total += done[i].n
    return out


def rel_rms(got, want, axis=None):
    d = np.asarray(got, np.float64) - np.asarray(want, np.float64)
    w = np.asarray(want, np.float64)
    return np.sqrt(np.mean(d * d, axis=axis)) / np.sqrt(np.mean(w * w,
                                                                axis=axis))


def reference_output(cfg: dict, weights: dict, images: np.ndarray,
                     precision: str) -> np.ndarray:
    """The family's reference output of ``images``, ``REF_BLOCK`` images
    at a time."""
    forward = manifest.family(cfg["family"]).forward
    out = []
    for i in range(0, len(images), REF_BLOCK):
        out.append(np.asarray(forward(
            cfg, weights, jnp.asarray(images[i:i + REF_BLOCK]), precision)))
    return np.concatenate(out)


def output_number(cfg, weights, pool, reqs, precision="highest",
                  served=None) -> float:
    """Worst per-image rel RMS, over all of an image's output elements,
    of ``served`` (default: what the window served) against the
    reference."""
    images = np.concatenate([pool[r.start:r.start + r.n] for r in reqs])
    got = (np.concatenate([r.output for r in reqs]) if served is None
           else served)
    want = reference_output(cfg, weights, images, precision)
    n = len(images)
    return float(np.max(rel_rms(np.reshape(got, (n, -1)),
                                want.reshape(n, -1), axis=-1)))


def ops_numbers(got: dict, want: dict) -> dict:
    return {k: float(rel_rms(got[k], want[k])) for k in want}


def verdict(numbers: dict, limits: dict) -> tuple:
    """(correct, {name: {"value", "limit"}}); a missing number fails."""
    checks = {k: {"value": numbers.get(k), "limit": limits[k]}
              for k in limits}
    ok = all(c["value"] is not None and np.isfinite(c["value"])
             and c["value"] <= c["limit"] for c in checks.values())
    return ok, checks
