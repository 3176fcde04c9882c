"""The comparison that decides ``correct``.

Two numbers, each with a limit from the cell's file:

* ``logits_rel_rms``: served logits from the window against the plain
  reference at ``"highest"``, on a sample of finished requests drawn
  from the seed, the longest among them.  Per image, the RMS of the
  difference over the RMS of the reference; the worst image counts.
  It catches a wrong weight, layer, image or row; it cannot tell float32
  from bfloat16, because this datapath parts any two float32
  implementations by a few percent at the logits.
* ``ops_rel_rms``: each op of a block (fused LayerNorm -> linear,
  attention, linear, GELU, LayerNorm) through the program's kernels at
  the cell's widths and batch, against the reference op on the same
  seeded inputs; the worst op counts.  This is the number a lower
  precision fails.

The reference runs after the window, once the program's state is freed,
in blocks of images.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from bench.harness import reference as R

REF_BLOCK = 8


def sample(requests, rng, images: int) -> list:
    """Finished requests drawn from the seed, the longest first, until
    they hold at least ``images`` images."""
    done = [r for r in requests if r.logits is not None]
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


def reference_logits(cfg: dict, weights: dict, images: np.ndarray,
                     precision: str) -> np.ndarray:
    out = []
    for i in range(0, len(images), REF_BLOCK):
        out.append(np.asarray(R.logits(
            weights, jnp.asarray(images[i:i + REF_BLOCK]),
            heads=cfg["num_attention_heads"], patch=cfg["patch_size"],
            dp=R.Datapath.of(cfg), precision=precision)))
    return np.concatenate(out)


def logits_number(cfg, weights, pool, reqs, precision="highest",
                  served=None) -> float:
    """Worst per-image rel RMS of ``served`` (default: what the window
    served) against the reference."""
    images = np.concatenate([pool[r.start:r.start + r.n] for r in reqs])
    got = (np.concatenate([r.logits for r in reqs]) if served is None
           else served)
    want = reference_logits(cfg, weights, images, precision)
    return float(np.max(rel_rms(got, want, axis=-1)))


def op_inputs(cfg: dict, batch: int, seed: int) -> dict:
    """Seeded op inputs at the cell's batch and the config's widths."""
    rng = np.random.default_rng([seed, 3])
    d, ff, h = (cfg["hidden_size"], cfg["intermediate_size"],
                cfg["num_attention_heads"])
    t = (cfg["image_size"] // cfg["patch_size"]) ** 2 + 1

    def arr(*shape):
        return jnp.asarray(rng.standard_normal(shape, dtype=np.float32))

    return {"x": arr(batch, t, d), "h": arr(batch, t, ff),
            "q": arr(batch, t, h, d // h), "k": arr(batch, t, h, d // h),
            "v": arr(batch, t, h, d // h)}


def layer0(weights: dict) -> dict:
    return jax.tree_util.tree_map(lambda a: a[0], weights["blocks"])


def reference_ops(cfg: dict, layer: dict, x: dict, precision: str) -> dict:
    """Each op that ``program.ops`` runs, by the reference, on one
    layer's weights (the benchmark's float tree) and the inputs ``x``."""
    dp = R.Datapath.of(cfg)

    @jax.jit
    def run(layer, x):
        f = layer["ffn"]
        return {
            "ln_linear": R.linear(R.layernorm(x["x"], layer["ln2_g"],
                                              layer["ln2_b"], dp),
                                  f["wi"], f["bi"], dp, precision),
            "attention": R.attention(x["q"], x["k"], x["v"], dp, precision),
            "linear": R.linear(x["h"], f["wo"], f["bo"], dp, precision),
            "gelu": R.gelu(x["h"], dp),
            "layernorm": R.layernorm(x["x"], layer["ln1_g"], layer["ln1_b"],
                                     dp),
        }
    return {k: np.asarray(v) for k, v in run(layer, x).items()}


def ops_numbers(got: dict, want: dict) -> dict:
    return {k: float(rel_rms(got[k], want[k])) for k in want}


def verdict(numbers: dict, limits: dict) -> tuple:
    """(correct, {name: {"value", "limit"}}); a missing number fails."""
    checks = {k: {"value": numbers.get(k), "limit": limits[k]}
              for k in limits}
    ok = all(c["value"] is not None and np.isfinite(c["value"])
             and c["value"] <= c["limit"] for c in checks.values())
    return ok, checks
