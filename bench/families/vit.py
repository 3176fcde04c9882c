"""The ViT family as the program runs DeiT: patch linear, class token and a
learned position table, pre-LayerNorm encoder blocks with GELU MLPs, and
a linear head on the class token.  Everything the benchmark knows of
this architecture is here; ``manifest.family`` finds it by the
configuration's ``"family"``.

Departures from the published DeiT, as the program runs it: the q/k/v
and output projections carry no bias, and the patch features are laid
out channel-major (the program's ``patchify``).

The reference side (``patchify``, ``block``, ``logits``, ``forward``,
``reference_ops``) is built from the MXInt primitives of
``bench.harness.reference`` and imports nothing of the program; the
program side (``model``, ``program_ops``) imports ``repro`` inside its
functions.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

from bench.harness import reference as R

# the number that compares what the window served with ``forward``
OUTPUT = "logits_rel_rms"

# the configuration keys ``shapes`` reads
DIMS = ("hidden_size", "intermediate_size", "num_hidden_layers",
        "patch_size", "image_size", "num_labels")


def shapes(cfg: dict) -> dict:
    """Leaf name -> (shape, kind) of a DeiT with the config's sizes."""
    d, ff, L = cfg["hidden_size"], cfg["intermediate_size"], cfg["num_hidden_layers"]
    p, c = cfg["patch_size"], cfg["num_labels"]
    tokens = (cfg["image_size"] // p) ** 2 + 1
    return {
        "patch_proj": ((3 * p * p, d), "matrix"),
        "patch_bias": ((d,), "bias"),
        "cls_token": ((1, 1, d), "bias"),
        "pos_embed": ((tokens, d), "bias"),
        "blocks": {
            "ln1_g": ((L, d), "scale"), "ln1_b": ((L, d), "shift"),
            "attn": {n: ((L, d, d), "matrix") for n in ("wq", "wk", "wv", "wo")},
            "ln2_g": ((L, d), "scale"), "ln2_b": ((L, d), "shift"),
            "ffn": {"wi": ((L, d, ff), "matrix"), "bi": ((L, ff), "bias"),
                    "wo": ((L, ff, d), "matrix"), "bo": ((L, d), "bias")},
        },
        "final_ln_g": ((d,), "scale"), "final_ln_b": ((d,), "shift"),
        "head": ((d, c), "matrix"), "head_b": ((c,), "bias"),
    }


# -- the program ---------------------------------------------------------

def model(cfg: dict):
    """The program's ViT with the configuration's sizes and datapath."""
    from repro.models import build_model
    from repro.models.model_api import ModelConfig
    from bench.harness.program import quant_config
    heads = cfg["num_attention_heads"]
    return build_model(ModelConfig(
        name=cfg["name"], family="vit", n_layers=cfg["num_hidden_layers"],
        d_model=cfg["hidden_size"], n_heads=heads, n_kv_heads=heads,
        d_ff=cfg["intermediate_size"], vocab=0, unit=("attn",),
        ffn_kind="gelu", image_size=cfg["image_size"],
        patch_size=cfg["patch_size"], n_classes=cfg["num_labels"],
        dtype=jnp.float32, quant=quant_config(cfg)))


def layer0(tree: dict) -> dict:
    """Layer 0's leaves of a weight tree: the benchmark's float tree or
    the engine's packed one."""
    return jax.tree_util.tree_map(lambda a: a[0], tree["blocks"])


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


def program_ops(engine, inputs: dict) -> dict:
    """Each op of one block through the program's kernel-mode layer entry
    points, on the given inputs and layer 0 of the weights the engine
    serves (its packed planes), with the engine's model's datapath.
    Returns name -> output as numpy."""
    from repro.models import layers as L

    q = engine.model.cfg.quant
    layer = layer0(engine.params)
    f = layer["ffn"]
    tokens = inputs["x"].shape[1]

    def attention(qv, k, v):
        b, s, h, d = qv.shape
        return q.datapath.attention(
            qv.reshape(b, s, h, 1, d), k, v, q=q,
            positions=jnp.arange(tokens)[None, :], causal=False, window=0,
            scale=d ** -0.5, chunk=tokens).reshape(b, s, h, d)

    cases = {
        "ln_linear": (lambda x, g, b, w, wb: L.layernorm_linear(
            x, g, b, w, wb, q=q),
            (inputs["x"], layer["ln2_g"], layer["ln2_b"], f["wi"], f["bi"])),
        "attention": (attention, (inputs["q"], inputs["k"], inputs["v"])),
        "linear": (lambda h, w, wb: L.linear(h, w, wb, q=q),
                   (inputs["h"], f["wo"], f["bo"])),
        "gelu": (lambda h: L.act_fn(h, "gelu", q), (inputs["h"],)),
        "layernorm": (lambda x, g, b: L.layernorm(x, g, b, q=q),
                      (inputs["x"], layer["ln1_g"], layer["ln1_b"])),
    }
    return {name: np.asarray(jax.jit(fn)(*args))
            for name, (fn, args) in cases.items()}


# -- the plain reference -------------------------------------------------

def reference_ops(cfg: dict, layer: dict, x: dict, precision: str) -> dict:
    """Each op that ``program_ops`` runs, by the reference, on one
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


def patchify(images, patch: int):
    """(b, H, W, 3) -> (b, patches, 3*patch*patch), channel-major."""
    b, h, w, c = images.shape
    x = images.reshape(b, h // patch, patch, w // patch, patch, c)
    x = x.transpose(0, 1, 3, 5, 2, 4)
    return x.reshape(b, (h // patch) * (w // patch), c * patch * patch)


def block(x, bp, heads: int, dp: R.Datapath, precision: str):
    """One pre-LayerNorm encoder block; ``bp`` holds one layer's leaves."""
    b, s, d = x.shape
    hd = d // heads
    a = bp["attn"]
    h = R.layernorm(x, bp["ln1_g"], bp["ln1_b"], dp)
    q, k, v = (R.linear(h, a[n], None, dp, precision).reshape(b, s, heads, hd)
               for n in ("wq", "wk", "wv"))
    o = R.attention(q, k, v, dp, precision).reshape(b, s, d)
    x = x + R.linear(o, a["wo"], None, dp, precision)
    f = bp["ffn"]
    h = R.layernorm(x, bp["ln2_g"], bp["ln2_b"], dp)
    h = R.gelu(R.linear(h, f["wi"], f["bi"], dp, precision), dp)
    return x + R.linear(h, f["wo"], f["bo"], dp, precision)


@functools.partial(jax.jit,
                   static_argnames=("heads", "patch", "dp", "precision"))
def logits(params, images, *, heads: int, patch: int, dp: R.Datapath,
           precision: str):
    """Class logits of a batch of images, layer after layer."""
    x = R.linear(patchify(images.astype(jnp.float32), patch),
                 params["patch_proj"], params["patch_bias"], dp, precision)
    cls = jnp.broadcast_to(params["cls_token"], (x.shape[0], 1, x.shape[-1]))
    x = jnp.concatenate([cls, x], axis=1) + params["pos_embed"][None]

    def step(x, bp):
        return block(x, bp, heads, dp, precision), None

    x, _ = jax.lax.scan(step, x, params["blocks"])
    x = R.layernorm(x, params["final_ln_g"], params["final_ln_b"], dp)
    return R.linear(x[:, 0], params["head"], params["head_b"], dp, precision)


def forward(cfg: dict, weights: dict, images, precision: str):
    """The reference's output for a block of images: class logits."""
    return logits(weights, images, heads=cfg["num_attention_heads"],
                  patch=cfg["patch_size"], dp=R.Datapath.of(cfg),
                  precision=precision)
