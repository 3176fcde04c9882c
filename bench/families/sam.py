"""The Segment Anything image encoder (Kirillov et al., arXiv:2304.02643;
``build_sam_vit_b`` -> ``ImageEncoderViT`` of
``facebookresearch/segment-anything``): patch embedding, a 2-D position
table, pre-LayerNorm blocks whose attention runs in windows in every
layer but the last of each period and over the whole grid in that one,
decomposed relative positions in every layer, and a convolutional neck.
``manifest.family`` finds it by the configuration's ``"family"``.

The reference side (``block``, ``embed``, ``forward``, ``reference_ops``)
follows the published equations, built from the MXInt primitives of
``bench.harness.reference`` and plain float32 ``jax.numpy``, and imports
nothing of the program:

* ``x = x + proj(attn(qkv(pad(norm1(x)))))`` then ``x + mlp(norm2(x))``;
  the grid is zero-padded AFTER ``norm1`` to a multiple of the window and
  the padded tokens go through the fused qkv linear unmasked;
* scores ``(q * scale) @ k^T + rel_h + rel_w`` with
  ``rel_h[q, ky] = sum_c q[c] R_h[qy - ky + K - 1, c]`` of the unscaled
  q (likewise over x), then softmax and ``@ v``;
* the neck: 1x1 conv, LayerNorm over channels, 3x3 conv with padding 1,
  LayerNorm over channels, the 3x3 conv as a convolution
  (``lax.conv_general_dilated``) of MXInt-quantized operands.

Departures, as in the configuration's ``assumed``: the MXInt datapath
(every linear and conv, LayerNorm, GELU and softmax as the config's
``"datapath"`` states them; the exact GELU replaced by its LUT, eps by the
LUT LayerNorm); random weights; N(0, 1) images without SAM's pixel
normalisation; the embedding channels last.  Products of the scores, the
relative terms and p @ V are float32 at the given precision, as every
contraction here.

The global layers' 4,096^2 scores are computed in blocks of
``Q_BLOCK`` query rows: the softmax is row-wise, so the blocks are exact.
Windows (196 rows) are computed whole.

Weight shapes depend only on the hashable keys of ``DIMS``:
``weights.make`` passes them to a jitted function as a static argument,
which a JSON list (``global_attn_indexes``) cannot be, so the layer
pattern enters as ``global_attn_period``, and ``model`` checks that the
two agree.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

from bench.harness import reference as R

# the number that compares what the window served with ``forward``
OUTPUT = "embedding_rel_rms"

# the configuration keys ``shapes`` reads (all hashable; see above)
DIMS = ("hidden_size", "intermediate_size", "num_hidden_layers",
        "num_attention_heads", "patch_size", "image_size", "window_size",
        "global_attn_period", "out_chans")

# query rows per block of the reference's global attention: at 128 rows
# and more (8 images x 12 heads x 4,096 keys), XLA's TPU compiler asks for
# more scoped VMEM than its limit in one softmax fusion (compiled for a
# described v5e; the chip raised the same at 256)
Q_BLOCK = 64


def grid(cfg: dict) -> int:
    return cfg["image_size"] // cfg["patch_size"]


def _block_shapes(cfg: dict, lead: tuple, side: int) -> dict:
    d, ff = cfg["hidden_size"], cfg["intermediate_size"]
    hd = d // cfg["num_attention_heads"]
    attn = {n: (lead + (d, d), "matrix") for n in ("wq", "wk", "wv", "wo")}
    attn.update({n: (lead + (d,), "bias") for n in ("bq", "bk", "bv", "bo")})
    attn.update({n: (lead + (2 * side - 1, hd), "shift")
                 for n in ("rel_pos_h", "rel_pos_w")})
    return {
        "ln1_g": (lead + (d,), "scale"), "ln1_b": (lead + (d,), "shift"),
        "attn": attn,
        "ln2_g": (lead + (d,), "scale"), "ln2_b": (lead + (d,), "shift"),
        "ffn": {"wi": (lead + (d, ff), "matrix"), "bi": (lead + (ff,), "bias"),
                "wo": (lead + (ff, d), "matrix"), "bo": (lead + (d,), "bias")},
    }


def shapes(cfg: dict) -> dict:
    """Leaf name -> (shape, kind) of the encoder with the config's sizes:
    window layers stacked (periods, period - 1, ...), global layers
    (periods, ...), the program's layout."""
    d, p, c = cfg["hidden_size"], cfg["patch_size"], cfg["out_chans"]
    per = cfg["global_attn_period"]
    periods = cfg["num_hidden_layers"] // per
    g = grid(cfg)
    return {
        "patch_proj": ((3 * p * p, d), "matrix"),
        "patch_bias": ((d,), "bias"),
        "pos_embed": ((1, g, g, d), "bias"),
        "blocks": {
            "window": _block_shapes(cfg, (periods, per - 1),
                                    cfg["window_size"]),
            "global": _block_shapes(cfg, (periods,), g),
        },
        "neck": {
            "conv1": ((d, c), "matrix"),
            "ln1_g": ((c,), "scale"), "ln1_b": ((c,), "shift"),
            "conv2": ((9 * c, c), "matrix"),
            "ln2_g": ((c,), "scale"), "ln2_b": ((c,), "shift"),
        },
    }


def layer_is_global(cfg: dict) -> list:
    """Per layer, whether it attends globally (``global_attn_indexes``)."""
    return [i in cfg["global_attn_indexes"]
            for i in range(cfg["num_hidden_layers"])]


# -- the program ---------------------------------------------------------

def model(cfg: dict):
    """The program's SAM encoder with the configuration's sizes and
    datapath."""
    from repro.models import build_model
    from repro.models.model_api import ModelConfig
    from bench.harness.program import quant_config
    per = cfg["global_attn_period"]
    if layer_is_global(cfg) != [i % per == per - 1
                                for i in range(cfg["num_hidden_layers"])]:
        raise ValueError("global_attn_indexes is not every "
                         f"global_attn_period ({per}) layers' last")
    heads = cfg["num_attention_heads"]
    return build_model(ModelConfig(
        name=cfg["name"], family="sam", n_layers=cfg["num_hidden_layers"],
        d_model=cfg["hidden_size"], n_heads=heads, n_kv_heads=heads,
        d_ff=cfg["intermediate_size"], vocab=0, ffn_kind="gelu",
        image_size=cfg["image_size"], patch_size=cfg["patch_size"],
        n_classes=0, attn_window_size=cfg["window_size"],
        global_attn_period=per, neck_dim=cfg["out_chans"],
        norm_eps=cfg["layer_norm_eps"], dtype=jnp.float32,
        quant=quant_config(cfg)))


def layer0(tree: dict) -> dict:
    """The first window layer, the first global layer and the neck of a
    weight tree: the benchmark's float tree or the engine's packed one."""
    b = tree["blocks"]
    return {"window": jax.tree_util.tree_map(lambda a: a[0, 0], b["window"]),
            "global": jax.tree_util.tree_map(lambda a: a[0], b["global"]),
            "neck": tree["neck"]}


def op_inputs(cfg: dict, batch: int, seed: int) -> dict:
    """Seeded op inputs at the cell's batch and the config's widths."""
    rng = np.random.default_rng([seed, 3])
    d, ff, h = (cfg["hidden_size"], cfg["intermediate_size"],
                cfg["num_attention_heads"])
    g = grid(cfg)

    def arr(*shape):
        return jnp.asarray(rng.standard_normal(shape, dtype=np.float32))

    return {"x": arr(batch, g * g, d), "h": arr(batch, g * g, ff),
            "q": arr(batch, g, g, h, d // h), "k": arr(batch, g, g, h, d // h),
            "v": arr(batch, g, g, h, d // h),
            "n": arr(batch, g, g, cfg["out_chans"])}


def program_ops(engine, inputs: dict) -> dict:
    """Each op case through the program's kernel-mode entry points, on the
    given inputs and the first window layer, first global layer and neck
    of the weights the engine serves (its packed planes), with the
    engine's model's datapath.  Returns name -> output as numpy."""
    from repro.models import layers as L

    m = engine.model
    q = m.cfg.quant
    layer = layer0(engine.params)
    w, gl, neck = layer["window"], layer["global"], layer["neck"]

    def ln_qkv(x, bp):
        return jnp.concatenate(m.qkv(bp["attn"], x, bp["ln1_g"],
                                     bp["ln1_b"]), -1)

    cases = {
        "ln_qkv": (ln_qkv, (inputs["x"], w)),
        "window_attention": (lambda a, *qkv: m.attend(a, *qkv, True),
                             (w["attn"], inputs["q"], inputs["k"],
                              inputs["v"])),
        "global_attention": (lambda a, *qkv: m.attend(a, *qkv, False),
                             (gl["attn"], inputs["q"], inputs["k"],
                              inputs["v"])),
        "linear": (lambda h, f: L.linear(h, f["wo"], f["bo"], q=q),
                   (inputs["h"], w["ffn"])),
        "gelu": (lambda h: L.act_fn(h, "gelu", q), (inputs["h"],)),
        "layernorm2d": (lambda n, p: L.layernorm(n, p["ln1_g"], p["ln1_b"],
                                                 q=q),
                        (inputs["n"], neck)),
    }
    return {name: np.asarray(jax.jit(fn)(*args))
            for name, (fn, args) in cases.items()}


# -- the plain reference -------------------------------------------------

def rel_rows(side: int, table):
    """(2 * side - 1, hd) -> (side, side, hd): [i, j] = table[i - j +
    side - 1], the published ``get_rel_pos`` at equal query and key
    sizes."""
    idx = np.arange(side)[:, None] - np.arange(side)[None, :] + side - 1
    return table[idx]


def attention(q, k, v, table_h, table_w, dp: R.Datapath, precision: str):
    """(n, side, side, heads, hd) q/k/v -> (n, side * side, heads, hd):
    MXInt softmax over scores with the decomposed relative positions,
    ``Q_BLOCK`` query rows at a time where they divide the rows."""
    n, side, _, h, hd = q.shape
    s_len = side * side
    scale = hd ** -0.5
    rh, rw = rel_rows(side, table_h), rel_rows(side, table_w)
    kf = k.reshape(n, s_len, h, hd)
    vf = v.reshape(n, s_len, h, hd)
    qf = q.reshape(n, s_len, h, hd)
    rows = Q_BLOCK if s_len % Q_BLOCK == 0 else s_len

    def rows_out(i):
        qb = jax.lax.dynamic_slice_in_dim(qf, i * rows, rows, 1)
        pos = i * rows + jnp.arange(rows)
        s = R.matmul("bshd,bShd->bhsS", qb * scale, kf, precision)
        rel_h = R.matmul("bshc,skc->bhsk", qb, rh[pos // side], precision)
        rel_w = R.matmul("bshc,skc->bhsk", qb, rw[pos % side], precision)
        s = s.reshape(n, h, rows, side, side) + rel_h[..., :, None]
        s = (s + rel_w[..., None, :]).reshape(n, h, rows, s_len)
        p = R.softmax(s, dp)
        return R.matmul("bhsS,bShd->bshd", p, vf, precision)

    out = jax.lax.map(rows_out, jnp.arange(s_len // rows))
    return jnp.moveaxis(out, 0, 1).reshape(n, s_len, h, hd)


def windows(x, window: int):
    """(b, g, g, c) -> zero-padded (b * m * m, window, window, c)."""
    b, g, _, c = x.shape
    pad = -g % window
    x = jnp.pad(x, ((0, 0), (0, pad), (0, pad), (0, 0)))
    m = (g + pad) // window
    x = x.reshape(b, m, window, m, window, c).transpose(0, 1, 3, 2, 4, 5)
    return x.reshape(b * m * m, window, window, c)


def unwindows(x, b: int, g: int):
    """Inverse of ``windows``, the padding cut off."""
    window, c = x.shape[1], x.shape[-1]
    m = -(-g // window)
    x = x.reshape(b, m, m, window, window, c).transpose(0, 1, 3, 2, 4, 5)
    return x.reshape(b, m * window, m * window, c)[:, :g, :g]


def attend(a, h, heads: int, dp: R.Datapath, precision: str):
    """Attention of (n, side, side, d) normed tokens: the fused qkv linear
    (the three weights side by side), scores with relative positions,
    the output projection."""
    n, side, _, d = h.shape
    hd = d // heads
    w = jnp.concatenate([a["wq"], a["wk"], a["wv"]], -1)
    b = jnp.concatenate([a["bq"], a["bk"], a["bv"]], -1)
    qkv = R.linear(h, w, b, dp, precision).reshape(n, side, side, 3, heads,
                                                   hd)
    o = attention(qkv[..., 0, :, :], qkv[..., 1, :, :], qkv[..., 2, :, :],
                  a["rel_pos_h"], a["rel_pos_w"], dp, precision)
    o = R.linear(o.reshape(n, side * side, d), a["wo"], a["bo"], dp,
                 precision)
    return o.reshape(n, side, side, d)


def block(x, bp, *, heads: int, window: int, dp: R.Datapath,
          precision: str):
    """One block on (b, g, g, d) tokens; ``window`` 0 attends globally."""
    b, g = x.shape[0], x.shape[1]
    h = R.layernorm(x, bp["ln1_g"], bp["ln1_b"], dp)
    if window:
        h = unwindows(attend(bp["attn"], windows(h, window), heads, dp,
                             precision), b, g)
    else:
        h = attend(bp["attn"], h, heads, dp, precision)
    x = x + h
    f = bp["ffn"]
    h = R.layernorm(x, bp["ln2_g"], bp["ln2_b"], dp)
    h = R.gelu(R.linear(h, f["wi"], f["bi"], dp, precision), dp)
    return x + R.linear(h, f["wo"], f["bo"], dp, precision)


def conv3x3(x, w, dp: R.Datapath, precision: str):
    """3x3 conv, padding 1, no bias, of MXInt operands.  The weight
    (9c, c') holds its taps in (ky, kx, c) rows, quantized in blocks
    along those rows as a linear's; activations in blocks along channels,
    which are the same blocks when the act block divides c."""
    c, k = x.shape[-1], w.shape[0]
    act = R.resolve_block(c, dp.act_block)
    assert act == R.resolve_block(k, dp.act_block), (c, act)
    xq = R.qdq(x, act, dp.act_mant_bits)
    wq = R.qdq(w.T, R.resolve_block(k, dp.weight_block),
               dp.weight_mant_bits).T.reshape(3, 3, c, -1)
    prec = (jax.lax.Precision.HIGHEST if precision == "highest"
            else jax.lax.Precision.DEFAULT)
    if precision == "bfloat16":
        xq, wq = xq.astype(jnp.bfloat16), wq.astype(jnp.bfloat16)
    return jax.lax.conv_general_dilated(
        xq, wq, (1, 1), ((1, 1), (1, 1)),
        dimension_numbers=("NHWC", "HWIO", "NHWC"), precision=prec,
        preferred_element_type=jnp.float32)


def neck(x, p, dp: R.Datapath, precision: str):
    x = R.linear(x, p["conv1"], None, dp, precision)
    x = R.layernorm(x, p["ln1_g"], p["ln1_b"], dp)
    x = conv3x3(x, p["conv2"], dp, precision)
    return R.layernorm(x, p["ln2_g"], p["ln2_b"], dp)


@functools.partial(jax.jit, static_argnames=("heads", "patch", "window",
                                             "dp", "precision"))
def embed(params, images, *, heads: int, patch: int, window: int,
          dp: R.Datapath, precision: str):
    """(b, H, W, 3) images -> (b, g, g, out_chans), layer after layer."""
    from bench.harness.manifest import family
    patchify = family("vit").patchify
    b, size = images.shape[0], images.shape[1]
    g = size // patch
    x = R.linear(patchify(images.astype(jnp.float32), patch),
                 params["patch_proj"], params["patch_bias"], dp, precision)
    x = x.reshape(b, g, g, -1) + params["pos_embed"]
    kw = dict(heads=heads, dp=dp, precision=precision)

    def period(x, bp):
        for i in range(bp["window"]["ln1_g"].shape[0]):
            x = block(x, jax.tree_util.tree_map(lambda a, i=i: a[i],
                                                bp["window"]),
                      window=window, **kw)
        return block(x, bp["global"], window=0, **kw), None

    x, _ = jax.lax.scan(period, x, params["blocks"])
    return neck(x, params["neck"], dp, precision)


def forward(cfg: dict, weights: dict, images, precision: str):
    """The reference's output for a block of images: the embedding."""
    return embed(weights, images, heads=cfg["num_attention_heads"],
                 patch=cfg["patch_size"], window=cfg["window_size"],
                 dp=R.Datapath.of(cfg), precision=precision)


@functools.partial(jax.jit, static_argnames=("heads", "window", "dp",
                                             "precision"))
def ops_reference(layer, x, *, heads: int, window: int, dp: R.Datapath,
                  precision: str):
    """The reference's op cases (see ``reference_ops``)."""
    w, gl, nk = layer["window"], layer["global"], layer["neck"]
    a = w["attn"]
    b, g, hd = x["q"].shape[0], x["q"].shape[1], x["q"].shape[-1]

    def padded(t, bias):
        tw = windows(t.reshape(b, g, g, heads * hd), window)
        real = windows(jnp.ones((b, g, g, 1), jnp.float32), window) > 0
        tw = jnp.where(real, tw, bias)
        n, side = tw.shape[0], tw.shape[1]
        return tw.reshape(n, side, side, heads, hd)

    qw, kw, vw = (padded(x[n], a[bn]) for n, bn in
                  (("q", "bq"), ("k", "bk"), ("v", "bv")))
    ow = attention(qw, kw, vw, a["rel_pos_h"], a["rel_pos_w"], dp, precision)
    n, side = qw.shape[0], qw.shape[1]
    ow = unwindows(ow.reshape(n, side, side, heads * hd), b, g)
    ag = gl["attn"]
    og = attention(x["q"], x["k"], x["v"], ag["rel_pos_h"], ag["rel_pos_w"],
                   dp, precision)
    qkv_w = jnp.concatenate([a["wq"], a["wk"], a["wv"]], -1)
    qkv_b = jnp.concatenate([a["bq"], a["bk"], a["bv"]], -1)
    return {
        "ln_qkv": R.linear(R.layernorm(x["x"], w["ln1_g"], w["ln1_b"], dp),
                           qkv_w, qkv_b, dp, precision),
        "window_attention": ow.reshape(b, g * g, heads * hd),
        "global_attention": og.reshape(b, g * g, heads * hd),
        "linear": R.linear(x["h"], w["ffn"]["wo"], w["ffn"]["bo"], dp,
                           precision),
        "gelu": R.gelu(x["h"], dp),
        "layernorm2d": R.layernorm(x["n"], nk["ln1_g"], nk["ln1_b"], dp),
    }


def reference_ops(cfg: dict, layer: dict, x: dict, precision: str) -> dict:
    """Each op that ``program_ops`` runs, by the reference, on the float
    tree's layers (``layer0``) and the inputs ``x``.  Window attention
    pads q/k/v with the projections' biases, what zero-padded tokens
    give."""
    out = ops_reference(layer, x, heads=cfg["num_attention_heads"],
                        window=cfg["window_size"], dp=R.Datapath.of(cfg),
                        precision=precision)
    return {k: np.asarray(v) for k, v in out.items()}
