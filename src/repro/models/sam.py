"""Segment Anything image encoder (Kirillov et al., arXiv:2304.02643;
``ImageEncoderViT`` of ``facebookresearch/segment-anything``).

A plain ViT over a 2-D grid of patches, without a class token, whose
attention alternates between windows and the whole grid, followed by a
convolutional neck:

* patches: the ViT's exact patchify + linear (the stride-P conv, channel-
  major features), plus a learned (1, g, g, d) position table;
* blocks: pre-LayerNorm, ``x + attn(norm1(x))`` then ``x + mlp(norm2(x))``
  (GELU MLP with biases), the ViT's FFN;
* attention: q/k/v and output projections with biases; scores
  ``scale * q.k + rel_h + rel_w`` with the decomposed relative-position
  terms ``rel_h[q, ky] = q . R_h[qy - ky + K - 1]`` (likewise over x) of
  the UNSCALED q, from per-layer tables of 2K - 1 rows;
* windows: in every layer but the last of each ``global_attn_period``
  layers (SAM ViT-B: 0, 1, 3, 4, 6, 7, 9, 10), the grid is zero-padded
  after ``norm1`` to a multiple of the window and attention runs inside
  each window.  Padded tokens are not masked: their q/k/v are the
  projections' biases, which is what the zero rows give, so the program
  projects only the real tokens and pads q/k/v with the bias rows;
* neck: 1x1 conv (a linear d -> c), LayerNorm over channels, 3x3 conv
  with zero padding (a linear over each pixel's 3x3xc neighbourhood, in
  (ky, kx, c) order), LayerNorm over channels, no conv biases.

The output is the (b, g, g, c) embedding, channels last (the published
encoder returns it channels first).  Layers scan over periods of
(window, ..., window, global): ``blocks['window']`` stacks leaves
(periods, period - 1, ...), ``blocks['global']`` (periods, ...), so one
``lax.scan`` serves the depth although the two kinds' relative-position
tables differ in shape.

Every op routes through the datapath seam, as in ``models/vit.py``: in
``mode='kernel'`` the fused LN -> q/k/v, the linears, GELU and the
LayerNorms run their Pallas kernels, window attention (few keys) the
whole-row softmax kernel and global attention the flash kernel, each
with the relative-position bias (``Datapath.attention(bias=...)``).
"""
from __future__ import annotations

from typing import Any, Dict

import jax
import jax.numpy as jnp

from repro.models import attention as A
from repro.models import layers as L
from repro.models.model_api import (ModelConfig, dense_init, ones_init,
                                    zeros_init)
from repro.models.transformer import _stacked_init
from repro.models.vit import ViT

_HIGHEST = jax.lax.Precision.HIGHEST


def rel_pos_rows(side: int, table: jnp.ndarray) -> jnp.ndarray:
    """(2 * side - 1, hd) table -> (side, side, hd): row [i, j] is the
    table's entry for offset i - j (query i, key j)."""
    idx = jnp.arange(side)[:, None] - jnp.arange(side)[None, :] + side - 1
    return table[idx]


def rel_pos_bias(q: jnp.ndarray, table_h: jnp.ndarray,
                 table_w: jnp.ndarray) -> tuple:
    """Decomposed relative-position terms of unscaled queries.

    q: (b, side, side, heads, hd).  Returns (rel_h, rel_w), each
    (b, heads, 1, side * side, side), the layout of
    ``Datapath.attention``'s ``bias``; float32 products at full
    precision, as the score contractions run."""
    b, side, _, heads, _ = q.shape
    qf = q.astype(jnp.float32)
    rh = jnp.einsum("byxhc,ykc->bhyxk", qf, rel_pos_rows(side, table_h),
                    precision=_HIGHEST)
    rw = jnp.einsum("byxhc,xkc->bhyxk", qf, rel_pos_rows(side, table_w),
                    precision=_HIGHEST)
    shape = (b, heads, 1, side * side, side)
    return rh.reshape(shape), rw.reshape(shape)


def pad_windows(t: jnp.ndarray, fill: jnp.ndarray, padded: int,
                window: int) -> jnp.ndarray:
    """(b, g, g, heads, hd) -> (b * n * n, window^2, heads, hd) windows of
    the grid padded to ``padded`` = n * window, padded cells = ``fill``
    (heads, hd)."""
    b, g, _, h, hd = t.shape
    t = jnp.pad(t, ((0, 0), (0, padded - g), (0, padded - g), (0, 0),
                    (0, 0)))
    real = jnp.arange(padded) < g
    t = jnp.where((real[:, None] & real[None, :])[None, :, :, None, None],
                  t, fill.astype(t.dtype))
    n = padded // window
    t = t.reshape(b, n, window, n, window, h, hd).transpose(0, 1, 3, 2, 4,
                                                            5, 6)
    return t.reshape(b * n * n, window * window, h, hd)


def unpad_windows(t: jnp.ndarray, b: int, g: int, padded: int,
                  window: int) -> jnp.ndarray:
    """Inverse of ``pad_windows``: (b * n * n, window^2, ...) windows ->
    (b, g, g, ...), the padded cells cut off."""
    n = padded // window
    rest = t.shape[2:]
    t = t.reshape(b, n, n, window, window, *rest)
    t = jnp.moveaxis(t, 3, 2).reshape(b, padded, padded, *rest)
    return t[:, :g, :g]


class SamViT:
    def __init__(self, cfg: ModelConfig):
        assert cfg.image_size % cfg.patch_size == 0
        assert cfg.global_attn_period > 0
        assert cfg.n_layers % cfg.global_attn_period == 0
        self.cfg = cfg
        self.grid = cfg.image_size // cfg.patch_size
        self.window = cfg.attn_window_size
        self.padded = -(-self.grid // self.window) * self.window
        self.n_periods = cfg.n_layers // cfg.global_attn_period
        # the per-image output: the neck's embedding, channels last
        self.out_shape = (self.grid, self.grid, cfg.neck_dim)

    patchify = ViT.patchify

    # -- params -------------------------------------------------------------
    def init(self, rng) -> Dict[str, Any]:
        cfg = self.cfg
        d, c, dtype = cfg.d_model, cfg.neck_dim, cfg.dtype
        ks = jax.random.split(rng, 6)
        per = cfg.global_attn_period - 1
        return {
            "patch_proj": dense_init(ks[0], (3 * cfg.patch_size ** 2, d),
                                     ("patch", "embed"), dtype=dtype),
            "patch_bias": zeros_init((d,), ("embed",), dtype=dtype),
            "pos_embed": dense_init(ks[1], (1, self.grid, self.grid, d),
                                    (None, None, None, "embed"), scale=0.02,
                                    dtype=dtype),
            "blocks": {
                "window": _stacked_init(
                    lambda k: _stacked_init(
                        lambda kk: self._init_block(kk, self.window), k,
                        per), ks[2], self.n_periods),
                "global": _stacked_init(
                    lambda k: self._init_block(k, self.grid), ks[3],
                    self.n_periods),
            },
            "neck": {
                "conv1": dense_init(ks[4], (d, c), ("embed", "neck"),
                                    dtype=dtype),
                "ln1_g": ones_init((c,), ("neck",), dtype=dtype),
                "ln1_b": zeros_init((c,), ("neck",), dtype=dtype),
                "conv2": dense_init(ks[5], (9 * c, c), ("neck_taps", "neck"),
                                    dtype=dtype),
                "ln2_g": ones_init((c,), ("neck",), dtype=dtype),
                "ln2_b": zeros_init((c,), ("neck",), dtype=dtype),
            },
        }

    def _init_block(self, key, side: int):
        cfg = self.cfg
        d, dtype = cfg.d_model, cfg.dtype
        ks = jax.random.split(key, 3)
        attn = A.init_attn_params(ks[0], cfg, dtype, bias=True)
        for name in ("rel_pos_h", "rel_pos_w"):
            attn[name] = zeros_init((2 * side - 1, cfg.hd), (None, None),
                                    dtype=dtype)
        return {
            "ln1_g": ones_init((d,), ("embed",), dtype=dtype),
            "ln1_b": zeros_init((d,), ("embed",), dtype=dtype),
            "attn": attn,
            "ln2_g": ones_init((d,), ("embed",), dtype=dtype),
            "ln2_b": zeros_init((d,), ("embed",), dtype=dtype),
            "ffn": {
                "wi": dense_init(ks[1], (d, cfg.d_ff), ("embed", "mlp"),
                                 dtype=dtype),
                "bi": zeros_init((cfg.d_ff,), ("mlp",), dtype=dtype),
                "wo": dense_init(ks[2], (cfg.d_ff, d), ("mlp", "embed"),
                                 dtype=dtype),
                "bo": zeros_init((d,), ("embed",), dtype=dtype),
            },
        }

    # -- forward --------------------------------------------------------------
    def qkv(self, p, x, ln_g, ln_b):
        """LayerNorm -> q, k, v of (b, n, d) tokens, each (b, n, d): the
        fused LN -> linear composite where the backend has it, else one
        norm for the three projections."""
        cfg = self.cfg
        quant = cfg.quant
        ws = [p["wq"], p["wk"], p["wv"]]
        bs = [p["bq"], p["bk"], p["bv"]]
        if all(quant.datapath.fuses_norm_linear(quant, x, w) for w in ws):
            return [L.layernorm_linear(x, ln_g, ln_b, w, b, q=quant,
                                       eps=cfg.norm_eps)
                    for w, b in zip(ws, bs)]
        h = L.layernorm(x, ln_g, ln_b, q=quant, eps=cfg.norm_eps)
        return [L.linear(h, w, b, q=quant) for w, b in zip(ws, bs)]

    def attend(self, p, q, k, v, windowed: bool):
        """Attention of (b, g, g, heads, hd) q/k/v with the relative-
        position terms, in windows or over the whole grid; returns
        (b, g * g, d) before the output projection."""
        cfg = self.cfg
        b, g, _, h, hd = q.shape
        if windowed:
            def win(t, bias):
                return pad_windows(t, bias.value.reshape(h, hd),
                                   self.padded, self.window)
            q, k, v = win(q, p["bq"]), win(k, p["bk"]), win(v, p["bv"])
            side = self.window
        else:
            q, k, v = (t.reshape(b, g * g, h, hd) for t in (q, k, v))
            side = g
        n = q.shape[0]
        bias = rel_pos_bias(q.reshape(n, side, side, h, hd),
                            p["rel_pos_h"].value, p["rel_pos_w"].value)
        o = cfg.quant.datapath.attention(
            q.reshape(n, side * side, h, 1, hd), k, v, q=cfg.quant,
            positions=jnp.arange(side * side)[None, :], causal=False,
            window=0, scale=hd ** -0.5, chunk=side * side, bias=bias)
        o = o.reshape(n, side * side, h * hd)
        if windowed:
            o = unpad_windows(o, b, g, self.padded, self.window)
        return o.reshape(b, g * g, h * hd)

    def _attention(self, bp, x, windowed: bool):
        cfg = self.cfg
        a = bp["attn"]
        b, n, _ = x.shape
        q, k, v = (t.reshape(b, self.grid, self.grid, cfg.n_heads, cfg.hd)
                   for t in self.qkv(a, x, bp["ln1_g"], bp["ln1_b"]))
        o = self.attend(a, q, k, v, windowed)
        return L.linear(o, a["wo"], a["bo"], q=cfg.quant)

    def _block(self, x, bp, windowed: bool):
        cfg = self.cfg
        x = x + self._attention(bp, x, windowed)
        return x + L.ffn(x, bp["ffn"], "gelu", cfg.quant,
                         prenorm=("ln", bp["ln2_g"], bp["ln2_b"]),
                         eps=cfg.norm_eps)

    def neck(self, p, x):
        """(b, g, g, d) -> (b, g, g, c): 1x1 conv, LayerNorm, 3x3 conv,
        LayerNorm, the convs as linears."""
        cfg = self.cfg
        quant = cfg.quant
        b, g = x.shape[0], x.shape[1]
        x = L.linear(x, p["conv1"], q=quant)
        x = L.layernorm(x, p["ln1_g"], p["ln1_b"], q=quant, eps=cfg.norm_eps)
        xp = jnp.pad(x, ((0, 0), (1, 1), (1, 1), (0, 0)))
        taps = jnp.concatenate([xp[:, dy:dy + g, dx:dx + g]
                                for dy in range(3) for dx in range(3)], -1)
        x = L.linear(taps, p["conv2"], q=quant)
        return L.layernorm(x, p["ln2_g"], p["ln2_b"], q=quant,
                           eps=cfg.norm_eps)

    def logits(self, params, images):
        """(b, H, W, 3) images -> (b, g, g, neck_dim) embeddings, under
        the name the serving engines call (not class logits)."""
        cfg = self.cfg
        g, d = self.grid, cfg.d_model
        x = self.patchify(images.astype(cfg.dtype))
        x = L.linear(x, params["patch_proj"], params["patch_bias"],
                     q=cfg.quant, scope="patch")
        b = x.shape[0]
        x = (x.reshape(b, g, g, d)
             + params["pos_embed"].value.astype(x.dtype)).reshape(b, g * g, d)
        per = cfg.global_attn_period - 1

        def period(x, bp):
            for i in range(per):
                x = self._block(x, jax.tree_util.tree_map(
                    lambda a, i=i: a[i], bp["window"]), True)
            return self._block(x, bp["global"], False), None

        x, _ = jax.lax.scan(period, x, params["blocks"])
        return self.neck(params["neck"], x.reshape(b, g, g, d))
