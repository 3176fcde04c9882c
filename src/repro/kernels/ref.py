"""Pure-jnp oracles for every Pallas kernel in this package.

Each function mirrors the corresponding kernel's *mathematics* through the
independent `repro.core` implementation path (quantize.py / nonlinear.py),
so a kernel bug and an oracle bug would have to coincide to pass the tests.
LUT contents are shared via `repro.core.luts` by construction — the tables
ARE the spec.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.core import luts
from repro.core.mx_types import MXFormat, NonlinearConfig
from repro.core.nonlinear import (_rsqrt_datapath, exp_datapath, mxint_gelu,
                                  mxint_silu)
from repro.core.quantize import (MXTensor, dequantize, quantize,
                                 quantize_dequantize,
                                 requantize_to_max_exponent)

from repro.core.mx_types import NEG_INF as _NEG_INF

_LOG2E = 1.4426950408889634


# ---------------------------------------------------------------------------
# mxint_matmul oracle
# ---------------------------------------------------------------------------
def mxint_matmul_ref(x: jnp.ndarray, w_mant: jnp.ndarray, w_exp: jnp.ndarray,
                     *, w_block: int, act_block: int = 16,
                     act_mant_bits: int = 8,
                     quantize_act: bool = False) -> jnp.ndarray:
    """Dequantize-then-dot reference."""
    k, n = w_mant.shape
    w = MXTensor(w_mant, w_exp, 0, 8, w_block)
    wf = dequantize(w)
    xf = x.astype(jnp.float32)
    if quantize_act:
        fmt = MXFormat(mant_bits=act_mant_bits, block_size=act_block)
        xf = quantize_dequantize(xf, fmt, axis=-1)
    return jnp.dot(xf, wf, preferred_element_type=jnp.float32)


# ---------------------------------------------------------------------------
# mxint_layernorm oracle
# ---------------------------------------------------------------------------
def mxint_layernorm_ref(x: jnp.ndarray, gamma: jnp.ndarray, beta: jnp.ndarray,
                        *, act_block: int = 16, mant_bits: int = 8,
                        lut_bits: int = 5, rms_only: bool = False):
    """Quantize -> requantize -> integer LN -> LUT rsqrt, NO output requant
    (the kernel hands the scaled f32 tile to the next op)."""
    fmt = MXFormat(mant_bits=mant_bits, block_size=act_block)
    t = quantize(x, fmt, axis=-1)
    m, _lam = requantize_to_max_exponent(t, axis=-1)
    mf = m.astype(jnp.float32)
    if rms_only:
        centered = mf
    else:
        centered = mf - jnp.mean(mf, axis=-1, keepdims=True)
    var = jnp.mean(centered * centered, axis=-1, keepdims=True)
    inv = _rsqrt_datapath(var, lut_bits)
    y = centered * inv * gamma[None, :]
    if not rms_only:
        y = y + beta[None, :]
    return y.astype(x.dtype)


# ---------------------------------------------------------------------------
# mxint_softmax oracle
# ---------------------------------------------------------------------------
def mxint_softmax_ref(x: jnp.ndarray, *, act_block: int = 16,
                      mant_bits: int = 8, r_bits: int = 2) -> jnp.ndarray:
    fmt = MXFormat(mant_bits=mant_bits, block_size=min(act_block, x.shape[-1]))
    t = quantize(x, fmt, axis=-1)
    m, lam = requantize_to_max_exponent(t, axis=-1)
    mf = m.astype(jnp.float32)
    tt = mf - jnp.max(mf, axis=-1, keepdims=True)
    z = tt * jnp.exp2(lam.astype(jnp.float32)) * _LOG2E
    p = exp_datapath(z, r_bits)
    s = jnp.sum(p, axis=-1, keepdims=True)
    s_m, s_e = jnp.frexp(s)
    return ((p / s_m) * jnp.exp2(-s_e.astype(jnp.float32))).astype(x.dtype)


# ---------------------------------------------------------------------------
# mxint_gelu oracle
# ---------------------------------------------------------------------------
def mxint_gelu_ref(x: jnp.ndarray, *, act_block: int = 16, mant_bits: int = 8,
                   lut_bits: int = 5, domain: float = 3.0,
                   fn: str = "gelu") -> jnp.ndarray:
    fmt = MXFormat(mant_bits=mant_bits, block_size=min(act_block, x.shape[-1]))
    cfg = NonlinearConfig(gelu_lut_bits=lut_bits, gelu_domain=domain)
    t = quantize(x, fmt, axis=-1)
    out = mxint_gelu(t, cfg) if fn == "gelu" else mxint_silu(t, cfg)
    return dequantize(out).astype(x.dtype)


# ---------------------------------------------------------------------------
# flash_attention oracle
# ---------------------------------------------------------------------------
def attention_ref(q: jnp.ndarray, k: jnp.ndarray, v: jnp.ndarray, *,
                  causal: bool = True, window: int = 0,
                  exp_mode: str = "float", r_bits: int = 2,
                  scale: float | None = None) -> jnp.ndarray:
    """Unblocked attention; exp through the same datapath when requested."""
    bh, sq, d = q.shape
    sk = k.shape[1]
    if scale is None:
        scale = 1.0 / (d ** 0.5)
    s = jnp.einsum("bqd,bkd->bqk", q.astype(jnp.float32),
                   k.astype(jnp.float32)) * scale
    q_pos = jnp.arange(sq)[:, None]
    k_pos = jnp.arange(sk)[None, :]
    mask = jnp.ones((sq, sk), dtype=bool)
    if causal:
        mask &= q_pos >= k_pos
    if window > 0:
        mask &= (q_pos - k_pos) < window
    s = jnp.where(mask[None], s, _NEG_INF)
    m = jnp.max(s, axis=-1, keepdims=True)
    if exp_mode == "mxint":
        p = exp_datapath((s - m) * _LOG2E, r_bits)
    else:
        p = jnp.exp(s - m)
    p = jnp.where(mask[None], p, 0.0)
    sm = jnp.sum(p, axis=-1, keepdims=True)
    s_m, s_e = jnp.frexp(jnp.maximum(sm, 1e-30))
    p = (p / s_m) * jnp.exp2(-s_e.astype(jnp.float32))
    return jnp.einsum("bqk,bkd->bqd", p, v.astype(jnp.float32)).astype(q.dtype)


# ---------------------------------------------------------------------------
# whole-row 'paper' oracle for the quantize_scores flash datapath
# ---------------------------------------------------------------------------
def mxint_flash_attention_ref(q: jnp.ndarray, k: jnp.ndarray, v: jnp.ndarray,
                              *, causal: bool = True, window: int = 0,
                              act_block: int = 16, mant_bits: int = 8,
                              r_bits: int = 2, scale: float | None = None,
                              key_mask: jnp.ndarray | None = None,
                              bias: tuple | None = None) -> jnp.ndarray:
    """Whole-row Eq. 14-20 attention oracle (DESIGN.md §11).

    The full paper softmax on MASKED score rows: Eq. 2-3 score
    quantization (the NEG_INF fill goes through the quantizer, sim
    parity), Eq. 14-19 exp LUT, Eq. 20 divide, probability quantization
    onto the act grid, zero the masked lanes, then p @ V.  This is what
    ``flash_attention(exp_mode='mxint', quantize_scores=True)`` computes
    blocked; when one k block covers the row the kernel matches this
    oracle exactly.  ``key_mask``: optional (Sk,) or per-row (BH, Sk)
    validity (the decode variant's ring mask).  ``bias``: optional
    (bias_h (BH, sq, kh), bias_w (BH, sq, kw)), the decomposed relative-
    position term added to the scaled scores (``flash_attention``).
    """
    bh, sq, d = q.shape
    sk = k.shape[1]
    if scale is None:
        scale = 1.0 / (d ** 0.5)
    s = jnp.einsum("bqd,bkd->bqk", q.astype(jnp.float32),
                   k.astype(jnp.float32)) * scale
    if bias is not None:
        bias_h, bias_w = bias
        kh, kw = bias_h.shape[-1], bias_w.shape[-1]
        s = s.reshape(bh, sq, kh, kw) + bias_h[..., :, None]
        s = (s + bias_w[..., None, :]).reshape(bh, sq, sk)
    q_pos = jnp.arange(sq)[:, None]
    k_pos = jnp.arange(sk)[None, :]
    mask = jnp.ones((sq, sk), dtype=bool)
    if causal:
        mask &= q_pos >= k_pos
    if window > 0:
        mask &= (q_pos - k_pos) < window
    mask = mask[None]                                      # (1, sq, sk)
    if key_mask is not None:
        km = (key_mask > 0)
        mask = mask & (km[:, None, :] if km.ndim == 2 else km[None, None, :])
    s = jnp.where(mask, s, _NEG_INF)
    fmt = MXFormat(mant_bits, act_block)
    t = quantize(s, fmt, axis=-1)
    m, lam = requantize_to_max_exponent(t, axis=-1)
    mf = m.astype(jnp.float32)
    tt = mf - jnp.max(mf, axis=-1, keepdims=True)
    z = tt * jnp.exp2(lam.astype(jnp.float32)) * _LOG2E
    p = exp_datapath(z, r_bits)
    sm = jnp.sum(p, axis=-1, keepdims=True)
    s_m, s_e = jnp.frexp(jnp.maximum(sm, 1e-30))
    y = (p / s_m) * jnp.exp2(-s_e.astype(jnp.float32))
    y = quantize_dequantize(y, fmt, axis=-1)
    y = jnp.where(mask, y, 0.0)
    return jnp.einsum("bqk,bkd->bqd", y, v.astype(jnp.float32)).astype(q.dtype)


# ---------------------------------------------------------------------------
# decode-variant oracle
# ---------------------------------------------------------------------------
def decode_attention_ref(q: jnp.ndarray, k: jnp.ndarray, v: jnp.ndarray,
                         valid: jnp.ndarray, *, exp_mode: str = "float",
                         r_bits: int = 2,
                         scale: float | None = None) -> jnp.ndarray:
    """Unblocked single-position decode oracle.

    q: (BH, G, D); k, v: (BH, W, D) cache rings; valid: (W,) shared or
    (BH, W) per-row slot validity.  Masked softmax over the ring with
    the requested exp datapath — the jnp mirror of
    ``flash_attention_decode``.
    """
    bh, g, d = q.shape
    if scale is None:
        scale = 1.0 / (d ** 0.5)
    s = jnp.einsum("bgd,bwd->bgw", q.astype(jnp.float32),
                   k.astype(jnp.float32)) * scale
    vm = valid > 0
    mask = vm[:, None, :] if vm.ndim == 2 else vm[None, None, :]
    s = jnp.where(mask, s, _NEG_INF)
    m = jnp.max(s, axis=-1, keepdims=True)
    if exp_mode == "mxint":
        p = exp_datapath((s - m) * _LOG2E, r_bits)
    else:
        p = jnp.exp(s - m)
    p = jnp.where(mask, p, 0.0)
    sm = jnp.sum(p, axis=-1, keepdims=True)
    s_m, s_e = jnp.frexp(jnp.maximum(sm, 1e-30))
    p = (p / s_m) * jnp.exp2(-s_e.astype(jnp.float32))
    return jnp.einsum("bgw,bwd->bgd", p, v.astype(jnp.float32)).astype(q.dtype)
