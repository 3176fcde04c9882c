"""Plain reference of the MXInt datapath in jax.numpy: the primitives
every family's reference forward (``bench/families/<family>.py``) is
built from.

This is what ``correct`` compares the program with.  It imports nothing
of the program and takes nothing the program made: the weights come from
``bench.harness.weights`` (the benchmark's own seeded generator) and are
quantized here.  The arithmetic follows the datapath the configuration
states in its ``"datapath"`` group (``Datapath``; paper "Refining
Datapath for Microscaling ViTs", Eq. 2-3, 5-9, 12, 14-20), in float32.
For the paper's W6A8 configurations:

* linear: activations quantized to MXInt8 in blocks of 16 along K,
  weights to MXInt6 in blocks of 256 along K (each block clamped to the
  largest divisor of K), one float32 contraction, then the bias;
* LayerNorm: MXInt8 input aligned to the row's largest block exponent
  by an arithmetic right shift, integer mean and variance, 1/sqrt from a
  32-entry table with the even/odd exponent split, MXInt8 output;
* GELU: MXInt8 input, ReLU tails outside [-3, 3), a 64-entry table
  inside, the output kept on the input's block exponents;
* softmax: MXInt8 scores aligned to the row's largest exponent, the
  exponential as 2^n times a 4-entry table of 2^r, division by the
  normalised sum, MXInt8 output;
* attention: scores and the probability-weighted values in float32.

``precision`` is ``"highest"`` for the reference and ``"bfloat16"`` for
the control: every matrix product then rounds its operands to bfloat16
and accumulates in float32, as one MXU pass does.
"""
from __future__ import annotations

import dataclasses
import functools
import math

import jax
import jax.numpy as jnp
import numpy as np

LOG2E = 1.4426950408889634
PRECISIONS = ("highest", "bfloat16")


@dataclasses.dataclass(frozen=True)
class Datapath:
    """The MXInt datapath a configuration states (its ``"datapath"``
    group): weight and activation formats and the LUT widths."""
    weight_mant_bits: int
    weight_block: int
    act_mant_bits: int
    act_block: int
    layernorm_lut_bits: int
    gelu_domain: float
    gelu_lut_bits: int
    softmax_r_bits: int

    @classmethod
    def of(cls, cfg: dict) -> "Datapath":
        return cls(**cfg["datapath"])


def resolve_block(dim: int, block: int) -> int:
    """The block size used along a dim: ``block`` if it divides ``dim``,
    ``dim`` if shorter, else the largest divisor of ``dim`` below it."""
    if dim >= block and dim % block == 0:
        return block
    if dim < block:
        return dim
    return next(b for b in range(block, 0, -1) if dim % b == 0)


def _quantize(x, block: int, bits: int):
    """MXInt along the last axis.  Returns (mantissas as integer-valued
    f32, shape (..., nb, block); exponents int32, shape (..., nb))."""
    xb = x.astype(jnp.float32).reshape(*x.shape[:-1], x.shape[-1] // block,
                                       block)
    amax = jnp.max(jnp.abs(xb), axis=-1)
    _, k = jnp.frexp(jnp.maximum(amax, jnp.finfo(jnp.float32).tiny))
    e = jnp.clip(jnp.where(amax > 0, k - 1 - (bits - 2), 0), -127, 127)
    lim = 2 ** (bits - 1) - 1
    m = jnp.clip(jnp.round(xb * jnp.exp2(-e.astype(jnp.float32))[..., None]),
                 -lim, lim)
    return m, e.astype(jnp.int32)


def qdq(x, block: int, bits: int):
    """Quantize to MXInt along the last axis and back to float32."""
    m, e = _quantize(x, block, bits)
    return (m * jnp.exp2(e.astype(jnp.float32))[..., None]).reshape(x.shape)


def _aligned(x, block: int, bits: int):
    """MXInt row aligned to its largest block exponent (Eq. 3).  Returns
    (shifted integer mantissas as f32, shape of x; row exponent (..., 1))."""
    m, e = _quantize(x, block, bits)
    e_max = jnp.max(e, axis=-1, keepdims=True)
    shift = jnp.minimum(e_max - e, 31)[..., None]
    mi = jnp.right_shift(m.astype(jnp.int32), shift)
    return mi.reshape(x.shape).astype(jnp.float32), e_max


def matmul(eq: str, a, b, precision: str):
    if precision == "highest":
        return jnp.einsum(eq, a, b, precision=jax.lax.Precision.HIGHEST)
    if precision == "bfloat16":
        return jnp.einsum(eq, a.astype(jnp.bfloat16), b.astype(jnp.bfloat16),
                          preferred_element_type=jnp.float32)
    raise ValueError(f"precision {precision!r} not in {PRECISIONS}")


def linear(x, w, b, dp: Datapath, precision: str):
    """MXInt activations x MXInt weights (blocks along K) + bias."""
    k = w.shape[0]
    wq = qdq(w.T, resolve_block(k, dp.weight_block), dp.weight_mant_bits).T
    xq = qdq(x, resolve_block(k, dp.act_block), dp.act_mant_bits)
    y = matmul("...k,kn->...n", xq, wq, precision)
    return y if b is None else y + b


@functools.lru_cache(maxsize=None)
def _tables(dp: Datapath):
    """(1/sqrt table of LayerNorm, GELU table, 2^r table of softmax)."""
    n = 2 ** dp.layernorm_lut_bits
    centers = 0.5 + 1.5 * np.arange(n) / n + 0.75 / n
    rsqrt = (1.0 / np.sqrt(centers)).astype(np.float32)
    gelu_bits = (dp.gelu_lut_bits
                 + max(math.ceil(math.log2(dp.gelu_domain)), 0) - 1)
    ng = 2 ** gelu_bits
    c = -dp.gelu_domain + (2.0 * dp.gelu_domain / ng) * (np.arange(ng) + 0.5)
    gelu = np.array([v * 0.5 * (1.0 + math.erf(v / math.sqrt(2.0)))
                     for v in c], np.float32)
    npow = 2 ** dp.softmax_r_bits
    pow2 = np.exp2(np.arange(npow) / npow).astype(np.float32)
    return rsqrt, gelu, pow2


def layernorm(x, g, b, dp: Datapath):
    """MXInt LayerNorm over the last axis (Fig. 3 datapath)."""
    rsqrt, _, _ = _tables(dp)
    block = resolve_block(x.shape[-1], dp.act_block)
    mf, _ = _aligned(x, block, dp.act_mant_bits)
    centered = mf - jnp.mean(mf, axis=-1, keepdims=True)
    var = jnp.maximum(jnp.mean(centered * centered, axis=-1, keepdims=True),
                      2.0 ** -24)
    v_m, v_e = jnp.frexp(var)
    v_m, v_e = v_m * 2.0, v_e - 1
    odd = (v_e % 2) != 0
    u = jnp.where(odd, v_m * 0.5, v_m)
    e_half = jnp.where(odd, (v_e + 1) // 2, v_e // 2)
    n = rsqrt.shape[0]
    idx = jnp.clip(jnp.floor((u - 0.5) * (n / 1.5)).astype(jnp.int32), 0,
                   n - 1)
    inv = jnp.take(jnp.asarray(rsqrt), idx) * jnp.exp2(
        -e_half.astype(jnp.float32))
    return qdq(centered * inv * g + b, block, dp.act_mant_bits)


def gelu(x, dp: Datapath):
    """MXInt GELU (Eq. 12): table inside [-a, a), ReLU tails outside; the
    output keeps the input's block exponents."""
    _, table, _ = _tables(dp)
    bits = dp.act_mant_bits
    block = resolve_block(x.shape[-1], dp.act_block)
    m, e = _quantize(x, block, bits)
    scale = jnp.exp2(e.astype(jnp.float32))[..., None]
    xf = m * scale
    n = table.shape[0]
    a = dp.gelu_domain
    idx = jnp.clip(jnp.floor((xf + a) * (n / (2.0 * a))).astype(jnp.int32),
                   0, n - 1)
    y = jnp.where(xf >= a, xf,
                  jnp.where(xf <= -a, 0.0, jnp.take(jnp.asarray(table), idx)))
    top = 2 ** (bits - 1)
    my = jnp.clip(jnp.round(y / scale), -top, top - 1)
    return (my * scale).reshape(x.shape)


def softmax(x, dp: Datapath):
    """MXInt softmax over the last axis (Eq. 14-20)."""
    _, _, pow2 = _tables(dp)
    block = resolve_block(x.shape[-1], dp.act_block)
    mf, lam = _aligned(x, block, dp.act_mant_bits)
    t = mf - jnp.max(mf, axis=-1, keepdims=True)
    z = t * jnp.exp2(lam.astype(jnp.float32)) * LOG2E
    n = jnp.floor(z)
    nlut = pow2.shape[0]
    idx = jnp.clip(jnp.floor((z - n) * nlut).astype(jnp.int32), 0, nlut - 1)
    p = jnp.take(jnp.asarray(pow2), idx) * jnp.exp2(jnp.maximum(n, -126.0))
    s_m, s_e = jnp.frexp(jnp.sum(p, axis=-1, keepdims=True))
    y = (p / s_m) * jnp.exp2(-s_e.astype(jnp.float32))
    return qdq(y, block, dp.act_mant_bits)


def attention(q, k, v, dp: Datapath, precision: str):
    """(b, s, h, hd) q/k/v -> (b, s, h, hd); whole-row MXInt softmax."""
    scale = q.shape[-1] ** -0.5
    s = matmul("bshd,bShd->bhsS", q, k, precision) * scale
    p = softmax(s, dp)
    return matmul("bhsS,bShd->bshd", p, v, precision)

