"""In-kernel MXInt block quantization shared by every Pallas kernel.

A (rows, d) tile is quantized in blocks of ``block`` consecutive lanes
(paper Eq. 2).  Splitting the lane axis into (d / block, block) is the
obvious reading, but Mosaic refuses that shape cast on the TPU.  The block
max is instead taken along the SUBLANE axis: transpose the tile, split the
second-minor dim into (d / block, block), reduce, broadcast back and
transpose again.  Every result is returned per lane — each element carries
its block's shared exponent — so callers never reshape either.

Bit-identical to ``repro.core.quantize`` (same frexp exponent, same
round/clip), which the interpret-mode tests assert.
"""
from __future__ import annotations

import jax.numpy as jnp

_EXP_MIN, _EXP_MAX = -127, 127


def block_amax(x: jnp.ndarray, block: int) -> jnp.ndarray:
    """(rows, d) -> (rows, d): each element replaced by max |x| over its
    block of ``block`` consecutive lanes."""
    a = jnp.abs(x)
    if block == 1:
        return a
    r, d = a.shape
    t = a.T.reshape(d // block, block, r)
    m = jnp.max(t, axis=1, keepdims=True)
    return jnp.broadcast_to(m, t.shape).reshape(d, r).T


def block_quantize(x: jnp.ndarray, block: int, mant_bits: int):
    """Quantize (rows, d) along d in blocks of ``block`` lanes.

    Returns (mantissas as integer-valued f32, shared exponent as int32),
    both (rows, d) — the exponent repeated over its block's lanes.
    """
    amax = block_amax(x, block)
    _, k = jnp.frexp(jnp.maximum(amax, jnp.finfo(jnp.float32).tiny))
    e = jnp.where(amax > 0, k - 1 - (mant_bits - 2), 0)
    e = jnp.clip(e, _EXP_MIN, _EXP_MAX).astype(jnp.int32)
    lim = float(2 ** (mant_bits - 1) - 1)
    m = jnp.clip(jnp.round(x * jnp.exp2(-e.astype(jnp.float32))), -lim, lim)
    return m, e


def requantize_rows(m: jnp.ndarray, e: jnp.ndarray):
    """Align every block of each row to the row-max exponent (Eq. 3).

    m, e: per-lane mantissas / exponents from ``block_quantize``.  Returns
    (shifted mantissas (rows, d), row-max exponent (rows, 1)).
    """
    e_max = jnp.max(e, axis=-1, keepdims=True)
    shift = jnp.minimum(e_max - e, 31)
    # arithmetic right shift on integer-valued f32 mantissas: floor of the
    # exact power-of-two scale matches >> for the int32 the hardware holds
    # (incl. negatives, floor -> -inf), and unlike `1 << shift` it cannot
    # overflow at the shift=31 saturation point (hit when masked -inf
    # scores share a row with real scores).
    mi = jnp.floor(m * jnp.exp2(-shift.astype(jnp.float32)))
    return mi, e_max


def requantize_to_grid(y: jnp.ndarray, block: int, mant_bits: int):
    """Snap a (rows, d) tile onto the MXInt act grid (quantize-dequantize).

    The shared epilogue of the LayerNorm, softmax and attention kernels:
    the 'sim' datapath quantizes each op's output back to act_fmt before
    the next op consumes it.
    """
    m, e = block_quantize(y, block, mant_bits)
    return m * jnp.exp2(e.astype(jnp.float32))
