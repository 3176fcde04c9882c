"""Pallas TPU kernel: MXInt softmax datapath (paper §III-B-3, Eq. 14-20).

Row softmax with the llama.cpp-style exponential lifted into the kernel:

  1. block-quantize the row to MXInt, requantize to the row-max exponent,
  2. integer max-subtract in the mantissa domain,
  3. z = t * 2^lambda * log2(e); split z = n + r,
  4. e^x ~= 2^n * LUT_pow2(r)  (LUT_pow2 has 2^r_bits entries — 4 for the
     paper's final 2-bit design),
  5. accumulate, then divide in (mantissa, exponent) form (Eq. 20):
     frexp on the sum == the hardware's leading-zero-count + shift.

One kernel instance owns a (rows_block, n) tile; attention-shaped inputs
(b*h*q, k) stream through the grid.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.core import luts
from repro.kernels.block_quant import (block_quantize, requantize_rows,
                                       requantize_to_grid)
from repro.kernels.mxint_layernorm import lut_lookup

_LOG2E = 1.4426950408889634


def exp2_datapath(z: jnp.ndarray, table: tuple, r_bits: int):
    """2^z for z <= 0 via 2^n * LUT_pow2(r)."""
    n = jnp.floor(z)
    r = z - n
    nmax = 2 ** r_bits
    idx = jnp.clip(jnp.floor(r * nmax).astype(jnp.int32), 0, nmax - 1)
    p_m = lut_lookup(idx, table)
    return p_m * jnp.exp2(jnp.maximum(n, -126.0))


def _mxint_softmax_kernel(x_ref, o_ref, *, act_block: int, mant_bits: int,
                          lut: tuple, r_bits: int, quantize_out: bool):
    x = x_ref[...].astype(jnp.float32)                  # (br, n)
    m, e = block_quantize(x, act_block, mant_bits)
    mf, lam = requantize_rows(m, e)
    t = mf - jnp.max(mf, axis=-1, keepdims=True)        # <= 0, mantissa units
    z = t * jnp.exp2(lam.astype(jnp.float32)) * _LOG2E
    p = exp2_datapath(z, lut, r_bits)
    s = jnp.sum(p, axis=-1, keepdims=True)
    s_m, s_e = jnp.frexp(s)                             # LZC + shift in HW
    y = (p / s_m) * jnp.exp2(-s_e.astype(jnp.float32))
    if quantize_out:
        # probabilities leave on the MXInt act grid (the 'sim' datapath's
        # final quantize before the p @ V matmul)
        y = requantize_to_grid(y, act_block, mant_bits)
    o_ref[...] = y.astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=(
    "act_block", "mant_bits", "r_bits", "quantize_out", "block_rows",
    "interpret"))
def mxint_softmax(x: jnp.ndarray, *, act_block: int = 16, mant_bits: int = 8,
                  r_bits: int = 2, quantize_out: bool = False,
                  block_rows: int = 256,
                  interpret: bool = True) -> jnp.ndarray:
    """Row softmax over the last axis of a 2-D array via the MXInt datapath."""
    rows, n = x.shape
    br = min(block_rows, rows)
    assert rows % br == 0
    act_block = min(act_block, n)
    assert n % act_block == 0, (n, act_block)

    kernel = functools.partial(_mxint_softmax_kernel, act_block=act_block,
                               mant_bits=mant_bits,
                               lut=luts.pow2_table(r_bits), r_bits=r_bits,
                               quantize_out=quantize_out)
    return pl.pallas_call(
        kernel,
        grid=(rows // br,),
        in_specs=[pl.BlockSpec((br, n), lambda i: (i, 0))],
        out_specs=pl.BlockSpec((br, n), lambda i: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((rows, n), x.dtype),
        # Row blocks touch disjoint state: the whole grid is
        # parallel (DESIGN.md §14).
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel",)),
        interpret=interpret,
    )(x)
