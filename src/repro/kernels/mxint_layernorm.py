"""Pallas TPU kernel: MXInt LayerNorm / RMSNorm datapath (paper Fig. 3).

Stages inside one kernel invocation (a (rows_block, d) tile resident in
VMEM):

  1. block-quantize the activation row to MXInt (act_block shared exponents),
  2. requantize every block to the row-max exponent — integer right shifts,
  3. integer mean / variance on mantissas (lambda cancels, Eq. 5-7),
  4. variance -> (v_m, v_e); 1/sqrt via the tiny LUT with the even/odd
     exponent split of Eq. 9; exponent handled by shift,
  5. scale, gamma/beta, write.

The LUT is baked into the kernel as constants and applied as a select
chain over its entries (``lut_lookup``) — the TPU-native analogue of the
FPGA LUT (DESIGN.md §2), bit-identical to `jnp.take`.
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


def lut_lookup(idx: jnp.ndarray, table: tuple) -> jnp.ndarray:
    """``table[idx]`` for a static table of f32 entries, as a select chain.

    Exact, and it lowers on Mosaic, unlike a gather; a one-hot MXU
    contraction would round the f32 entries to bf16 at default precision.
    """
    y = jnp.full(idx.shape, table[0], jnp.float32)
    for i, v in enumerate(table[1:], 1):
        y = jnp.where(idx == i, jnp.float32(v), y)
    return y


def _rsqrt_lut_stage(var: jnp.ndarray, table: tuple, bits: int):
    var = jnp.maximum(var, 2.0 ** -24)
    v_m, v_e = jnp.frexp(var)
    v_m, v_e = v_m * 2.0, v_e - 1
    odd = (v_e % 2) != 0
    u = jnp.where(odd, v_m * 0.5, v_m)
    e_half = jnp.where(odd, (v_e + 1) // 2, v_e // 2)
    n = 2 ** bits
    idx = jnp.clip(jnp.floor((u - 0.5) * (n / 1.5)).astype(jnp.int32), 0, n - 1)
    r = lut_lookup(idx, table)
    return r * jnp.exp2(-e_half.astype(jnp.float32))


def _mxint_layernorm_kernel(x_ref, g_ref, b_ref, o_ref, *, act_block: int,
                            mant_bits: int, lut: tuple, lut_bits: int,
                            rms_only: bool, quantize_out: bool):
    x = x_ref[...].astype(jnp.float32)                 # (br, d)
    m, e = block_quantize(x, act_block, mant_bits)
    mf, _ = requantize_rows(m, e)                      # lambda cancels
    if rms_only:
        centered = mf
    else:
        centered = mf - jnp.mean(mf, axis=-1, keepdims=True)
    var = jnp.mean(centered * centered, axis=-1, keepdims=True)
    inv = _rsqrt_lut_stage(var, lut, lut_bits)
    y = centered * inv
    y = y * g_ref[...]
    if not rms_only:
        y = y + b_ref[...]
    if quantize_out:
        y = requantize_to_grid(y, act_block, mant_bits)
    o_ref[...] = y.astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=(
    "act_block", "mant_bits", "lut_bits", "rms_only", "quantize_out",
    "block_rows", "interpret"))
def mxint_layernorm(x: jnp.ndarray, gamma: jnp.ndarray, beta: jnp.ndarray, *,
                    act_block: int = 16, mant_bits: int = 8,
                    lut_bits: int = 5, rms_only: bool = False,
                    quantize_out: bool = False,
                    block_rows: int = 256, interpret: bool = True):
    """(rows, d) MXInt LayerNorm over the last axis."""
    rows, d = x.shape
    br = min(block_rows, rows)
    assert rows % br == 0, (rows, br)
    assert d % min(act_block, d) == 0
    act_block = min(act_block, d)

    kernel = functools.partial(
        _mxint_layernorm_kernel, act_block=act_block, mant_bits=mant_bits,
        lut=luts.rsqrt_table(lut_bits), lut_bits=lut_bits,
        rms_only=rms_only, quantize_out=quantize_out)

    return pl.pallas_call(
        kernel,
        grid=(rows // br,),
        in_specs=[
            pl.BlockSpec((br, d), lambda i: (i, 0)),
            pl.BlockSpec((1, d), lambda i: (0, 0)),
            pl.BlockSpec((1, d), lambda i: (0, 0)),
        ],
        out_specs=pl.BlockSpec((br, d), lambda i: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((rows, d), x.dtype),
        # Row blocks touch disjoint state: the whole grid is
        # parallel (DESIGN.md §14).
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel",)),
        interpret=interpret,
    )(x, gamma.reshape(1, d), beta.reshape(1, d))
