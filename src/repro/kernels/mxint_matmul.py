"""Pallas TPU kernel: MXInt matmul (paper Fig. 2b, adapted to the MXU).

The paper's dot-product unit multiplies integer mantissas and applies ONE
dynamic shift per block (the shared-exponent product).  The TPU-native
reading of that datapath:

  * weight mantissas live in HBM as int8 planes; the shared exponents are a
    (K/B, N) int8 plane — HBM->VMEM traffic is the *quantized* bytes, which
    is the paper's memory win, preserved;
  * inside the kernel each (K, bn) mantissa tile is scaled by
    2^exponent once per block — the "one dynamic shift per block", expressed
    as a broadcasted `exp2` multiply feeding the MXU;
  * optionally the activation tile is block-quantized in-register, and
    the mantissas scaled by their block exponents feed the MXU — the
    integer datapath of Fig. 2b, exact in f32 (the operands are <=8-bit
    mantissas times powers of two);
  * the full K is contracted in one tile with an f32 result (the paper's
    12-bit accumulator DSE is subsumed — DESIGN.md §2).

Grid: (cdiv(M, bm), N/bn).  Rows are independent, so the last row block
may be ragged: Pallas reads past M into it and drops those output rows.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.block_quant import block_quantize


def _broadcast_block_exp(e_tile: jnp.ndarray, block: int) -> jnp.ndarray:
    """(kb, bn) int8 exponents -> (kb*block, bn) f32 scales, 2^e.

    Expands along the sublane axis (a leading-dim merge), which Mosaic
    lowers when ``block`` is a multiple of the 8-row sublane tile.
    """
    kb, bn = e_tile.shape
    s = jnp.exp2(e_tile.astype(jnp.float32))
    s = jnp.broadcast_to(s[:, None, :], (kb, block, bn))
    return s.reshape(kb * block, bn)


def mxint_tile_product(x: jnp.ndarray, wm: jnp.ndarray, we: jnp.ndarray, *,
                       w_block: int, act_block: int, act_mant_bits: int,
                       quantize_act: bool) -> jnp.ndarray:
    """(bm, K) activations x packed (K, bn) planes -> (bm, bn) f32.

    The whole contraction is one tile, so the accumulation order matches
    the XLA einsum of the 'sim' oracle.  With ``quantize_act`` the
    activation tile is block-quantized in-register and its mantissas,
    scaled by their block exponent, feed the contraction: the integer
    datapath of Fig. 2b, exact in f32 for <=11-bit mantissa products.
    """
    x = x.astype(jnp.float32)
    if quantize_act:
        xm, xe = block_quantize(x, act_block, act_mant_bits)
        x = xm * jnp.exp2(xe.astype(jnp.float32))
    w = wm.astype(jnp.float32) * _broadcast_block_exp(we, w_block)
    return jax.lax.dot_general(x, w, (((1,), (0,)), ((), ())),
                               preferred_element_type=jnp.float32)


def _mxint_matmul_kernel(x_ref, wm_ref, we_ref, o_ref, **kw):
    """One (bm, bn) output tile over the full K."""
    o_ref[...] = mxint_tile_product(x_ref[...], wm_ref[...], we_ref[...],
                                    **kw).astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=(
    "w_block", "act_block", "act_mant_bits", "quantize_act",
    "bm", "bn", "interpret", "out_dtype"))
def mxint_matmul(x: jnp.ndarray, w_mant: jnp.ndarray, w_exp: jnp.ndarray, *,
                 w_block: int, act_block: int = 16, act_mant_bits: int = 8,
                 quantize_act: bool = False, bm: int = 128, bn: int = 128,
                 interpret: bool = True,
                 out_dtype=jnp.float32) -> jnp.ndarray:
    """y[M,N] = x[M,K] @ (w_mant * 2^w_exp)[K,N] with MXInt weights.

    w_mant: (K, N) int8 mantissas; w_exp: (K/w_block, N) int8 exponents.
    Grid (cdiv(M, bm), N/bn); each step contracts the full K, so the
    exponent plane's block spans its whole first dim, which the (8,128)
    block rule accepts for any K.  A ragged last row block computes rows
    past M from whatever the block holds there; every stage is per row,
    so they reach no valid row, and Pallas never writes them out.
    """
    M, K = x.shape
    K2, N = w_mant.shape
    assert K == K2, (K, K2)
    assert w_exp.shape == (K // w_block, N), (w_exp.shape, K, w_block, N)

    bm = min(bm, M)
    bn = min(bn, N)
    assert N % bn == 0, (N, bn)
    if quantize_act:
        assert K % act_block == 0, (K, act_block)

    kernel = functools.partial(
        _mxint_matmul_kernel, w_block=w_block, act_block=act_block,
        act_mant_bits=act_mant_bits, quantize_act=quantize_act)

    return pl.pallas_call(
        kernel,
        grid=(pl.cdiv(M, bm), N // bn),
        in_specs=[
            pl.BlockSpec((bm, K), lambda i, j: (i, 0)),
            pl.BlockSpec((K, bn), lambda i, j: (0, j)),
            pl.BlockSpec((K // w_block, bn), lambda i, j: (0, j)),
        ],
        out_specs=pl.BlockSpec((bm, bn), lambda i, j: (i, j)),
        out_shape=jax.ShapeDtypeStruct((M, N), out_dtype),
        # every (bm, bn) output tile is computed once from its own inputs
        # (DESIGN.md §14).
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel")),
        interpret=interpret,
    )(x, w_mant, w_exp)
