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
  * with ``quantize_act`` each (bm, K) activation row block is
    block-quantized ONCE, at the first column tile, into a VMEM scratch of
    mantissas times their block's power of two; every column tile
    contracts that resident operand (the integer datapath of Fig. 2b);
  * the full K is contracted in one tile with an f32 result (the paper's
    12-bit accumulator DSE is subsumed — DESIGN.md §2).

The MXU operands (``operand_path``).  A mantissa of at most 8 bits (|m| <=
127) times a power of two has at most 7 significant bits, and so has an
int8 weight mantissa (the symmetric clip keeps |m| <= 127): both are exact
in bf16, whose exponent range is f32's.  Their products are exact in f32,
and the accumulator stays f32.  So ``'bf16'`` — quantized activations of
at most 8 mantissa bits against an int8 weight plane — feeds the MXU
bf16 x bf16 with an f32 result and drops nothing.  ``'f32'`` — quantized
activations of more than 8 bits, or a wider weight plane (int16 for 9-16
bit weights) — keeps an f32 scratch and an f32 contraction.  ``'raw'`` —
activations not quantized, not exact in bf16 — contracts the f32 tile as
it arrives, with no scratch.

Interpret mode (``interpret=True``, the CPU) casts the same bf16 operands
to f32 before the dot: XLA's CPU sums a bf16 x bf16 -> f32 dot in another
order than an f32 dot, and the f32 dot is what the 'sim' oracle's einsum
sums in.  The two modes multiply the same exact values and may differ
only in the order of the f32 sum, as the f32 contraction on the chip
already did.  (XLA's CPU ``exp2`` misses 2^e by a few ulps for |e| > 12,
so there the oracle's f32 operand lies off the grid and the bf16 operand
rounds it back on: CPU parity with the oracle is bit for bit where block
exponents stay within +-12.)

Grid: (cdiv(M, bm), N/bn), N innermost.  The quantized paths fill the
scratch at j == 0 and read it at every j, so the column axis is
"arbitrary" (v5e has one TensorCore: nothing is lost); the raw path's
tiles are independent, "parallel" on both axes.  Rows are independent, so
the last row block may be ragged: Pallas reads past M into it and drops
those output rows.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.block_quant import requantize_to_grid

# mantissa bits (sign included) whose values times a power of two bf16
# holds exactly: |m| <= 127 is 7 significant bits of bf16's 8
BF16_MANT_BITS = 8
OPERAND_DTYPES = {"bf16": jnp.bfloat16, "f32": jnp.float32}


def operand_path(quantize_act: bool, act_mant_bits: int, w_mant_dtype) -> str:
    """How the linear kernels feed the MXU: 'bf16' (quantized
    activations and weight mantissas, both exact in bf16), 'f32'
    (quantized, but a side is wider than bf16 holds) or 'raw'
    (activations not quantized).  The one rule both linear kernels'
    wrappers and their counters read."""
    if not quantize_act:
        return "raw"
    exact = (act_mant_bits <= BF16_MANT_BITS
             and jnp.dtype(w_mant_dtype).itemsize == 1)
    return "bf16" if exact else "f32"


def _broadcast_block_exp(e_tile: jnp.ndarray, block: int) -> jnp.ndarray:
    """(kb, bn) int8 exponents -> (kb*block, bn) f32 scales, 2^e.

    Expands along the sublane axis (a leading-dim merge), which Mosaic
    lowers when ``block`` is a multiple of the 8-row sublane tile.
    """
    kb, bn = e_tile.shape
    s = jnp.exp2(e_tile.astype(jnp.float32))
    s = jnp.broadcast_to(s[:, None, :], (kb, block, bn))
    return s.reshape(kb * block, bn)


def tile_product(a: jnp.ndarray, wm: jnp.ndarray, we: jnp.ndarray, *,
                 w_block: int, interpret: bool) -> jnp.ndarray:
    """(bm, K) operand x packed (K, bn) planes -> (bm, bn) f32.

    The weight tile is widened to ``a``'s dtype (bf16 or f32, exact
    either way) and the whole K is one contraction with an f32 result,
    so the accumulation order matches the XLA einsum of the 'sim'
    oracle.  In interpret mode both operands are cast to f32 first.
    """
    w = (wm.astype(jnp.float32) * _broadcast_block_exp(we, w_block)
         ).astype(a.dtype)
    if interpret:
        a, w = a.astype(jnp.float32), w.astype(jnp.float32)
    return jax.lax.dot_general(a, w, (((1,), (0,)), ((), ())),
                               preferred_element_type=jnp.float32)


def _mxint_matmul_kernel(x_ref, wm_ref, we_ref, o_ref, a_ref, *,
                         act_block: int, act_mant_bits: int, w_block: int,
                         interpret: bool):
    """One (bm, bn) output tile over the full K; the row block's operand
    is quantized at j == 0 and stays resident in ``a_ref`` for every j."""

    @pl.when(pl.program_id(1) == 0)
    def _quantize():
        a_ref[...] = requantize_to_grid(
            x_ref[...].astype(jnp.float32), act_block,
            act_mant_bits).astype(a_ref.dtype)

    o_ref[...] = tile_product(a_ref[...], wm_ref[...], we_ref[...],
                              w_block=w_block, interpret=interpret
                              ).astype(o_ref.dtype)


def _mxint_matmul_raw_kernel(x_ref, wm_ref, we_ref, o_ref, *, w_block: int,
                             interpret: bool):
    """One (bm, bn) output tile of the unquantized activation, f32."""
    o_ref[...] = tile_product(x_ref[...].astype(jnp.float32), wm_ref[...],
                              we_ref[...], w_block=w_block,
                              interpret=interpret).astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=(
    "w_block", "act_block", "act_mant_bits", "quantize_act",
    "bm", "bn", "interpret", "out_dtype"))
def mxint_matmul(x: jnp.ndarray, w_mant: jnp.ndarray, w_exp: jnp.ndarray, *,
                 w_block: int, act_block: int = 16, act_mant_bits: int = 8,
                 quantize_act: bool = False, bm: int = 128, bn: int = 128,
                 interpret: bool = True,
                 out_dtype=jnp.float32) -> jnp.ndarray:
    """y[M,N] = x[M,K] @ (w_mant * 2^w_exp)[K,N] with MXInt weights.

    w_mant: (K, N) int8 mantissas (int16 for 9-16 bit weights); w_exp:
    (K/w_block, N) int8 exponents.  ``operand_path`` picks the MXU
    operand dtype and the grid semantics from the static arguments.
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
    path = operand_path(quantize_act, act_mant_bits, w_mant.dtype)
    if path == "raw":
        kernel = functools.partial(_mxint_matmul_raw_kernel,
                                   w_block=w_block, interpret=interpret)
        scratch = []
        # every (bm, bn) output tile is computed once from its own inputs
        # (DESIGN.md §14)
        semantics = ("parallel", "parallel")
    else:
        assert K % act_block == 0, (K, act_block)
        kernel = functools.partial(
            _mxint_matmul_kernel, act_block=act_block,
            act_mant_bits=act_mant_bits, w_block=w_block,
            interpret=interpret)
        scratch = [pltpu.VMEM((bm, K), OPERAND_DTYPES[path])]
        # row blocks are independent; the N axis reads the operand
        # quantized into scratch at j == 0, so it runs in order
        semantics = ("parallel", "arbitrary")

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
        scratch_shapes=scratch,
        compiler_params=pltpu.CompilerParams(dimension_semantics=semantics),
        interpret=interpret,
    )(x, w_mant, w_exp)
