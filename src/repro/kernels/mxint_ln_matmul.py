"""Pallas TPU kernel: fused MXInt LayerNorm -> matmul (DESIGN.md §12).

The unfused kernel path runs Fig. 3 LayerNorm and the consuming quantized
linear as two ``pallas_call``s: the normalized, act-quantized tile is
written to HBM by the first kernel and read straight back by the second —
a full round-trip of (rows, d) activation bytes that exists only because
the ops are separate program launches.  This kernel fuses them: the
LayerNorm datapath runs once per row block into a VMEM scratch, and every
N-tile of the matmul contracts directly against that resident tile.

Grid: (cdiv(rows, bm), N/bn), N innermost — the same scratch-persistence
pattern as the matmul accumulator, but inverted: instead of one output
tile surviving across K steps, one *input* tile survives across N steps.
The N axis is therefore "arbitrary", the row axis "parallel".  The last
row block may be ragged, as in ``mxint_matmul``: the LayerNorm is per
row, so rows past ``rows`` stay in their own rows and are dropped.

  j == 0:  x tile (bm, d) -> block-quantize -> row-max requantize ->
           integer mean/var -> rsqrt LUT -> gamma/beta -> output
           quantization (Eq. 2-3 epilogue) -> round trip through the
           model dtype (the unfused path's HBM round trip) -> act
           quantization into the MXU operand -> VMEM scratch ``a``
           (bf16 for act mantissas of at most 8 bits against int8
           weight mantissas, else f32: ``mxint_matmul.operand_path``);
  all j:   ``a`` x the packed (d, bn) weight planes, one contraction with
           an f32 result (``mxint_matmul.tile_product``).

Bit-exactness vs the unfused sequence holds by construction: both paths
execute the same float ops in the same order on the same tiles (the K
contraction is a single tile in both, matching the interpret-mode
``mxint_linear``), and the bf16 operand holds its grid values exactly;
asserted in tests/test_datapath.py and tests/test_kernels.py.
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
from repro.kernels.mxint_layernorm import _rsqrt_lut_stage
from repro.kernels.mxint_matmul import (OPERAND_DTYPES, operand_path,
                                       tile_product)


def _mxint_ln_matmul_kernel(x_ref, g_ref, b_ref, wm_ref, we_ref, o_ref,
                            a_ref, *, act_block: int, mant_bits: int,
                            lut: tuple, lut_bits: int, rms_only: bool,
                            w_block: int, interpret: bool):
    """One (bm, bn) output tile; the LN stage and the act quantizer run
    only at j == 0 and their operand stays resident in ``a_ref``."""

    @pl.when(pl.program_id(1) == 0)
    def _ln():
        x = x_ref[...].astype(jnp.float32)             # (bm, d)
        m, e = block_quantize(x, act_block, mant_bits)
        mf, _ = requantize_rows(m, e)                  # lambda cancels
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
        y = requantize_to_grid(y, act_block, mant_bits)
        # the model dtype round trip of the unfused path, then the
        # consuming linear's act quantizer (mxint_matmul, quantize_act)
        a_ref[...] = requantize_to_grid(
            y.astype(x_ref.dtype).astype(jnp.float32), act_block,
            mant_bits).astype(a_ref.dtype)

    o_ref[...] = tile_product(a_ref[...], wm_ref[...], we_ref[...],
                              w_block=w_block, interpret=interpret
                              ).astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=(
    "w_block", "act_block", "mant_bits", "lut_bits", "rms_only",
    "bm", "bn", "interpret"))
def mxint_ln_matmul(x: jnp.ndarray, gamma: jnp.ndarray, beta: jnp.ndarray,
                    w_mant: jnp.ndarray, w_exp: jnp.ndarray, *,
                    w_block: int, act_block: int = 16, mant_bits: int = 8,
                    lut_bits: int = 5, rms_only: bool = False,
                    bm: int = 128, bn: int = 128,
                    interpret: bool = True) -> jnp.ndarray:
    """y[M,N] = MXIntLN(x)[M,K] @ (w_mant * 2^w_exp)[K,N], one kernel.

    x: (rows, d) activations (any float dtype — the LN stage computes in
    f32 and rounds its output through this dtype); gamma/beta: (d,) scale /
    shift (beta ignored with ``rms_only``); w_mant: (d, N) int8 mantissas;
    w_exp: (d/w_block, N) int8 shared exponents.  The output is NOT
    bias-added (the wrapper adds bias after any tensor-parallel
    collective, like ``mxint_linear``).
    """
    rows, d = x.shape
    K, N = w_mant.shape
    assert K == d, (K, d)
    assert d % w_block == 0, (d, w_block)
    assert w_exp.shape == (d // w_block, N), (w_exp.shape, d, w_block, N)
    bm = min(bm, rows)
    bn = min(bn, N)
    assert N % bn == 0, (N, bn)
    assert d % min(act_block, d) == 0
    act_block = min(act_block, d)
    beta_arr = beta if beta is not None else jnp.zeros_like(gamma)

    kernel = functools.partial(
        _mxint_ln_matmul_kernel, act_block=act_block, mant_bits=mant_bits,
        lut=luts.rsqrt_table(lut_bits), lut_bits=lut_bits,
        rms_only=rms_only, w_block=w_block, interpret=interpret)

    return pl.pallas_call(
        kernel,
        grid=(pl.cdiv(rows, bm), N // bn),
        in_specs=[
            pl.BlockSpec((bm, d), lambda i, j: (i, 0)),
            pl.BlockSpec((1, d), lambda i, j: (0, 0)),
            pl.BlockSpec((1, d), lambda i, j: (0, 0)),
            pl.BlockSpec((d, bn), lambda i, j: (0, j)),
            pl.BlockSpec((d // w_block, bn), lambda i, j: (0, j)),
        ],
        out_specs=pl.BlockSpec((bm, bn), lambda i, j: (i, j)),
        out_shape=jax.ShapeDtypeStruct((rows, N), jnp.float32),
        scratch_shapes=[pltpu.VMEM(
            (bm, d),
            OPERAND_DTYPES[operand_path(True, mant_bits, w_mant.dtype)])],
        # Row blocks are independent; the N axis reuses the operand
        # cached in scratch at j == 0, so it must run in order
        # (DESIGN.md §14).
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary")),
        interpret=interpret,
    )(x, gamma.reshape(1, d), beta_arr.reshape(1, d), w_mant, w_exp)
