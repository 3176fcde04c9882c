"""Pallas TPU kernel: fused MXInt LayerNorm -> matmul (DESIGN.md §12).

The unfused kernel path runs Fig. 3 LayerNorm and the consuming quantized
linear as two ``pallas_call``s: the normalized, act-quantized tile is
written to HBM by the first kernel and read straight back by the second —
a full round-trip of (rows, d) activation bytes that exists only because
the ops are separate program launches.  This kernel fuses them: the
LayerNorm datapath runs once per row block into a VMEM scratch, and every
N-tile of the matmul contracts directly against that resident tile.

Grid: (rows/bm, N/bn), N innermost — the same scratch-persistence pattern
as the matmul accumulator, but inverted: instead of one output tile
surviving across K steps, one *input* tile survives across N steps.

  j == 0:  x tile (bm, d) -> block-quantize -> row-max requantize ->
           integer mean/var -> rsqrt LUT -> gamma/beta -> output
           quantization (Eq. 2-3 epilogue) -> VMEM scratch ``y``
           (stored in the model dtype, so the scratch round-trip is
           bit-identical to the unfused HBM round-trip);
  all j:   y -> in-register act quantization -> mantissa x mantissa
           contraction against the packed (d, bn) weight planes
           (identical stages to mxint_matmul with quantize_act=True).

Bit-exactness vs the unfused sequence holds by construction: both paths
execute the same float ops in the same order on the same tiles (the K
contraction is a single tile in both, matching the interpret-mode
``mxint_linear``); asserted in tests/test_datapath.py.
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
from repro.kernels.mxint_matmul import mxint_tile_product


def _mxint_ln_matmul_kernel(x_ref, g_ref, b_ref, wm_ref, we_ref, o_ref,
                            y_ref, *, act_block: int, mant_bits: int,
                            lut: tuple, lut_bits: int, rms_only: bool,
                            w_block: int):
    """One (bm, bn) output tile; the LN stage runs only at j == 0 and its
    result stays resident in the ``y_ref`` VMEM scratch for every j."""

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
        y_ref[...] = y.astype(y_ref.dtype)

    # matmul stage — the same tile product as mxint_matmul with
    # quantize_act=True (a single K tile, bk == d)
    o_ref[...] = mxint_tile_product(
        y_ref[...], wm_ref[...], we_ref[...], w_block=w_block,
        act_block=act_block, act_mant_bits=mant_bits,
        quantize_act=True).astype(o_ref.dtype)


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
    f32 and the scratch holds the model dtype); gamma/beta: (d,) scale /
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
    assert rows % bm == 0 and N % bn == 0, (rows, N, bm, bn)
    assert d % min(act_block, d) == 0
    act_block = min(act_block, d)
    beta_arr = beta if beta is not None else jnp.zeros_like(gamma)

    kernel = functools.partial(
        _mxint_ln_matmul_kernel, act_block=act_block, mant_bits=mant_bits,
        lut=luts.rsqrt_table(lut_bits), lut_bits=lut_bits,
        rms_only=rms_only, w_block=w_block)

    return pl.pallas_call(
        kernel,
        grid=(rows // bm, N // bn),
        in_specs=[
            pl.BlockSpec((bm, d), lambda i, j: (i, 0)),
            pl.BlockSpec((1, d), lambda i, j: (0, 0)),
            pl.BlockSpec((1, d), lambda i, j: (0, 0)),
            pl.BlockSpec((d, bn), lambda i, j: (0, j)),
            pl.BlockSpec((d // w_block, bn), lambda i, j: (0, j)),
        ],
        out_specs=pl.BlockSpec((bm, bn), lambda i, j: (i, j)),
        out_shape=jax.ShapeDtypeStruct((rows, N), jnp.float32),
        scratch_shapes=[pltpu.VMEM((bm, d), x.dtype)],
        # Row blocks are independent; the N axis reuses the normalised
        # tile cached in scratch at j == 0, so it must run in order
        # (DESIGN.md §14).
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary")),
        interpret=interpret,
    )(x, gamma.reshape(1, d), beta_arr.reshape(1, d), w_mant, w_exp)
