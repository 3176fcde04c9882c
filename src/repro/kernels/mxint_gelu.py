"""Pallas TPU kernel: MXInt GELU / SiLU datapath (paper §III-B-2, Eq. 12).

Elementwise 3-piece activation on a VMEM tile:

    y = x                      for x >= a       (ReLU tail)
    y = LUT[fix(x)]            for -a < x < a   (2^k-entry table, Fig. 6)
    y = 0                      for x <= -a

The input tile is block-quantized first so the LUT sees exactly the MXInt
value grid (the kernel's numerics match `repro.core.nonlinear.mxint_gelu`:
quantize -> lookup -> requantize onto the forwarded block exponent).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.core import luts
from repro.core.mx_types import NonlinearConfig
from repro.kernels.block_quant import block_quantize
from repro.kernels.mxint_layernorm import lut_lookup


def _mxint_gelu_kernel(x_ref, o_ref, *, act_block: int, mant_bits: int,
                       lut: tuple, index_bits: int, domain: float):
    x = x_ref[...].astype(jnp.float32)                       # (br, d)
    m, e = block_quantize(x, act_block, mant_bits)
    scale = jnp.exp2(e.astype(jnp.float32))
    xq = m * scale                                           # on-grid values

    n = 2 ** index_bits
    idx = jnp.clip(jnp.floor((xq + domain) * (n / (2.0 * domain)))
                   .astype(jnp.int32), 0, n - 1)
    y_small = lut_lookup(idx, lut)
    y = jnp.where(xq >= domain, xq, jnp.where(xq <= -domain, 0.0, y_small))

    # requantize onto the forwarded input exponent (paper: exponent is
    # "directly forwarded to the output")
    lim = float(2 ** (mant_bits - 1) - 1)
    ym = jnp.clip(jnp.round(y / scale), -lim, lim)
    o_ref[...] = (ym * scale).astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=(
    "act_block", "mant_bits", "lut_bits", "domain", "fn", "block_rows",
    "interpret"))
def mxint_gelu(x: jnp.ndarray, *, act_block: int = 16, mant_bits: int = 8,
               lut_bits: int = 5, domain: float = 3.0, fn: str = "gelu",
               block_rows: int = 256, interpret: bool = True) -> jnp.ndarray:
    """Elementwise MXInt GELU (or SiLU) over a 2-D (rows, d) array."""
    rows, d = x.shape
    br = min(block_rows, rows)
    assert rows % br == 0
    act_block = min(act_block, d)
    assert d % act_block == 0

    cfg = NonlinearConfig(gelu_lut_bits=lut_bits, gelu_domain=domain)
    if fn == "gelu":
        index_bits = cfg.gelu_index_bits
        lut = luts.gelu_table(index_bits, domain)
        eff_domain = domain
    elif fn == "silu":
        eff_domain = 2.0 * domain
        index_bits = cfg.gelu_index_bits + 1
        nent = 2 ** index_bits
        centers = -eff_domain + (2.0 * eff_domain / nent) * (np.arange(nent) + 0.5)
        lut = tuple((centers / (1.0 + np.exp(-centers))).astype(np.float32)
                    .tolist())
    else:
        raise ValueError(fn)

    kernel = functools.partial(_mxint_gelu_kernel, act_block=act_block,
                               mant_bits=mant_bits, lut=lut,
                               index_bits=index_bits, domain=eff_domain)
    return pl.pallas_call(
        kernel,
        grid=(rows // br,),
        in_specs=[pl.BlockSpec((br, d), lambda i: (i, 0))],
        out_specs=pl.BlockSpec((br, d), lambda i: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((rows, d), x.dtype),
        # Row blocks touch disjoint state: the whole grid is
        # parallel (DESIGN.md §14).
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel",)),
        interpret=interpret,
    )(x)
