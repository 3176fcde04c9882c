"""Pallas TPU kernel: blocked (flash) attention with the MXInt softmax datapath.

Online-softmax attention over (batch*heads, seq, head_dim) operands with
BlockSpec VMEM tiling:

  grid = (bh, q_blocks, k_blocks), k innermost; running max / sum / output
  accumulator live in VMEM scratch across the k dimension.

``exp_mode``:
  'float'  — exact exp (standard flash attention; the Float baseline).
  'mxint'  — the paper's Eq. 14-19 datapath: 2^n * LUT_pow2(r) with r_bits
             fractional bits, applied to both the new-block exponentials and
             the running-accumulator rescale (both arguments are <= 0, the
             datapath's domain).

``quantize_scores`` (requires exp_mode='mxint') adds the REST of the paper
softmax (DESIGN.md §11): per-row-block MXInt quantization of the incoming
score tile (Eq. 2-3: shared exponents per ``act_block`` lanes, requantize to
the tile-row max exponent) before the exp LUT, and Eq. 20 probability
quantization before the p @ V matmul.  The final k block's matmul is
deferred to the flush so its probabilities are quantized FULLY NORMALIZED
(the true Eq. 20 output); interior blocks quantize their unnormalized
probabilities (their shared exponents absorb the pending normalization up
to the Eq. 20 mantissa divide).  When a single k block covers the whole
row this degenerates to exactly the whole-row 'paper' kernel.

Supports causal masking and sliding-window (SWA) masking — window > 0 masks
keys older than ``window`` positions (Mixtral-style).  ``kv_len`` marks
wrapper padding (keys added to reach tile multiples): padded lanes are
numerically INVISIBLE — zeroed for the quantizer's amax, excluded from the
row max, the Eq. 19 sum and the accumulator — unlike model-masked lanes,
which are filled with ``NEG_INF`` BEFORE quantization exactly as the
whole-row 'sim' datapath fills them.

``bias`` adds a decomposed 2-D relative-position term to the scaled
scores, before the Eq. 2-3 score quantization (the Segment Anything
image encoder's global attention): keys lie on a (kh, kw) grid, key
``c`` at row ``c // kw`` and column ``c % kw``, and

  s[q, c] = scale * q.k_c + bias_h[q, c // kw] + bias_w[q, c % kw].

The (bq, kh) and (bq, kw) bias tiles are spread over each (bq, bk) score
tile by exact one-hot contractions, so no (Sq, Sk) bias ever exists.
The score, bias and p @ V contractions run at full float32 precision
(``Precision.HIGHEST``), as the whole-row path runs its einsums.

``flash_attention_decode`` is the single-query variant: one query position
per KV head (the G query heads of a GQA group folded into sublane rows),
K/V streamed from the cache ring in k blocks, slot validity supplied as an
explicit ``valid`` vector (ring/window masking is the caller's slot
arithmetic, not in-kernel position math).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.core import luts
from repro.core.quantize import _resolve_block
from repro.kernels.block_quant import (block_quantize, requantize_rows,
                                       requantize_to_grid)
from repro.kernels.mxint_softmax import exp2_datapath

_LOG2E = 1.4426950408889634
# Masking sentinel, unified with models/attention.py and kernels/ops.py.
# The Eq. 2-3 score quantization runs on the MASKED tile (sim parity), so
# kernel, wrapper and model must fill with the same value — the single
# definition lives in core/mx_types.py (re-exported here for kernel code).
from repro.core.mx_types import NEG_INF
_NEG_INF = NEG_INF
# Fill value for wrapper-padding lanes during score quantization: must be
# (a) too small to ever win an act block's amax against real scores, so a
# mixed real/pad block keeps the unpadded shared exponent, and (b) nonzero,
# because an all-zero block quantizes to exponent 0 — which would RAISE the
# tile's row-max exponent above typical score exponents (~2^-6) and
# re-floor the real mantissas, breaking whole-row parity.
_PAD_FILL = 2.0 ** -100
_HIGHEST = jax.lax.Precision.HIGHEST


def _softmax_block_update(s, mask, pad_mask, v, write, m_sc, l_sc, acc_sc,
                          lut, *, exp_mode: str, r_bits: int,
                          quantize_scores: bool, act_block: int,
                          mant_bits: int, kb, n_k: int):
    """Online-softmax update for one (bq, bk) score tile (DESIGN.md §11).

    ``mask`` is the MODEL mask (causal / window / cache validity): masked
    lanes are filled with NEG_INF BEFORE the Eq. 2-3 score quantization,
    matching the whole-row 'paper' datapath.  ``pad_mask`` (True = real
    key) marks wrapper padding: those lanes are numerically invisible.
    """
    s = jnp.where(mask, s, NEG_INF)
    if quantize_scores:
        if pad_mask is not None:
            # padding must not poison the shared exponents: fill with
            # _PAD_FILL for the quantizer's amax (see its comment),
            # reinstate NEG_INF after dequantization
            s = jnp.where(pad_mask, s, _PAD_FILL)
        m, e = block_quantize(s, act_block, mant_bits)
        mf, lam = requantize_rows(m, e)
        # exact dequantize: integer-valued f32 mantissas times a power of
        # two — (mf_i - mf_max) * 2^lam stays exact, so the z fed to the
        # LUT is bit-identical to the whole-row kernel's mantissa-domain
        # subtract when one k block covers the row
        s = mf * jnp.exp2(lam.astype(jnp.float32))
    if pad_mask is not None:
        s = jnp.where(pad_mask, s, NEG_INF)

    m_prev = m_sc[...]                                     # (bq, 1)
    m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1, keepdims=True))
    if exp_mode == "mxint":
        p = exp2_datapath((s - m_new) * _LOG2E, lut, r_bits)
    else:
        p = jnp.exp(s - m_new)
    # The running rescale alpha is kept exact: the FPGA design is
    # row-at-once and never rescales, so quantizing alpha would compound
    # LUT error across k blocks with no hardware analogue — exact alpha is
    # the faithful blocked reading (DESIGN.md §11).
    alpha = jnp.exp(m_prev - m_new)
    # fully-masked row guard (SWA can mask a whole block)
    alpha = jnp.where(m_prev <= NEG_INF / 2, 0.0, alpha)

    live = mask if pad_mask is None else (mask & pad_mask)
    if quantize_scores:
        # Eq. 19 sum includes model-masked lanes (their p is the datapath's
        # 2^-126 tail, exactly as the whole-row kernel sums them) but never
        # wrapper padding.
        p_l = p if pad_mask is None else jnp.where(pad_mask, p, 0.0)
    else:
        p = jnp.where(live, p, 0.0)
        p_l = p
    psum = jnp.sum(p_l, axis=-1, keepdims=True)

    if quantize_scores:
        @pl.when(kb < n_k - 1)
        def _interior():
            # interior blocks: probabilities leave on the MXInt act grid
            # before the p @ V matmul, still unnormalized (the Eq. 20
            # divide is a pending per-row scalar applied at flush)
            pq = requantize_to_grid(p, act_block, mant_bits)
            pq = jnp.where(live, pq, 0.0)
            l_sc[...] = l_sc[...] * alpha + psum
            acc_sc[...] = acc_sc[...] * alpha + jax.lax.dot_general(
                pq, v, (((1,), (0,)), ((), ())), precision=_HIGHEST,
                preferred_element_type=jnp.float32)
            m_sc[...] = m_new

        @pl.when(kb == n_k - 1)
        def _flush():
            l = l_sc[...] * alpha + psum
            # Eq. 20: division in (mantissa, exponent) form
            l_m, l_e = jnp.frexp(jnp.maximum(l, 1e-30))
            inv_e = jnp.exp2(-l_e.astype(jnp.float32))
            y = (p / l_m) * inv_e
            yq = requantize_to_grid(y, act_block, mant_bits)
            yq = jnp.where(live, yq, 0.0)
            o = (acc_sc[...] * alpha) / l_m * inv_e + jax.lax.dot_general(
                yq, v, (((1,), (0,)), ((), ())), precision=_HIGHEST,
                preferred_element_type=jnp.float32)
            write(o)
    else:
        l_sc[...] = l_sc[...] * alpha + psum
        acc_sc[...] = acc_sc[...] * alpha + jax.lax.dot_general(
            p, v, (((1,), (0,)), ((), ())), precision=_HIGHEST,
            preferred_element_type=jnp.float32)
        m_sc[...] = m_new

        @pl.when(kb == n_k - 1)
        def _flush():
            l = l_sc[...]
            # Eq. 20: division in (mantissa, exponent) form
            l_m, l_e = jnp.frexp(jnp.maximum(l, 1e-30))
            o = acc_sc[...] / l_m * jnp.exp2(-l_e.astype(jnp.float32))
            write(o)


def _bias_tile(rel, k0, block_k: int, key_index):
    """(bq, n) bias rows -> (bq, block_k) over keys k0..k0+block_k: key
    ``c`` takes column ``key_index(c)`` of ``rel``.  An exact one-hot
    contraction (one nonzero product per output)."""
    n = rel.shape[1]
    c = k0 + jax.lax.broadcasted_iota(jnp.int32, (n, block_k), 1)
    col = jax.lax.broadcasted_iota(jnp.int32, (n, block_k), 0)
    onehot = (key_index(c) == col).astype(jnp.float32)
    return jax.lax.dot_general(rel, onehot, (((1,), (0,)), ((), ())),
                               precision=_HIGHEST,
                               preferred_element_type=jnp.float32)


def _flash_kernel(*refs, lut: tuple, scale: float, causal: bool,
                  window: int, kv_len: int | None, exp_mode: str,
                  r_bits: int, quantize_scores: bool, act_block: int,
                  mant_bits: int, block_q: int, block_k: int, n_k: int,
                  bias_w: int):
    if bias_w:
        (q_ref, k_ref, v_ref, bh_ref, bw_ref, o_ref, m_sc, l_sc,
         acc_sc) = refs
    else:
        q_ref, k_ref, v_ref, o_ref, m_sc, l_sc, acc_sc = refs
    kb = pl.program_id(2)

    @pl.when(kb == 0)
    def _init():
        m_sc[...] = jnp.full_like(m_sc, NEG_INF)
        l_sc[...] = jnp.zeros_like(l_sc)
        acc_sc[...] = jnp.zeros_like(acc_sc)

    q = q_ref[0].astype(jnp.float32)                       # (bq, d)
    k = k_ref[0].astype(jnp.float32)                       # (bk, d)
    v = v_ref[0].astype(jnp.float32)                       # (bk, d)

    s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                            precision=_HIGHEST,
                            preferred_element_type=jnp.float32) * scale
    if bias_w:
        # (s + bias_h) + bias_w, the order the whole-row path adds them
        k0 = kb * block_k
        s = s + _bias_tile(bh_ref[0].astype(jnp.float32), k0, block_k,
                           lambda c: c // bias_w)
        s = s + _bias_tile(bw_ref[0].astype(jnp.float32), k0, block_k,
                           lambda c: c % bias_w)

    q_pos = pl.program_id(1) * block_q + jax.lax.broadcasted_iota(
        jnp.int32, s.shape, 0)
    k_pos = kb * block_k + jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
    mask = jnp.ones_like(s, dtype=jnp.bool_)
    if causal:
        mask &= q_pos >= k_pos
    if window > 0:
        mask &= (q_pos - k_pos) < window
    pad_mask = (k_pos < kv_len) if kv_len is not None else None

    def write(o):
        o_ref[0] = o.astype(o_ref.dtype)

    _softmax_block_update(s, mask, pad_mask, v, write, m_sc, l_sc, acc_sc,
                          lut, exp_mode=exp_mode, r_bits=r_bits,
                          quantize_scores=quantize_scores,
                          act_block=act_block, mant_bits=mant_bits,
                          kb=kb, n_k=n_k)


@functools.partial(jax.jit, static_argnames=(
    "causal", "window", "exp_mode", "r_bits", "quantize_scores", "act_block",
    "mant_bits", "block_q", "block_k", "scale", "kv_len", "kv_groups",
    "vmem_limit_bytes", "interpret"))
def flash_attention(q: jnp.ndarray, k: jnp.ndarray, v: jnp.ndarray,
                    bias: tuple | None = None, *,
                    causal: bool = True, window: int = 0,
                    exp_mode: str = "float", r_bits: int = 2,
                    quantize_scores: bool = False, act_block: int = 16,
                    mant_bits: int = 8,
                    block_q: int = 128, block_k: int = 128,
                    scale: float | None = None, kv_len: int | None = None,
                    kv_groups: int = 1, vmem_limit_bytes: int | None = None,
                    interpret: bool = True) -> jnp.ndarray:
    """q: (BH, Sq, D); k, v: (BH // kv_groups, Sk, D).  Returns (BH, Sq, D).

    ``kv_len``: number of REAL keys when the caller padded Sk to a tile
    multiple — lanes >= kv_len are numerically invisible (see module doc).
    ``quantize_scores`` runs the full Eq. 14-20 datapath and requires
    ``exp_mode='mxint'``.  ``kv_groups``: GQA — query head b attends KV
    head b // kv_groups via the BlockSpec index map (q heads must be laid
    out KV-major), so grouped K/V are NEVER broadcast-copied.
    ``bias``: optional (bias_h (BH, Sq, kh), bias_w (BH, Sq, kw)), the
    decomposed relative-position term over a (kh, kw) key grid (see
    module doc).  ``vmem_limit_bytes``: Mosaic's scoped VMEM limit, for
    tiles past its default.
    """
    bh, sq, d = q.shape
    bhkv, sk, _ = k.shape
    assert bh == bhkv * kv_groups, (bh, bhkv, kv_groups)
    block_q = min(block_q, sq)
    block_k = min(block_k, sk)
    assert sq % block_q == 0 and sk % block_k == 0
    if quantize_scores:
        assert exp_mode == "mxint", "quantize_scores is the MXInt datapath"
        act_block = _resolve_block(block_k, act_block)
    if scale is None:
        scale = 1.0 / (d ** 0.5)
    n_k = sk // block_k

    in_specs = [
        pl.BlockSpec((1, block_q, d), lambda b, i, j: (b, i, 0)),
        pl.BlockSpec((1, block_k, d), lambda b, i, j: (b // kv_groups, j, 0)),
        pl.BlockSpec((1, block_k, d), lambda b, i, j: (b // kv_groups, j, 0)),
    ]
    operands = [q, k, v]
    bias_w = 0
    if bias is not None:
        bias_h, bias_w_rows = bias
        assert bias_h.shape[:2] == bias_w_rows.shape[:2] == (bh, sq)
        bias_w = bias_w_rows.shape[2]
        for rel in bias:
            in_specs.append(pl.BlockSpec((1, block_q, rel.shape[2]),
                                         lambda b, i, j: (b, i, 0)))
            operands.append(rel)

    kernel = functools.partial(
        _flash_kernel, lut=luts.pow2_table(r_bits), scale=scale,
        causal=causal, window=window,
        kv_len=kv_len if (kv_len is not None and kv_len < sk) else None,
        exp_mode=exp_mode, r_bits=r_bits, quantize_scores=quantize_scores,
        act_block=act_block, mant_bits=mant_bits, block_q=block_q,
        block_k=block_k, n_k=n_k, bias_w=bias_w)

    return pl.pallas_call(
        kernel,
        grid=(bh, sq // block_q, n_k),
        in_specs=in_specs,
        out_specs=pl.BlockSpec((1, block_q, d), lambda b, i, j: (b, i, 0)),
        out_shape=jax.ShapeDtypeStruct(q.shape, q.dtype),
        scratch_shapes=[
            pltpu.VMEM((block_q, 1), jnp.float32),
            pltpu.VMEM((block_q, 1), jnp.float32),
            pltpu.VMEM((block_q, d), jnp.float32),
        ],
        # (batch*head, q-block) tiles are independent; the key axis
        # carries the online-softmax (m, l, acc) scratch sequentially
        # (DESIGN.md §14).
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary"),
            vmem_limit_bytes=vmem_limit_bytes),
        name="mxint_flash_attention" if quantize_scores else None,
        interpret=interpret,
    )(*operands)


# ---------------------------------------------------------------------------
# single-query decode variant (DESIGN.md §11)
# ---------------------------------------------------------------------------
def _decode_kernel(q_ref, k_ref, v_ref, valid_ref, o_ref, m_sc, l_sc,
                   acc_sc, *, lut: tuple, scale: float, w_len: int | None,
                   exp_mode: str, r_bits: int, quantize_scores: bool,
                   act_block: int, mant_bits: int, block_k: int, n_k: int):
    kb = pl.program_id(2)

    @pl.when(kb == 0)
    def _init():
        m_sc[...] = jnp.full_like(m_sc, NEG_INF)
        l_sc[...] = jnp.zeros_like(l_sc)
        acc_sc[...] = jnp.zeros_like(acc_sc)

    q = q_ref[0, 0].astype(jnp.float32)                    # (g, d)
    k = k_ref[0, :, 0].astype(jnp.float32)                 # (bk, d)
    v = v_ref[0, :, 0].astype(jnp.float32)                 # (bk, d)

    s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                            preferred_element_type=jnp.float32) * scale

    mask = jnp.broadcast_to((valid_ref[0] > 0)[None, :], s.shape)
    if w_len is not None:
        k_pos = kb * block_k + jax.lax.broadcasted_iota(
            jnp.int32, s.shape, 1)
        pad_mask = k_pos < w_len
    else:
        pad_mask = None

    def write(o):
        o_ref[0, 0] = o.astype(o_ref.dtype)

    _softmax_block_update(s, mask, pad_mask, v, write, m_sc, l_sc, acc_sc,
                          lut, exp_mode=exp_mode, r_bits=r_bits,
                          quantize_scores=quantize_scores,
                          act_block=act_block, mant_bits=mant_bits,
                          kb=kb, n_k=n_k)


@functools.partial(jax.jit, static_argnames=(
    "exp_mode", "r_bits", "quantize_scores", "act_block", "mant_bits",
    "block_k", "scale", "w_len", "interpret"))
def flash_attention_decode(q: jnp.ndarray, k: jnp.ndarray, v: jnp.ndarray,
                           valid: jnp.ndarray, *, exp_mode: str = "float",
                           r_bits: int = 2, quantize_scores: bool = False,
                           act_block: int = 16, mant_bits: int = 8,
                           block_k: int = 128, scale: float | None = None,
                           w_len: int | None = None,
                           interpret: bool = True) -> jnp.ndarray:
    """Single-position decode attention over a KV cache ring.

    q: (B, Hkv, G, D) — the G query heads sharing each KV head folded
    into sublane rows, all at ONE sequence position; k, v:
    (B, W, Hkv, D) cache rings in the model's NATIVE layout — the kernel
    grid indexes the W and Hkv axes directly via BlockSpecs, so the
    caller never transposes/copies the cache per decode step; valid:
    (B, W) bool/int — nonzero where row b's slot holds a live key (the
    caller's PER-ROW ring/window slot arithmetic; a shared (W,) vector
    broadcasts over the batch).  Returns (B, Hkv, G, D).

    Invalid-but-real slots follow the model's NEG_INF masking (quantized
    with the row, sim parity); slots >= ``w_len`` are wrapper padding and
    numerically invisible.  One q block of G rows per (batch, KV head);
    K/V stream through the grid in ``block_k`` slices with online
    softmax scratch.
    """
    b, hkv, g, d = q.shape
    W = k.shape[1]
    if valid.ndim == 1:
        valid = jnp.broadcast_to(valid[None, :], (b, W))
    block_k = min(block_k, W)
    assert W % block_k == 0
    if quantize_scores:
        assert exp_mode == "mxint", "quantize_scores is the MXInt datapath"
        act_block = _resolve_block(block_k, act_block)
    if scale is None:
        scale = 1.0 / (d ** 0.5)
    n_k = W // block_k

    kernel = functools.partial(
        _decode_kernel, lut=luts.pow2_table(r_bits), scale=scale,
        w_len=w_len if (w_len is not None and w_len < W) else None,
        exp_mode=exp_mode, r_bits=r_bits, quantize_scores=quantize_scores,
        act_block=act_block, mant_bits=mant_bits, block_k=block_k, n_k=n_k)

    return pl.pallas_call(
        kernel,
        grid=(b, hkv, n_k),
        in_specs=[
            pl.BlockSpec((1, 1, g, d), lambda i, h, j: (i, h, 0, 0)),
            pl.BlockSpec((1, block_k, 1, d), lambda i, h, j: (i, j, h, 0)),
            pl.BlockSpec((1, block_k, 1, d), lambda i, h, j: (i, j, h, 0)),
            pl.BlockSpec((1, block_k), lambda i, h, j: (i, j)),
        ],
        out_specs=pl.BlockSpec((1, 1, g, d), lambda i, h, j: (i, h, 0, 0)),
        out_shape=jax.ShapeDtypeStruct(q.shape, q.dtype),
        scratch_shapes=[
            pltpu.VMEM((g, 1), jnp.float32),
            pltpu.VMEM((g, 1), jnp.float32),
            pltpu.VMEM((g, d), jnp.float32),
        ],
        # (batch, kv-head) tiles are independent; the cache-window axis
        # carries the online-softmax scratch sequentially (DESIGN.md §14).
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=interpret,
    )(q, k, v, valid.astype(jnp.int32))
