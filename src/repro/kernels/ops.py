"""Public jit'd wrappers around the Pallas kernels.

These handle shape plumbing (leading-dim flattening, row padding to the
sublane multiple, output columns to the lane multiple), backend selection
(Pallas compiled on TPU, interpret=True on CPU — the same wrapper code
either way) and expose the kernels under the names the model zoo
consumes.  Every linear and norm shape runs in its kernel; the only XLA
fallback left is attention's pathological head dim, counted in
``FALLBACKS``.

This module is the execution layer behind the ``pallas_kernel`` datapath
backend (``QuantConfig(mode='kernel')`` — DESIGN.md §12):
``repro.datapath.pallas_kernel`` calls these wrappers, and each wrapper
feeds the packed int8 mantissa/exponent planes (weights) or the raw
activations straight into the corresponding Pallas kernel.  Block sizes
are resolved exactly like ``repro.core.quantize`` resolves them (clamp
to the dim, largest divisor), so the kernel datapath is numerically
identical to the ``mode='sim'`` oracle.
"""
from __future__ import annotations

import functools
import warnings

import jax
import jax.numpy as jnp

from repro.core.quantize import _resolve_block
from repro.kernels import ref
from repro.kernels.flash_attention import (flash_attention,
                                           flash_attention_decode)
from repro.kernels.mxint_gelu import mxint_gelu as _gelu_kernel
from repro.kernels.mxint_layernorm import mxint_layernorm as _ln_kernel
from repro.kernels.mxint_matmul import mxint_matmul as _mm_kernel
from repro.kernels.mxint_matmul import operand_path
from repro.kernels.mxint_softmax import mxint_softmax as _sm_kernel

# ---------------------------------------------------------------------------
# flash-attention fallback accounting.  The shape gate is STATIC (python
# control flow over shapes at trace time), so a fallback is counted once per
# jit specialization that takes it — exactly the granularity at which the
# Pallas kernel is or is not in the compiled program.  tests assert DeiT
# shapes never land here (ISSUE 3 acceptance).
#
# The counts live in the ``repro.telemetry`` default registry under
# ``kernels/attention_fallback/<reason>`` (DESIGN.md §15), so a metrics
# snapshot carries them alongside the serving counters.  ``FALLBACKS``
# stays importable as a read view with the Counter semantics the tests
# use (zero counts are absent, ``clear()`` resets).
# ---------------------------------------------------------------------------
_FALLBACK_PREFIX = "kernels/attention_fallback/"


class _FallbackView:
    """dict/Counter-shaped read view over the telemetry fallback
    counters; the historical ``ops.FALLBACKS`` surface."""

    def _counts(self) -> dict:
        from repro import telemetry as T
        return T.default_registry().counters_with_prefix(_FALLBACK_PREFIX)

    def __getitem__(self, reason: str) -> int:
        return self._counts().get(reason, 0)

    def __contains__(self, reason: str) -> bool:
        return reason in self._counts()

    def __iter__(self):
        return iter(self._counts())

    def __len__(self) -> int:
        return len(self._counts())

    def __eq__(self, other) -> bool:
        return self._counts() == dict(other)

    def __repr__(self) -> str:
        return f"FALLBACKS({self._counts()!r})"

    def keys(self):
        return self._counts().keys()

    def items(self):
        return self._counts().items()

    def values(self):
        return self._counts().values()

    def clear(self) -> None:
        from repro import telemetry as T
        T.reset(_FALLBACK_PREFIX)


FALLBACKS = _FallbackView()

# interpret-mode pathology guard: a (block_q, d) + 2*(block_k, d) f32 tile
# set beyond this head dim blows past any useful VMEM budget and the
# interpreter's memory; everything smaller is padded and runs in-kernel.
_FLASH_MAX_HEAD_DIM = 2048


def attention_fallback_counts() -> dict:
    """Copy of the per-reason fallback counts (trace-time granularity)."""
    return FALLBACKS._counts()


def reset_attention_fallbacks() -> None:
    FALLBACKS.clear()


def _count_fallback(reason: str, detail: str) -> None:
    from repro import telemetry as T
    T.counter(_FALLBACK_PREFIX + reason).inc()
    warnings.warn(
        f"attention_op fell back to the XLA reference ({reason}: {detail}); "
        "the Pallas flash kernel is NOT in this program (the MXInt "
        "quantization datapath, if requested, still runs via the whole-row "
        "oracle)", stacklevel=3)


def _ceil_to(n: int, m: int) -> int:
    return -(-n // m) * m


def _pad_dim(x: jnp.ndarray, axis: int, target: int) -> jnp.ndarray:
    pad = target - x.shape[axis]
    if pad <= 0:
        return x
    spec = [(0, 0)] * x.ndim
    spec[axis] = (0, pad)
    return jnp.pad(x, spec)


def on_tpu() -> bool:
    return jax.default_backend() == "tpu"


def _interpret() -> bool:
    return not on_tpu()


def _flatten_rows(x):
    lead = x.shape[:-1]
    return x.reshape(-1, x.shape[-1]), lead


def _pad_rows(x, multiple):
    rows = x.shape[0]
    pad = (-rows) % multiple
    if pad:
        x = jnp.pad(x, ((0, pad), (0, 0)))
    return x, rows


def _pick_block_rows(rows: int, cap: int = 256) -> int:
    for b in (cap, 128, 64, 32, 16, 8, 4, 2, 1):
        if b <= cap and rows % b == 0:
            return b
    return 1


# Row tile of the MXInt linear kernels.  Every (row tile, N tile) grid
# step re-fetches its K x 128 weight tile, widens it and scales it by
# 2^e, so the tile is as tall as VMEM allows: 256 rows, or 128 (one full
# pass of v5e's 128 x 128 MXU) where a 256-row f32 activation block
# would pass _ROW_TILE_ACT_BYTES.  The quantized activation of a row
# block is made once, at the first N tile, into a (bm, K) VMEM scratch
# (bf16 for act mantissas of at most 8 bits against int8 weight
# mantissas, exact there; f32 otherwise), which
# every N tile contracts.  VMEM budget: the smallest vmem_limit_bytes
# under which Mosaic compiles each kernel for a described v5e (to 1/16
# MiB), 256 rows: DeiT-Base fc2 (K = 3072) 10.06 MiB, of it the bf16
# operand scratch 1.5 MiB (8.56 MiB with the quantizer rerun per N tile
# and no scratch); SAM's neck (K = 2304) 7.56 MiB (6.44); the fused LN
# kernel at K = 768 3.94 MiB (4.56 with an f32 LN scratch).  A 512-row
# tile at K = 3072 passes Mosaic's 16 MiB default.
# tests/test_v5e_compile.py compiles every DeiT and SAM linear at the
# benchmark's batches.
_ROW_TILE = 256
_ROW_TILE_ACT_BYTES = 256 * 3072 * 4
_LINEAR_PREFIX = "kernels/linear_"


def linear_row_tile(rows: int, k: int) -> int:
    """Row tile for a (rows, k) activation of a linear kernel.

    The fewest row blocks of at most the cap, balanced and rounded up to
    the 8-row sublane; the last block may be ragged.  A call of at most
    the cap's rows (decode, small batches) is one block of ``rows``
    rounded up to 8.
    """
    cap = _ROW_TILE if _ROW_TILE * k * 4 <= _ROW_TILE_ACT_BYTES else 128
    blocks = -(-rows // cap)
    return _ceil_to(-(-rows // blocks), 8)


def _counted_row_tile(rows: int, k: int, operand: str) -> int:
    """``linear_row_tile`` of a call, counted at trace time in
    ``repro.telemetry``: calls by row tile and by MXU operand path
    (``mxint_matmul.operand_path``), rows, and rows the grid computes
    past them (the pad to 8 and the ragged last block)."""
    from repro import telemetry as T
    bm = linear_row_tile(rows, k)
    T.counter(f"{_LINEAR_PREFIX}operand/{operand}").inc()
    T.counter(f"{_LINEAR_PREFIX}row_tile/{bm}").inc()
    T.counter(_LINEAR_PREFIX + "rows").inc(rows)
    T.counter(_LINEAR_PREFIX + "rows_padded").inc(
        _ceil_to(rows, bm) - rows)
    return bm


def linear_tiling() -> dict:
    """The linear kernels' tiling since the last reset: calls by row
    tile, calls by MXU operand path ('bf16', 'f32', 'raw'), rows, padded
    rows and their share of the rows."""
    from repro import telemetry as T
    counts = T.default_registry().counters_with_prefix(_LINEAR_PREFIX)
    rows = counts.get("rows", 0)
    padded = counts.get("rows_padded", 0)
    return {"row_tile_calls": {int(k.split("/")[1]): v
                               for k, v in sorted(counts.items())
                               if k.startswith("row_tile/")},
            "operand_calls": {k.split("/")[1]: v
                              for k, v in sorted(counts.items())
                              if k.startswith("operand/")},
            "rows": rows, "rows_padded": padded,
            "padded_share": padded / rows if rows else 0.0}


def reset_linear_tiling() -> None:
    from repro import telemetry as T
    T.reset(_LINEAR_PREFIX)


def _pad_planes(w_mant: jnp.ndarray, w_exp: jnp.ndarray):
    """Pad packed (K, N) / (K/B, N) planes to a lane multiple of N."""
    n_p = _ceil_to(w_mant.shape[1], 128)
    return _pad_dim(w_mant, 1, n_p), _pad_dim(w_exp, 1, n_p)


# ---------------------------------------------------------------------------
def mxint_linear(x: jnp.ndarray, w_mant: jnp.ndarray, w_exp: jnp.ndarray,
                 bias: jnp.ndarray | None = None, *, w_block: int,
                 quantize_act: bool = False, act_block: int = 16,
                 act_mant_bits: int = 8, tp_axis: str | None = None,
                 tp_mode: str | None = None) -> jnp.ndarray:
    """y = x @ W_mx (+ bias) for arbitrary leading dims of x.

    Args:
      x: activations, float, shape (..., K).
      w_mant: packed int8 mantissa plane, shape (K, N) — or the local
        shard (K, N/S) / (K/S, N) when called inside a ``shard_map``
        with ``tp_axis`` set (DESIGN.md §10).
      w_exp: packed int8 shared-exponent plane, shape (K/w_block, N)
        (sharded exactly like ``w_mant``: the block axis is the
        contraction axis, so the exponent plane inherits the mantissa
        plane's PartitionSpec).
      bias: optional float (N,) bias, added AFTER any tensor-parallel
        collective so sharded and single-device execution add it to
        identical full-width tiles.
      w_block: weight block size the planes were packed with (static).
      quantize_act / act_block / act_mant_bits: in-kernel MXInt
        quantization of the activation tile (the full integer datapath of
        paper Fig. 2b).
      tp_axis: mesh axis name when running inside a ``shard_map`` whose
        in_specs shard the weight planes; None for single-device.
      tp_mode: 'gather' — planes are sharded along N (column-parallel):
        each shard contracts the FULL K for its column slice and the
        shards are concatenated with a tiled all_gather.  Pure data
        movement, so the result is bit-identical to the single-device
        kernel.  'psum' — planes are sharded along K (row-parallel):
        ``x`` arrives replicated with the full K, is sliced to this
        shard's K rows, and the partial products are summed with a psum.
        The f32 psum re-associates the accumulation, so this mode is
        numerically close but NOT bit-exact (DESIGN.md §10).

    The packed planes go into the Pallas kernel untouched — HBM traffic is
    the quantized bytes (the paper's memory win).  Rows are padded to the
    sublane multiple and output columns to the lane multiple so ANY model
    shape runs through the kernel, compiled or interpreted alike; rows
    are tiled by ``linear_row_tile``, with a ragged last block; the K
    contraction is a single tile, which keeps the accumulation order
    identical to the XLA einsum of the 'sim' oracle (bit-exact parity on
    the CPU).
    """
    x2, lead = _flatten_rows(x)
    if tp_axis is not None and tp_mode == "psum":
        # row-parallel: slice the replicated activations to this shard's
        # K rows (the weight planes arrive pre-sharded along K)
        k_local = w_mant.shape[0]
        x2 = jax.lax.dynamic_slice_in_dim(
            x2, jax.lax.axis_index(tp_axis) * k_local, k_local, axis=1)
    K = x2.shape[1]
    N = w_mant.shape[1]
    x2p, rows = _pad_rows(x2, 8)
    wm, we = _pad_planes(w_mant, w_exp)
    y = _mm_kernel(x2p, wm, we, w_block=w_block,
                   act_block=_resolve_block(K, act_block),
                   act_mant_bits=act_mant_bits, quantize_act=quantize_act,
                   bm=_counted_row_tile(rows, K, operand_path(
                       quantize_act, act_mant_bits, w_mant.dtype)),
                   bn=128, interpret=_interpret())[:rows, :N]
    if tp_axis is not None:
        if tp_mode == "gather":
            y = jax.lax.all_gather(y, tp_axis, axis=1, tiled=True)
        elif tp_mode == "psum":
            y = jax.lax.psum(y, tp_axis)
        else:
            raise ValueError(f"unknown tp_mode {tp_mode!r}")
        N = y.shape[1]
    if bias is not None:
        y = y + bias
    return y.reshape(*lead, N).astype(x.dtype)


def mxint_layernorm_op(x: jnp.ndarray, gamma: jnp.ndarray,
                       beta: jnp.ndarray | None = None, *,
                       act_block: int = 16, mant_bits: int = 8,
                       lut_bits: int = 5, rms_only: bool = False,
                       quantize_out: bool = False):
    """In-kernel MXInt LayerNorm/RMSNorm (paper Fig. 3 datapath).

    x: float (..., d) activations, normalized over the last axis.
    gamma/beta: float (d,) scale/shift (beta=None with ``rms_only``).
    act_block/mant_bits: input block-quantization format; lut_bits: width
    of the rsqrt LUT.  ``quantize_out`` appends the output MXInt
    quantize stage (the epilogue the kernel datapath feeds the next
    quantized linear with — DESIGN.md §5).  Returns float, shape of x.
    """
    x2, lead = _flatten_rows(x)
    beta_arr = beta if beta is not None else jnp.zeros_like(gamma)
    x2p, rows = _pad_rows(x2, 8)
    y = _ln_kernel(x2p, gamma, beta_arr,
                   act_block=_resolve_block(x.shape[-1], act_block),
                   mant_bits=mant_bits, lut_bits=lut_bits, rms_only=rms_only,
                   quantize_out=quantize_out,
                   block_rows=_pick_block_rows(x2p.shape[0]),
                   interpret=_interpret())
    return y[:rows].reshape(*lead, x.shape[-1])


def mxint_ln_linear_op(x: jnp.ndarray, gamma: jnp.ndarray,
                       beta: jnp.ndarray | None,
                       w_mant: jnp.ndarray, w_exp: jnp.ndarray,
                       bias: jnp.ndarray | None = None, *, w_block: int,
                       act_block: int = 16, mant_bits: int = 8,
                       lut_bits: int = 5, rms_only: bool = False,
                       tp_axis: str | None = None,
                       tp_mode: str | None = None) -> jnp.ndarray:
    """Fused MXInt LayerNorm/RMSNorm -> linear (DESIGN.md §12).

    y = MXIntLN(x) @ W_mx (+ bias) for arbitrary leading dims of x — the
    composite behind ``Datapath.layernorm_linear``: the normalized,
    act-quantized tile stays in VMEM and feeds the packed-plane
    contraction directly, removing the full HBM round-trip of the
    normalized activations that the two-kernel sequence pays.  Argument
    semantics match ``mxint_layernorm_op`` (gamma/beta/lut_bits/rms_only)
    plus ``mxint_linear`` (planes/bias/tp_axis/tp_mode); output
    quantization of the LN stage is always on (the kernel-mode epilogue).

    Bit-identical to ``mxint_layernorm_op(...)`` followed by
    ``mxint_linear(...)`` — same stages, same order, same single-tile K
    contraction; the fused VMEM scratch holds the model dtype so even the
    unfused path's dtype round-trip is reproduced.  Only the 'gather'
    tensor-parallel mode composes (the collective moves output columns —
    pure data movement after the fused kernel); 'psum' shards the
    contraction axis, which the full-row LN never sees, so callers run
    the two-op sequence instead (``repro.datapath.pallas_kernel``).
    """
    from repro.kernels.mxint_ln_matmul import mxint_ln_matmul

    if tp_mode not in (None, "gather") or \
            (tp_axis is not None and tp_mode is None):
        # mirror mxint_linear: a sharded call with anything but 'gather'
        # fails loudly
        raise ValueError(f"fused ln_linear shards only with "
                         f"tp_mode='gather', got tp_axis={tp_axis!r} "
                         f"tp_mode={tp_mode!r}")
    x2, lead = _flatten_rows(x)
    K = x2.shape[1]
    N = w_mant.shape[1]
    x2p, rows = _pad_rows(x2, 8)
    wm, we = _pad_planes(w_mant, w_exp)
    y = mxint_ln_matmul(x2p, gamma, beta, wm, we, w_block=w_block,
                        act_block=_resolve_block(K, act_block),
                        mant_bits=mant_bits, lut_bits=lut_bits,
                        rms_only=rms_only,
                        bm=_counted_row_tile(rows, K, operand_path(
                            True, mant_bits, w_mant.dtype)),
                        bn=128, interpret=_interpret())[:rows, :N]
    if tp_axis is not None and tp_mode == "gather":
        y = jax.lax.all_gather(y, tp_axis, axis=1, tiled=True)
        N = y.shape[1]
    if bias is not None:
        y = y + bias
    return y.reshape(*lead, N).astype(x.dtype)


def mxint_softmax_op(x: jnp.ndarray, *, act_block: int = 16,
                     mant_bits: int = 8, r_bits: int = 2,
                     quantize_out: bool = False) -> jnp.ndarray:
    """Whole-row MXInt softmax over the last axis (paper Eq. 14-20).

    x: float (..., S) score rows; r_bits: the exp-datapath residual LUT
    width; ``quantize_out`` quantizes the probabilities (Eq. 20) exactly
    as the FPGA streams them to the p @ V matmul.  Returns float, same
    shape (DESIGN.md §5).
    """
    x2, lead = _flatten_rows(x)
    x2p, rows = _pad_rows(x2, 8)
    y = _sm_kernel(x2p, act_block=_resolve_block(x.shape[-1], act_block),
                   mant_bits=mant_bits, r_bits=r_bits,
                   quantize_out=quantize_out,
                   block_rows=_pick_block_rows(x2p.shape[0]),
                   interpret=_interpret())
    return y[:rows].reshape(x.shape)


def mxint_gelu_op(x: jnp.ndarray, *, fn: str = "gelu", act_block: int = 16,
                  mant_bits: int = 8, lut_bits: int = 5,
                  domain: float = 3.0) -> jnp.ndarray:
    """Elementwise MXInt GELU/SiLU through the LUT datapath (paper Eq. 12).

    x: float (..., d); fn: 'gelu' | 'silu'; lut_bits/domain parameterize
    the folded LUT.  Output is MXInt-quantized by construction (the LUT
    emits mantissas).  Returns float, same shape as x.
    """
    x2, lead = _flatten_rows(x)
    x2p, rows = _pad_rows(x2, 8)
    y = _gelu_kernel(x2p, act_block=_resolve_block(x.shape[-1], act_block),
                     mant_bits=mant_bits,
                     lut_bits=lut_bits, domain=domain, fn=fn,
                     block_rows=_pick_block_rows(x2p.shape[0]),
                     interpret=_interpret())
    return y[:rows].reshape(x.shape)


# Attention tiling.  The quantized flash datapath keeps a whole score row
# in one k block up to _FLASH_ROW_KEYS keys: one block degenerates to the
# whole-row datapath exactly (DESIGN.md §11), where several blocks match
# it only within LUT granularity.  At 4,096 keys with the relative-
# position bias, compiles for a described v5e put the kernel's scoped
# VMEM within Mosaic's 16 MiB default at block_q 128, 16.8 MiB at 256 and
# 25.7 MiB at 512; whole rows take up to 256 query rows under a 32 MiB
# limit (v5e has 128 MiB); tests/test_v5e_compile.py compiles that case.
_FLASH_ROW_KEYS = 4096
_FLASH_ROW_BLOCK_Q = 256
_FLASH_ROW_VMEM = 32 * 1024 * 1024
_ATTN_PREFIX = "kernels/attention/"


def _count_attention(path: str, block_q: int, block_k: int, k_blocks: int,
                     head_dim: int, keys_padded: int) -> None:
    """Count one attention call by its tiling in ``repro.telemetry``, at
    trace time."""
    from repro import telemetry as T
    T.counter(f"{_ATTN_PREFIX}{path}/{block_q}/{block_k}/{k_blocks}/"
              f"{head_dim}/{keys_padded}").inc()


def count_whole_row_attention(rows: int, keys: int, head_dim: int) -> None:
    """Count a whole-row attention call: the softmax kernel's row block
    over ``rows`` score rows of ``keys`` keys, contractions in XLA at the
    unpadded head dim."""
    _count_attention("whole_row", _pick_block_rows(_ceil_to(rows, 8)),
                     keys, 1, head_dim, 0)


def attention_tiling() -> list:
    """The attention calls traced since the last reset, one dict per
    tiling: ``path`` ('whole_row': score einsums around the softmax
    kernel; 'flash': the blocked kernel), ``block_q``, ``block_k``,
    ``k_blocks`` per row, ``head_dim`` as padded, ``keys_padded``, and
    ``calls``."""
    from repro import telemetry as T
    out = []
    for key, n in sorted(T.default_registry().counters_with_prefix(
            _ATTN_PREFIX).items()):
        path, *nums = key.split("/")
        out.append(dict(zip(("block_q", "block_k", "k_blocks", "head_dim",
                             "keys_padded"), map(int, nums)),
                        path=path, calls=n))
    return out


def reset_attention_tiling() -> None:
    from repro import telemetry as T
    T.reset(_ATTN_PREFIX)


def flash_tiling(sq: int, sk: int, quantize_scores: bool) -> tuple:
    """(block_q, block_k, vmem_limit_bytes) of the flash kernel over
    ``sq`` query rows and ``sk`` keys, both already padded (8, 128)."""
    if quantize_scores and sk <= _FLASH_ROW_KEYS:
        return _pick_block_rows(sq, _FLASH_ROW_BLOCK_Q), sk, _FLASH_ROW_VMEM
    return _pick_block_rows(sq, 128), min(128, sk), None


def attention_op(q: jnp.ndarray, k: jnp.ndarray, v: jnp.ndarray,
                 bias: tuple | None = None, *,
                 causal: bool = True, window: int = 0,
                 exp_mode: str = "float", r_bits: int = 2,
                 quantize_scores: bool = False,
                 act_block: int = 16, mant_bits: int = 8) -> jnp.ndarray:
    """(B, H, S, D) attention through the blocked flash kernel.

    ``exp_mode='mxint'`` runs the Eq. 14-19 exp LUT inside the flash
    kernel, and ``quantize_scores=True`` adds the Eq. 2-3 score and Eq. 20
    probability quantization stages (the full paper datapath, blocked —
    DESIGN.md §11).  The whole-row ViT path does not come here: it runs
    the model's own contractions around the softmax kernel
    (``repro.datapath.pallas_kernel``).

    Padding contract: ANY shape reaches the flash kernel —
    query rows are padded to the sublane multiple (8), keys and head lanes
    to the lane multiple (128), and the pads are sliced off the result.
    Padded KEYS are masked inside the kernel via the static ``kv_len``
    cutoff and are numerically INVISIBLE (excluded from the quantizer's
    shared exponents, the row max, the Eq. 19 sum and the accumulator),
    unlike model-masked keys which are filled with the unified ``NEG_INF``
    sentinel BEFORE quantization (sim parity).  Padded query rows compute
    garbage that is sliced away.  The XLA reference fallback remains ONLY
    for interpret-mode pathologies (head dim beyond
    ``_FLASH_MAX_HEAD_DIM``) and is counted + warned via ``FALLBACKS`` —
    it is never taken silently.

    GQA: k/v may carry fewer heads than q (q heads must be a multiple,
    laid out KV-major: q[:, i] attends k[:, i // groups]).  K/V are not
    copied per query head: the flash kernel maps query head b to KV head
    b // groups in its BlockSpec index map (``kv_groups``); only the
    pathological-head-dim oracle fallback broadcasts.

    ``bias``: optional (bias_h (B, H, S, kh), bias_w (B, H, S, kw)) with
    kh * kw keys, the decomposed relative-position term added to the
    scaled scores in-kernel (``flash_attention``).  Tiling:
    ``flash_tiling``, counted per call (``attention_tiling``).
    """
    b, h, sq, d = q.shape
    hkv = k.shape[1]
    sk = k.shape[2]
    groups = h // hkv
    scale = d ** -0.5
    qf = q.reshape(b * h, sq, d)
    kf = k.reshape(b * hkv, sk, d)
    vf = v.reshape(b * hkv, sk, d)
    d_p = _ceil_to(d, 128)
    if bias is not None:
        bias = tuple(x.reshape(b * h, sq, x.shape[-1]).astype(jnp.float32)
                     for x in bias)
    if d_p > _FLASH_MAX_HEAD_DIM:
        if bias is not None:
            raise ValueError("a score bias needs the flash kernel; head dim "
                             f"{d} pads past {_FLASH_MAX_HEAD_DIM}")
        _count_fallback("head_dim", f"d={d} pads to {d_p}")
        if groups > 1:                     # oracles want matched heads
            kf = jnp.broadcast_to(k[:, :, None], (b, hkv, groups, sk, d)
                                  ).reshape(b * h, sk, d)
            vf = jnp.broadcast_to(v[:, :, None], (b, hkv, groups, sk, d)
                                  ).reshape(b * h, sk, d)
        if quantize_scores:
            # the fallback must keep the Eq. 2-3 / Eq. 20 datapath, not
            # just the exp LUT — use the whole-row quantized oracle
            o = ref.mxint_flash_attention_ref(
                qf, kf, vf, causal=causal, window=window,
                act_block=act_block, mant_bits=mant_bits, r_bits=r_bits,
                scale=scale)
        else:
            o = ref.attention_ref(qf, kf, vf, causal=causal, window=window,
                                  exp_mode=exp_mode, r_bits=r_bits,
                                  scale=scale)
    else:
        sq_p = _ceil_to(sq, 8)
        sk_p = _ceil_to(sk, 128)
        qp = _pad_dim(_pad_dim(qf, 1, sq_p), 2, d_p)
        kp = _pad_dim(_pad_dim(kf, 1, sk_p), 2, d_p)
        vp = _pad_dim(_pad_dim(vf, 1, sk_p), 2, d_p)
        if bias is not None:
            bias = tuple(_pad_dim(x, 1, sq_p) for x in bias)
        bq, bk, vmem = flash_tiling(sq_p, sk_p, quantize_scores)
        _count_attention("flash", bq, bk, sk_p // bk, d_p, sk_p - sk)
        o = flash_attention(qp, kp, vp, bias, causal=causal, window=window,
                            exp_mode=exp_mode, r_bits=r_bits,
                            quantize_scores=quantize_scores,
                            act_block=act_block, mant_bits=mant_bits,
                            block_q=bq, block_k=bk, scale=scale,
                            kv_len=sk if sk != sk_p else None,
                            kv_groups=groups, vmem_limit_bytes=vmem,
                            interpret=_interpret())[:, :sq, :d]
    return o.reshape(b, h, sq, d)


def attention_decode_op(q: jnp.ndarray, k: jnp.ndarray, v: jnp.ndarray,
                        valid: jnp.ndarray, *, exp_mode: str = "float",
                        r_bits: int = 2, quantize_scores: bool = False,
                        act_block: int = 16,
                        mant_bits: int = 8) -> jnp.ndarray:
    """Single-position decode attention over a KV cache ring (DESIGN.md §11).

    q: (B, Hkv, G, D) — the G query heads sharing each KV head folded
    into rows, all at the current decode position; k, v: (B, W, Hkv, D)
    cache rings in the model's NATIVE layout (the kernel grid indexes W
    and Hkv directly — no per-step transpose/copy of the cache); valid:
    (B, W) bool/int — nonzero where row b's slot holds a live key (the
    caller's PER-ROW ring/window slot arithmetic; a shared (W,) vector
    broadcasts over the batch).  Returns (B, Hkv, G, D).

    Padding contract: G is padded to the sublane multiple (8), W and D to
    the lane multiple (128).  Padded SLOTS are masked via the static
    ``w_len`` cutoff and numerically invisible; invalid-but-real slots
    follow the model's NEG_INF masking through the quantizer (sim
    parity).  Fallback to the jnp oracle only for pathological head dims,
    counted + warned exactly like ``attention_op`` (and it keeps the
    Eq. 2-3 / Eq. 20 datapath via the whole-row oracle).
    """
    b, hkv, g, d = q.shape
    W = k.shape[1]
    if valid.ndim == 1:
        valid = jnp.broadcast_to(valid[None, :], (b, W))
    d_p = _ceil_to(d, 128)
    if d_p > _FLASH_MAX_HEAD_DIM:
        _count_fallback("head_dim", f"decode d={d} pads to {d_p}")
        qf = q.reshape(b * hkv, g, d)
        kf = jnp.einsum("bwhd->bhwd", k).reshape(b * hkv, W, d)
        vf = jnp.einsum("bwhd->bhwd", v).reshape(b * hkv, W, d)
        # per-row validity follows the (b, hkv) fold: row b's mask
        # repeats across its hkv head rows
        validf = jnp.repeat(valid, hkv, axis=0)
        if quantize_scores:
            o = ref.mxint_flash_attention_ref(
                qf, kf, vf, causal=False, key_mask=validf.astype(jnp.int32),
                act_block=act_block, mant_bits=mant_bits, r_bits=r_bits,
                scale=d ** -0.5)
        else:
            o = ref.decode_attention_ref(qf, kf, vf, validf,
                                         exp_mode=exp_mode, r_bits=r_bits)
        return o.reshape(b, hkv, g, d)
    g_p = _ceil_to(g, 8)
    W_p = _ceil_to(W, 128)
    qp = _pad_dim(_pad_dim(q, 2, g_p), 3, d_p)
    kp = _pad_dim(_pad_dim(k, 1, W_p), 3, d_p)
    vp = _pad_dim(_pad_dim(v, 1, W_p), 3, d_p)
    validp = _pad_dim(valid.astype(jnp.int32), 1, W_p)
    o = flash_attention_decode(qp, kp, vp, validp, exp_mode=exp_mode,
                               r_bits=r_bits,
                               quantize_scores=quantize_scores,
                               act_block=act_block, mant_bits=mant_bits,
                               block_k=min(128, W_p), scale=d ** -0.5,
                               w_len=W if W != W_p else None,
                               interpret=_interpret())
    return o[:, :, :g, :d]
