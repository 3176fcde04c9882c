"""Attention for every arch: GQA/MQA, RoPE, qk-norm, sliding window, caches.

This module owns the attention ORCHESTRATION — projections, RoPE, cache
ring arithmetic, mask semantics — while the execution of the attention
math itself dispatches through the pluggable backend resolved from the
config (``quant.datapath`` — DESIGN.md §12):

  * xla_float / mxint_sim — masked softmax on the full score matrix
               (direct; the paper's whole-row ViT path, also the MXInt
               'sim' datapath) or the lax.scan online-softmax over query
               blocks for score matrices that would not fit (32k prefill,
               4k training).  The direct/chunked helpers below are shared
               by both backends.
  * pallas_kernel — repro.kernels.ops.attention_op: the whole-row Pallas
               MXInt softmax ('paper' variant, bit-identical to the sim
               direct path) when quantize_nonlinear is set and the score
               matrix is small, the blocked mxint flash kernel (Eq. 14-20
               without the O(S^2) scores, DESIGN.md §11) for long
               sequences, the float flash kernel otherwise.  Decode
               (s == 1 with a cache) goes through
               ops.attention_decode_op — scoring, softmax and p @ V fused
               in one Pallas kernel over the cache ring.

``prenorm``: blocks may hand their pre-attention norm parameters to
``attention`` instead of normalizing first; the q/k/v projections then
ride the backend's fused ``layernorm_linear`` composite when it exists
(kernel mode: normalized tile stays in VMEM) and fall back to the
norm-then-linear sequence otherwise — bit-identical either way.

KV caches:
  full ring: (b, kv_heads, S_max, hd) with dynamic_update_slice writes.
  sliding window: ring buffer of size W; slot i at step t holds absolute
  position t - ((t - i) mod W) — no position side-array needed.
"""
from __future__ import annotations

import functools
from typing import Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.core.mx_types import NEG_INF as _NEG_INF
from repro.core.mx_types import QuantConfig
from repro.models import layers as L
from repro.models.model_api import (ModelConfig, Param, dense_init,
                                    ones_init, zeros_init)


# ---------------------------------------------------------------------------
# params
# ---------------------------------------------------------------------------
def init_attn_params(key, cfg: ModelConfig, dtype, cross: bool = False,
                     bias: bool = False):
    """q/k/v and output projections; ``bias`` adds their biases (bq, bk,
    bv, bo), which ``models/sam.py`` applies (``attention`` does not)."""
    hd = cfg.hd
    ks = jax.random.split(key, 4)
    p = {
        "wq": dense_init(ks[0], (cfg.d_model, cfg.n_heads * hd),
                         ("embed", "q_heads"), dtype=dtype),
        "wk": dense_init(ks[1], (cfg.d_model, cfg.n_kv_heads * hd),
                         ("embed", "kv_heads"), dtype=dtype),
        "wv": dense_init(ks[2], (cfg.d_model, cfg.n_kv_heads * hd),
                         ("embed", "kv_heads"), dtype=dtype),
        "wo": dense_init(ks[3], (cfg.n_heads * hd, cfg.d_model),
                         ("q_heads", "embed"), dtype=dtype),
    }
    if bias:
        p["bq"] = zeros_init((cfg.n_heads * hd,), ("q_heads",), dtype=dtype)
        p["bk"] = zeros_init((cfg.n_kv_heads * hd,), ("kv_heads",),
                             dtype=dtype)
        p["bv"] = zeros_init((cfg.n_kv_heads * hd,), ("kv_heads",),
                             dtype=dtype)
        p["bo"] = zeros_init((cfg.d_model,), ("embed",), dtype=dtype)
    if cfg.qk_norm and not cross:
        p["q_norm"] = ones_init((hd,), (None,), dtype=dtype)
        p["k_norm"] = ones_init((hd,), (None,), dtype=dtype)
    return p


# ---------------------------------------------------------------------------
# score/softmax cores
# ---------------------------------------------------------------------------
def _split_heads(x, n, hd):
    return x.reshape(*x.shape[:-1], n, hd)


def _gqa_scores(q, k, scale):
    """q: (b, s, kv, g, hd); k: (b, S, kv, hd) -> (b, kv, g, s, S)."""
    return jnp.einsum("bskgd,bSkd->bkgsS", q, k) * scale


def positions_mask(positions, s: int, kv_len: int, causal: bool,
                   window: int) -> jnp.ndarray:
    """(1|b, s, kv_len) bool mask from per-row positions.

    per-ROW masks: positions may be (b, s) with ragged per-batch offsets
    (left-padded prompts) — collapsing to the last batch row's positions
    masked every other row wrongly (ISSUE 3).  Self-attention keys are
    the same tokens, so they carry the same position VALUES: comparing q
    values against key INDICES would let offset rows attend their own
    future (position relabeling must be a no-op when rope is off).
    """
    pos2 = positions if positions.ndim == 2 else positions.reshape(1, -1)
    q_pos = pos2[:, -s:]                             # (1|b, s)
    if kv_len == s:
        k_pos = q_pos[:, None, :]                    # self-attn: values
    else:
        k_pos = jnp.arange(kv_len)[None, None, :]    # cross: indices
    mask = jnp.ones((q_pos.shape[0], s, kv_len), dtype=bool)
    if causal:
        mask &= q_pos[:, :, None] >= k_pos
    if window > 0:
        mask &= (q_pos[:, :, None] - k_pos) < window
    return mask


def add_score_bias(s, bias):
    """Scores (..., S) plus a decomposed bias over a (kh, kw) key grid:
    ``s[..., c] + bias_h[..., c // kw] + bias_w[..., c % kw]``, added in
    that order (the Segment Anything relative-position term)."""
    bias_h, bias_w = bias
    kh, kw = bias_h.shape[-1], bias_w.shape[-1]
    s = s.reshape(*s.shape[:-1], kh, kw) + bias_h[..., :, None]
    s = s + bias_w[..., None, :]
    return s.reshape(*s.shape[:-2], kh * kw)


def _direct_attention(q, k, v, mask, quant: QuantConfig, scale, bias=None):
    s = _gqa_scores(q, k, scale).astype(jnp.float32)
    if bias is not None:
        s = add_score_bias(s, bias)
    s = jnp.where(mask, s, _NEG_INF)
    p = L.softmax(s, quant, axis=-1).astype(q.dtype)
    p = jnp.where(mask, p, 0.0)
    return jnp.einsum("bkgsS,bSkd->bskgd", p, v)


def _q_chunked_attention(q, k, v, *, q_offset, causal, window, chunk, scale,
                         positions=None):
    """Attention chunked over QUERY blocks (lax.scan, no carry).

    For long prefill the kv-chunked online-softmax form drags a
    (b, heads, s, hd) f32 accumulator through every scan iteration — at 32k
    that carry alone is GBs of HBM round-trips per chunk (§Perf iteration
    log, llama3 prefill).  Query blocks are independent: each block does one
    full-width softmax, there is no carry, and the score tensor crosses
    fusion boundaries in bf16 (the f32 accumulation lives inside the dot).
    On real TPU the Pallas flash kernel keeps scores in VMEM entirely; this
    is the XLA-path equivalent structure.

    positions: optional (1|b, >=s) per-row positions with the exact mask
    semantics of ``positions_mask`` — ragged/left-padded batches mask each
    row from its own position VALUES (self-attn keys carry the same values;
    cross keys stay contiguous indices).  ``None`` keeps the contiguous
    ``q_offset + row-index`` arithmetic.
    """
    b, s, kv, g, hd = q.shape
    S = k.shape[1]
    chunk = min(chunk, s)
    assert s % chunk == 0, (s, chunk)
    nq = s // chunk
    # fold the softmax scale into q (one fused pass instead of a full-score
    # rescale) and pre-transpose K/V ONCE to the dot layouts — leaving them
    # (b, S, kv, hd) made XLA re-copy them inside every q-block iteration
    # (§Perf: llama3 prefill, copy_bitcast_fusion ~1TB).
    qs = (q * scale).astype(q.dtype)
    qc = jnp.swapaxes(qs.reshape(b, nq, chunk, kv, g, hd), 0, 1)
    kt = jnp.einsum("bSkd->bkdS", k)
    vt = jnp.einsum("bSkd->bkSd", v)
    k_pos = jnp.arange(S, dtype=jnp.int32)[None, :]            # (1, S)
    if positions is None:
        pc = (q_offset + jnp.arange(s, dtype=jnp.int32)).reshape(nq, 1, chunk)
    else:
        pos2 = positions if positions.ndim == 2 else positions.reshape(1, -1)
        q_pos = pos2[:, -s:].astype(jnp.int32)                 # (1|b, s)
        if S == s:
            k_pos = q_pos                                      # self: values
        rows = q_pos.shape[0]
        pc = jnp.swapaxes(q_pos.reshape(rows, nq, chunk), 0, 1)
    neg = jnp.asarray(jnp.finfo(q.dtype).min, q.dtype)

    def block(_, inp):
        qb, qp = inp                                           # qp: (1|b, c)
        # f32 accumulation inside the dot; scores cross the fusion boundary
        # in the model dtype (halves every downstream score pass)
        s_blk = jnp.einsum("bckgd,bkdS->bkgcS", qb, kt,
                           preferred_element_type=jnp.float32
                           ).astype(q.dtype)
        mask = jnp.ones((qp.shape[0], chunk, S), dtype=bool)
        if causal:
            mask &= qp[:, :, None] >= k_pos[:, None, :]
        if window > 0:
            mask &= (qp[:, :, None] - k_pos[:, None, :]) < window
        s_blk = jnp.where(mask[:, None, None], s_blk, neg)
        m = jnp.max(s_blk, axis=-1, keepdims=True)
        # exp(neg - m) == 0 and every query row sees at least itself (its
        # own position value), so no second masking pass is needed
        p = jnp.exp((s_blk - m).astype(jnp.float32))
        l = jnp.sum(p, axis=-1, keepdims=True)
        pb = (p / jnp.maximum(l, 1e-30)).astype(q.dtype)
        o = jnp.einsum("bkgcS,bkSd->bckgd", pb, vt,
                       preferred_element_type=jnp.float32)
        return None, o.astype(q.dtype)

    _, outs = jax.lax.scan(block, None, (qc, pc))
    return jnp.swapaxes(outs, 0, 1).reshape(b, s, kv, g, hd)


def _chunked_attention(q, k, v, *, q_offset, causal, window, chunk, scale):
    """Online-softmax over KV chunks via lax.scan; O(s*chunk) live memory.

    RETAINED FOR COMPARISON ONLY: superseded by _q_chunked_attention after
    the §Perf llama3-prefill iteration showed the (m, l, acc) scan carry
    costs GBs of HBM round-trips per chunk (EXPERIMENTS.md §4, cell C).
    Still the right shape when queries are few and keys huge AND a carry is
    acceptable (e.g. speculative scoring); kept tested via kernels/ref.
    """
    b, s, kv, g, hd = q.shape
    S = k.shape[1]
    chunk = min(chunk, S)
    assert S % chunk == 0, (S, chunk)
    n_chunks = S // chunk
    kc = jnp.swapaxes(k.reshape(b, n_chunks, chunk, kv, hd), 0, 1)
    vc = jnp.swapaxes(v.reshape(b, n_chunks, chunk, kv, hd), 0, 1)

    q_pos = q_offset + jnp.arange(s)
    qf = q.astype(jnp.float32)

    def step(carry, inputs):
        m, l, acc = carry
        ci, kb, vb = inputs
        s_blk = _gqa_scores(qf, kb.astype(jnp.float32), scale)  # (b,kv,g,s,c)
        k_pos = ci * chunk + jnp.arange(chunk)
        mask = jnp.ones((s, chunk), dtype=bool)
        if causal:
            mask &= q_pos[:, None] >= k_pos[None, :]
        if window > 0:
            mask &= (q_pos[:, None] - k_pos[None, :]) < window
        s_blk = jnp.where(mask[None, None, None], s_blk, _NEG_INF)
        m_new = jnp.maximum(m, jnp.max(s_blk, -1))
        alpha = jnp.exp(m - m_new)
        p = jnp.exp(s_blk - m_new[..., None])
        p = jnp.where(mask[None, None, None], p, 0.0)
        l_new = l * alpha + jnp.sum(p, -1)
        acc_new = acc * alpha[..., None] + jnp.einsum(
            "bkgsc,bckd->bkgsd", p, vb.astype(jnp.float32))
        return (m_new, l_new, acc_new), None

    m0 = jnp.full((b, kv, g, s), _NEG_INF, jnp.float32)
    l0 = jnp.zeros((b, kv, g, s), jnp.float32)
    a0 = jnp.zeros((b, kv, g, s, hd), jnp.float32)
    (m, l, acc), _ = jax.lax.scan(
        step, (m0, l0, a0),
        (jnp.arange(n_chunks), kc, vc))
    out = acc / jnp.maximum(l, 1e-30)[..., None]
    return jnp.einsum("bkgsd->bskgd", out).astype(q.dtype)


# ---------------------------------------------------------------------------
# cache helpers
# ---------------------------------------------------------------------------
def init_kv_cache(cfg: ModelConfig, batch: int, max_len: int, window: int,
                  dtype) -> Dict[str, jnp.ndarray]:
    W = min(max_len, window) if window > 0 else max_len
    shape = (batch, W, cfg.n_kv_heads, cfg.hd)
    return {"k": jnp.zeros(shape, dtype), "v": jnp.zeros(shape, dtype)}


def kv_cache_specs(cfg: ModelConfig, batch: int, max_len: int, window: int,
                   dtype):
    W = min(max_len, window) if window > 0 else max_len
    shape = (batch, W, cfg.n_kv_heads, cfg.hd)
    sds = jax.ShapeDtypeStruct(shape, dtype)
    return {"k": sds, "v": sds}


CACHE_AXES = ("batch", "kv_seq", "kv_heads", None)
CACHE_AXES_TREE = {"k": CACHE_AXES, "v": CACHE_AXES}


# ---------------------------------------------------------------------------
# main entry
# ---------------------------------------------------------------------------
def attention(p, x: jnp.ndarray, cfg: ModelConfig, *,
              quant: QuantConfig,
              positions: Optional[jnp.ndarray] = None,
              cache: Optional[Dict[str, jnp.ndarray]] = None,
              cache_index: Optional[jnp.ndarray] = None,
              window: int = 0,
              causal: bool = True,
              kv_override: Optional[Tuple[jnp.ndarray, jnp.ndarray]] = None,
              use_rope: bool = True,
              chunk: int = 1024,
              prenorm: Optional[Tuple] = None,
              scope: Optional[str] = None):
    """Returns (output (b, s, d), updated cache or None).

    Modes:
      cache=None                      -> training / encoder (no state)
      cache given, s > 1              -> prefill (writes 0..s)
      cache given, s == 1             -> decode at cache_index
      kv_override                     -> cross attention (encoder K/V)

    prenorm: optional ('ln'|'rms', gamma, beta) — the block's
    pre-attention norm.  When given, x arrives UN-normalized and the
    q/k/v projections run through the ``layernorm_linear`` composite
    (fused on backends that provide it; norm-then-linear otherwise —
    bit-identical, DESIGN.md §12).  beta is None for 'rms'.

    scope: optional layer-group tag ("block/3/attn"); per-layer
    overrides on the config resolve ONCE here (``quant.scoped``), and
    the whole attention op — projections, scores, softmax — runs the
    scoped config (DESIGN.md §16).
    """
    quant = quant.scoped(scope)
    b, s, _ = x.shape
    hd = cfg.hd
    kvh = cfg.n_kv_heads
    g = cfg.n_heads // kvh
    scale = hd ** -0.5

    _proj_ws = [p["wq"]] if kv_override is not None else \
        [p["wq"], p["wk"], p["wv"]]
    if prenorm is not None and not all(
            quant.datapath.fuses_norm_linear(quant, x, w)
            for w in _proj_ws):
        # no fusion for EVERY projection this call feeds (config,
        # sharding, or compiled-TPU tiling — GQA gives wk/wv a different
        # N than wq): normalize ONCE up front — the classic block; a
        # partial answer would replay the norm inside the declining
        # projections' fallbacks
        nk, ng, nb = prenorm
        x = (L.rmsnorm(x, ng, q=quant, eps=cfg.norm_eps) if nk == "rms"
             else L.layernorm(x, ng, nb, q=quant, eps=cfg.norm_eps))
        prenorm = None

    def in_proj(w):
        if prenorm is None:
            return L.linear(x, w, q=quant)
        nk, ng, nb = prenorm
        return L.layernorm_linear(x, ng, nb, w, q=quant, eps=cfg.norm_eps,
                                  rms_only=(nk == "rms"))

    q = _split_heads(in_proj(p["wq"]), cfg.n_heads, hd)
    if kv_override is None:
        k = _split_heads(in_proj(p["wk"]), kvh, hd)
        v = _split_heads(in_proj(p["wv"]), kvh, hd)
    else:
        k, v = kv_override

    if cfg.qk_norm and "q_norm" in p:
        q = L.rmsnorm(q, p["q_norm"], q=quant, eps=cfg.norm_eps)
        if kv_override is None:
            k = L.rmsnorm(k, p["k_norm"], q=quant, eps=cfg.norm_eps)

    if positions is None:
        if cache_index is not None:
            base = jnp.asarray(cache_index, jnp.int32)
            if base.ndim == 1:                           # per-row (b,) index
                base = base[:, None]
        else:
            base = 0
        positions = base + jnp.arange(s)[None, :]        # (1|b, s)
    if use_rope:
        q = L.rope(q, positions, cfg.rope_theta)
        if kv_override is None:
            k = L.rope(k, positions, cfg.rope_theta)

    q = q.reshape(b, s, kvh, g, hd)
    new_cache = None

    if cache is not None and kv_override is None:
        W = cache["k"].shape[1]
        if s == 1:
            # decode at PER-ROW indices: cache_index may be () (legacy
            # scalar, e.g. the encoder-decoder stack) or (b,) — scalars
            # broadcast so every consumer below sees one (b,) contract.
            # Row i writes its own slot and masks its own ring validity,
            # which is what lets a freshly prefilled slot coexist with
            # rows deep into decode (slot-level batching, DESIGN.md §7).
            idx = jnp.asarray(cache_index, jnp.int32)
            if idx.ndim == 0:
                idx = jnp.broadcast_to(idx, (b,))
            slot = (idx % W) if window > 0 else idx          # (b,)
            rows = jnp.arange(b)
            ck = cache["k"].at[rows, slot].set(
                k[:, 0].astype(cache["k"].dtype))
            cv = cache["v"].at[rows, slot].set(
                v[:, 0].astype(cache["v"].dtype))
            new_cache = {"k": ck, "v": cv}
            # absolute position of every slot, per row
            t = idx[:, None]                                 # (b, 1)
            pos = jnp.arange(W)[None, :]                     # (1, W)
            if window > 0:
                slot_pos = t - jnp.mod(t - pos, W)
            else:
                slot_pos = jnp.broadcast_to(pos, (b, W))
            valid = (slot_pos >= 0) & (slot_pos <= t)        # (b, W)
            if window > 0:
                valid &= (t - slot_pos) < window
            # backend decode: pallas_kernel runs one fused Pallas kernel
            # over the ring (scoring + online softmax + p @ V, no XLA
            # L.softmax in the trace — DESIGN.md §11); the XLA backends
            # score the ring directly through their own softmax
            o = quant.datapath.attention_decode(q, ck, cv, valid, q=quant,
                                                scale=scale)
        elif window > 0 and s >= W:
            # SWA prefill longer than the ring: only the last W positions
            # survive; they land on slots (pos % W) — a permutation scatter.
            pos = jnp.arange(s - W, s)
            slots = jnp.mod(pos, W)
            ck = cache["k"].at[:, slots].set(
                k[:, -W:].astype(cache["k"].dtype))
            cv = cache["v"].at[:, slots].set(
                v[:, -W:].astype(cache["v"].dtype))
            new_cache = {"k": ck, "v": cv}
            o = _q_chunked_attention(q, k, v, q_offset=0, causal=causal,
                                     window=window, chunk=chunk, scale=scale,
                                     positions=positions)
        else:
            # prefill fits the cache: write slots [0, s)
            ck = jax.lax.dynamic_update_slice(
                cache["k"], k.astype(cache["k"].dtype), (0, 0, 0, 0))
            cv = jax.lax.dynamic_update_slice(
                cache["v"], v.astype(cache["v"].dtype), (0, 0, 0, 0))
            new_cache = {"k": ck, "v": cv}
            o = _q_chunked_attention(q, k, v, q_offset=0, causal=causal,
                                     window=window, chunk=chunk, scale=scale,
                                     positions=positions)
    else:
        # cache-less execution: the backend picks its path — direct masked
        # softmax / query-chunked online softmax (XLA backends, with the
        # ragged-positions mask semantics of ``positions_mask``) or the
        # Pallas attention kernels (pallas_kernel)
        o = quant.datapath.attention(q, k, v, q=quant, positions=positions,
                                     causal=causal, window=window,
                                     scale=scale, chunk=chunk)

    o = o.reshape(b, s, cfg.n_heads * hd)
    out = L.linear(o, p["wo"], q=quant)
    return out, new_cache
