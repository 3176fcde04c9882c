"""``pallas_kernel`` backend: the 'kernel' execution mode.

Every op executes on the Pallas accelerator datapath
(``repro.kernels.ops``): linears feed packed int8 mantissa/exponent
planes straight into ``mxint_linear`` (no host-side dequantize — HBM
traffic is the quantized bytes), and when ``quantize_nonlinear`` is set
the non-linear ops run the in-kernel MXInt datapaths.  Numerically
identical to the ``mxint_sim`` oracle (same LUTs, same integer stages).
Inference-only: the Pallas calls carry no VJP.

Provides the ``layernorm_linear`` composite hook: LayerNorm/RMSNorm
fused into the consuming quantized matmul through
``ops.mxint_ln_linear_op``, which keeps the normalized, act-quantized
tile in VMEM and feeds it straight into the packed-plane contraction —
one full HBM round-trip of the normalized activations removed per block,
bit-identical to the unfused two-kernel sequence by construction
(DESIGN.md §12).
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.datapath.base import Datapath


class PallasKernelDatapath(Datapath):
    name = "pallas_kernel"
    quantized_nonlinear = True
    qdq_linears = False

    # -- linears -------------------------------------------------------------
    @staticmethod
    def _packed(wv, q):
        from repro.core.quantize import MXTensor, pack_weight
        if isinstance(wv, MXTensor):
            return wv
        return pack_weight(jnp.asarray(wv, jnp.float32), q.weight_fmt,
                           axis=0)

    def linear(self, x, w, b=None, *, q):
        return self._linear_planes(x, self._packed(w.value, q), b, q)

    @staticmethod
    def _linear_planes(x, wv, b, q):
        from repro.kernels import ops
        # tp_axis/tp_mode are static MXTensor metadata stamped by
        # tp_shard_packed_params: inside a shard_map the kernel runs on the
        # local planes and mxint_linear inserts the matching collective
        # (all_gather / psum) before the bias add (DESIGN.md §10).
        return ops.mxint_linear(
            x, wv.mantissa, wv.exponent,
            None if b is None else b.value.astype(jnp.float32),
            w_block=wv.block_size, quantize_act=True,
            act_block=q.act_fmt.block_size,
            act_mant_bits=q.act_fmt.mant_bits,
            tp_axis=wv.tp_axis, tp_mode=wv.tp_mode)

    # -- norms ---------------------------------------------------------------
    def rmsnorm(self, x, gamma, *, q, eps: float = 1e-6):
        if not self.nl_on(q, "layernorm"):
            return self._float_rmsnorm(x, gamma, eps)
        from repro.kernels import ops
        y = ops.mxint_layernorm_op(
            x.astype(jnp.float32), gamma.value, None,
            act_block=q.act_fmt.block_size, mant_bits=q.act_fmt.mant_bits,
            lut_bits=q.nonlinear.ln_lut_bits, rms_only=True,
            quantize_out=True)
        return y.astype(x.dtype)

    def layernorm(self, x, gamma, beta, *, q, eps: float = 1e-6):
        if not self.nl_on(q, "layernorm"):
            return self._float_layernorm(x, gamma, beta, eps)
        from repro.kernels import ops
        y = ops.mxint_layernorm_op(
            x.astype(jnp.float32), gamma.value, beta.value,
            act_block=q.act_fmt.block_size, mant_bits=q.act_fmt.mant_bits,
            lut_bits=q.nonlinear.ln_lut_bits, quantize_out=True)
        return y.astype(x.dtype)

    # -- fused LN -> linear composite (DESIGN.md §12) ------------------------
    def fuses_norm_linear(self, q, x=None, w=None) -> bool:
        """Fusion needs the MXInt LN datapath (float LN has no kernel) and
        un-psum-sharded planes (the contraction shard never sees the full
        row the LN normalizes); the fused kernel pads any shape in.
        Callers hoist the norm whenever this says False, so the composite
        never degrades into replaying the unfused pair per consumer."""
        if not self.nl_on(q, "layernorm"):
            return False
        if w is None:
            return True
        from repro.core.quantize import MXTensor
        wv = w.value
        return not (isinstance(wv, MXTensor) and wv.tp_mode == "psum")

    def _norm_then_linear(self, x, gamma, beta, wv, b, *, q, eps,
                          rms_only):
        """The unfused pair on pre-packed planes — the sequence the fused
        kernel is bit-identical to (single shared fallback)."""
        h = (self.rmsnorm(x, gamma, q=q, eps=eps) if rms_only
             else self.layernorm(x, gamma, beta, q=q, eps=eps))
        return self._linear_planes(h, wv, b, q)

    def layernorm_linear(self, x, gamma, beta, w, b=None, *, q,
                         eps: float = 1e-6, rms_only: bool = False):
        """Fused norm + quantized matmul; bit-identical to the unfused
        kernel sequence.  Falls back to the two-op path when the norm is
        not on the MXInt datapath or the weight planes are row/psum
        sharded (the fused kernel normalizes the FULL row, which a
        contraction-sharded plane never sees)."""
        wv = self._packed(w.value, q)
        if not self.nl_on(q, "layernorm") or wv.tp_mode == "psum":
            return self._norm_then_linear(x, gamma, beta, wv, b, q=q,
                                          eps=eps, rms_only=rms_only)
        from repro.kernels import ops
        return ops.mxint_ln_linear_op(
            x, gamma.value, None if beta is None else beta.value,
            wv.mantissa, wv.exponent,
            None if b is None else b.value.astype(jnp.float32),
            w_block=wv.block_size, act_block=q.act_fmt.block_size,
            mant_bits=q.act_fmt.mant_bits,
            lut_bits=q.nonlinear.ln_lut_bits, rms_only=rms_only,
            tp_axis=wv.tp_axis, tp_mode=wv.tp_mode)

    # -- activations / softmax -----------------------------------------------
    def act(self, x, kind: str, *, q):
        if not self.nl_on(q, "gelu"):
            return super().act(x, kind, q=q)
        from repro.kernels import ops
        cfg = q.nonlinear
        y = ops.mxint_gelu_op(
            x.astype(jnp.float32), fn=kind,
            act_block=q.act_fmt.block_size, mant_bits=q.act_fmt.mant_bits,
            lut_bits=cfg.gelu_lut_bits, domain=cfg.gelu_domain)
        return y.astype(x.dtype)

    def softmax(self, x, *, q, axis: int = -1):
        if not self.nl_on(q, "softmax"):
            return super().softmax(x, q=q, axis=axis)
        if axis in (-1, x.ndim - 1):
            from repro.kernels import ops
            y = ops.mxint_softmax_op(
                x.astype(jnp.float32), act_block=q.act_fmt.block_size,
                mant_bits=q.act_fmt.mant_bits,
                r_bits=q.nonlinear.softmax_r_bits, quantize_out=True)
            return y.astype(x.dtype)
        # non-trailing axis: the whole-row kernel does not apply — run the
        # bit-identical sim datapath
        from repro.core import nonlinear as nl
        y = nl.softmax_value(x.astype(jnp.float32), q.nonlinear, q.act_fmt,
                             axis=axis)
        return y.astype(x.dtype)

    # -- attention -----------------------------------------------------------
    def attention(self, qv, k, v, *, q, positions, causal: bool,
                  window: int, scale: float, chunk: int, bias=None):
        b, s, kvh, g, hd = qv.shape
        S = k.shape[1]
        from repro.kernels import ops as kops
        if self.nl_on(q, "softmax") and s * S <= 512 * 512:
            # whole-row paper softmax (the ViT / encoder path): the sim
            # oracle's own masking and contractions around the Eq. 14-20
            # softmax kernel (``self.softmax``), so 'kernel' and 'sim'
            # agree bit for bit.  The contractions take f32 operands off
            # any quantization grid; full precision keeps them f32 on the
            # TPU, whose default would round them to bf16.
            kops.count_whole_row_attention(b * kvh * g * s, S, hd)
            with jax.default_matmul_precision("highest"):
                return super().attention(
                    qv, k, v, q=q, positions=positions, causal=causal,
                    window=window, scale=scale, chunk=chunk, bias=bias)
        # heads-major layout into the flash kernel: the blocked mxint
        # datapath for long sequences (no O(S^2) score matrix —
        # DESIGN.md §11), float flash otherwise; a score bias goes in as
        # its (b, heads, s, kh|kw) rows and is spread in-kernel
        qh = jnp.einsum("bskgd->bkgsd", qv).reshape(b, kvh * g, s, hd)
        kh = jnp.einsum("bSkd->bkSd", k)          # (b, kvh, S, hd), no copy
        vh = jnp.einsum("bSkd->bkSd", v)
        if bias is not None:
            bias = tuple(x.reshape(b, kvh * g, s, x.shape[-1]) for x in bias)
        if self.nl_on(q, "softmax"):
            o = kops.attention_op(
                qh, kh, vh, bias, causal=causal, window=window,
                exp_mode="mxint", quantize_scores=True,
                act_block=q.act_fmt.block_size,
                mant_bits=q.act_fmt.mant_bits,
                r_bits=q.nonlinear.softmax_r_bits)
        else:
            o = kops.attention_op(qh, kh, vh, bias, causal=causal,
                                  window=window, exp_mode="float")
        return jnp.einsum("bkgsd->bskgd", o.reshape(b, kvh, g, s, hd))

    def attention_decode(self, qv, ck, cv, valid, *, q, scale: float):
        # Pallas decode: one fused kernel scores the ring, runs the
        # (optionally Eq. 14-20 quantized) online softmax and the p @ V
        # matmul — no XLA softmax on the decode path (DESIGN.md §11).
        # GQA groups fold into the kernel's sublane rows; ring validity
        # streams in as `valid`; the cache planes go in UNTRANSPOSED (the
        # kernel grid walks the native (b, W, kv, hd) layout).
        from repro.kernels import ops as kops
        qd = qv[:, 0]                              # (b, kv, g, hd)
        kd = ck.astype(qv.dtype)
        vd = cv.astype(qv.dtype)
        if self.nl_on(q, "softmax"):
            od = kops.attention_decode_op(
                qd, kd, vd, valid, exp_mode="mxint",
                r_bits=q.nonlinear.softmax_r_bits,
                quantize_scores=True,
                act_block=q.act_fmt.block_size,
                mant_bits=q.act_fmt.mant_bits)
        else:
            od = kops.attention_decode_op(qd, kd, vd, valid)
        return od[:, None]                         # (b, 1, kv, g, hd)
