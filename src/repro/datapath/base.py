"""The ``Datapath`` backend protocol (DESIGN.md §12).

A *datapath* answers one question for every quantized operator the model
zoo emits: WHERE does this op execute and WITH WHAT numerics.  The paper's
whole design space — which ops run on the accelerator datapath, what fuses
with what — is exactly this choice, so it lives in one pluggable policy
object instead of per-op ``q.mode`` if-chains scattered through
``models/``.

One backend instance exists per ``QuantConfig.mode`` (stateless
singletons; all per-op knobs arrive via the ``q`` kwarg), registered in
``repro.datapath`` and resolved ONCE per config through the
``QuantConfig.datapath`` cached property.  ``models/layers.py`` and
``models/attention.py`` are thin forwarding wrappers over these methods —
the only place allowed to branch on mode strings is this package (plus the
mode validation in ``core/mx_types.py``), enforced by
``tools/check_dispatch.py`` in CI.

Composite hooks: an attribute that is ``None`` on the base class and a
bound method on backends that provide it.  Callers probe
``dp.layernorm_linear`` and fall back to the equivalent op sequence when
absent; a provided composite MUST be bit-identical to that fallback
sequence (the contract that lets blocks call it unconditionally —
DESIGN.md §12).
"""
from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp


class Datapath:
    """Execution backend for the quantized-op protocol.

    Subclasses implement the per-op methods below; the base class carries
    the shared float reference implementations and the attention
    orchestration helpers every XLA backend uses.  Capability flags:

      quantized_nonlinear: this backend CAN run the MXInt non-linear
        datapaths (``nl_on`` consults it — 'off'/'fake' never quantize
        LayerNorm/GELU/Softmax, matching the pre-refactor mode gate).
      qdq_linears: float weights/activations of linears pass through the
        quantize-dequantize grid ('fake'/'sim'; 'packed'/'kernel' consume
        pre-packed planes, 'off' is untouched float).

    Composite hooks (``None`` unless the backend provides them):

      layernorm_linear(x, gamma, beta, w, b, *, q, eps, rms_only) —
        LayerNorm/RMSNorm immediately followed by a quantized linear,
        with the normalized act-quantized tile staying on-chip
        (DESIGN.md §12).  Must be bit-identical to
        ``linear(layernorm(x), w, b)`` under the same config.
    """

    name: str = "base"
    quantized_nonlinear: bool = False
    qdq_linears: bool = False

    # composite hooks — None means "not provided; caller runs the op
    # sequence instead"
    layernorm_linear = None

    def nl_on(self, q, op: str) -> bool:
        """Does ``op`` run the MXInt non-linear datapath under ``q``?"""
        return (q.enabled and q.quantize_nonlinear and
                self.quantized_nonlinear and op in q.nl_ops)

    def fuses_norm_linear(self, q, x=None, w=None) -> bool:
        """Will ``layernorm_linear`` actually FUSE for this call?  When
        False, callers feeding several linears from one norm should
        normalize once and reuse (the composite, if present, would only
        replay the unfused sequence per consumer).  ``x``/``w`` let the
        backend consult shapes and weight sharding, not just the config;
        both optional (config-level answer without them)."""
        return False

    # -- linears ------------------------------------------------------------
    def qdq_weight(self, w: jnp.ndarray, *, q) -> jnp.ndarray:
        """Weight quantize-dequantize onto this backend's weight grid
        (identity unless ``qdq_linears``)."""
        if not self.qdq_linears:
            return w
        if q.emulate == "int":
            from repro.core.quantize import per_tensor_int_qdq
            return per_tensor_int_qdq(w, q.weight_fmt.mant_bits)
        if q.emulate == "fp8":
            from repro.core.quantize import fp8_e4m3_qdq
            return fp8_e4m3_qdq(w)
        from repro.core.quantize import fake_quant
        return fake_quant(w, q.weight_fmt.mant_bits,
                          q.weight_fmt.block_size, 0)

    def qdq_act(self, x: jnp.ndarray, *, q) -> jnp.ndarray:
        """Activation quantize-dequantize onto the act grid (identity
        unless ``qdq_linears``)."""
        if not self.qdq_linears:
            return x
        if q.emulate == "int":
            from repro.core.quantize import per_tensor_int_qdq
            return per_tensor_int_qdq(x, q.act_fmt.mant_bits)
        if q.emulate == "fp8":
            from repro.core.quantize import fp8_e4m3_qdq
            return fp8_e4m3_qdq(x)
        from repro.core.quantize import fake_quant
        return fake_quant(x, q.act_fmt.mant_bits, q.act_fmt.block_size, -1)

    def weight_value(self, wv, *, q, dtype) -> jnp.ndarray:
        """Materialize a weight leaf as float: dequantize packed MXTensor
        planes (fused into the consuming op by XLA) or QDQ float values."""
        import importlib
        # module object, not the `repro.core.quantize` FUNCTION re-export;
        # attribute call so tests can spy on the dequant seam
        qz = importlib.import_module("repro.core.quantize")
        if isinstance(wv, qz.MXTensor):
            return qz.dequantize(wv, dtype=dtype)
        return self.qdq_weight(wv, q=q).astype(dtype)

    def linear(self, x: jnp.ndarray, w, b=None, *, q) -> jnp.ndarray:
        """y = x @ w (+ b).  w/b are Params; w may hold packed planes."""
        wf = self.weight_value(w.value, q=q, dtype=x.dtype)
        xf = self.qdq_act(x, q=q)
        y = jnp.einsum("...k,kn->...n", xf, wf)
        if b is not None:
            y = y + b.value.astype(y.dtype)
        return y

    # -- norms --------------------------------------------------------------
    @staticmethod
    def _float_layernorm(x, gamma, beta, eps):
        xf = x.astype(jnp.float32)
        mu = jnp.mean(xf, -1, keepdims=True)
        var = jnp.mean((xf - mu) ** 2, -1, keepdims=True)
        y = (xf - mu) * jax.lax.rsqrt(var + eps)
        return (y * gamma.value + beta.value).astype(x.dtype)

    @staticmethod
    def _float_rmsnorm(x, gamma, eps):
        xf = x.astype(jnp.float32)
        y = xf * jax.lax.rsqrt(jnp.mean(xf * xf, -1, keepdims=True) + eps)
        return (y * gamma.value).astype(x.dtype)

    def layernorm(self, x, gamma, beta, *, q, eps: float = 1e-6):
        return self._float_layernorm(x, gamma, beta, eps)

    def rmsnorm(self, x, gamma, *, q, eps: float = 1e-6):
        return self._float_rmsnorm(x, gamma, eps)

    # -- activations / softmax / exp ----------------------------------------
    def act(self, x, kind: str, *, q):
        return {"gelu": lambda v: jax.nn.gelu(v, approximate=False),
                "silu": jax.nn.silu}[kind](x)

    def softmax(self, x, *, q, axis: int = -1):
        return jax.nn.softmax(x, axis=axis)

    def exp(self, x, *, q):
        """e^x for scalar gate datapaths (mLSTM input gate)."""
        return jnp.exp(x)

    # -- attention ----------------------------------------------------------
    def _attention_use_direct(self, q, s: int, kv_len: int) -> bool:
        return s * kv_len <= 512 * 512

    def attention(self, qv, k, v, *, q, positions, causal: bool,
                  window: int, scale: float, chunk: int, bias=None):
        """Cache-less attention core.  qv: (b, s, kv, g, hd);
        k/v: (b, S, kv, hd).  Returns (b, s, kv, g, hd).

        ``bias``: optional decomposed score bias (bias_h, bias_w), shapes
        (b, kv, g, s, kh) and (b, kv, g, s, kw) with kh * kw == S, added
        to the scaled scores before masking and softmax
        (``attention.add_score_bias``); only the direct path takes it."""
        from repro.models import attention as A
        s = qv.shape[1]
        kv_len = k.shape[1]
        if self._attention_use_direct(q, s, kv_len):
            mask = A.positions_mask(positions, s, kv_len, causal, window)
            return A._direct_attention(qv, k, v, mask[:, None, None], q,
                                       scale, bias)
        if bias is not None:
            raise ValueError(f"a score bias needs whole score rows; "
                             f"{self.name} chunks {s} x {kv_len}")
        # per-row positions thread into the q-block masks: a left-padded
        # batch long enough to overflow the direct threshold must mask
        # exactly like ``positions_mask`` (ISSUE 6 ragged-chunked fix)
        return A._q_chunked_attention(qv, k, v, q_offset=0, causal=causal,
                                      window=window, chunk=chunk,
                                      scale=scale, positions=positions)

    def attention_decode(self, qv, ck, cv, valid, *, q, scale: float):
        """Single-position decode over a cache ring.  qv: (b, 1, kv, g, hd);
        ck/cv: (b, W, kv, hd); valid: (b, W) per-row ring validity (a (W,)
        vector broadcasts — shared validity).  Returns qv's shape."""
        from repro.models import attention as A
        v2 = valid if valid.ndim == 2 else valid[None]     # (1|b, W)
        mask = v2[:, None, None, None, :]                  # (1|b,1,1,1,W)
        sc = A._gqa_scores(qv, ck.astype(qv.dtype), scale)
        sc = jnp.where(mask, sc.astype(jnp.float32), A._NEG_INF)
        pr = self.softmax(sc, q=q, axis=-1).astype(qv.dtype)
        pr = jnp.where(mask, pr, 0.0)
        return jnp.einsum("bkgsS,bSkd->bskgd", pr, cv.astype(qv.dtype))
