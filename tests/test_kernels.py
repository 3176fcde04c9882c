"""Per-kernel allclose tests: Pallas (interpret=True) vs pure-jnp oracles.

Every kernel is swept over shapes and dtypes and compared against ref.py.
LayerNorm / softmax / GELU kernels must match their oracles bit-for-bit
(identical op graph per row); matmul and flash attention allow accumulation-
order tolerance.
"""
import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import MXFormat, quantize
from repro.kernels import ref
from repro.kernels.block_quant import block_quantize
from repro.kernels.flash_attention import (flash_attention,
                                           flash_attention_decode)
from repro.kernels.mxint_gelu import mxint_gelu as gelu_kernel
from repro.kernels.mxint_layernorm import mxint_layernorm as ln_kernel
from repro.kernels.mxint_matmul import mxint_matmul as mm_kernel
from repro.kernels.mxint_softmax import mxint_softmax as sm_kernel
from repro.kernels import ops


def _rand(shape, seed=0, scale=1.0, dtype=jnp.float32):
    rng = np.random.default_rng(seed)
    return jnp.asarray(rng.normal(size=shape).astype(np.float32) * scale,
                       dtype=dtype)


# ---------------------------------------------------------------------------
# mxint_matmul
# ---------------------------------------------------------------------------
class TestMXIntMatmul:
    @pytest.mark.parametrize("m,k,n", [(8, 128, 128), (16, 256, 384),
                                       (128, 512, 128), (32, 1024, 256)])
    @pytest.mark.parametrize("w_block", [128, 256])
    def test_shape_sweep_weight_only(self, m, k, n, w_block):
        if k % w_block and w_block % k:
            pytest.skip("block/tile mismatch")
        x = _rand((m, k), seed=m + k, scale=0.5)
        w = _rand((k, n), seed=n, scale=0.1)
        wq = quantize(w, MXFormat(8, w_block), axis=0)
        got = mm_kernel(x, wq.mantissa, wq.exponent, w_block=wq.block_size,
                        bm=8, bn=128, interpret=True)
        want = ref.mxint_matmul_ref(x, wq.mantissa, wq.exponent,
                                    w_block=wq.block_size)
        np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                   rtol=1e-5, atol=1e-5)

    @pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
    def test_dtype_sweep(self, dtype):
        x = _rand((16, 256), seed=1, dtype=dtype)
        w = _rand((256, 128), seed=2, scale=0.1)
        wq = quantize(w, MXFormat(6, 256), axis=0)
        got = mm_kernel(x, wq.mantissa, wq.exponent, w_block=256,
                        bm=16, bn=128, interpret=True)
        want = ref.mxint_matmul_ref(x, wq.mantissa, wq.exponent, w_block=256)
        np.testing.assert_allclose(np.asarray(got, np.float32),
                                   np.asarray(want, np.float32),
                                   rtol=2e-2, atol=2e-2)

    def test_quantized_activation_path(self):
        """Fig 2b full-integer datapath: kernel == oracle with act QDQ."""
        x = _rand((32, 512), seed=3, scale=2.0)
        w = _rand((512, 128), seed=4, scale=0.05)
        wq = quantize(w, MXFormat(6, 256), axis=0)
        got = mm_kernel(x, wq.mantissa, wq.exponent, w_block=256,
                        quantize_act=True, act_block=16, act_mant_bits=8,
                        bm=32, bn=128, interpret=True)
        want = ref.mxint_matmul_ref(x, wq.mantissa, wq.exponent, w_block=256,
                                    quantize_act=True, act_block=16,
                                    act_mant_bits=8)
        np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                   rtol=1e-5, atol=1e-5)

    def test_small_wblock_multiple_tiles(self):
        """w_block == K: one exponent row scales the whole contraction
        (the exponent plane's block is a single row, the full dim)."""
        x = _rand((8, 512), seed=5)
        w = _rand((512, 128), seed=6, scale=0.1)
        wq = quantize(w, MXFormat(8, 512), axis=0)
        got = mm_kernel(x, wq.mantissa, wq.exponent, w_block=512,
                        bm=8, bn=128, interpret=True)
        want = ref.mxint_matmul_ref(x, wq.mantissa, wq.exponent, w_block=512)
        np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                   rtol=1e-5, atol=1e-5)


# ---------------------------------------------------------------------------
# mxint_layernorm
# ---------------------------------------------------------------------------
class TestMXIntLayerNorm:
    @pytest.mark.parametrize("rows,d", [(8, 128), (32, 192), (64, 768),
                                        (128, 1024)])
    @pytest.mark.parametrize("rms_only", [False, True])
    def test_bitexact_vs_oracle(self, rows, d, rms_only):
        x = _rand((rows, d), seed=rows + d, scale=3.0)
        g = _rand((d,), seed=1, scale=0.5) + 1.0
        b = _rand((d,), seed=2, scale=0.1)
        got = ln_kernel(x, g, b, rms_only=rms_only,
                        block_rows=min(rows, 32), interpret=True)
        want = ref.mxint_layernorm_ref(x, g, b, rms_only=rms_only)
        # 1-ulp differences allowed: XLA picks different reduction trees for
        # the (block_rows, d) kernel tile vs the full-array oracle.
        np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                   rtol=3e-6, atol=3e-6)

    @pytest.mark.parametrize("lut_bits", [3, 4, 5, 8])
    def test_lut_bits_sweep(self, lut_bits):
        x = _rand((16, 256), seed=9, scale=2.0)
        g, b = jnp.ones((256,)), jnp.zeros((256,))
        got = ln_kernel(x, g, b, lut_bits=lut_bits, block_rows=16,
                        interpret=True)
        want = ref.mxint_layernorm_ref(x, g, b, lut_bits=lut_bits)
        np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                   rtol=3e-6, atol=3e-6)

    def test_vs_float_layernorm(self):
        x = _rand((32, 768), seed=10, scale=2.0)
        g, b = jnp.ones((768,)), jnp.zeros((768,))
        got = np.asarray(ln_kernel(x, g, b, block_rows=32, interpret=True))
        mean = np.asarray(x).mean(-1, keepdims=True)
        ref_ln = (np.asarray(x) - mean) / np.sqrt(
            np.asarray(x).var(-1, keepdims=True) + 1e-6)
        cos = np.vdot(got, ref_ln) / (np.linalg.norm(got) *
                                      np.linalg.norm(ref_ln))
        assert cos > 0.999


# ---------------------------------------------------------------------------
# mxint_softmax
# ---------------------------------------------------------------------------
class TestMXIntSoftmax:
    @pytest.mark.parametrize("rows,n", [(8, 128), (32, 197 - 5), (64, 1024)])
    @pytest.mark.parametrize("r_bits", [2, 4])
    def test_bitexact_vs_oracle(self, rows, n, r_bits):
        n = n - (n % 16) if n % 16 else n   # kernel wants divisible rows
        x = _rand((rows, n), seed=rows + n, scale=4.0)
        got = sm_kernel(x, r_bits=r_bits, block_rows=min(rows, 32),
                        interpret=True)
        want = ref.mxint_softmax_ref(x, r_bits=r_bits)
        np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                   rtol=3e-6, atol=1e-7)

    def test_rows_sum_to_one(self):
        x = _rand((64, 256), seed=12, scale=6.0)
        got = np.asarray(sm_kernel(x, block_rows=64, interpret=True))
        np.testing.assert_allclose(got.sum(-1), 1.0, atol=0.02)


# ---------------------------------------------------------------------------
# mxint_gelu
# ---------------------------------------------------------------------------
class TestMXIntGELU:
    @pytest.mark.parametrize("rows,d", [(8, 128), (32, 768), (128, 3072)])
    @pytest.mark.parametrize("fn", ["gelu", "silu"])
    def test_bitexact_vs_oracle(self, rows, d, fn):
        x = _rand((rows, d), seed=rows + d, scale=3.0)
        got = gelu_kernel(x, fn=fn, block_rows=min(rows, 32), interpret=True)
        want = ref.mxint_gelu_ref(x, fn=fn)
        np.testing.assert_array_equal(np.asarray(got), np.asarray(want))

    @pytest.mark.parametrize("lut_bits,domain", [(4, 3.0), (5, 3.0),
                                                 (5, 4.0), (8, 2.0)])
    def test_dse_sweep(self, lut_bits, domain):
        x = _rand((16, 256), seed=14, scale=2.0)
        got = gelu_kernel(x, lut_bits=lut_bits, domain=domain, block_rows=16,
                          interpret=True)
        want = ref.mxint_gelu_ref(x, lut_bits=lut_bits, domain=domain)
        np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


# ---------------------------------------------------------------------------
# flash attention
# ---------------------------------------------------------------------------
class TestFlashAttention:
    @pytest.mark.parametrize("sq,sk", [(128, 128), (256, 256), (128, 512)])
    def test_float_vs_exact(self, sq, sk):
        q = _rand((2, sq, 128), seed=sq, scale=0.5)
        k = _rand((2, sk, 128), seed=sk + 1, scale=0.5)
        v = _rand((2, sk, 128), seed=sk + 2)
        got = flash_attention(q, k, v, causal=True, interpret=True)
        want = ref.attention_ref(q, k, v, causal=True)
        np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                   rtol=2e-4, atol=2e-4)

    def test_mxint_exp_mode_close_to_oracle(self):
        q = _rand((2, 128, 128), seed=20, scale=0.5)
        k = _rand((2, 128, 128), seed=21, scale=0.5)
        v = _rand((2, 128, 128), seed=22)
        got = flash_attention(q, k, v, causal=True, exp_mode="mxint",
                              r_bits=2, interpret=True)
        want = ref.attention_ref(q, k, v, causal=True, exp_mode="mxint",
                                 r_bits=2)
        # blocked vs row-at-once accumulation differ (exact alpha rescale);
        # values agree to LUT granularity
        np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                   rtol=0.1, atol=0.05)

    def test_sliding_window(self):
        q = _rand((1, 256, 128), seed=30, scale=0.5)
        k = _rand((1, 256, 128), seed=31, scale=0.5)
        v = _rand((1, 256, 128), seed=32)
        got = flash_attention(q, k, v, causal=True, window=64, interpret=True)
        want = ref.attention_ref(q, k, v, causal=True, window=64)
        np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                   rtol=2e-4, atol=2e-4)

    def test_mxint_attention_close_to_float(self):
        """End check: the paper's softmax datapath keeps attention faithful."""
        q = _rand((4, 128, 128), seed=40, scale=0.3)
        k = _rand((4, 128, 128), seed=41, scale=0.3)
        v = _rand((4, 128, 128), seed=42)
        a = flash_attention(q, k, v, causal=True, exp_mode="mxint",
                            interpret=True)
        b = flash_attention(q, k, v, causal=True, exp_mode="float",
                            interpret=True)
        err = float(jnp.max(jnp.abs(a - b))) / float(jnp.max(jnp.abs(b)))
        assert err < 0.15


# ---------------------------------------------------------------------------
# mxint flash attention: the full Eq. 14-20 blocked datapath (ISSUE 3)
# ---------------------------------------------------------------------------
class TestMXIntFlashAttention:
    """flash_attention(exp_mode='mxint', quantize_scores=True) vs the
    whole-row 'paper' oracle (ref.mxint_flash_attention_ref).

    Exactness contract: when ONE k block covers the whole row (block
    boundaries align), the blocked kernel degenerates to the whole-row
    datapath — per-tile Eq. 2-3 requantization IS the row requantization,
    the online max never rescales, and the flush quantizes the fully
    normalized Eq. 20 probabilities before p @ V.  Multi-block rows keep
    a per-TILE shared-exponent alignment and an exact running rescale, so
    they match within LUT/requantization granularity only.
    """

    @pytest.mark.parametrize("causal", [True, False])
    def test_single_kblock_bit_exact_vs_paper_oracle(self, causal):
        q = _rand((2, 128, 64), seed=60, scale=0.5)
        k = _rand((2, 128, 64), seed=61, scale=0.5)
        v = _rand((2, 128, 64), seed=62)
        got = flash_attention(q, k, v, causal=causal, exp_mode="mxint",
                              quantize_scores=True, interpret=True)
        want = ref.mxint_flash_attention_ref(q, k, v, causal=causal)
        np.testing.assert_array_equal(np.asarray(got), np.asarray(want))

    def test_single_kblock_exact_at_256(self):
        """Causal-LM row of 256 keys in one 256-wide block: still exact."""
        q = _rand((2, 256, 64), seed=63, scale=0.5)
        k = _rand((2, 256, 64), seed=64, scale=0.5)
        v = _rand((2, 256, 64), seed=65)
        got = flash_attention(q, k, v, causal=True, exp_mode="mxint",
                              quantize_scores=True, block_k=256,
                              interpret=True)
        want = ref.mxint_flash_attention_ref(q, k, v, causal=True)
        np.testing.assert_array_equal(np.asarray(got), np.asarray(want))

    def test_multiblock_tolerance_vs_paper_oracle(self):
        """Unmasked rows over 4 k blocks: per-tile lambda + online rescale
        differ from whole-row alignment only at LUT granularity."""
        q = _rand((2, 128, 64), seed=66, scale=0.5)
        k = _rand((2, 512, 64), seed=67, scale=0.5)
        v = _rand((2, 512, 64), seed=68)
        got = flash_attention(q, k, v, causal=False, exp_mode="mxint",
                              quantize_scores=True, block_k=128,
                              interpret=True)
        want = ref.mxint_flash_attention_ref(q, k, v, causal=False)
        np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                   rtol=0.15, atol=0.05)

    def test_multiblock_causal_rowwise_semantics(self):
        """Causal multi-block: the documented per-row semantics hold.

        A k tile containing a masked lane is exponent-poisoned by the
        NEG_INF fill exactly like the whole-row datapath poisons the whole
        row, so rows whose REAL keys all sit in poisoned tiles (here: q
        rows < 128, whose single real tile straddles the diagonal) track
        the whole-row oracle — loosely, because interior blocks quantize
        UNnormalized probabilities while the whole-row path quantizes the
        Eq. 20 output.  A row whose tiles are all fully real (the last
        row) sees only benign per-tile score quantization and tracks the
        same-LUT attention WITHOUT score quantization tightly."""
        q = _rand((2, 256, 64), seed=69, scale=0.5)
        k = _rand((2, 256, 64), seed=70, scale=0.5)
        v = _rand((2, 256, 64), seed=71)
        got = np.asarray(flash_attention(q, k, v, causal=True,
                                         exp_mode="mxint",
                                         quantize_scores=True, block_k=128,
                                         interpret=True))
        paper = np.asarray(ref.mxint_flash_attention_ref(q, k, v,
                                                         causal=True))
        np.testing.assert_allclose(got[:, :128], paper[:, :128],
                                   rtol=0.2, atol=0.2)
        base = np.asarray(ref.attention_ref(q, k, v, causal=True,
                                            exp_mode="mxint", r_bits=2))
        np.testing.assert_allclose(got[:, 255], base[:, 255],
                                   rtol=0.05, atol=0.01)

    def test_deit_shape_via_attention_op(self):
        """DeiT-Tiny geometry (197 tokens, head_dim 64) through the padded
        attention_op: padded keys are numerically invisible, so the result
        tracks the UNPADDED whole-row oracle up to the act-block geometry
        difference (the oracle resolves prime 197 to 1-wide blocks)."""
        q = _rand((2, 3, 197, 64), seed=72, scale=0.5)
        k = _rand((2, 3, 197, 64), seed=73, scale=0.5)
        v = _rand((2, 3, 197, 64), seed=74)
        o = ops.attention_op(q, k, v, causal=False, exp_mode="mxint",
                             quantize_scores=True)
        qf, kf, vf = (x.reshape(6, 197, 64) for x in (q, k, v))
        want = ref.mxint_flash_attention_ref(qf, kf, vf, causal=False)
        np.testing.assert_allclose(np.asarray(o.reshape(6, 197, 64)),
                                   np.asarray(want), rtol=0.2, atol=0.08)


# ---------------------------------------------------------------------------
# decode variant
# ---------------------------------------------------------------------------
def _flat_decode(q4, k4, v4):
    """Native (b, hkv, g, d) / (b, W, hkv, d) -> the flat (bh, g|W, d)
    layout the jnp oracles use."""
    b, hkv, g, d = q4.shape
    W = k4.shape[1]
    qf = q4.reshape(b * hkv, g, d)
    kf = jnp.einsum("bwhd->bhwd", k4).reshape(b * hkv, W, d)
    vf = jnp.einsum("bwhd->bhwd", v4).reshape(b * hkv, W, d)
    return qf, kf, vf


class TestFlashAttentionDecode:
    def test_float_partial_ring_vs_oracle(self):
        q = _rand((2, 2, 2, 64), seed=80, scale=0.5)     # b=2, hkv=2, g=2
        k = _rand((2, 128, 2, 64), seed=81, scale=0.5)
        v = _rand((2, 128, 2, 64), seed=82)
        valid = jnp.arange(128) <= 37
        got = flash_attention_decode(q, k, v, valid, interpret=True)
        qf, kf, vf = _flat_decode(q, k, v)
        want = ref.decode_attention_ref(qf, kf, vf, valid)
        np.testing.assert_allclose(np.asarray(got.reshape(4, 2, 64)),
                                   np.asarray(want), rtol=1e-5, atol=1e-6)

    def test_float_multiblock_ring(self):
        q = _rand((2, 1, 4, 64), seed=83, scale=0.5)
        k = _rand((2, 256, 1, 64), seed=84, scale=0.5)
        v = _rand((2, 256, 1, 64), seed=85)
        valid = jnp.arange(256) <= 200
        got = flash_attention_decode(q, k, v, valid, block_k=128,
                                     interpret=True)
        qf, kf, vf = _flat_decode(q, k, v)
        want = ref.decode_attention_ref(qf, kf, vf, valid)
        np.testing.assert_allclose(np.asarray(got.reshape(2, 4, 64)),
                                   np.asarray(want), rtol=1e-5, atol=1e-6)

    def test_quantized_single_block_exact_vs_paper_oracle(self):
        q = _rand((2, 2, 2, 64), seed=86, scale=0.5)
        k = _rand((2, 128, 2, 64), seed=87, scale=0.5)
        v = _rand((2, 128, 2, 64), seed=88)
        valid = jnp.arange(128) <= 37
        got = flash_attention_decode(q, k, v, valid, exp_mode="mxint",
                                     quantize_scores=True, interpret=True)
        qf, kf, vf = _flat_decode(q, k, v)
        want = ref.mxint_flash_attention_ref(
            qf, kf, vf, causal=False, key_mask=valid.astype(jnp.int32),
            scale=64 ** -0.5)
        np.testing.assert_array_equal(np.asarray(got.reshape(4, 2, 64)),
                                      np.asarray(want))

    @pytest.mark.parametrize("n_valid", [12, 32])
    def test_decode_op_padded_ring_exact(self, n_valid):
        """attention_decode_op pads W=32 -> 128 and G=2 -> 8; padding must
        be numerically invisible: the QUANTIZED result still equals the
        whole-row oracle on the unpadded ring — both for a partially
        filled ring (NEG_INF lanes poison the row exponent in BOTH paths,
        sim parity) and for a full one (sane exponents in both)."""
        q = _rand((2, 2, 2, 16), seed=89, scale=0.5)
        k = _rand((2, 32, 2, 16), seed=90, scale=0.5)
        v = _rand((2, 32, 2, 16), seed=91)
        valid = jnp.arange(32) < n_valid
        got = ops.attention_decode_op(q, k, v, valid, exp_mode="mxint",
                                      quantize_scores=True)
        qf, kf, vf = _flat_decode(q, k, v)
        want = ref.mxint_flash_attention_ref(
            qf, kf, vf, causal=False, key_mask=valid.astype(jnp.int32),
            scale=16 ** -0.5)
        np.testing.assert_array_equal(np.asarray(got.reshape(4, 2, 16)),
                                      np.asarray(want))

    def test_window_ring_layout(self):
        """Sliding-window ring: validity is the caller's slot arithmetic;
        the kernel must reproduce a dense masked softmax over the ring."""
        W = 32
        t = 40                                 # decode position, ring full
        q = _rand((2, 1, 2, 64), seed=92, scale=0.5)
        k = _rand((2, W, 1, 64), seed=93, scale=0.5)
        v = _rand((2, W, 1, 64), seed=94)
        idx = jnp.arange(W)
        slot_pos = t - jnp.mod(t - idx, W)
        valid = (slot_pos >= 0) & (slot_pos <= t) & ((t - slot_pos) < W)
        got = ops.attention_decode_op(q, k, v, valid)
        qf, kf, vf = _flat_decode(q, k, v)
        want = ref.decode_attention_ref(qf, kf, vf, valid)
        np.testing.assert_allclose(np.asarray(got.reshape(2, 2, 64)),
                                   np.asarray(want), rtol=1e-5, atol=1e-6)


# ---------------------------------------------------------------------------
# fallback accounting: DeiT shapes must run the Pallas kernel (ISSUE 3)
# ---------------------------------------------------------------------------
class TestAttentionOpFallbacks:
    def test_deit_shapes_reach_flash_kernel(self):
        """(b*h, 197, 64) used to fail the old shape gate and silently run
        ref.attention_ref; now it pads and runs the kernel — asserted via
        the fallback counter AND the presence of pallas_call in the traced
        program."""
        ops.reset_attention_fallbacks()
        q = _rand((1, 3, 197, 64), seed=95)
        k = _rand((1, 3, 197, 64), seed=96)
        v = _rand((1, 3, 197, 64), seed=97)
        jaxpr = jax.make_jaxpr(functools.partial(
            ops.attention_op, causal=False))(q, k, v)
        assert ops.attention_fallback_counts() == {}
        assert "pallas_call" in str(jaxpr)
        o = ops.attention_op(q, k, v, causal=False)
        np.testing.assert_allclose(
            np.asarray(o.reshape(3, 197, 64)),
            np.asarray(ref.attention_ref(q.reshape(3, 197, 64),
                                         k.reshape(3, 197, 64),
                                         v.reshape(3, 197, 64),
                                         causal=False)),
            rtol=2e-4, atol=2e-4)

    def test_pathological_head_dim_counted_and_warned_exactly_once(self):
        """One fallback event = one counter bump AND one UserWarning — a
        warn-per-head or warn-per-block regression would double-fire."""
        import warnings as W
        ops.reset_attention_fallbacks()
        q = _rand((1, 1, 8, 2064), seed=98, scale=0.1)
        k = _rand((1, 1, 8, 2064), seed=99, scale=0.1)
        v = _rand((1, 1, 8, 2064), seed=100, scale=0.1)
        with W.catch_warnings(record=True) as caught:
            W.simplefilter("always")
            o = ops.attention_op(q, k, v, causal=True)
        hits = [w for w in caught if "fell back" in str(w.message)]
        assert len(hits) == 1, [str(w.message) for w in caught]
        assert issubclass(hits[0].category, UserWarning)
        assert o.shape == q.shape
        assert ops.attention_fallback_counts() == {"head_dim": 1}
        ops.reset_attention_fallbacks()


# ---------------------------------------------------------------------------
# ops wrappers
# ---------------------------------------------------------------------------
class TestOpsWrappers:
    def test_linear_nd(self):
        x = _rand((2, 3, 256), seed=50)
        w = _rand((256, 128), seed=51, scale=0.1)
        wq = quantize(w, MXFormat(8, 256), axis=0)
        y = ops.mxint_linear(x, wq.mantissa, wq.exponent, w_block=256)
        assert y.shape == (2, 3, 128)
        want = x.reshape(-1, 256) @ np.asarray(
            ref.mxint_matmul_ref(jnp.eye(256), wq.mantissa, wq.exponent,
                                 w_block=256))
        np.testing.assert_allclose(np.asarray(y).reshape(-1, 128),
                                   np.asarray(want), rtol=1e-4, atol=1e-4)

    def test_odd_rows_padding(self):
        x = _rand((5, 7, 192), seed=52, scale=2.0)
        y = ops.mxint_layernorm_op(x, jnp.ones((192,)), jnp.zeros((192,)))
        assert y.shape == x.shape
        assert np.isfinite(np.asarray(y)).all()

    def test_attention_op_gqa_shapes(self):
        q = _rand((2, 4, 64, 64), seed=53)
        k = _rand((2, 4, 64, 64), seed=54)
        v = _rand((2, 4, 64, 64), seed=55)
        o = ops.attention_op(q, k, v, causal=True)
        assert o.shape == q.shape

    def test_attention_op_gqa_grouped_kv_no_broadcast(self):
        """Grouped K/V reach the flash kernel via the kv_groups BlockSpec
        index map (no broadcast copy): result equals the matched-heads
        kernel run on explicitly repeated K/V."""
        q = _rand((2, 4, 32, 64), seed=56)
        k = _rand((2, 2, 32, 64), seed=57)
        v = _rand((2, 2, 32, 64), seed=58)
        o = ops.attention_op(q, k, v, causal=True)
        kb = jnp.repeat(k, 2, axis=1)
        vb = jnp.repeat(v, 2, axis=1)
        want = ops.attention_op(q, kb, vb, causal=True)
        np.testing.assert_array_equal(np.asarray(o), np.asarray(want))


# ---------------------------------------------------------------------------
# linear row tile: a full tile from (rows, K), ragged last row block
# ---------------------------------------------------------------------------
def _linear_case(rows, k=256, n=256):
    x = _rand((rows, k), seed=rows, scale=2.0)
    w = _rand((k, n), seed=k + n, scale=0.1)
    wq = quantize(w, MXFormat(6, 256), axis=0)
    g = _rand((k,), seed=1, scale=0.5) + 1.0
    b = _rand((k,), seed=2, scale=0.1)
    return x, wq, g, b


def _pad8(x):
    return jnp.pad(x, ((0, (-x.shape[0]) % 8), (0, 0)))


class TestLinearRowTile:
    @pytest.mark.parametrize("rows,k,bm", [
        (6304, 768, 256), (6304, 3072, 256), (6304, 4096, 128),
        (6272, 768, 256), (1576, 768, 232), (197, 768, 200),
        (32, 768, 32), (2, 64, 8)])
    def test_tile_from_rows_and_k(self, rows, k, bm):
        assert ops.linear_row_tile(rows, k) == bm

    @pytest.mark.parametrize("rows", [197, 394, 650, 1576])
    @pytest.mark.parametrize("quantize_act", [False, True])
    def test_linear_bit_exact_at_rows_the_tile_does_not_divide(
            self, rows, quantize_act):
        x, wq, _, _ = _linear_case(rows)
        got = ops.mxint_linear(x, wq.mantissa, wq.exponent, w_block=256,
                               quantize_act=quantize_act)
        assert got.shape == (rows, 256)
        divides = mm_kernel(_pad8(x), wq.mantissa, wq.exponent, w_block=256,
                            quantize_act=quantize_act, bm=8, bn=128,
                            interpret=True)[:rows]
        want = ref.mxint_matmul_ref(x, wq.mantissa, wq.exponent,
                                    w_block=256, quantize_act=quantize_act)
        np.testing.assert_array_equal(np.asarray(got), np.asarray(divides))
        np.testing.assert_array_equal(np.asarray(got), np.asarray(want))

    @pytest.mark.parametrize("rows", [197, 394, 650, 1576])
    def test_ln_linear_bit_exact_at_rows_the_tile_does_not_divide(
            self, rows):
        from repro.core.quantize import quantize_dequantize
        from repro.kernels.mxint_ln_matmul import mxint_ln_matmul
        x, wq, g, b = _linear_case(rows)
        got = ops.mxint_ln_linear_op(x, g, b, wq.mantissa, wq.exponent,
                                     w_block=256)
        assert got.shape == (rows, 256)
        divides = mxint_ln_matmul(_pad8(x), g, b, wq.mantissa, wq.exponent,
                                  w_block=256, bm=8, bn=128,
                                  interpret=True)[:rows]
        normed = quantize_dequantize(ref.mxint_layernorm_ref(x, g, b),
                                     MXFormat(8, 16), axis=-1)
        want = ref.mxint_matmul_ref(normed, wq.mantissa, wq.exponent,
                                    w_block=256, quantize_act=True)
        np.testing.assert_array_equal(np.asarray(got), np.asarray(divides))
        np.testing.assert_array_equal(np.asarray(got), np.asarray(want))

    @pytest.mark.parametrize("fused", [False, True])
    def test_poisoned_rows_past_m_do_not_leak(self, fused):
        """1,576 rows in 232-row tiles: the last block holds 48 rows past
        M.  Filled with NaN they change no valid row, and the ragged call
        (whose interpreter fills them with NaN too) returns M rows."""
        from repro.kernels.mxint_ln_matmul import mxint_ln_matmul
        rows, bm = 1576, 232
        x, wq, g, b = _linear_case(rows)
        poisoned = jnp.concatenate(
            [x, jnp.full((7 * bm - rows, x.shape[1]), jnp.nan)])

        def run(a):
            if fused:
                return mxint_ln_matmul(a, g, b, wq.mantissa, wq.exponent,
                                       w_block=256, bm=bm, bn=128,
                                       interpret=True)
            return mm_kernel(a, wq.mantissa, wq.exponent, w_block=256,
                             quantize_act=True, bm=bm, bn=128,
                             interpret=True)

        ragged = np.asarray(run(x))
        full = np.asarray(run(poisoned))
        assert ragged.shape == (rows, 256)
        assert np.isfinite(ragged).all()
        assert np.isnan(full[rows:]).all()
        np.testing.assert_array_equal(full[:rows], ragged)

    def test_tiling_counted_once_per_trace(self):
        x, wq, g, b = _linear_case(1576)
        fn = jax.jit(lambda a: ops.mxint_ln_linear_op(
            a, g, b, wq.mantissa, wq.exponent, w_block=256) +
            ops.mxint_linear(a, wq.mantissa, wq.exponent, w_block=256))
        ops.reset_linear_tiling()
        try:
            for _ in range(3):
                fn(x).block_until_ready()
            assert ops.linear_tiling() == {
                "row_tile_calls": {232: 2},
                "operand_calls": {"bf16": 1, "raw": 1}, "rows": 2 * 1576,
                "rows_padded": 2 * 48, "padded_share": 48 / 1576}
        finally:
            ops.reset_linear_tiling()


# ---------------------------------------------------------------------------
# the hoisted MXU operand: bit-identical to the per-tile f32 product
# ---------------------------------------------------------------------------
def _per_tile_product_kernel(x_ref, wm_ref, we_ref, o_ref, *, w_block,
                             act_block, act_mant_bits, quantize_act):
    """The linear kernels' grid step before the operand was hoisted: the
    activation tile quantized again at every column tile, the weight
    tile widened to f32, an f32 x f32 contraction."""
    x = x_ref[...].astype(jnp.float32)
    if quantize_act:
        xm, xe = block_quantize(x, act_block, act_mant_bits)
        x = xm * jnp.exp2(xe.astype(jnp.float32))
    scale = jnp.repeat(jnp.exp2(we_ref[...].astype(jnp.float32)), w_block,
                       axis=0)
    w = wm_ref[...].astype(jnp.float32) * scale
    o_ref[...] = jax.lax.dot_general(x, w, (((1,), (0,)), ((), ())),
                                     preferred_element_type=jnp.float32)


def _per_tile_reference(x, wm, we, *, bm, w_block, act_mant_bits,
                        quantize_act):
    from jax.experimental import pallas as pl
    (M, K), N = x.shape, wm.shape[1]
    kernel = functools.partial(
        _per_tile_product_kernel, w_block=w_block, act_block=16,
        act_mant_bits=act_mant_bits, quantize_act=quantize_act)
    return pl.pallas_call(
        kernel, grid=(pl.cdiv(M, bm), N // 128),
        in_specs=[pl.BlockSpec((bm, K), lambda i, j: (i, 0)),
                  pl.BlockSpec((K, 128), lambda i, j: (0, j)),
                  pl.BlockSpec((K // w_block, 128), lambda i, j: (0, j))],
        out_specs=pl.BlockSpec((bm, 128), lambda i, j: (i, j)),
        out_shape=jax.ShapeDtypeStruct((M, N), jnp.float32),
        interpret=True)(x, wm, we)


def _edge_rows(rows, k, seed, dtype=jnp.float32):
    """Seeded activations with, in every row, a block of 16 zeros and a
    block whose values all lie below 2^-120, and one row of zeros."""
    x = _rand((rows, k), seed=seed, scale=2.0)
    x = x.at[:, :16].set(0.0).at[:, 16:32].multiply(2.0 ** -125)
    return x.at[rows // 2].set(0.0).astype(dtype)


def _planes(k, n, seed, w_bits=6):
    """MXInt weight planes of (k, n) (int8 mantissas up to 8 bits, int16
    above), padded to a lane multiple of n."""
    from repro.core.quantize import _resolve_block
    w_block = _resolve_block(k, 256)
    wq = quantize(_rand((k, n), seed=seed, scale=k ** -0.5),
                  MXFormat(w_bits, w_block), axis=0)
    wm, we = ops._pad_planes(wq.mantissa, wq.exponent)
    return wm, we, w_block


class TestHoistedOperand:
    """The operand quantized once per row block (bf16, or f32 past 8 act
    mantissa bits) against the per-tile f32 product, bit for bit in
    interpret mode."""

    @pytest.mark.parametrize("rows,k,n,act_mant_bits,w_bits", [
        (8, 384, 128, 8, 6), (197, 768, 768, 8, 6), (197, 768, 1000, 4, 6),
        (6304, 384, 128, 8, 6), (8, 3072, 3072, 8, 6),
        (197, 2304, 256, 10, 6), (197, 768, 256, 8, 10),
        (197, 768, 768, None, 6), (6304, 768, 128, None, 6)])
    def test_matmul_bit_identical_to_per_tile_product(self, rows, k, n,
                                                      act_mant_bits, w_bits):
        from repro.kernels.mxint_matmul import operand_path
        x = _edge_rows(rows, k, seed=rows + k)
        wm, we, w_block = _planes(k, n, seed=n, w_bits=w_bits)
        quantize_act = act_mant_bits is not None
        bits = act_mant_bits or 8
        bm = ops.linear_row_tile(rows, k)
        got = mm_kernel(x, wm, we, w_block=w_block, act_block=16,
                        act_mant_bits=bits, quantize_act=quantize_act,
                        bm=bm, bn=128, interpret=True)
        want = _per_tile_reference(x, wm, we, bm=bm, w_block=w_block,
                                   act_mant_bits=bits,
                                   quantize_act=quantize_act)
        assert operand_path(quantize_act, bits, wm.dtype) == (
            "raw" if not quantize_act
            else "bf16" if bits <= 8 and w_bits <= 8 else "f32")
        np.testing.assert_array_equal(np.asarray(got), np.asarray(want))

    @pytest.mark.parametrize("rows,d,n,dtype,rms_only,mant_bits", [
        (197, 768, 768, jnp.float32, False, 8),
        (197, 384, 1000, jnp.bfloat16, False, 8),
        (8, 768, 3072, jnp.float32, True, 8),
        (6304, 384, 128, jnp.float32, False, 8),
        (197, 768, 256, jnp.float32, False, 10),
        (197, 768, 768, jnp.bfloat16, True, 4)])
    def test_ln_matmul_bit_identical_to_per_tile_product(
            self, rows, d, n, dtype, rms_only, mant_bits):
        """The fused kernel against the LN kernel's quantized output,
        rounded through the model dtype, into the per-tile product."""
        from repro.kernels.mxint_ln_matmul import mxint_ln_matmul
        x = _edge_rows(rows, d, seed=rows + d, dtype=dtype)
        g = _rand((d,), seed=1, scale=0.5) + 1.0
        b = _rand((d,), seed=2, scale=0.1)
        wm, we, w_block = _planes(d, n, seed=n)
        bm = ops.linear_row_tile(rows, d)
        got = mxint_ln_matmul(x, g, b, wm, we, w_block=w_block,
                              act_block=16, mant_bits=mant_bits,
                              rms_only=rms_only, bm=bm, bn=128,
                              interpret=True)
        xp = _pad8(x.astype(jnp.float32))
        normed = ln_kernel(xp, g, b, act_block=16, mant_bits=mant_bits,
                           rms_only=rms_only, quantize_out=True,
                           block_rows=8, interpret=True)[:rows]
        want = _per_tile_reference(normed.astype(dtype), wm, we, bm=bm,
                                   w_block=w_block, act_mant_bits=mant_bits,
                                   quantize_act=True)
        np.testing.assert_array_equal(np.asarray(got), np.asarray(want))

    @pytest.mark.parametrize("operand", ["act4", "act8", "weight"])
    def test_bf16_operand_round_trips(self, operand):
        """The operand's grid values, mantissas times exact powers of two
        (np.ldexp), come back from bf16 to f32 unchanged."""
        if operand == "weight":
            rng = np.random.default_rng(3)
            m = rng.integers(-127, 128, size=(256, 128))
            e = rng.integers(-128, 100, size=(1, 128))
        else:
            m, e = block_quantize(_edge_rows(64, 768, seed=4), 16,
                                  int(operand[3:]))
        f32 = np.ldexp(np.asarray(m, np.float64),
                       np.asarray(e)).astype(np.float32)
        back = f32.astype(jnp.bfloat16).astype(np.float32)
        np.testing.assert_array_equal(back, f32)

    @pytest.mark.parametrize("quantize_act,bits,w_dtype,path", [
        (True, 4, jnp.int8, "bf16"), (True, 8, jnp.int8, "bf16"),
        (True, 9, jnp.int8, "f32"), (True, 8, jnp.int16, "f32"),
        (False, 8, jnp.int8, "raw")])
    def test_operand_path_rule(self, quantize_act, bits, w_dtype, path):
        from repro.kernels.mxint_matmul import operand_path
        assert operand_path(quantize_act, bits, w_dtype) == path

    @pytest.mark.parametrize("act_mant_bits,path", [(8, "bf16"), (10, "f32")])
    def test_deit_tiny_forward_counts_operand_paths(self, act_mant_bits,
                                                    path):
        """Patch linear, 3 fused LN->q/k/v, out-proj, fused LN->wi, wo and
        head of a kernel-mode DeiT-Tiny forward, each counted once while
        it is traced, all under the path its act format gives."""
        import dataclasses
        from repro.configs.deit import BY_NAME
        from repro.core.mx_types import QuantConfig
        from repro.models import build_model
        from repro.serving.engine import pack_params_mxint
        q = QuantConfig(mode="kernel", quantize_nonlinear=True,
                        act_fmt=MXFormat(act_mant_bits, 16))
        cfg = dataclasses.replace(BY_NAME["deit_tiny"], quant=q)
        model = build_model(cfg)
        params = pack_params_mxint(
            jax.eval_shape(model.init, jax.random.key(0)), q.weight_fmt,
            abstract=True)
        imgs = jax.ShapeDtypeStruct((3, 224, 224, 3), jnp.float32)
        ops.reset_linear_tiling()
        try:
            jax.eval_shape(model.logits, params, imgs)
            assert ops.linear_tiling()["operand_calls"] == {path: 8}
        finally:
            ops.reset_linear_tiling()


# ---------------------------------------------------------------------------
# dimension_semantics annotations
# ---------------------------------------------------------------------------
def _without_compiler_params(fn, *args, **kwargs):
    """Re-run a kernel wrapper with compiler_params stripped from every
    pallas_call it stages — the pre-annotation trace."""
    import jax.experimental.pallas as plmod

    real = plmod.pallas_call

    def naked(kernel, **kw):
        kw.pop("compiler_params", None)
        return real(kernel, **kw)

    jax.clear_caches()    # cached jaxprs would bypass the monkeypatch
    plmod.pallas_call = naked
    try:
        out = fn(*args, **kwargs)
        return np.asarray(jax.block_until_ready(out))
    finally:
        plmod.pallas_call = real
        jax.clear_caches()


class TestDimensionSemantics:
    """Annotating dimension_semantics must be bit-neutral in interpret
    mode (DESIGN.md §14) — asserted per kernel family."""

    def _assert_bit_identical(self, fn, *args, **kwargs):
        want = _without_compiler_params(fn, *args, **kwargs)
        got = np.asarray(fn(*args, **kwargs))
        np.testing.assert_array_equal(got, want)

    def test_matmul(self):
        x = _rand((16, 256), seed=60, scale=0.5)
        w = _rand((256, 128), seed=61, scale=0.1)
        wq = quantize(w, MXFormat(8, 32), axis=0)
        self._assert_bit_identical(
            mm_kernel, x, wq.mantissa, wq.exponent, w_block=32,
            quantize_act=True, bm=8, bn=128, interpret=True)

    def test_ln_matmul(self):
        from repro.kernels.mxint_ln_matmul import mxint_ln_matmul
        x = _rand((32, 256), seed=62, scale=2.0)
        w = _rand((256, 128), seed=63, scale=0.1)
        wq = quantize(w, MXFormat(8, 32), axis=0)
        self._assert_bit_identical(
            mxint_ln_matmul, x, jnp.ones((256,)), jnp.zeros((256,)),
            wq.mantissa, wq.exponent, w_block=32, bm=16, bn=128,
            interpret=True)

    def test_rowwise_kernels(self):
        x = _rand((16, 256), seed=64, scale=2.0)
        self._assert_bit_identical(
            ln_kernel, x, jnp.ones((256,)), jnp.zeros((256,)),
            block_rows=8, interpret=True)
        self._assert_bit_identical(
            sm_kernel, x, block_rows=8, interpret=True)
        self._assert_bit_identical(
            gelu_kernel, x, block_rows=8, interpret=True)

    def test_flash_and_decode(self):
        q = _rand((2, 64, 128), seed=65, scale=0.3)
        k = _rand((2, 64, 128), seed=66, scale=0.3)
        v = _rand((2, 64, 128), seed=67)
        self._assert_bit_identical(
            flash_attention, q, k, v, causal=True, block_q=32, block_k=32,
            interpret=True)
        qd = _rand((2, 2, 8, 128), seed=68, scale=0.3)
        kd = _rand((2, 128, 2, 128), seed=69, scale=0.3)
        vd = _rand((2, 128, 2, 128), seed=70)
        valid = jnp.arange(128) < 100
        self._assert_bit_identical(
            flash_attention_decode, qd, kd, vd, valid, block_k=64,
            interpret=True)
