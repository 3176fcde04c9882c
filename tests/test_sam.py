"""The Segment Anything image encoder on the CPU, at a tiny size.

A SAM of the published structure with every size cut: 64 x 64 images,
patch 16 (a 4 x 4 grid), windows of 3 (the grid padded to 6 x 6, so
padding is exercised), one period of (window, window, global) layers,
d 64, 2 heads, a neck of 32 channels.  The program ('sim' and 'kernel'
modes, Pallas in interpret mode) is compared with the benchmark's plain
reference (``bench/families/sam.py``) on the benchmark's seeded
weights; the flash kernel's in-kernel relative-position bias with the
whole-row oracle.
"""
import dataclasses
import sys
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

ROOT = Path(__file__).resolve().parents[1]
if str(ROOT) not in sys.path:
    sys.path.insert(0, str(ROOT))

from bench.harness import manifest, program  # noqa: E402
from bench.harness import weights as W  # noqa: E402
from repro.kernels import ops, ref  # noqa: E402
from repro.kernels.flash_attention import flash_attention  # noqa: E402
from repro.models import sam as S  # noqa: E402

SEED = 2 ** 34 + 17
TINY = dict(manifest.load_json(manifest.BENCH / "configs" / "sam_vit_b.json"),
            name="sam_tiny", image_size=64, patch_size=16, hidden_size=64,
            num_hidden_layers=3, num_attention_heads=2, intermediate_size=128,
            window_size=3, global_attn_indexes=[2], global_attn_period=3,
            out_chans=32)


@pytest.fixture(scope="module")
def fam():
    return manifest.family("sam")


@pytest.fixture(scope="module")
def weights():
    return W.make(TINY, SEED)


def _images(n, seed=0):
    return np.random.default_rng(seed).standard_normal(
        (n, TINY["image_size"], TINY["image_size"], 3), dtype=np.float32)


@pytest.mark.parametrize("mode", ["sim", "kernel"])
def test_program_matches_reference(mode, fam, weights):
    """The program's embedding of 3 images equals the reference's bit for
    bit: the reference zero-pads the normed grid before its fused qkv
    linear, the program pads q/k/v with the biases."""
    from repro.models import build_model
    from repro.serving.engine import pack_params_mxint
    cfg = fam.model(TINY).cfg
    q = dataclasses.replace(cfg.quant, mode=mode)
    model = build_model(dataclasses.replace(cfg, quant=q))
    params = W.to_program(weights, model)
    if mode == "kernel":
        params = pack_params_mxint(params, q.weight_fmt)
    imgs = _images(3)
    got = np.asarray(jax.jit(model.logits)(params, jnp.asarray(imgs)))
    want = np.asarray(fam.forward(TINY, weights, jnp.asarray(imgs),
                                  "highest"))
    assert got.shape == (3, 4, 4, 32) == want.shape
    np.testing.assert_array_equal(got, want)


def test_bfloat16_reference_departs(fam, weights):
    """The control the limits are set against: the reference at bfloat16
    products is not the float32 one."""
    imgs = jnp.asarray(_images(2, seed=1))
    hi = np.asarray(fam.forward(TINY, weights, imgs, "highest"))
    lo = np.asarray(fam.forward(TINY, weights, imgs, "bfloat16"))
    assert np.sqrt(np.mean((hi - lo) ** 2) / np.mean(hi ** 2)) > 1e-3


def test_op_cases_match_reference(fam, weights):
    """Every op case of the family through the served engine's packed
    planes against the reference ops."""
    engine, _ = program.scheduler(TINY, weights, 2)
    inputs = fam.op_inputs(TINY, 2, SEED)
    got = fam.program_ops(engine, inputs)
    want = fam.reference_ops(TINY, fam.layer0(weights), inputs, "highest")
    assert sorted(got) == ["gelu", "global_attention", "layernorm2d",
                           "linear", "ln_qkv", "window_attention"]
    for name in want:
        np.testing.assert_allclose(got[name], want[name], rtol=2e-6,
                                   atol=2e-6, err_msg=name)


def test_to_program_takes_the_family_tree(fam, weights):
    model = fam.model(TINY)
    params = W.to_program(weights, model)
    assert params["blocks"]["window"]["attn"]["rel_pos_h"].value.shape == (
        1, 2, 5, 32)
    assert params["blocks"]["global"]["attn"]["rel_pos_w"].value.shape == (
        1, 7, 32)
    bad = dict(weights, pos_embed=weights["pos_embed"][:, :2])
    with pytest.raises(ValueError, match="pos_embed"):
        W.to_program(bad, model)


def test_window_partition_round_trip_with_bias_cells():
    rng = np.random.default_rng(0)
    t = jnp.asarray(rng.standard_normal((2, 4, 4, 2, 3), dtype=np.float32))
    fill = jnp.asarray(rng.standard_normal((2, 3), dtype=np.float32))
    win = S.pad_windows(t, fill, 6, 3)
    assert win.shape == (2 * 4, 9, 2, 3)
    np.testing.assert_array_equal(S.unpad_windows(win, 2, 4, 6, 3), t)
    # window (0, 1) of image 0 holds grid columns 3..5: column 3 real,
    # columns 4 and 5 padded with the fill
    cells = np.asarray(win[1]).reshape(3, 3, 2, 3)
    np.testing.assert_array_equal(cells[:, 0], np.asarray(t[0, :3, 3]))
    np.testing.assert_array_equal(cells[:, 1:],
                                  np.broadcast_to(fill, (3, 2, 2, 3)))


def test_padded_keys_are_attended(fam, weights):
    """Unmasked padding: the window layer's output moves with the q/k/v
    biases that fill the padded cells, as the published model's does."""
    model = fam.model(TINY)
    a = fam.layer0(W.to_program(weights, model))["window"]["attn"]
    rng = np.random.default_rng(1)
    qkv = [jnp.asarray(rng.standard_normal((1, 4, 4, 2, 32),
                                           dtype=np.float32))
           for _ in range(3)]
    base = np.asarray(model.attend(a, *qkv, True))
    a2 = dict(a, bk=a["bk"]._replace(value=a["bk"].value + 1.0))
    moved = np.asarray(model.attend(a2, *qkv, True))
    # a grid row whose window holds padded cells changes; the first
    # window (grid rows and columns 0..2) holds none and does not
    rows = base.reshape(4, 4, -1)
    rows2 = moved.reshape(4, 4, -1)
    np.testing.assert_array_equal(rows[:3, :3], rows2[:3, :3])
    assert not np.array_equal(rows[3], rows2[3])


def _bias_case(bh, sq, kh, kw, d, seed):
    rng = np.random.default_rng(seed)

    def arr(*shape, scale=1.0):
        return jnp.asarray(scale * rng.standard_normal(shape,
                                                       dtype=np.float32))
    return (arr(bh, sq, d, scale=0.5), arr(bh, kh * kw, d, scale=0.5),
            arr(bh, kh * kw, d), (arr(bh, sq, kh, scale=0.3),
                                  arr(bh, sq, kw, scale=0.3)))


def test_flash_bias_one_kblock_bit_exact_vs_whole_row_oracle():
    """One k block over the 16 x 16 key grid: the in-kernel bias spread
    (one-hot contractions) and the quantized softmax equal the whole-row
    oracle with the dense bias, bit for bit."""
    q, k, v, bias = _bias_case(2, 64, 16, 16, 64, seed=3)
    got = flash_attention(q, k, v, bias, causal=False, exp_mode="mxint",
                          quantize_scores=True, block_q=32, block_k=256,
                          interpret=True)
    want = ref.mxint_flash_attention_ref(q, k, v, causal=False, bias=bias)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


def test_flash_bias_several_kblocks_within_lut_granularity():
    """Four k blocks: per-tile shared exponents and the exact running
    rescale part from the whole-row reading only at LUT granularity, the
    tolerance of the unbiased multi-block test (tests/test_kernels.py)."""
    q, k, v, bias = _bias_case(2, 64, 16, 32, 64, seed=4)
    got = flash_attention(q, k, v, bias, causal=False, exp_mode="mxint",
                          quantize_scores=True, block_q=32, block_k=128,
                          interpret=True)
    want = ref.mxint_flash_attention_ref(q, k, v, causal=False, bias=bias)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=0.15, atol=0.05)
    one = flash_attention(q, k, v, bias, causal=False, exp_mode="mxint",
                          quantize_scores=True, block_q=32, block_k=512,
                          interpret=True)
    np.testing.assert_array_equal(np.asarray(one), np.asarray(want))


def test_attention_op_bias_matches_whole_row_path():
    """A global layer's shape through ``attention_op`` (head dim padded to
    128, one k block) against the datapath's whole-row path with the
    same bias: equal, and counted as one flash call."""
    from repro.core.mx_types import QuantConfig
    from repro.models import attention as A
    q, k, v, bias = _bias_case(2, 256, 16, 16, 64, seed=5)
    ops.reset_attention_tiling()
    got = ops.attention_op(q[None], k[None], v[None],
                           tuple(x[None] for x in bias), causal=False,
                           exp_mode="mxint", quantize_scores=True)
    assert ops.attention_tiling() == [dict(
        path="flash", block_q=256, block_k=256, k_blocks=1, head_dim=128,
        keys_padded=0, calls=1)]
    sim = QuantConfig(mode="sim", quantize_nonlinear=True)
    qv = jnp.einsum("hsd->shd", q)[None, :, :, None]
    want = A._direct_attention(qv, jnp.einsum("hsd->shd", k)[None],
                               jnp.einsum("hsd->shd", v)[None], True, sim,
                               64 ** -0.5,
                               tuple(x[None, :, None] for x in bias))
    np.testing.assert_array_equal(
        np.asarray(got[0]), np.asarray(jnp.einsum("shgd->hsgd",
                                                  want[0])[:, :, 0]))


@pytest.mark.parametrize("mode", ["sim", "kernel"])
def test_no_bias_path_unchanged(mode):
    """The ViT path passes no bias: its attention equals the same call with
    a zero bias, bit for bit, in both the whole-row and flash paths."""
    from repro.core.mx_types import QuantConfig
    qc = QuantConfig(mode=mode, quantize_nonlinear=True)
    rng = np.random.default_rng(6)
    for s in (16, 1024):                 # whole-row; flash in kernel mode
        qv = jnp.asarray(rng.standard_normal((1, s, 2, 1, 64),
                                             dtype=np.float32))
        kk = jnp.asarray(rng.standard_normal((1, s, 2, 64),
                                             dtype=np.float32))
        if mode == "sim" and s > 16:
            continue
        kw = dict(q=qc, positions=jnp.arange(s)[None], causal=False,
                  window=0, scale=0.125, chunk=s)
        side = int(s ** 0.5)
        zero = (jnp.zeros((1, 2, 1, s, side)),) * 2
        a = qc.datapath.attention(qv, kk, kk, **kw)
        b = qc.datapath.attention(qv, kk, kk, bias=zero, **kw)
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
