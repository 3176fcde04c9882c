"""Compile the DeiT and SAM kernel-mode paths for a described TPU v5e chip.

Nothing runs: the TPU compiler, which is installed here, compiles for a
chip that is described and not attached, and raises what Mosaic would
raise on the chip (unsupported shape casts, block shapes off the (8, 128)
tiling, VMEM overruns).  Interpret-mode tests cannot see any of that.

The kernels are compiled through the ``repro.kernels.ops`` wrappers, with
``ops.on_tpu`` patched so they take their compiled branch, at the shapes a
batch of 8 and of 32 images launches at DeiT-Tiny, -Small and -Base
widths: at 32 images every linear runs 6,304 rows in 256-row tiles with a
ragged last block, fc2 at K = 3072 the largest VMEM case.  One test
compiles the whole jitted DeiT-Base kernel-mode forward at each batch and
counts its Pallas custom calls.  The SAM ViT-B encoder's attention cores
(window and global, with the relative-position bias), neck kernels and
whole forward compile at the benchmark's batch of 8 images.

The topology is described inside a fixture, never while a module is
imported: only one process at a time may load the TPU library, and every
test worker imports every test file.
"""
import dataclasses
import os

import jax
import jax.numpy as jnp
import pytest

from repro.configs.deit import BY_NAME
from repro.core.mx_types import QuantConfig
from repro.kernels import ops
from repro.models.model_api import Param

BATCHES = (8, 32)
F32, I8 = jnp.float32, jnp.int8
KCFG = QuantConfig(mode="kernel", quantize_nonlinear=True)
WIDTHS = ("deit_tiny", "deit_small", "deit_base")


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:                       # no TPU compiler here
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    from jax.sharding import SingleDeviceSharding
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture
def compiled_path(monkeypatch):
    """Wrappers take their compiled branch; the persistent compile cache
    is off (an entry written for an absent chip cannot be read back)."""
    from jax.experimental.compilation_cache import compilation_cache
    monkeypatch.setattr(ops, "on_tpu", lambda: True)
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


def _compile(fn, *shapes):
    return jax.jit(fn).lower(*shapes).compile()


def _kernel_cases(cfg, batch):
    """(name, fn, arg shapes) for every kernel the forward launches."""
    d, ff = cfg.d_model, cfg.d_ff
    tokens = (cfg.image_size // cfg.patch_size) ** 2
    rows = batch * (tokens + 1)
    wfmt, act = KCFG.weight_fmt, KCFG.act_fmt
    nl = KCFG.nonlinear

    def linear(m, k, n):
        from repro.core.quantize import _resolve_block
        wb = _resolve_block(k, wfmt.block_size)
        fn = (lambda x, wm, we: ops.mxint_linear(
            x, wm, we, w_block=wb, quantize_act=True,
            act_block=act.block_size, act_mant_bits=act.mant_bits))
        return fn, [((m, k), F32), ((k, n), I8), ((k // wb, n), I8)]

    def ln_linear(n):
        from repro.core.quantize import _resolve_block
        wb = _resolve_block(d, wfmt.block_size)
        fn = (lambda x, g, b, wm, we: ops.mxint_ln_linear_op(
            x, g, b, wm, we, w_block=wb, act_block=act.block_size,
            mant_bits=act.mant_bits, lut_bits=nl.ln_lut_bits))
        return fn, [((rows, d), F32), ((d,), F32), ((d,), F32),
                    ((d, n), I8), ((d // wb, n), I8)]

    patch_dim = 3 * cfg.patch_size ** 2
    return {
        "patch_linear": linear(batch * tokens, patch_dim, d),
        "ln_qkv": ln_linear(d),
        "out_proj": linear(rows, d, d),
        "ln_wi": ln_linear(ff),
        "wo": linear(rows, ff, d),
        "head": linear(batch, d, cfg.n_classes),
        "final_ln": (lambda x, g, b: ops.mxint_layernorm_op(
            x, g, b, act_block=act.block_size, mant_bits=act.mant_bits,
            lut_bits=nl.ln_lut_bits, quantize_out=True),
            [((rows, d), F32), ((d,), F32), ((d,), F32)]),
        "softmax": (lambda s: ops.mxint_softmax_op(
            s, act_block=act.block_size, mant_bits=act.mant_bits,
            r_bits=nl.softmax_r_bits, quantize_out=True),
            [((batch * cfg.n_heads, tokens + 1, tokens + 1), F32)]),
        "gelu": (lambda x: ops.mxint_gelu_op(
            x, act_block=act.block_size, mant_bits=act.mant_bits,
            lut_bits=nl.gelu_lut_bits, domain=nl.gelu_domain),
            [((rows, ff), F32)]),
    }


KERNELS = ("patch_linear", "ln_qkv", "out_proj", "ln_wi", "wo", "head",
           "final_ln", "softmax", "gelu")


@pytest.mark.parametrize("kernel", KERNELS)
@pytest.mark.parametrize("arch", WIDTHS)
@pytest.mark.parametrize("batch", BATCHES)
def test_kernel_compiles_for_v5e(batch, arch, kernel, one_chip,
                                 compiled_path):
    fn, shapes = _kernel_cases(BY_NAME[arch], batch)[kernel]
    args = [jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)
            for shape, dtype in shapes]
    compiled = _compile(fn, *args)
    assert "tpu_custom_call" in compiled.as_text()


@pytest.mark.parametrize("batch", BATCHES)
def test_deit_base_forward_compiles_for_v5e(batch, one_chip, compiled_path):
    from repro.models import build_model
    from repro.serving.engine import pack_params_mxint

    cfg = dataclasses.replace(BY_NAME["deit_base"], quant=KCFG)
    model = build_model(cfg)
    params = pack_params_mxint(jax.eval_shape(model.init, jax.random.key(0)),
                               KCFG.weight_fmt, abstract=True)
    params = jax.tree_util.tree_map(
        lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=one_chip),
        params)
    imgs = jax.ShapeDtypeStruct((batch, cfg.image_size, cfg.image_size, 3),
                                jnp.float32, sharding=one_chip)
    text = _compile(model.logits, params, imgs).as_text()
    # patch linear + the 8 kernels of the scanned block body + final LN
    # + head
    assert text.count("tpu_custom_call") == 11


# -- the Segment Anything ViT-B image encoder at the benchmark's batch ------
SAM_BATCH = 8


def _sam_config():
    from repro.models.model_api import ModelConfig
    return ModelConfig(name="sam_vit_b", family="sam", n_layers=12,
                       d_model=768, n_heads=12, n_kv_heads=12, d_ff=3072,
                       vocab=0, ffn_kind="gelu", image_size=1024,
                       patch_size=16, n_classes=0, attn_window_size=14,
                       global_attn_period=3, neck_dim=256, dtype=F32,
                       quant=KCFG)


def _sam_attention(windowed):
    """The model's attention core (q/k/v of the grid in, before the
    output projection) with the relative-position bias: window layers
    through the whole-row softmax kernel (200 windows of 196 keys at
    batch 8), global layers through the flash kernel (4,096 keys)."""
    from repro.models import build_model
    model = build_model(_sam_config())
    side = 27 if windowed else 127
    fn = (lambda q, k, v, ba, rh, rw: model.attend(
        {"bq": Param(ba, ()), "bk": Param(ba, ()), "bv": Param(ba, ()),
         "rel_pos_h": Param(rh, ()), "rel_pos_w": Param(rw, ())},
        q, k, v, windowed))
    qkv = ((SAM_BATCH, 64, 64, 12, 64), F32)
    return fn, [qkv, qkv, qkv, ((768,), F32), ((side, 64), F32),
                ((side, 64), F32)]


def _sam_cases():
    from repro.core.quantize import _resolve_block
    wfmt, act = KCFG.weight_fmt, KCFG.act_fmt
    rows = SAM_BATCH * 64 * 64

    def linear(k, n):
        wb = _resolve_block(k, wfmt.block_size)
        fn = (lambda x, wm, we: ops.mxint_linear(
            x, wm, we, w_block=wb, quantize_act=True,
            act_block=act.block_size, act_mant_bits=act.mant_bits))
        return fn, [((rows, k), F32), ((k, n), I8), ((k // wb, n), I8)]

    return {
        "window_attention": _sam_attention(True),
        "global_attention": _sam_attention(False),
        "neck_conv1": linear(768, 256),
        "neck_conv2": linear(9 * 256, 256),
        "neck_layernorm": (lambda x, g, b: ops.mxint_layernorm_op(
            x, g, b, act_block=act.block_size, mant_bits=act.mant_bits,
            lut_bits=KCFG.nonlinear.ln_lut_bits, quantize_out=True),
            [((rows, 256), F32), ((256,), F32), ((256,), F32)]),
    }


@pytest.mark.parametrize("kernel", ("window_attention", "global_attention",
                                    "neck_conv1", "neck_conv2",
                                    "neck_layernorm"))
def test_sam_kernel_compiles_for_v5e(kernel, one_chip, compiled_path):
    fn, shapes = _sam_cases()[kernel]
    args = [jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)
            for shape, dtype in shapes]
    ops.reset_attention_tiling()
    text = _compile(fn, *args).as_text()
    assert "tpu_custom_call" in text
    if kernel == "global_attention":
        # one k block covers the 4,096 keys: the whole-row reading
        assert "mxint_flash_attention" in text
        (tiling,) = ops.attention_tiling()
        assert tiling == {"path": "flash", "block_q": 256, "block_k": 4096,
                          "k_blocks": 1, "head_dim": 128, "keys_padded": 0,
                          "calls": 1}
    if kernel == "window_attention":
        assert "mxint_softmax" in text


def test_sam_vit_b_forward_compiles_for_v5e(one_chip, compiled_path):
    from repro.models import build_model
    from repro.serving.engine import pack_params_mxint

    model = build_model(_sam_config())
    params = pack_params_mxint(jax.eval_shape(model.init, jax.random.key(0)),
                               KCFG.weight_fmt, abstract=True)
    params = jax.tree_util.tree_map(
        lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=one_chip),
        params)
    imgs = jax.ShapeDtypeStruct((SAM_BATCH, 1024, 1024, 3), jnp.float32,
                                sharding=one_chip)
    text = _compile(model.logits, params, imgs).as_text()
    # patch linear; in the scanned period body two window layers (LN->q,
    # k, v, softmax, out-proj, LN->wi, GELU, wo) and one global layer
    # (the same with the flash kernel for the softmax); the neck's two
    # linears and two LayerNorms
    assert text.count("tpu_custom_call") == 1 + 2 * 8 + 8 + 4
