"""Wall-time of the Pallas kernels (interpret mode on CPU) vs jnp oracles.

interpret=True timings are NOT TPU performance — they validate that the
kernels run and give a cost sanity check; the TPU performance story is the
roofline analysis (benchmarks/roofline.py).

Also reports the end-to-end DeiT execution-mode comparison: the same
forward pass in mode='off' (float), mode='sim' (XLA emulation of the MXInt
datapaths) and mode='kernel' (packed planes through the Pallas wrappers).
"""
from __future__ import annotations

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np

from benchmarks.common import timer
from repro.core import MXFormat, QuantConfig, quantize
from repro.kernels import ref
from repro.kernels.flash_attention import (flash_attention,
                                           flash_attention_decode)
from repro.kernels.mxint_gelu import mxint_gelu
from repro.kernels.mxint_layernorm import mxint_layernorm
from repro.kernels.mxint_matmul import mxint_matmul
from repro.kernels.mxint_softmax import mxint_softmax


def run():
    rng = np.random.default_rng(0)
    rows = []

    x = jnp.asarray(rng.normal(size=(128, 1024)).astype(np.float32))
    w = jnp.asarray(rng.normal(size=(1024, 512)).astype(np.float32) * 0.05)
    wq = quantize(w, MXFormat(6, 256), axis=0)

    t = timer(lambda: mxint_matmul(x, wq.mantissa, wq.exponent, w_block=256,
                                   bm=128, bn=128))
    rows.append(("kernel/mxint_matmul_128x1024x512", round(t, 1),
                 "pallas interpret"))
    t = timer(lambda: ref.mxint_matmul_ref(x, wq.mantissa, wq.exponent,
                                           w_block=256))
    rows.append(("kernel/mxint_matmul_ref", round(t, 1), "jnp oracle"))

    xl = jnp.asarray(rng.normal(size=(256, 768)).astype(np.float32))
    g, b = jnp.ones((768,)), jnp.zeros((768,))
    t = timer(lambda: mxint_layernorm(xl, g, b, block_rows=128))
    rows.append(("kernel/mxint_layernorm_256x768", round(t, 1), "pallas"))
    t = timer(lambda: ref.mxint_layernorm_ref(xl, g, b))
    rows.append(("kernel/mxint_layernorm_ref", round(t, 1), "jnp oracle"))

    t = timer(lambda: mxint_softmax(xl, block_rows=128))
    rows.append(("kernel/mxint_softmax_256x768", round(t, 1), "pallas"))
    t = timer(lambda: mxint_gelu(xl, block_rows=128))
    rows.append(("kernel/mxint_gelu_256x768", round(t, 1), "pallas"))

    q = jnp.asarray(rng.normal(size=(4, 256, 128)).astype(np.float32)) * 0.3
    k = jnp.asarray(rng.normal(size=(4, 256, 128)).astype(np.float32)) * 0.3
    v = jnp.asarray(rng.normal(size=(4, 256, 128)).astype(np.float32))
    t = timer(lambda: flash_attention(q, k, v, causal=True))
    rows.append(("kernel/flash_attention_float", round(t, 1), "pallas"))
    t = timer(lambda: flash_attention(q, k, v, causal=True,
                                      exp_mode="mxint"))
    rows.append(("kernel/flash_attention_mxint", round(t, 1),
                 "pallas, Eq14-19 exp datapath"))
    t = timer(lambda: flash_attention(q, k, v, causal=True,
                                      exp_mode="mxint",
                                      quantize_scores=True))
    rows.append(("kernel/flash_attention_mxint_flash", round(t, 1),
                 "pallas, full Eq14-20 blocked datapath"))

    # native cache layout: (b, hkv, g, d) queries, (b, W, hkv, d) rings
    qd = jnp.asarray(rng.normal(size=(2, 4, 4, 128)).astype(np.float32)) * 0.3
    kd = jnp.asarray(rng.normal(
        size=(2, 256, 4, 128)).astype(np.float32)) * 0.3
    vd = jnp.asarray(rng.normal(size=(2, 256, 4, 128)).astype(np.float32))
    valid = jnp.arange(256) <= 200
    t = timer(lambda: flash_attention_decode(qd, kd, vd, valid))
    rows.append(("kernel/flash_decode_float", round(t, 1),
                 "pallas, single-query cache-ring decode"))
    t = timer(lambda: flash_attention_decode(qd, kd, vd, valid,
                                             exp_mode="mxint",
                                             quantize_scores=True))
    rows.append(("kernel/flash_decode_mxint", round(t, 1),
                 "pallas, Eq14-20 decode datapath"))

    rows.extend(deit_mode_rows())
    rows.extend(deit_ln_fusion_rows())
    rows.extend(lm_batching_rows())
    return rows


def _ln_linear_hbm_bytes(rows: int, d: int, n: int, w_block: int,
                         n_linears: int, fused: bool,
                         act_bytes: int = 4) -> int:
    """Analytic HBM bytes for a pre-norm feeding ``n_linears`` linears.

    Interpret-mode counters for the DESIGN.md §12 accounting: the kernels
    are deterministic about what crosses HBM — activations at
    ``act_bytes``/elt, packed planes at 1 byte/elt (int8 mantissas +
    int8 shared exponents), outputs at 4 bytes/elt.  Unfused pays the
    LN write + per-linear read of the normalized tile; fused keeps it in
    VMEM (the x tile is re-read per fused call instead).
    """
    a = rows * d * act_bytes                     # one activation tile
    planes = d * n + (d // w_block) * n          # mantissa + exponent plane
    outs = rows * n * 4
    per_linear = planes + outs
    if fused:
        return n_linears * (a + per_linear)      # x read per fused call
    #         LN read + LN write   + per-linear read of y
    return (a + a) + n_linears * (a + per_linear)


def deit_ln_fusion_rows(archs=("deit_tiny", "deit_small"), batch: int = 1):
    """Fused vs unfused LN->qkv on DeiT shapes (ROADMAP fused-LN item).

    Wall-clocks are CPU interpret mode (validity, not TPU perf); the
    HBM-byte rows are the meaningful counters — the fused composite
    moves strictly fewer bytes (the normalized tile never leaves VMEM),
    which on TPU is the win for these bandwidth-bound blocks.
    """
    from repro.configs.deit import BY_NAME
    from repro.core.quantize import pack_weight
    from repro.kernels import ops

    q = QuantConfig(mode="kernel", quantize_nonlinear=True)
    rng = np.random.default_rng(0)
    rows = []
    for arch in archs:
        cfg = BY_NAME[arch]
        d = cfg.d_model
        seq = (cfg.image_size // cfg.patch_size) ** 2 + 1
        M = batch * seq
        x = jnp.asarray(rng.normal(size=(M, d)).astype(np.float32))
        g = jnp.ones((d,), jnp.float32)
        b = jnp.zeros((d,), jnp.float32)
        wqkv = [pack_weight(
            jnp.asarray(rng.normal(size=(d, d)).astype(np.float32) * 0.05),
            q.weight_fmt, axis=0) for _ in range(3)]
        w_block = wqkv[0].block_size
        kw = dict(act_block=q.act_fmt.block_size,
                  mant_bits=q.act_fmt.mant_bits,
                  lut_bits=q.nonlinear.ln_lut_bits)

        def unfused():
            h = ops.mxint_layernorm_op(x, g, b, quantize_out=True, **kw)
            return [ops.mxint_linear(
                h, w.mantissa, w.exponent, w_block=w_block,
                quantize_act=True, act_block=q.act_fmt.block_size,
                act_mant_bits=q.act_fmt.mant_bits) for w in wqkv]

        def fused():
            return [ops.mxint_ln_linear_op(
                x, g, b, w.mantissa, w.exponent, w_block=w_block, **kw)
                for w in wqkv]

        # parity guard: the bench never times two different computations
        for got, want in zip(fused(), unfused()):
            np.testing.assert_array_equal(np.asarray(got), np.asarray(want))

        t_un = timer(lambda: unfused(), repeats=3)
        t_fu = timer(lambda: fused(), repeats=3)
        rows.append((f"kernel/{arch}_ln_qkv_unfused", round(t_un, 1),
                     "pallas interpret, LN kernel + 3 linear kernels"))
        rows.append((f"kernel/{arch}_ln_qkv_fused", round(t_fu, 1),
                     "pallas interpret, 3 fused LN->linear kernels"))
        hbm_un = _ln_linear_hbm_bytes(M, d, d, w_block, 3, fused=False)
        hbm_fu = _ln_linear_hbm_bytes(M, d, d, w_block, 3, fused=True)
        rows.append((f"kernel/{arch}_ln_qkv_hbm_bytes_unfused", hbm_un,
                     "activation+plane+output bytes over HBM"))
        rows.append((f"kernel/{arch}_ln_qkv_hbm_bytes_fused", hbm_fu,
                     f"normalized tile stays in VMEM "
                     f"(-{100 * (hbm_un - hbm_fu) // hbm_un}% bytes)"))
    return rows


def deit_mode_rows(archs=("deit_tiny", "deit_small"), batch: int = 1,
                   n_layers: int = 2):
    """off / sim / kernel wall-clock of a DeiT forward (CPU interpret).

    ``n_layers`` is truncated (the per-layer cost is uniform) so the CPU
    bench stays minutes-scale; relative mode cost is what matters here —
    absolute TPU numbers come from the roofline.
    """
    from repro.configs.deit import BY_NAME
    from repro.models import build_model
    from repro.serving.engine import pack_params_mxint

    modes = {
        "off": (QuantConfig(mode="off"), False),
        "sim": (QuantConfig(mode="sim", quantize_nonlinear=True), False),
        "kernel": (QuantConfig(mode="kernel", quantize_nonlinear=True),
                   True),
    }
    rows = []
    rng = np.random.default_rng(0)
    for arch in archs:
        cfg = dataclasses.replace(BY_NAME[arch], n_layers=n_layers)
        imgs = jnp.asarray(rng.normal(
            size=(batch, cfg.image_size, cfg.image_size, 3))
            .astype(np.float32))
        params = build_model(cfg).init(jax.random.key(0))
        for mode, (qcfg, pack) in modes.items():
            model = build_model(dataclasses.replace(cfg, quant=qcfg))
            p = pack_params_mxint(params, qcfg.weight_fmt) if pack else params
            fwd = jax.jit(model.logits)
            t = timer(lambda: fwd(p, imgs), repeats=3)
            rows.append((f"kernel/{arch}_L{n_layers}_forward_{mode}",
                         round(t, 1),
                         "pallas interpret" if mode == "kernel"
                         else "xla"))
    return rows


def lm_batching_rows(batch: int = 4, n_requests: int = 16):
    """Slot vs wave continuous batching on a ragged decode workload.

    Same engine, same requests, same per-row index datapath — only the
    admission policy differs.  The workload alternates short and long
    ``max_new_tokens`` so wave admission (slots freed only when the whole
    batch drains) strands capacity behind each long tail while slot
    admission refills freed rows immediately.  CPU wall-clock, xla mode
    (mode='off') — the ratio, not the absolute tokens/sec, is the point.
    """
    import time

    from repro.models.model_api import ModelConfig
    from repro.models.transformer import DecoderLM
    from repro.serving.engine import ServeConfig, ServingEngine
    from repro.serving.scheduler import BatchScheduler, Request

    cfg = ModelConfig(n_layers=2, d_model=64, n_heads=4, n_kv_heads=2,
                      d_ff=128, vocab=128, ffn_kind="gelu",
                      dtype=jnp.float32, quant=QuantConfig(mode="off"))
    model = DecoderLM(cfg)
    params = model.init(jax.random.key(0))
    eng = ServingEngine(model, params, ServeConfig(max_len=96, batch=batch))

    def stream():
        rng = np.random.default_rng(0)             # identical every replay
        reqs = []
        for uid in range(n_requests):
            plen = int(rng.integers(2, 12))
            max_new = 48 if uid % batch == 0 else 4    # heavy ragged tail
            prompt = rng.integers(1, 128, plen).astype(np.int32)
            reqs.append(Request(uid=uid, prompt=prompt,
                                max_new_tokens=max_new))
        return reqs

    def bench(admission):
        sched = BatchScheduler(eng, batch_size=batch, prefill_len=16,
                               admission=admission)
        for r in stream():
            sched.submit(r)
        sched.run()                                    # warm the jits
        sched = BatchScheduler(eng, batch_size=batch, prefill_len=16,
                               admission=admission)
        for r in stream():
            sched.submit(r)
        t0 = time.perf_counter()
        done = sched.run()
        dt = time.perf_counter() - t0
        toks = sum(len(r.generated) for r in done)
        assert len(done) == n_requests
        return toks / dt

    rows = []
    wave = bench("wave")
    slot = bench("slot")
    rows.append(("kernel/lm_batching_wave_tok_s", round(wave, 1),
                 "wave-synchronous admission, ragged max_new"))
    rows.append(("kernel/lm_batching_slot_tok_s", round(slot, 1),
                 "slot-level admission, same workload"))
    rows.append(("kernel/lm_batching_slot_speedup", round(slot / wave, 2),
                 "slot / wave decode throughput"))
    return rows


if __name__ == "__main__":
    for r in run():
        print(",".join(map(str, r)))
