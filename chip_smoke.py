"""Smoke run of the paper's deployment on a TPU chip.

Serves DeiT-Base at its published widths (12 layers, d=768, 12 heads,
ff=3072, 224x224 images = 197 tokens, 1000 classes) in ``mode='kernel'``
with every non-linear op on the MXInt datapath: packed int8 weight planes
and every linear, norm, GELU and softmax in a Pallas kernel, through the
entry points a user calls (``ViTServingEngine`` + ``ClassifyScheduler``).
The weights are random, drawn from ``--seed``; no file is read.

    python chip_smoke.py             # one chip: serve, then check
    python chip_smoke.py --chips 4   # only the sharded phase, on 4 chips

It fails, and prints no result, when JAX finds no TPU.  On success the
last line of standard output is one JSON object:
``{"ok": true, "device": {"platform": "tpu", "kind": ..., "count": N}}``.
Earlier lines are a smoke run's readings, not a benchmark.
"""
from __future__ import annotations

import argparse
import dataclasses
import json
import sys
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parent / "src"))

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402

# The reference is the 'sim' oracle at "highest" matmul precision: the
# same MXInt datapath in XLA, in f32.  Kernel and reference differ only
# where f32 summation order or the last bit of an elementwise op moves a
# value across a quantizer rounding boundary (every matmul operand of a
# quantized linear is a <=8-bit mantissa times a power of two, exact in
# any MXU pass).  Each such flip moves one element by one act-grid step,
# 2^-7 of its block's max, or one LayerNorm row by one rsqrt-LUT step.
#
# Served logits.  A flip in one layer spreads through attention to every
# token and seeds more flips in the next, so two f32 implementations of
# this datapath part by a few percent at the logits: scaling the input
# images by (1 + 2^-20) moves the DeiT-Base 'sim' logits by 5.1e-2 rel
# RMS (seed 0, on the CPU), and the run reports that floor again
# (``sim_noise_floor``).  The end-to-end bound is that floor with room,
# still below the float model ('off' sits at 1.2e-1 rel RMS from 'sim'):
# it catches a wrong weight, layer or op, not a precision loss.
E2E_REL_RMS_TOL = 8e-2
E2E_REL_MAX_TOL = 1e-1

# Per op.  Each op of the block runs in its kernel and in the reference
# on the SAME inputs at the model's widths, so flips cannot compound: a
# handful at most in a tile of 10^6 values.  OP_REL_RMS_TOL bounds the
# RMS error over the RMS output.  OP_REL_MAX_TOL allows one flip of the
# largest kind: one rsqrt-LUT step on a LayerNorm row (entries 2^-5
# apart), or four act-grid steps of the largest value.  A reference run
# in one bf16 pass rounds attention's f32 scores and values to 8
# significant bits: emulated on the CPU at DeiT-Base widths it misses
# the attention op by 2.2e-2 rel RMS, 22x OP_REL_RMS_TOL.  The one-chip
# run checks that a bf16 reference fails.
OP_REL_RMS_TOL = 1e-3
OP_REL_MAX_TOL = 2 ** -5

# classify program of a scanned L-layer DeiT: patch linear, the 8 kernels
# of the scanned block body (3 fused LN->q/k/v, softmax, out-proj, fused
# LN->wi, GELU, wo), final LayerNorm and head
EXPECTED_KERNELS = 1 + 8 + 1 + 1

REQUEST_SIZES = (3, 8, 1, 5)
BATCH = 8


def tpu_devices():
    """JAX's devices, or None (after saying why) when they are not TPUs."""
    devs = jax.devices()
    if devs[0].platform != "tpu":
        print(f"chip_smoke: needs a TPU; JAX found {devs[0].platform!r}",
              file=sys.stderr)
        return None
    return devs


def build_models(cfg, seed: int):
    """(kernel, sim, off) models of one config and its seeded params."""
    from repro.core.mx_types import QuantConfig
    from repro.models import build_model

    kcfg = QuantConfig(mode="kernel", quantize_nonlinear=True)
    scfg = QuantConfig(mode="sim", quantize_nonlinear=True)
    m_ker = build_model(dataclasses.replace(cfg, quant=kcfg))
    m_sim = build_model(dataclasses.replace(cfg, quant=scfg))
    m_off = build_model(dataclasses.replace(cfg, quant=QuantConfig()))
    params = m_ker.init(jax.random.key(seed))
    return m_ker, m_sim, m_off, params


def make_images(n: int, size: int, seed: int) -> np.ndarray:
    rng = np.random.default_rng(seed)
    return rng.normal(size=(n, size, size, 3)).astype(np.float32)


def make_engine(m_ker, params, batch: int, mesh=None):
    from repro.serving.engine import ServeConfig, ViTServingEngine
    return ViTServingEngine(
        m_ker, params,
        ServeConfig(batch=batch, pack_weights=True,
                    weight_fmt=m_ker.cfg.quant.weight_fmt),
        mesh=mesh)


def serve(engine, images: np.ndarray, sizes) -> np.ndarray:
    """Submit ``sizes``-image requests cut from ``images`` in order,
    drain the scheduler; returns the logits in image order."""
    from repro.serving.scheduler import ClassifyRequest, ClassifyScheduler
    sched = ClassifyScheduler(engine)
    off = 0
    for uid, n in enumerate(sizes):
        sched.submit(ClassifyRequest(uid=uid, images=images[off:off + n]))
        off += n
    done = sorted(sched.run(), key=lambda r: r.uid)
    if [r.uid for r in done] != list(range(len(sizes))):
        raise RuntimeError(f"served {[r.uid for r in done]}, "
                           f"submitted {len(sizes)} requests")
    return np.concatenate([r.logits for r in done])


def reference_logits(model, params, images, precision: str) -> np.ndarray:
    with jax.default_matmul_precision(precision):
        return np.asarray(jax.jit(model.logits)(params, jnp.asarray(images)))


def errors(got, want) -> dict:
    d = np.asarray(got, np.float64) - np.asarray(want, np.float64)
    w = np.asarray(want, np.float64)
    return {
        "rel_max": float(np.max(np.abs(d)) / np.max(np.abs(w))),
        "rel_rms": float(np.sqrt(np.mean(d * d)) / np.sqrt(np.mean(w * w))),
        "bit_exact": bool(np.array_equal(got, want)),
    }


def within(err: dict, rms_tol: float, max_tol: float) -> bool:
    return err["rel_rms"] <= rms_tol and err["rel_max"] <= max_tol


def argmax_agree(got: np.ndarray, want: np.ndarray) -> float:
    return float(np.mean(np.argmax(got, -1) == np.argmax(want, -1)))


def op_cases(cfg, batch: int, seed: int) -> dict:
    """name -> (fn(q, *args), args) for each op a DeiT block runs, at the
    config's widths.  ``fn`` calls the model's own layer entry points;
    inputs are seeded and the weights packed as the engine packs them."""
    from repro.core.mx_types import QuantConfig
    from repro.models import layers as L
    from repro.models.model_api import Param
    from repro.serving.engine import pack_params_mxint

    rng = np.random.default_rng(seed)
    d, ff, heads = cfg.d_model, cfg.d_ff, cfg.n_heads
    hd = d // heads
    tokens = (cfg.image_size // cfg.patch_size) ** 2 + 1
    eps = cfg.norm_eps

    def arr(*shape, scale=1.0):
        return jnp.asarray(rng.normal(scale=scale, size=shape)
                           .astype(np.float32))

    def linear_params(k, n):
        p = pack_params_mxint(
            {"w": Param(arr(k, n, scale=k ** -0.5), ("embed", "mlp")),
             "b": Param(arr(n, scale=0.02), ("mlp",))},
            QuantConfig(mode="kernel").weight_fmt)
        return p["w"], p["b"]

    def norm_params():
        return (Param(1.0 + arr(d, scale=0.1), ("embed",)),
                Param(arr(d, scale=0.1), ("embed",)))

    def attention(q, qv, k, v):
        return q.datapath.attention(
            qv, k, v, q=q, positions=jnp.arange(tokens)[None, :],
            causal=False, window=0, scale=hd ** -0.5, chunk=tokens)

    x = arr(batch, tokens, d)
    return {
        "ln_linear": (lambda q, x, g, b, w, wb: L.layernorm_linear(
            x, g, b, w, wb, q=q, eps=eps), (x, *norm_params(),
                                            *linear_params(d, ff))),
        "attention": (attention, (arr(batch, tokens, heads, 1, hd),
                                  arr(batch, tokens, heads, hd),
                                  arr(batch, tokens, heads, hd))),
        "linear": (lambda q, h, w, wb: L.linear(h, w, wb, q=q),
                   (arr(batch, tokens, ff), *linear_params(ff, d))),
        "gelu": (lambda q, h: L.act_fn(h, "gelu", q),
                 (arr(batch, tokens, ff),)),
        "layernorm": (lambda q, x, g, b: L.layernorm(x, g, b, q=q, eps=eps),
                      (x, *norm_params())),
    }


def op_report(cfg, batch: int, seed: int, *, probe_bf16: bool):
    """Kernel vs 'sim' ("highest") per op on identical inputs; with
    ``probe_bf16`` also the 'sim' op at one bf16 pass vs "highest"."""
    from repro.core.mx_types import QuantConfig

    kq = QuantConfig(mode="kernel", quantize_nonlinear=True)
    sq = QuantConfig(mode="sim", quantize_nonlinear=True)
    report, bf16 = {}, {}
    for name, (fn, args) in op_cases(cfg, batch, seed).items():
        got = jax.jit(lambda *a: fn(kq, *a))(*args)
        with jax.default_matmul_precision("highest"):
            want = jax.jit(lambda *a: fn(sq, *a))(*args)
        report[name] = errors(got, want)
        if probe_bf16:
            with jax.default_matmul_precision("bfloat16"):
                low = jax.jit(lambda *a: fn(sq, *a))(*args)
            bf16[name] = errors(low, want)
    return report, bf16


def count_kernels(engine, batch: int) -> int:
    """Pallas custom calls in the compiled classify program."""
    size = engine.model.cfg.image_size
    chunk = jax.ShapeDtypeStruct((batch, size, size, 3), jnp.float32)
    text = engine._logits.lower(engine.params, chunk).compile().as_text()
    return text.count("tpu_custom_call")


def one_chip_phase(cfg, seed: int, *, batch: int = BATCH,
                   sizes=REQUEST_SIZES):
    """Serve mixed-size requests in kernel mode and check them.

    Returns (report, failures).  Off the TPU (the CPU test, Pallas in
    interpret mode) the two checks only a TPU can make are skipped: the
    compiled program's kernel count and the bf16-reference probe (the
    CPU computes every f32 matmul in f32 whatever the precision).
    """
    from repro import telemetry as T
    from repro.kernels import ops

    on_chip = ops.on_tpu()
    ops.reset_attention_fallbacks()
    m_ker, m_sim, m_off, params = build_models(cfg, seed)
    images = make_images(sum(sizes), cfg.image_size, seed + 1)
    engine = make_engine(m_ker, params, batch)
    failures = []
    report = {}

    with T.span("smoke/warmup") as sp:
        jax.block_until_ready(engine.logits_batch(
            make_images(batch, cfg.image_size, seed + 2)))
    report["warmup_compile_s"] = sp.elapsed_s
    if on_chip:
        report["pallas_kernels"] = count_kernels(engine, batch)
        if report["pallas_kernels"] != EXPECTED_KERNELS:
            failures.append(f"{report['pallas_kernels']} Pallas kernels in "
                            f"the classify program, want {EXPECTED_KERNELS}")
    cache_warm = engine.jit_cache_size()
    recompiles_before = T.counter("serving/recompiles").value

    with T.span("smoke/serve") as sp:
        got = serve(engine, images, sizes)
    report["serve_ms_smoke"] = sp.elapsed_s * 1e3
    report["ms_per_request_smoke"] = sp.elapsed_s * 1e3 / len(sizes)
    report["recompiles"] = engine.jit_cache_size() - cache_warm
    report["recompiles_counter"] = (T.counter("serving/recompiles").value -
                                    recompiles_before)
    if report["recompiles"] or report["recompiles_counter"]:
        failures.append(f"recompiled after warm-up: {report['recompiles']} "
                        f"jit entries, counter "
                        f"{report['recompiles_counter']}")

    want = reference_logits(m_sim, params, images, "highest")
    report["vs_sim_highest"] = errors(got, want)
    report["vs_sim_highest"]["argmax_agree"] = argmax_agree(got, want)
    report["sim_noise_floor"] = errors(
        reference_logits(m_sim, params, images * (1 + 2.0 ** -20),
                         "highest"), want)
    if not within(report["vs_sim_highest"], E2E_REL_RMS_TOL,
                  E2E_REL_MAX_TOL):
        failures.append(f"kernel vs sim logits out of tolerance: "
                        f"{report['vs_sim_highest']}")
    off = reference_logits(m_off, params, images, "highest")
    report["vs_off_highest"] = errors(got, off)
    report["vs_off_highest"]["argmax_agree"] = argmax_agree(got, off)

    report["ops_vs_sim_highest"], bf16 = op_report(
        cfg, batch, seed + 3, probe_bf16=on_chip)
    for name, err in report["ops_vs_sim_highest"].items():
        if not within(err, OP_REL_RMS_TOL, OP_REL_MAX_TOL):
            failures.append(f"{name}: kernel vs sim out of tolerance: {err}")
    if on_chip:
        report["ops_bf16_sim_vs_sim_highest"] = bf16
        if all(within(e, OP_REL_RMS_TOL, OP_REL_MAX_TOL)
               for e in bf16.values()):
            failures.append("a one-pass bf16 reference passes every op "
                            "tolerance; they cannot tell precision apart")

    report["fallbacks"] = ops.attention_fallback_counts()
    if report["fallbacks"]:
        failures.append(f"XLA fallbacks taken: {report['fallbacks']}")
    if not np.all(np.isfinite(got)) or got.shape != (sum(sizes),
                                                     cfg.n_classes):
        failures.append(f"bad logits: shape {got.shape}")
    return report, failures


def four_chip_phase(cfg, seed: int, *, batch: int = BATCH,
                    sizes=REQUEST_SIZES):
    """dp=2 x tp=2 column-parallel engine vs the one-device kernel engine
    on the same images.  Returns (report, failures)."""
    from repro.launch.mesh import make_serving_mesh

    m_ker, _, _, params = build_models(cfg, seed)
    images = make_images(sum(sizes), cfg.image_size, seed + 1)
    want = serve(make_engine(m_ker, params, batch), images, sizes)
    sharded = make_engine(m_ker, params, batch, mesh=make_serving_mesh(2, 2))
    got = serve(sharded, images, sizes)
    report = {"mesh": "data=2 x model=2, column",
              "vs_one_device_kernel": errors(got, want)}
    report["vs_one_device_kernel"]["argmax_agree"] = argmax_agree(got, want)
    failures = []
    if not within(report["vs_one_device_kernel"], E2E_REL_RMS_TOL,
                  E2E_REL_MAX_TOL):
        failures.append(f"sharded vs one-device kernel out of tolerance: "
                        f"{report['vs_one_device_kernel']}")
    return report, failures


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="4: run only the dp=2 x tp=2 sharded phase")
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)

    devs = tpu_devices()
    if devs is None:
        return 2
    if len(devs) < args.chips:
        print(f"chip_smoke: --chips {args.chips} needs {args.chips} TPU "
              f"devices; JAX found {len(devs)}", file=sys.stderr)
        return 2

    from repro.configs.deit import DEIT_BASE
    from repro.launch.compile_cache import use_persistent_compile_cache

    cache = use_persistent_compile_cache()
    print(f"device: {devs[0].device_kind} x{len(devs)}; compile cache "
          f"{cache}")
    if args.chips == 4:
        report, failures = four_chip_phase(DEIT_BASE, args.seed)
    else:
        report, failures = one_chip_phase(DEIT_BASE, args.seed)
    print(json.dumps({"smoke": report}, default=str))
    if failures:
        for f in failures:
            print(f"chip_smoke: FAILED: {f}", file=sys.stderr)
        return 1
    print(json.dumps({"ok": True, "device": {
        "platform": devs[0].platform, "kind": devs[0].device_kind,
        "count": len(devs)}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
