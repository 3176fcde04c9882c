"""The system under test, as a user drives it.

Everything the benchmark takes from the program goes through here: the
model built from a configuration file, ``ViTServingEngine`` with packed
weights, ``ClassifyScheduler``, the program's own counters, and its
layer entry points, fed the engine's own packed weights, for the per-op
comparison.  Imports of ``repro`` stay inside functions, so the
benchmark's other modules load without it.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np


def quant_config(cfg: dict):
    """The kernel-mode datapath the configuration states."""
    from repro.core.mx_types import MXFormat, NonlinearConfig, QuantConfig
    dp = cfg["datapath"]
    return QuantConfig(
        mode="kernel", quantize_nonlinear=True,
        weight_fmt=MXFormat(mant_bits=dp["weight_mant_bits"],
                            block_size=dp["weight_block"]),
        act_fmt=MXFormat(mant_bits=dp["act_mant_bits"],
                         block_size=dp["act_block"]),
        nonlinear=NonlinearConfig(
            ln_lut_bits=dp["layernorm_lut_bits"],
            gelu_domain=float(dp["gelu_domain"]),
            gelu_lut_bits=dp["gelu_lut_bits"],
            softmax_r_bits=dp["softmax_r_bits"]))


def model(cfg: dict):
    from repro.models import build_model
    from repro.models.model_api import ModelConfig
    heads = cfg["num_attention_heads"]
    return build_model(ModelConfig(
        name=cfg["name"], family="vit", n_layers=cfg["num_hidden_layers"],
        d_model=cfg["hidden_size"], n_heads=heads, n_kv_heads=heads,
        d_ff=cfg["intermediate_size"], vocab=0, unit=("attn",),
        ffn_kind="gelu", image_size=cfg["image_size"],
        patch_size=cfg["patch_size"], n_classes=cfg["num_labels"],
        dtype=jnp.float32, quant=quant_config(cfg)))


def scheduler(cfg: dict, weights: dict, batch: int):
    """(engine, scheduler) serving ``weights`` (the benchmark's tree)
    with packed MXInt planes at a fixed batch."""
    from repro.serving.engine import ServeConfig, ViTServingEngine
    from repro.serving.scheduler import ClassifyScheduler
    from bench.harness.weights import to_program
    m = model(cfg)
    engine = ViTServingEngine(
        m, to_program(weights, m),
        ServeConfig(batch=batch, pack_weights=True,
                    weight_fmt=m.cfg.quant.weight_fmt))
    return engine, ClassifyScheduler(engine)


def request(uid: int, images: np.ndarray):
    from repro.serving.scheduler import ClassifyRequest
    return ClassifyRequest(uid=uid, images=images)


def counters() -> dict:
    """The program's counters this benchmark reads."""
    from repro import telemetry as T
    steps, _ = T.span_stats("scheduler/classify_step")
    return {"images_classified": T.counter("scheduler/images_classified").value,
            "classify_steps": steps,
            "recompiles": T.counter("serving/recompiles").value}


def fallbacks() -> dict:
    from repro.kernels import ops
    return ops.attention_fallback_counts()


def ops(engine, inputs: dict) -> dict:
    """Each op of one block through the program's kernel-mode layer entry
    points, on the given inputs and layer 0 of the weights the engine
    serves (its packed planes), with the engine's model's datapath.
    Returns name -> output as numpy."""
    from repro.models import layers as L

    q = engine.model.cfg.quant
    layer = jax.tree_util.tree_map(lambda a: a[0], engine.params["blocks"])
    f = layer["ffn"]
    tokens = inputs["x"].shape[1]

    def attention(qv, k, v):
        b, s, h, d = qv.shape
        return q.datapath.attention(
            qv.reshape(b, s, h, 1, d), k, v, q=q,
            positions=jnp.arange(tokens)[None, :], causal=False, window=0,
            scale=d ** -0.5, chunk=tokens).reshape(b, s, h, d)

    cases = {
        "ln_linear": (lambda x, g, b, w, wb: L.layernorm_linear(
            x, g, b, w, wb, q=q),
            (inputs["x"], layer["ln2_g"], layer["ln2_b"], f["wi"], f["bi"])),
        "attention": (attention, (inputs["q"], inputs["k"], inputs["v"])),
        "linear": (lambda h, w, wb: L.linear(h, w, wb, q=q),
                   (inputs["h"], f["wo"], f["bo"])),
        "gelu": (lambda h: L.act_fn(h, "gelu", q), (inputs["h"],)),
        "layernorm": (lambda x, g, b: L.layernorm(x, g, b, q=q),
                      (inputs["x"], layer["ln1_g"], layer["ln1_b"])),
    }
    return {name: np.asarray(jax.jit(fn)(*args))
            for name, (fn, args) in cases.items()}
