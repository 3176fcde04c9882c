"""The system under test, as a user drives it.

Everything the benchmark takes from the program goes through here or
through the program side of the configuration's family module
(``bench/families/<family>.py``: the model built from the configuration
and the per-op cases on the engine's packed weights): the datapath the
configuration states, ``ViTServingEngine`` with packed weights,
``ClassifyScheduler`` and the program's own counters.  Imports of
``repro`` stay inside functions, so the benchmark's other modules load
without it.
"""
from __future__ import annotations

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


def scheduler(cfg: dict, weights: dict, batch: int):
    """(engine, scheduler) serving ``weights`` (the benchmark's tree)
    with packed MXInt planes at a fixed batch."""
    from repro.serving.engine import ServeConfig, ViTServingEngine
    from repro.serving.scheduler import ClassifyScheduler
    from bench.harness.manifest import family
    from bench.harness.weights import to_program
    m = family(cfg["family"]).model(cfg)
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

