"""Sharded kernel-mode serving self-check (DESIGN.md §10).

Runs in the calling process, on the devices that process already has
(``run_checks`` for callers, ``main`` for the command line).  On the CPU
the fake host devices must exist before JAX starts, so set them in the
environment of a fresh process:

    XLA_FLAGS=--xla_force_host_platform_device_count=2 \
        PYTHONPATH=src python -m repro.serving.sharded_check [--tp 2]

Checks, emitted as one JSON object on stdout:
  1. PARITY — DeiT-Tiny-shape ``classify()`` on the sharded kernel-mode
     engine (packed int8 planes partitioned over the mesh, every linear
     through ``mxint_linear`` per shard under shard_map) equals the
     single-device ``mode='sim'`` XLA oracle BIT-FOR-BIT with the default
     column strategy; the row/psum strategy is reported with its max
     deviation (expected small, nonzero).
  2. SCHEDULING — a mixed-size request stream through
     ``ClassifyScheduler`` sustains a fixed-shape jit: after the warmup
     batch, the jit cache stays at ONE specialization.
  3. --dp N — the mesh grows a 'data' axis: batch rows shard over N data
     shards COMPOSED with the 'model' TP shards (one engine scales both
     axes).  Batch sharding is trivially bit-exact, so the same bitwise
     parity assertions run against the dp x tp engine, plus a dp-only
     (tp=1) engine when enough devices exist.
"""
import argparse
import dataclasses
import json
import sys

import jax
import numpy as np

from repro import telemetry as T
from repro.configs.deit import DEIT_TINY
from repro.core.mx_types import QuantConfig
from repro.launch.mesh import make_serving_mesh, make_tp_mesh
from repro.models import build_model
from repro.serving.engine import ServeConfig, ViTServingEngine
from repro.serving.scheduler import ClassifyRequest, ClassifyScheduler

SIM = QuantConfig(mode="sim", quantize_nonlinear=True)
KERNEL = QuantConfig(mode="kernel", quantize_nonlinear=True)


def _models(n_layers: int, n_classes: int):
    cfg = dataclasses.replace(DEIT_TINY, n_layers=n_layers,
                              n_classes=n_classes)
    m_sim = build_model(dataclasses.replace(cfg, quant=SIM))
    m_ker = build_model(dataclasses.replace(cfg, quant=KERNEL))
    params = m_sim.init(jax.random.key(0))
    return cfg, m_sim, m_ker, params


def _engine(m_ker, params, batch: int, mesh, strategy: str):
    return ViTServingEngine(
        m_ker, params,
        ServeConfig(batch=batch, pack_weights=True,
                    weight_fmt=KERNEL.weight_fmt, tp_strategy=strategy),
        mesh=mesh)


def parity_check(m_sim, m_ker, params, mesh, batch: int, image_size: int):
    rng = np.random.default_rng(0)
    imgs = np.asarray(rng.normal(size=(batch, image_size, image_size, 3)),
                      np.float32)
    want = np.asarray(jax.jit(m_sim.logits)(params, imgs))
    out = {}
    for strategy in ("column", "row"):
        eng = _engine(m_ker, params, batch, mesh, strategy)
        _, logits = eng.classify(imgs)
        got = np.asarray(logits)
        out[strategy] = {
            "bit_exact": bool(np.array_equal(got, want)),
            "max_abs_diff": float(np.max(np.abs(got - want))),
        }
    return out


def scheduler_check(m_ker, params, mesh, batch: int, image_size: int,
                    sizes=(3, 5, 1, 8, 2, 7, 4)):
    """Mixed request sizes; zero recompiles after the warmup step."""
    eng = _engine(m_ker, params, batch, mesh, "column")
    sched = ClassifyScheduler(eng)
    rng = np.random.default_rng(1)
    warm = np.asarray(rng.normal(size=(batch, image_size, image_size, 3)),
                      np.float32)
    eng.classify(warm)                          # warmup: 1 specialization
    cache_after_warmup = eng.jit_cache_size()
    for uid, n in enumerate(sizes):
        sched.submit(ClassifyRequest(
            uid=uid, images=np.asarray(
                rng.normal(size=(n, image_size, image_size, 3)), np.float32)))
    done = sched.run()
    ok_results = all(
        r.done and r.logits.shape == (sizes[r.uid], m_ker.cfg.n_classes)
        for r in done)
    return {
        "requests": len(done),
        "images": int(sum(sizes)),
        "all_classified": bool(ok_results and len(done) == len(sizes)),
        "jit_cache_after_warmup": cache_after_warmup,
        "jit_cache_after_stream": eng.jit_cache_size(),
        "recompiles_after_warmup":
            eng.jit_cache_size() - cache_after_warmup,
        # the telemetry view of the same contract (DESIGN.md §15): the
        # scheduler folds jit-cache deltas into this counter per step
        "recompiles_counter": T.counter("serving/recompiles").value,
    }


def run_checks(tp: int = 2, dp: int = 1, layers: int = 2,
               classes: int = 100, batch: int = 4) -> dict:
    """Parity + scheduling report on a dp x tp mesh; ``report['ok']``
    holds the verdict."""
    mesh = make_serving_mesh(dp, tp) if dp > 1 else make_tp_mesh(tp)
    cfg, m_sim, m_ker, params = _models(layers, classes)
    report = {
        "devices": jax.device_count(),
        "tp": tp,
        "dp": dp,
        "arch": f"deit_tiny_L{layers}",
        "parity": parity_check(m_sim, m_ker, params, mesh, batch,
                               cfg.image_size),
        "scheduler": scheduler_check(m_ker, params, mesh, batch,
                                     cfg.image_size),
    }
    ok = (report["parity"]["column"]["bit_exact"] and
          report["scheduler"]["all_classified"] and
          report["scheduler"]["recompiles_after_warmup"] == 0)
    if dp > 1:
        # data-only engine (tp=1): batch shards, planes replicated — the
        # minimal 'data' axis configuration must be bit-exact too
        dp_mesh = make_serving_mesh(dp, 1)
        report["parity_dp_only"] = parity_check(
            m_sim, m_ker, params, dp_mesh, batch, cfg.image_size)
        ok = ok and report["parity_dp_only"]["column"]["bit_exact"]
    report["ok"] = bool(ok)
    return report


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--tp", type=int, default=2, help="model-axis shards")
    ap.add_argument("--dp", type=int, default=1, help="data-axis shards "
                    "(batch sharding composed with TP)")
    ap.add_argument("--layers", type=int, default=2)
    ap.add_argument("--classes", type=int, default=100)
    ap.add_argument("--batch", type=int, default=4)
    args = ap.parse_args(argv)
    report = run_checks(args.tp, args.dp, args.layers, args.classes,
                        args.batch)
    print(json.dumps(report))
    return 0 if report["ok"] else 1


if __name__ == "__main__":
    sys.exit(main())
