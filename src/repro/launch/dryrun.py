import os
os.environ["XLA_FLAGS"] = (os.environ.get("REPRO_XLA_FLAGS") or
                           "--xla_force_host_platform_device_count=512")
"""Multi-pod dry-run: lower + compile every (arch x shape x mesh) cell.

The first two lines force 512 host platform devices BEFORE any jax import so
``make_production_mesh`` can build the 16x16 single-pod and 2x16x16
multi-pod meshes.  Never import this module from tests — run it as a
subprocess (`python -m repro.launch.dryrun ...`).

Per cell the dry-run:
  1. builds ShapeDtypeStruct inputs (launch.specs) — zero allocation;
  2. jits the train/prefill/decode step with NamedShardings derived from
     the Param logical axes (parallel.sharding);
  3. .lower().compile() — success proves the sharding config is coherent;
  4. records memory_analysis / cost_analysis / parsed collective bytes and
     the three roofline terms to a JSON artifact in experiments/dryrun/.

Serve cells run twice: weights in bf16 (float baseline) and packed MXInt
(the paper's format) — the Fig-10 comparison at cluster scale.
"""
import argparse
import dataclasses
import json
import traceback
from pathlib import Path

import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as P

from repro import telemetry as T
from repro.configs import ARCH_IDS, full_config, shape_supported, skip_reason
from repro.launch import hlo_analysis, specs as S
from repro.launch.mesh import make_production_mesh
from repro.models import build_model, shape_by_name, ALL_SHAPES
from repro.models.model_api import axes_tree
from repro.optim.adamw import AdamWConfig
from repro.parallel.sharding import (ShardingRules, logical_to_pspec,
                                     named_sharding_tree)
from repro.serving.engine import (make_decode_step, make_prefill_step,
                                  pack_params_mxint)
from repro.train.state import abstract_train_state, train_state_axes
from repro.train.step import make_train_step

OUT_DIR = Path(__file__).resolve().parents[3] / "experiments" / "dryrun"


def _axes_leaf(x):
    return isinstance(x, tuple) and all(a is None or isinstance(a, str)
                                        for a in x)


def _leaf_shape(val):
    """Shape of the value paired with an axes leaf: Param -> its value;
    MXTensor -> the mantissa plane (the exponent shares the spec)."""
    from repro.models.model_api import Param
    from repro.core.quantize import MXTensor
    if isinstance(val, Param):
        val = val.value
    if isinstance(val, MXTensor):
        val = val.mantissa
    return getattr(val, "shape", None)


def shardings_for(axes_pytree, rules: ShardingRules, mesh,
                  values_pytree=None):
    names = mesh.axis_names
    mesh_shape = dict(mesh.shape)

    def one(axes, val=None):
        shape = _leaf_shape(val) if val is not None else None
        return NamedSharding(mesh, logical_to_pspec(
            axes, rules, names, shape=shape, mesh_shape=mesh_shape))

    if values_pytree is None:
        return jax.tree_util.tree_map(one, axes_pytree, is_leaf=_axes_leaf)
    from repro.models.model_api import Param
    return jax.tree_util.tree_map(
        one, axes_pytree, values_pytree, is_leaf=_axes_leaf)


def _result(ok, mesh_name, arch, shape, kind, variant, extra=None,
            error=None, seconds=None):
    rec = {"arch": arch, "shape": shape, "mesh": mesh_name, "kind": kind,
           "variant": variant, "ok": ok, "compile_seconds": seconds}
    if extra:
        rec.update(extra)
    if error:
        rec["error"] = error
    return rec


def run_cell(arch: str, shape_name: str, mesh, mesh_name: str,
             rules: ShardingRules, variant: str = "bf16",
             grad_compression: bool = False,
             microbatches: int = 1):
    cfg = full_config(arch)
    shape = shape_by_name(shape_name)
    model = build_model(cfg)
    n_dev = mesh.size
    if shape.kind == "decode" and shape.global_batch < (
            mesh.shape.get("pod", 1) * mesh.shape.get("data", 1)):
        # long-context decode at batch 1: no batch DP possible — switch to
        # sequence-parallel KV (ring/local caches shard their seq dim over
        # 'data') and replicate the batch dim.
        rules = dataclasses.replace(rules, batch=None, kv_seq="data")
    # one span per cell compile: the wall-clock lands in the
    # span/dryrun/compile/ms histogram AND in this cell's record
    with T.span("dryrun/compile", devices=mesh.size) as sp:
        if shape.kind == "train":
            state = abstract_train_state(
                model, grad_compression=grad_compression,
                n_pods=mesh.shape.get("pod", 1))
            st_axes = train_state_axes(state)
            st_sh = shardings_for(st_axes, rules, mesh, state)
            batch, b_axes = S.batch_specs(cfg, shape, "train")
            b_sh = shardings_for(b_axes, rules, mesh, batch)
            step = make_train_step(
                model, lr_fn=lambda s: jnp.asarray(1e-4, jnp.float32),
                opt_cfg=AdamWConfig(), microbatches=microbatches,
                grad_compression=grad_compression, mesh=mesh)
            jitted = jax.jit(step, in_shardings=(st_sh, b_sh),
                             out_shardings=(st_sh, None),
                             donate_argnums=(0,))
            with jax.set_mesh(mesh):
                lowered = jitted.lower(state, batch)
                compiled = lowered.compile()
        else:
            params = jax.eval_shape(lambda: model.init(jax.random.key(0)))
            if variant == "mxint":
                from repro.core.mx_types import MXINT6_WEIGHT
                params = pack_params_mxint(
                    params, MXINT6_WEIGHT, abstract=True,
                    tp_shards=mesh.shape.get("model", 1))
            p_sh = shardings_for(axes_tree(params), rules, mesh, params)
            cache = S.decode_cache_specs(model, shape)
            c_sh = shardings_for(S.decode_cache_axes(model), rules, mesh,
                                 cache)
            if shape.kind == "prefill":
                batch, b_axes = S.batch_specs(cfg, shape, "prefill")
                b_sh = shardings_for(b_axes, rules, mesh, batch)
                step = make_prefill_step(model)
                jitted = jax.jit(step, in_shardings=(p_sh, b_sh, c_sh),
                                 out_shardings=(None, c_sh),
                                 donate_argnums=(2,))
                with jax.set_mesh(mesh):
                    lowered = jitted.lower(params, batch, cache)
                    compiled = lowered.compile()
            else:
                batch, b_axes = S.batch_specs(cfg, shape, "decode")
                tok_sh = shardings_for(b_axes, rules, mesh, batch)["tokens"]
                step = make_decode_step(model)
                jitted = jax.jit(step, in_shardings=(p_sh, tok_sh, c_sh),
                                 out_shardings=(tok_sh, c_sh),
                                 donate_argnums=(2,))
                with jax.set_mesh(mesh):
                    lowered = jitted.lower(params, batch["tokens"], cache)
                    compiled = lowered.compile()

    seconds = sp.elapsed_s
    if os.environ.get("REPRO_DUMP_HLO"):
        import gzip
        dump = (OUT_DIR.parent / "hlo" /
                f"{arch}.{shape_name}.{mesh_name}.{variant}.hlo.gz")
        dump.parent.mkdir(parents=True, exist_ok=True)
        with gzip.open(dump, "wt") as fh:
            fh.write(compiled.as_text())
    mf = hlo_analysis.model_flops_estimate(cfg, shape, n_dev)
    roof = hlo_analysis.roofline_from_compiled(compiled, model_flops=mf)
    ma = compiled.memory_analysis()
    extra = {
        "roofline": roof.as_dict(),
        "memory": {
            "argument_bytes": ma.argument_size_in_bytes,
            "output_bytes": ma.output_size_in_bytes,
            "temp_bytes": ma.temp_size_in_bytes,
            "alias_bytes": ma.alias_size_in_bytes,
            # donated caches/state alias their outputs; peak ~= args + temps
            "peak_device_bytes": (ma.argument_size_in_bytes +
                                  ma.temp_size_in_bytes),
            "total_device_bytes": (ma.argument_size_in_bytes +
                                   ma.output_size_in_bytes +
                                   ma.temp_size_in_bytes),
        },
        "n_devices": n_dev,
    }
    del compiled, lowered
    return extra, seconds


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="all",
                    help="arch id or 'all'")
    ap.add_argument("--shape", default="all",
                    help="shape name or 'all'")
    ap.add_argument("--mesh", default="both",
                    choices=["single", "multi", "both", "tiny_single",
                             "tiny_multi"],
                    help="tiny_* use a 2x2 / 2x2x2 mesh for CI-scale "
                         "machinery tests (set REPRO_XLA_FLAGS to force a "
                         "small device count)")
    ap.add_argument("--variant", default="auto",
                    help="bf16 | mxint | auto (serve cells run both)")
    ap.add_argument("--grad-compression", action="store_true")
    ap.add_argument("--microbatches", type=int, default=1)
    ap.add_argument("--rules", default="",
                    help="comma list rule=axis overrides, e.g. "
                         "fsdp=data,kv_seq=data")
    ap.add_argument("--out", default=str(OUT_DIR))
    ap.add_argument("--tag", default="")
    args = ap.parse_args()

    rules = ShardingRules()
    if args.rules:
        overrides = {}
        for kv in args.rules.split(","):
            k, _, v = kv.partition("=")
            overrides[k.strip()] = (None if v in ("", "None", "none")
                                    else v.strip())
        rules = dataclasses.replace(rules, **overrides)

    out_dir = Path(args.out)
    out_dir.mkdir(parents=True, exist_ok=True)

    archs = ARCH_IDS if args.arch == "all" else [args.arch]
    shapes = [s.name for s in ALL_SHAPES] if args.shape == "all" \
        else [args.shape]
    from repro.launch.mesh import make_test_mesh
    mesh_builders = {
        "single": [("single_16x16",
                    lambda: make_production_mesh(multi_pod=False))],
        "multi": [("multi_2x16x16",
                   lambda: make_production_mesh(multi_pod=True))],
        "both": [("single_16x16",
                  lambda: make_production_mesh(multi_pod=False)),
                 ("multi_2x16x16",
                  lambda: make_production_mesh(multi_pod=True))],
        "tiny_single": [("tiny_2x2",
                         lambda: make_test_mesh((2, 2),
                                                ("data", "model")))],
        "tiny_multi": [("tiny_2x2x2",
                        lambda: make_test_mesh((2, 2, 2),
                                               ("pod", "data", "model")))],
    }[args.mesh]

    results = []
    failures = 0
    for mesh_name, builder in mesh_builders:
        mesh = builder()
        for arch in archs:
            for shape_name in shapes:
                if not shape_supported(arch, shape_name):
                    results.append(_result(
                        True, mesh_name, arch, shape_name, "skip", "-",
                        extra={"skipped": True,
                               "reason": skip_reason(arch, shape_name)}))
                    continue
                kind = shape_by_name(shape_name).kind
                if args.variant != "auto":
                    variants = [args.variant]
                else:
                    variants = ["bf16"] if kind == "train" \
                        else ["bf16", "mxint"]
                for variant in variants:
                    tag = f"{arch}.{shape_name}.{mesh_name}.{variant}"
                    try:
                        extra, secs = run_cell(
                            arch, shape_name, mesh, mesh_name, rules,
                            variant=variant,
                            grad_compression=args.grad_compression,
                            microbatches=args.microbatches)
                        rec = _result(True, mesh_name, arch, shape_name,
                                      kind, variant, extra=extra,
                                      seconds=round(secs, 2))
                        print(f"[ok]   {tag}  compile={secs:.1f}s "
                              f"bottleneck={extra['roofline']['bottleneck']}",
                              flush=True)
                    except Exception:
                        failures += 1
                        rec = _result(False, mesh_name, arch, shape_name,
                                      kind, variant,
                                      error=traceback.format_exc())
                        print(f"[FAIL] {tag}", flush=True)
                        print(traceback.format_exc()[-2000:], flush=True)
                    results.append(rec)
                    fname = out_dir / (tag + (f".{args.tag}" if args.tag
                                              else "") + ".json")
                    fname.write_text(json.dumps(rec, indent=1))

    n_spans, mean_ms = T.span_stats("dryrun/compile")
    summary = {
        "cells": len(results),
        "failures": failures,
        "ok": failures == 0,
        "compile_spans": {"count": n_spans,
                          "mean_ms": round(mean_ms, 1)},
    }
    suffix = f".{args.tag}" if args.tag else ""
    (out_dir / f"summary.{args.mesh}.{args.arch}.{args.shape}{suffix}.json"
     ).write_text(json.dumps({"summary": summary, "results": results},
                             indent=1))
    print(json.dumps(summary))
    raise SystemExit(1 if failures else 0)


if __name__ == "__main__":
    main()
