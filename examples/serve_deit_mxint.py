"""End-to-end driver: serve a DeiT classifier fully quantized in MXInt.

This is the paper's deployment scenario — a ViT whose EVERY operator
(linears, LayerNorm, GELU, Softmax) runs the MXInt datapath — wrapped in a
batched inference service: requests arrive, are continuously batched into
one fixed-shape jit, classified, and answered; throughput and
accuracy-vs-float are reported.

The serving path is ``mode='kernel'``: weights are packed once into int8
mantissa/exponent planes and fed straight into the Pallas kernels through
``ViTServingEngine`` (on CPU the kernels run in interpret mode; on TPU
they compile).  The ``mode='sim'`` XLA oracle is also run and must agree
bit-for-bit — the serving datapath IS the validated datapath.

With ``--tp N`` the engine serves SHARDED: the packed planes are
partitioned over an N-way 'model' mesh and every linear runs per shard
under shard_map — still bit-identical to the single-device sim oracle
(DESIGN.md §10).  Under ``JAX_PLATFORMS=cpu`` the N fake host devices are
forced automatically; on a TPU host the mesh takes N chips.

Requests are streamed through ``ClassifyScheduler``: each request carries
a RANDOM number of images, and the scheduler packs them across request
boundaries into the fixed batch shape — zero recompiles after warmup.

``--metrics-json PATH`` dumps the full ``repro.telemetry`` snapshot of
the serving run — request-latency histograms, queue/slot gauges, the
``serving/recompiles`` counter (0 after warmup) — plus a
``predicted_vs_measured`` section joining live DeiT kernel probes
(``matmul-deit``, ``flash-deit``) against the static cost-model table
by row label (DESIGN.md §15).

Run:  PYTHONPATH=src python examples/serve_deit_mxint.py \
          [--requests 64] [--batch 16] [--tp 2] [--metrics-json out.json]
"""
import argparse
import dataclasses
import os
import sys
import time
from pathlib import Path


def _parse_args():
    ap = argparse.ArgumentParser()
    ap.add_argument("--requests", type=int, default=64,
                    help="total images to serve")
    ap.add_argument("--batch", type=int, default=16)
    ap.add_argument("--tp", type=int, default=1,
                    help="shard packed planes over an N-way 'model' mesh")
    ap.add_argument("--metrics-json", default=None, metavar="PATH",
                    help="write the telemetry snapshot + the "
                         "predicted-vs-measured kernel roofline here")
    return ap.parse_args()


def main():
    args = _parse_args()
    on_cpu = os.environ.get("JAX_PLATFORMS", "").split(",")[0] == "cpu"
    if args.tp > 1 and on_cpu and \
            "xla_force_host_platform_device_count" not in \
            os.environ.get("XLA_FLAGS", ""):
        # must land before the first jax device query (backend init)
        os.environ["XLA_FLAGS"] = (
            os.environ.get("XLA_FLAGS", "") +
            f" --xla_force_host_platform_device_count={args.tp}")

    import jax
    import jax.numpy as jnp
    import numpy as np

    sys.path.insert(0, str(Path(__file__).resolve().parents[1]))  # benchmarks/
    from benchmarks import common
    from repro.core.mx_types import QuantConfig
    from repro.data.pipeline import SyntheticImageData
    from repro.launch.compile_cache import use_persistent_compile_cache
    from repro.models import build_model
    from repro.serving.engine import ServeConfig, ViTServingEngine
    from repro.serving.scheduler import ClassifyRequest, ClassifyScheduler

    use_persistent_compile_cache()
    print(f"devices: {jax.devices()[0].platform} x{jax.device_count()}")
    print("training/loading the float DeiT (synthetic 100-class task)...")
    model_f, params = common.trained_deit_micro()

    mesh = None
    if args.tp > 1:
        from repro.launch.mesh import make_tp_mesh
        mesh = make_tp_mesh(args.tp)
        print(f"serving sharded: packed planes over a {args.tp}-way "
              "'model' mesh (column-parallel, bit-exact)")

    kcfg = QuantConfig(mode="kernel", quantize_nonlinear=True)
    model_k = build_model(dataclasses.replace(common.BENCH_DEIT, quant=kcfg))
    engine = ViTServingEngine(
        model_k, params,
        ServeConfig(batch=args.batch, pack_weights=True,
                    weight_fmt=kcfg.weight_fmt),
        mesh=mesh)

    scfg = QuantConfig(mode="sim", quantize_nonlinear=True)
    model_s = build_model(dataclasses.replace(common.BENCH_DEIT, quant=scfg))
    classify_s = jax.jit(model_s.logits)
    classify_f = jax.jit(model_f.logits)

    data = SyntheticImageData(batch=args.batch, seed=123, **common._TASK)
    # warm the one jit specialization, then stream mixed-size requests
    warm = data.next_batch()
    engine.classify(warm["images"])
    cache_warm = engine.jit_cache_size()

    rng = np.random.default_rng(7)
    sched = ClassifyScheduler(engine)
    pool_imgs, pool_labels = [], []
    served = 0
    uid = 0
    while served < args.requests:
        batch = data.next_batch()
        pool_imgs.append(np.asarray(batch["images"]))
        pool_labels.append(np.asarray(batch["labels"]))
        served += args.batch
    imgs = np.concatenate(pool_imgs)
    labels = np.concatenate(pool_labels)
    # slice the pool into randomly sized requests (1..batch images each)
    reqs, off = [], 0
    while off < imgs.shape[0]:
        n = int(rng.integers(1, args.batch + 1))
        reqs.append(ClassifyRequest(uid=uid, images=imgs[off:off + n]))
        uid += 1
        off += n

    t0 = time.time()
    for r in reqs:
        sched.submit(r)
    done = sched.run()
    dt = time.time() - t0

    pred = np.concatenate([r.labels for r in done])
    logits = np.concatenate([r.logits for r in done])
    ref = np.asarray(classify_f(params, imgs))
    sim = np.asarray(classify_s(params, imgs))
    n = imgs.shape[0]

    print(f"\nserved {n} images across {len(done)} mixed-size requests "
          f"in {dt:.2f}s ({n/dt:.1f} img/s, Pallas kernel path, packed "
          f"weights{f', tp={args.tp}' if args.tp > 1 else ''})")
    print(f"  accuracy (MXInt)    : {np.mean(pred == labels):.4f}")
    agree = np.mean(pred == np.argmax(ref, -1))
    print(f"  agreement w/float   : {agree:.4f}  "
          f"(paper budget: within 1% -> {agree >= 0.99})")
    print(f"  kernel == sim (bit) : {np.array_equal(logits, sim)}")
    rc = engine.jit_cache_size() - cache_warm
    print(f"  recompiles after warmup: {rc if cache_warm >= 0 else 'n/a'}")

    if args.metrics_json:
        from repro.telemetry import export as tel_export
        from repro.telemetry import probes as tel_probes

        print("\nrunning kernel probes for the predicted-vs-measured "
              "join (DeiT matmul + flash attention)...")
        tel_probes.run_probes()
        pvm = tel_export.predicted_vs_measured()
        payload = tel_export.json_snapshot(
            path=args.metrics_json,
            extra={"predicted_vs_measured": pvm,
                   "run": {"images": int(n), "requests": len(done),
                           "img_per_s": round(n / dt, 2),
                           "tp": args.tp}})
        joined = {k["label"]: k["measured_ms"]
                  for k in pvm["kernels"]}
        print(f"  metrics -> {args.metrics_json}  "
              f"({len(payload['histograms'])} histograms, "
              f"joined kernels: {joined})")


if __name__ == "__main__":
    main()
