"""Chip benchmark of MXInt ViTs served through ``ClassifyScheduler`` on a
TPU.

    python3 bench/run.py --workload deit_base.bulk --seed 7 --seconds 20 --trace 0

Runs one cell of ``BENCHMARK.json`` on the chips of this machine: set-up
(weights made on the device from ``--seed``, the engine, warm-up of the
cell's one batch shape), then ``--seconds`` of the cell's traffic through
the scheduler, then the comparison with the plain reference that decides
``correct``.  With ``--trace 0`` the metrics are the cell's end-to-end
metrics; with ``--trace 1`` the window runs under the profiler and the
metrics are the cell's per-layer metrics.

The last line of standard output is one JSON object: ``correct``,
``attempted``, ``failed``, ``metrics``, ``device`` (with ``--trace 1``
also ``breakdown``), and last ``checks``: each number compared with its
limit, which also close standard error.  Without a TPU, or with fewer
chips than the cell asks for, it exits with code 2 and prints no result.

JAX's persistent compilation cache lives in ``JAX_COMPILATION_CACHE_DIR``
when that is set, else in ``.jax_cache/`` at the root of the checkout.
"""
from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import gc  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

ROOT = Path(__file__).resolve().parents[1]
sys.path[:0] = [str(ROOT), str(ROOT / "src")]


def parse(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    return ap.parse_args(argv)


def use_compile_cache() -> str:
    import jax
    d = os.environ.get("JAX_COMPILATION_CACHE_DIR") or str(ROOT / ".jax_cache")
    os.makedirs(d, exist_ok=True)
    jax.config.update("jax_compilation_cache_dir", d)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    return d


def tpu_devices(chips: int):
    """JAX's TPU devices, or None (after saying why on stderr)."""
    import jax
    devs = jax.devices()
    if devs[0].platform != "tpu" or len(devs) < chips:
        print(f"bench: the cell needs {chips} TPU chip(s); JAX found "
              f"{len(devs)} {devs[0].platform!r} device(s)", file=sys.stderr)
        return None
    return devs


def read_metrics(specs, run) -> dict:
    from bench.harness.manifest import metric_reader
    out = {}
    for m in specs:
        v = metric_reader(m["name"])(run)
        if v is not None:
            out[m["name"]] = {"value": float(v), "unit": m["unit"]}
    return out


def judge(s, run) -> tuple:
    """Compare what the window served, and each op case of the family,
    with the reference.  Frees the program's state first.  Returns
    (numbers, per-op numbers)."""
    import numpy as np
    from bench.harness import check, manifest

    cfg, wl = s.cell.config, s.cell.workload
    fam = manifest.family(cfg["family"])
    reqs = check.sample(run.requests[:run.in_window],
                        np.random.default_rng([s.seed, 4]),
                        wl["sample_images"])
    inputs = fam.op_inputs(cfg, s.batch, s.seed)
    got = fam.program_ops(s.engine, inputs)
    s.engine = s.sched = None
    gc.collect()
    per_op = check.ops_numbers(got, fam.reference_ops(
        cfg, fam.layer0(s.weights), inputs, "highest"))
    numbers = {"ops_rel_rms": max(per_op.values())}
    if reqs:
        numbers[fam.OUTPUT] = check.output_number(cfg, s.weights, s.pool,
                                                  reqs)
    return numbers, per_op


def execute(cell, seed: int, seconds: float, trace: bool, devs, peaks,
            t_start: float) -> dict:
    """Set-up, window and comparison of one cell on ``devs``; returns the
    result line's object."""
    from bench.harness import check, load, program
    from bench.harness import trace as TR
    s = load.setup(cell, seed, t_start)
    run = load.window(s, seconds, trace, peaks)
    stats = devs[0].memory_stats() or {}
    device = {"platform": devs[0].platform, "kind": devs[0].device_kind,
              "count": len(devs),
              "memory_peak_bytes": stats.get("peak_bytes_in_use")}
    metrics = read_metrics(cell.per_layer if trace else cell.end_to_end, run)
    submit = [r.sent - r.arrival for r in run.requests[:run.in_window]]
    print(f"bench: {cell.name} seed {seed}: setup {s.setup_s:.3f} s, "
          f"window {run.window_s:.3f} s, {len(run.steps)} steps, "
          f"{run.in_window} requests, longest submit "
          f"{max(submit, default=0.0) * 1e3:.3f} ms, program counters "
          f"{run.counters}, fallbacks {program.fallbacks()}",
          file=sys.stderr)

    numbers, per_op = judge(s, run)
    failed = sum(r.finish is None for r in run.requests[:run.in_window])
    ok, checks = check.verdict(numbers, cell.workload["limits"])
    result = {"correct": bool(ok and failed == 0 and run.in_window > 0),
              "attempted": run.in_window, "failed": failed,
              "metrics": metrics, "device": device}
    if trace:
        device["busy_s"] = TR.busy_s(run.trace)
        device["window_s"] = run.window_s
        result["breakdown"] = {"device_ops": TR.top_ops(run.trace),
                               "idle_gaps": TR.idle_gaps(run.trace)}
    result["checks"] = checks
    for name, v in per_op.items():
        print(f"bench: op {name} rel_rms {v!r}", file=sys.stderr)
    for name, c in checks.items():
        print(f"bench: check {name} {c['value']!r} limit {c['limit']!r}",
              file=sys.stderr)
    return result


def main(argv=None) -> int:
    args = parse(argv)
    from bench.harness import manifest
    cell = manifest.cell(args.workload)
    use_compile_cache()
    devs = tpu_devices(cell.chips)
    if devs is None:
        return 2
    result = execute(cell, args.seed, args.seconds, bool(args.trace), devs,
                     manifest.peaks(devs[0].device_kind), T_START)
    print(json.dumps(result))
    return 0


if __name__ == "__main__":
    sys.exit(main())
