"""Readings that the limits of ``correct`` are set from, on the chip.

    python3 bench/control.py --workload deit_base.bulk --seeds 11,12,13 --seconds 3 --faults

For each seed, in one process: the cell's set-up and a short window at
the cell's own load, then the numbers ``bench/run.py`` compares, each
judged by ``check.verdict`` against the cell's limits, for

* ``program``: the program, as ``bench/run.py`` judges it;
* ``control``: the reference put in the program's place at the next
  precision down from the configuration's float32 (bfloat16 operands,
  float32 accumulation), for the same sampled images and op inputs;
* ``faults`` (with ``--faults``): a window whose timed path is broken
  underneath, one per fault in ``FAULTS``.

One JSON line per seed on standard output.  The benchmark's own runs
never run this.
"""
from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

import numpy as np  # noqa: E402

sys.path[:0] = [str(Path(__file__).resolve().parent)]

import run as bench_run  # noqa: E402


def half_batch(real):
    """Half of every batch left out: the program classifies zeros there."""
    def f(chunk):
        chunk = np.array(chunk)
        chunk[len(chunk) // 2:] = 0.0
        return real(chunk)
    return f


def altered(real):
    """An answer altered where it is produced: the first image of every
    batch gets the second image's logits."""
    def f(chunk):
        y = np.array(real(chunk))
        y[0] = y[1]
        return y
    return f


def stale(real):
    """A step that returns its state unchanged: every batch after the
    first gets the answers of the batch before it."""
    last = []

    def f(chunk):
        y = np.array(real(chunk))
        out = last[0] if last else y
        last[:] = [y]
        return out
    return f


FAULTS = {"half_batch": half_batch, "altered": altered, "stale": stale}


def judged(numbers: dict, limits: dict) -> dict:
    from bench.harness import check
    ok, checks = check.verdict(numbers, limits)
    return {"correct": bool(ok), **{k: c["value"] for k, c in checks.items()}}


def readings(cell, seed: int, seconds: float, faults: bool, peaks) -> dict:
    """Program, control and fault readings of one seed."""
    from bench.harness import check, load, manifest
    cfg, limits = cell.config, cell.workload["limits"]
    fam = manifest.family(cfg["family"])
    t = time.perf_counter()
    s = load.setup(cell, seed, t)
    run = load.window(s, seconds, False, peaks)
    broken = {}
    if faults:
        real = s.engine.logits_batch
        for name, fault in FAULTS.items():
            s.engine.logits_batch = fault(real)
            broken[name] = load.window(s, seconds, False, peaks)
        s.engine.logits_batch = real
    numbers, per_op = bench_run.judge(s, run)

    reqs = check.sample(run.requests[:run.in_window],
                        np.random.default_rng([seed, 4]),
                        cell.workload["sample_images"])
    images = np.concatenate([s.pool[r.start:r.start + r.n] for r in reqs])
    low = check.reference_output(cfg, s.weights, images, "bfloat16")
    inputs = fam.op_inputs(cfg, s.batch, seed)
    layer = fam.layer0(s.weights)
    ctl_ops = check.ops_numbers(
        fam.reference_ops(cfg, layer, inputs, "bfloat16"),
        fam.reference_ops(cfg, layer, inputs, "highest"))
    control = {fam.OUTPUT: check.output_number(
        cfg, s.weights, s.pool, reqs, served=low),
        "ops_rel_rms": max(ctl_ops.values())}

    out = {"workload": cell.name, "seed": seed,
           "program": dict(judged(numbers, limits), ops=per_op),
           "control": dict(judged(control, limits), ops=ctl_ops),
           "faults": {}}
    for name, r in broken.items():
        reqs = check.sample(r.requests[:r.in_window],
                            np.random.default_rng([seed, 4]),
                            cell.workload["sample_images"])
        fault = {fam.OUTPUT: check.output_number(cfg, s.weights, s.pool,
                                                 reqs),
                 "ops_rel_rms": numbers["ops_rel_rms"]}
        out["faults"][name] = judged(fault, limits)
    out["seconds"] = time.perf_counter() - t
    return out


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True, help="comma-separated seeds")
    ap.add_argument("--seconds", type=float, default=3.0)
    ap.add_argument("--faults", action="store_true")
    args = ap.parse_args(argv)

    from bench.harness import manifest
    cell = manifest.cell(args.workload)
    bench_run.use_compile_cache()
    devs = bench_run.tpu_devices(cell.chips)
    if devs is None:
        return 2
    peaks = manifest.peaks(devs[0].device_kind)
    for seed in (int(x) for x in args.seeds.split(",")):
        print(json.dumps(readings(cell, seed, args.seconds, args.faults,
                                  peaks)), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
