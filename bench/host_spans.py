"""Traced windows of one benchmark cell with the program's span recorder on
and off, joined to the device trace.

    python3 bench/host_spans.py --workload deit_base.bulk --seed 7 --seconds 20 --windows off,on

After the cell's set-up, runs one traced window per entry of
``--windows`` (``on``: with ``repro.telemetry.recording()``, ``off``:
without), each with the seed's traffic, as ``bench/run.py --trace 1``
runs its window.  For each window it reports the steps, ``images_per_s``
and the cell's per-layer metrics; for a window with spans also
``pack_ms.bulk``, ``put_ms.bulk``, ``readback_ms.bulk`` and
``queue_wait_ms.bulk``, the device's idle time between executions by the
host span it falls in, the share of it that spans cover, how far each
classify execution lies outside its ``scheduler/classify_step`` span on
the shared clock, and the longest idle gaps named by span.  The last line
of standard output is one JSON object; a one-line summary per window goes
to standard error.  There is no correctness check: ``bench/run.py``
makes it.  Without a TPU it exits with code 2.
"""
from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

ROOT = Path(__file__).resolve().parents[1]
sys.path[:0] = [str(ROOT), str(ROOT / "src")]

SPAN_METRICS = ("pack_ms.bulk", "put_ms.bulk", "readback_ms.bulk",
                "queue_wait_ms.bulk")


def parse(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--windows", default="off,on",
                    help="comma list of on/off, one traced window each")
    args = ap.parse_args(argv)
    args.windows = args.windows.split(",")
    if not set(args.windows) <= {"on", "off"}:
        ap.error(f"--windows takes on/off, got {args.windows}")
    return args


def measure(cell, seed: int, seconds: float, windows: list, peaks: dict,
            t_start: float) -> dict:
    """Set-up, then one traced window per entry of ``windows``."""
    from bench import run as R
    from bench.harness import load, spans
    s = load.setup(cell, seed, t_start)
    out = {"workload": cell.name, "seed": seed, "setup_s": s.setup_s,
           "windows": []}
    for w in windows:
        r = spans.window(s, seconds, peaks, record=w == "on")
        res = {"record": w, "steps": len(r.steps), "window_s": r.window_s,
               "in_window": r.in_window, "counters": r.counters}
        names = ["images_per_s"] + [m["name"] for m in cell.per_layer]
        names += [n for n in SPAN_METRICS if n not in names]
        res["metrics"] = {k: v["value"] for k, v in R.read_metrics(
            [{"name": n, "unit": ""} for n in names], r).items()}
        rep = spans.report(r)
        if rep is not None:
            res.update(rep)
            print(spans.describe(cell.name, rep), file=sys.stderr)
        print(f"bench: {cell.name} seed {seed} recorder {w}: "
              f"{res['steps']} steps, {res['metrics']}", file=sys.stderr)
        out["windows"].append(res)
    return out


def main(argv=None) -> int:
    args = parse(argv)
    from bench import run as R
    from bench.harness import manifest
    cell = manifest.cell(args.workload)
    R.use_compile_cache()
    devs = R.tpu_devices(cell.chips)
    if devs is None:
        return 2
    out = measure(cell, args.seed, args.seconds, args.windows,
                  manifest.peaks(devs[0].device_kind), T_START)
    out["device"] = {"kind": devs[0].device_kind, "count": len(devs)}
    print(json.dumps(out))
    return 0


if __name__ == "__main__":
    sys.exit(main())
