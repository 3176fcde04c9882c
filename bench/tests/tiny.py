"""A DeiT small enough for the CPU, as a cell the harness can drive with
its chip check skipped (Pallas kernels run in interpret mode)."""
from __future__ import annotations

import json
import sys
import time
from pathlib import Path

ROOT = Path(__file__).resolve().parents[2]
sys.path[:0] = [str(ROOT), str(ROOT / "src")]

from bench.harness import manifest  # noqa: E402

CONFIG = dict(manifest.load_json(manifest.BENCH / "configs" / "deit_base.json"),
              name="deit_tiny_test", image_size=32, patch_size=8,
              hidden_size=64, num_hidden_layers=2, num_attention_heads=4,
              intermediate_size=128, num_labels=10)
PEAKS = {"bf16_flops_per_s": 197e12, "int8_ops_per_s": 393e12,
         "hbm_bytes_per_s": 819e9, "hbm_bytes": 16e9}


E2E = {"bulk": ["images_per_s", "setup_s"]}
UNITS = {"images_per_s": "images/s", "setup_s": "s"}


def cell(traffic: str, **workload) -> manifest.Cell:
    """A cell of the tiny config under one of the benchmark's mixes, with
    the cell file of the matching DeiT-Base cell."""
    wl = dict(manifest.load_json(
        manifest.BENCH / "workloads" / f"deit_base.{traffic}.json"),
        batch=8, sample_images=64, **workload)
    return manifest.Cell(
        name=f"deit_tiny_test.{traffic}", chips=1, config=CONFIG,
        traffic=manifest.load_json(manifest.BENCH / "traffic" /
                                   f"{traffic}.json"),
        workload=wl, per_layer=[],
        end_to_end=[{"name": n, "unit": UNITS[n]} for n in E2E[traffic]])


def execute(c: manifest.Cell, seed: int = 3, seconds: float = 2.0) -> dict:
    """One run of ``c`` on the CPU, as ``bench/run.py`` would make it
    after its chip check; the result passes through JSON."""
    import jax
    from bench import run
    res = run.execute(c, seed, seconds, False, jax.devices(), PEAKS,
                      time.perf_counter())
    return json.loads(json.dumps(res))
