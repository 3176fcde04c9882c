"""Sharded kernel-mode serving: bit-exactness + zero-recompile batching.

Runs ``repro.serving.sharded_check`` in a SUBPROCESS whose environment
forces fake host devices (they must exist before JAX starts, and must not
leak into this test process) on a 2-device 'model' mesh:

  * column-parallel sharded kernel ``classify()`` on DeiT-Tiny shapes must
    equal the single-device ``mode='sim'`` oracle BIT-FOR-BIT;
  * the row-parallel (psum) strategy must run and stay close (its f32
    psum legitimately re-orders accumulation — DESIGN.md §10);
  * a mixed-size request stream through ``ClassifyScheduler`` must add
    ZERO jit specializations after the warmup batch (jit cache stats).
"""
import pytest
import json
import os
import subprocess
import sys
from pathlib import Path

pytestmark = pytest.mark.slow    # subprocess + forced multi-device jax init (fast CI lane skips)

ROOT = Path(__file__).resolve().parents[1]


def _run_check(extra=(), devices=2):
    env = dict(os.environ)
    env["XLA_FLAGS"] = f"--xla_force_host_platform_device_count={devices}"
    env["PYTHONPATH"] = str(ROOT / "src")
    proc = subprocess.run(
        [sys.executable, "-m", "repro.serving.sharded_check", *extra],
        capture_output=True, text=True, timeout=560, env=env, cwd=str(ROOT))
    assert proc.returncode == 0, proc.stdout[-2000:] + proc.stderr[-2000:]
    return json.loads(proc.stdout.strip().splitlines()[-1])


def test_sharded_kernel_bit_exact_and_zero_recompiles():
    rep = _run_check()
    assert rep["devices"] >= 2
    assert rep["ok"]

    # tentpole acceptance 1: sharded kernel == single-device sim, bitwise
    assert rep["parity"]["column"]["bit_exact"]
    assert rep["parity"]["column"]["max_abs_diff"] == 0.0

    # the row/psum strategy runs; close but honestly not bit-exact
    assert rep["parity"]["row"]["max_abs_diff"] < 1.0

    # tentpole acceptance 2: mixed request sizes, fixed-shape jit stays warm
    sched = rep["scheduler"]
    assert sched["all_classified"]
    assert sched["requests"] == 7
    assert sched["jit_cache_after_warmup"] == 1
    assert sched["recompiles_after_warmup"] == 0


def test_data_axis_composes_with_model_tp():
    """ROADMAP "Data-axis serving shards": a ("data", "model") mesh shards
    the batch over 2 data shards COMPOSED with 2-way model TP (4 forced
    host devices).  Batch rows are independent through the whole MXInt
    datapath, so both the composed dp x tp engine and the dp-only engine
    stay BIT-IDENTICAL to the single-device sim oracle, and the
    ClassifyScheduler stream still never recompiles."""
    rep = _run_check(["--dp", "2", "--tp", "2"], devices=4)
    assert rep["devices"] >= 4
    assert rep["ok"]
    assert rep["dp"] == 2

    # composed dp x tp column engine: bitwise vs single-device sim
    assert rep["parity"]["column"]["bit_exact"]
    assert rep["parity"]["column"]["max_abs_diff"] == 0.0
    # row/psum still runs under the data axis (close, not bit-exact)
    assert rep["parity"]["row"]["max_abs_diff"] < 1.0

    # dp-only (tp=1) engine: batch sharding alone is bit-exact too
    assert rep["parity_dp_only"]["column"]["bit_exact"]

    # continuous batching composes with the data axis: one specialization
    sched = rep["scheduler"]
    assert sched["all_classified"]
    assert sched["recompiles_after_warmup"] == 0
