"""Integration: the dry-run machinery on a tiny forced-device mesh.

Runs repro.launch.dryrun as a SUBPROCESS (so the 8 fake devices never leak
into this test process) for one representative arch per family, on the
2x2x2 pod/data/model mesh — the same code path the 512-chip production
dry-run takes.  The full production matrix is exercised offline
(EXPERIMENTS.md §Dry-run); this test keeps the machinery honest in CI.
"""
import json
import os
import subprocess
import sys
from pathlib import Path

import pytest

pytestmark = pytest.mark.slow    # subprocess dry-runs (fast CI lane skips)

ROOT = Path(__file__).resolve().parents[1]

CASES = [
    ("llama3_8b", "decode_32k"),          # dense + GQA + KV cache
    ("mixtral_8x7b", "long_500k"),        # MoE + SWA ring cache + seq rules
    ("xlstm_350m", "train_4k"),           # recurrent states + train step
]


@pytest.mark.parametrize("arch,shape", CASES)
def test_tiny_dryrun_cell(arch, shape, tmp_path):
    env = dict(os.environ)
    env["REPRO_XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    env["PYTHONPATH"] = str(ROOT / "src")
    out = tmp_path / "dryrun"
    proc = subprocess.run(
        [sys.executable, "-m", "repro.launch.dryrun",
         "--arch", arch, "--shape", shape, "--mesh", "tiny_multi",
         "--out", str(out), "--tag", "ci"],
        capture_output=True, text=True, timeout=560, env=env,
        cwd=str(ROOT))
    assert proc.returncode == 0, proc.stdout[-2000:] + proc.stderr[-2000:]
    recs = [json.loads(f.read_text()) for f in out.glob("*.ci.json")
            if not f.name.startswith("summary")]
    assert recs
    for rec in recs:
        assert rec["ok"], rec.get("error", "")[:500]
        roof = rec["roofline"]
        assert roof["compute_s"] >= 0
        assert roof["memory_s"] > 0
        assert roof["bottleneck"] in ("compute", "memory", "collective")
        assert rec["memory"]["total_device_bytes"] > 0


def test_grad_compression_cell(tmp_path):
    """The beyond-paper MXInt gradient-compression train step must lower
    on a pod mesh (fully manual shard_map splitting 'pod')."""
    env = dict(os.environ)
    env["REPRO_XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    env["PYTHONPATH"] = str(ROOT / "src")
    out = tmp_path / "dryrun"
    proc = subprocess.run(
        [sys.executable, "-m", "repro.launch.dryrun",
         "--arch", "xlstm_350m", "--shape", "train_4k",
         "--mesh", "tiny_multi", "--grad-compression",
         "--out", str(out), "--tag", "gc"],
        capture_output=True, text=True, timeout=560, env=env,
        cwd=str(ROOT))
    assert proc.returncode == 0, proc.stdout[-2000:] + proc.stderr[-2000:]
