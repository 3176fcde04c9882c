"""CPU checks of the benchmark's arithmetic and manifest.

    PYTHONPATH=src JAX_PLATFORMS=cpu python -m pytest -q bench/tests
"""
from __future__ import annotations

import json
import os
import re
import subprocess
import sys
from pathlib import Path
from types import SimpleNamespace

import numpy as np
import pytest

sys.path.insert(0, str(Path(__file__).resolve().parent))

import tiny  # noqa: E402
from bench.harness import manifest, traffic  # noqa: E402
from bench.harness.trace import Trace, busy_s, idle_gaps, leaves, union_ns  # noqa: E402

NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")
M = manifest.benchmark()


def read(metric, run):
    return manifest.metric_reader(metric)(run)


def fake_run(**kw):
    base = dict(cell=SimpleNamespace(config=manifest.load_json(
        manifest.BENCH / "configs" / "deit_base.json")),
        batch=32, setup_s=1.5, t0=10.0, t1=12.5, steps=[], requests=[],
        counters={}, peaks=tiny.PEAKS, trace=None, in_window=0)
    base.update(kw)
    run = SimpleNamespace(**base)
    run.window_s = run.t1 - run.t0
    return run


def test_images_per_s_is_images_over_whole_steps():
    steps = [(10.0, 11.0, 32), (11.0, 12.0, 32), (12.0, 12.5, 16)]
    assert read("images_per_s", fake_run(steps=steps)) == pytest.approx(80 / 2.5)


def test_union_of_intervals_and_idle_share():
    ops = [("a", 0, 10), ("b", 5, 20), ("c", 30, 40), ("d", 95, 120)]
    assert union_ns(ops, 0, 100) == 35
    tr = Trace(ops=[ops], modules=[[("jit_logits(7)", 0, 40),
                                    ("jit_logits(7)", 95, 120)]])
    assert busy_s(tr) == pytest.approx(55e-9)
    # the window is the host clock's: 2.5 s, busy 55 ns of it
    run = fake_run(trace=tr)
    assert read("device_idle_share.bulk", run) == pytest.approx(
        100.0 * (1 - 55e-9 / 2.5))
    assert idle_gaps(tr, top=2) == [
        ["between executions", pytest.approx(55e-9)],
        ["in jit_logits", pytest.approx(10e-9)]]


def test_container_events_are_not_leaves():
    ev = [("%while", 0, 100), ("%a", 1, 50), ("%b", 50, 99), ("%c", 100, 110)]
    assert [e[0] for e in leaves(ev)] == ["%a", "%b", "%c"]


def test_flops_per_image_match_a_hand_count():
    vit = manifest.work("vit")
    for name, total, linear in (("deit_base", 35.13e9, 33.70e9),
                                ("deit_small", 9.20e9, 8.48e9)):
        cfg = manifest.load_json(manifest.BENCH / "configs" / f"{name}.json")
        assert vit.flops(cfg, 1) == pytest.approx(total, rel=1e-3)
        lin = sum(2 * m * k * n for _, m, k, n in vit.linears(cfg, 1))
        assert lin == pytest.approx(linear, rel=1e-3)
        assert vit.flops(cfg, 32) == pytest.approx(32 * vit.flops(cfg, 1))


def test_mfu_and_roofline_arithmetic():
    vit = manifest.work("vit")
    cfg = manifest.load_json(manifest.BENCH / "configs" / "deit_base.json")
    # DeiT-Base wi at batch 32: 6304 x 768 x 3072, compute bound on v5e
    m, k, n = 32 * 197, 768, 3072
    assert 2 * m * k * n / 393e12 > vit.linear_bytes(cfg, m, k, n) / 819e9
    b = (m * k * 8.5 + k * n * (6 + 8 / 256) + m * n * 8.5) / 8
    assert vit.linear_bytes(cfg, m, k, n) == pytest.approx(b)
    least = vit.linear_least_s(cfg, 32, tiny.PEAKS)
    assert least == pytest.approx(32 * 33.70e9 / 393e12, rel=2e-3)
    # 100 classify executions of 0.5 s whose linear kernels run 0.25 s each
    ops = [("%mxint_ln_matmul.3 = f32[] custom-call()", i * 1e9, i * 1e9 + 2.5e8)
           for i in range(100)]
    ops += [("%mxint_gelu.1 = f32[] custom-call()", i * 1e9 + 3e8, i * 1e9 + 4e8)
            for i in range(100)]
    mods = [("jit_logits(1)", i * 1e9, i * 1e9 + 5e8) for i in range(100)]
    tr = Trace(ops=[ops], modules=[mods])
    run = fake_run(trace=tr, steps=[(0, 1, 32)] * 100, t0=0.0, t1=100.0)
    assert read("linear_roofline.bulk", run) == pytest.approx(
        100 * least / 0.25)
    assert read("mfu.bulk", run) == pytest.approx(
        100 * 3200 * 35.13e9 / 100 / 393e12, rel=1e-3)


def test_readers_find_nothing_without_a_trace():
    run = fake_run()
    for m in ("mfu.bulk", "linear_roofline.bulk", "device_idle_share.bulk"):
        assert read(m, run) is None


def test_traffic_repeats_exactly_for_one_seed():
    spec = manifest.load_json(manifest.BENCH / "traffic" / "bulk.json")

    def draw(seed):
        s = traffic.size_stream(spec["sizes"], np.random.default_rng([seed, 2]))
        return np.array([next(s) for _ in range(4096)])

    a, b, c = draw(2 ** 40 + 3), draw(2 ** 40 + 3), draw(7)
    assert np.array_equal(a, b) and not np.array_equal(a, c)
    # every seed gets the same sizes, in another order
    assert sorted(a) == sorted(c)


def test_size_distribution():
    lu = traffic.size_quantiles({"dist": "log_uniform", "min": 1, "max": 64},
                                4096)
    assert lu.min() == 1 and lu.max() == 64 and 14 < lu.mean() < 16


def test_manifest_names_units_and_keys():
    assert set(M) == {"command", "paths", "run_seconds", "configs",
                      "workloads", "end_to_end", "per_layer"}
    names = [x["name"] for k in ("configs", "workloads", "end_to_end",
                                 "per_layer") for x in M[k]]
    assert len(names) == len(set(names))
    for n in names:
        assert NAME.match(n), n
    for m in M["end_to_end"] + M["per_layer"]:
        assert UNIT.match(m["unit"]) and m["better"] in ("lower", "higher")
    for m in M["end_to_end"]:
        assert 0.01 <= m["bound"] <= 0.25 and m["source"] in (
            "host_clock", "device_trace")
    assert any(m["name"] == "setup_s" for m in M["end_to_end"])


def test_every_layer_metric_moves_what_its_cells_report():
    cells = {w["name"] for w in M["workloads"]}
    for m in M["per_layer"]:
        for c in m.get("workloads", cells):
            cell = manifest.cell(c, M)
            assert m["moves"] in {x["name"] for x in cell.end_to_end}, (m, c)


def test_every_cell_has_its_files_and_metrics():
    for w in M["workloads"]:
        cell = manifest.cell(w["name"], M)
        assert {x["name"] for x in cell.end_to_end} > {"setup_s"}
        assert cell.per_layer
        for m in cell.end_to_end + cell.per_layer:
            assert callable(manifest.metric_reader(m["name"]))
        assert set(cell.workload["limits"]) == {"logits_rel_rms", "ops_rel_rms"}


def test_peaks_are_keyed_by_device_kind():
    p = manifest.peaks("TPU v5 lite")
    assert p["int8_ops_per_s"] == 393e12 and p["hbm_bytes_per_s"] == 819e9
    with pytest.raises(KeyError):
        manifest.peaks("cpu")


def test_run_exits_nonzero_without_a_tpu():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    p = subprocess.run(
        [sys.executable, str(manifest.BENCH / "run.py"), "--workload",
         "deit_base.bulk", "--seed", str(2 ** 33), "--seconds", "1",
         "--trace", "0"], capture_output=True, text=True, env=env,
        timeout=300)
    assert p.returncode != 0
    assert not any(line.startswith("{") for line in p.stdout.splitlines())
    assert "TPU" in p.stderr
    json.dumps(p.stderr)
