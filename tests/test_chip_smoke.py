"""CPU checks of ``chip_smoke.py``'s phases (Pallas in interpret mode).

The script's own run needs a TPU; here its functions drive a tiny DeiT
through the same engine, scheduler and reference comparison.
"""
import dataclasses
import importlib.util
from pathlib import Path

import numpy as np
import pytest

from repro.configs.deit import DEIT_MICRO

ROOT = Path(__file__).resolve().parents[1]


def _load_chip_smoke():
    spec = importlib.util.spec_from_file_location(
        "chip_smoke", ROOT / "chip_smoke.py")
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


cs = _load_chip_smoke()


@pytest.fixture(scope="module")
def tiny_phase():
    cfg = dataclasses.replace(DEIT_MICRO, n_layers=1)
    return cs.one_chip_phase(cfg, seed=0, batch=4, sizes=(3, 4, 1, 2))


def test_platform_guard_exits_nonzero_on_cpu(capsys):
    assert cs.main([]) != 0
    out = capsys.readouterr()
    assert out.out == ""                    # no result line at all
    assert "needs a TPU" in out.err


def test_one_chip_phase_passes_on_tiny_deit(tiny_phase):
    report, failures = tiny_phase
    assert failures == []
    assert report["recompiles"] == 0
    assert report["recompiles_counter"] == 0
    # interpret mode runs the sim oracle's arithmetic: bit for bit
    assert report["vs_sim_highest"]["bit_exact"]
    assert report["vs_sim_highest"]["argmax_agree"] == 1.0
    assert all(e["bit_exact"] for e in report["ops_vs_sim_highest"].values())
    assert set(report["ops_vs_sim_highest"]) == {
        "ln_linear", "attention", "linear", "gelu", "layernorm"}
    # quantization error against float is real but bounded
    assert 0.0 < report["vs_off_highest"]["rel_rms"] < 0.5


def test_no_fallback_counted(tiny_phase):
    report, _ = tiny_phase
    assert report["fallbacks"] == {}


def test_linear_tiling_counted_once_per_call_site(tiny_phase):
    """patch linear, 3 fused LN->q/k/v, out-proj, fused LN->wi, wo and
    head, each counted once while the classify program was traced."""
    report, _ = tiny_phase
    tiling = report["linear_tiling"]
    assert sum(tiling["row_tile_calls"].values()) == 8
    assert tiling["operand_calls"] == {"bf16": 8}
    assert 0 <= tiling["rows_padded"] < tiling["rows"]


def test_benchmark_operand_paths_all_bf16():
    """Every linear of the benchmark's configurations, traced once per
    call site, feeds the MXU bf16: DeiT's patch linear, 3 fused LN->q/k/v,
    out-proj, fused LN->wi, wo and head; SAM's patch linear, those 6 in
    each of the scanned period's 3 layers, and the neck's two."""
    paths = cs.benchmark_operand_paths(batch=1)
    assert paths == {"deit_base": {"bf16": 8}, "deit_small": {"bf16": 8},
                     "sam_vit_b": {"bf16": 1 + 3 * 6 + 2}}


def test_attention_tiling_counted_once(tiny_phase):
    """The scanned block's attention, traced once: DeiT's short rows take
    the whole-row path (score einsums around the softmax kernel)."""
    report, _ = tiny_phase
    (tiling,) = report["attention_tiling"]
    assert tiling["path"] == "whole_row" and tiling["calls"] == 1
    assert tiling["k_blocks"] == 1 and tiling["keys_padded"] == 0


@pytest.mark.parametrize("level", ["logits", "op"])
@pytest.mark.parametrize("where", ["max", "rms"])
def test_reference_comparison_catches_perturbed_logit(where, level):
    rms_tol, max_tol = {
        "logits": (cs.E2E_REL_RMS_TOL, cs.E2E_REL_MAX_TOL),
        "op": (cs.OP_REL_RMS_TOL, cs.OP_REL_MAX_TOL)}[level]
    rng = np.random.default_rng(0)
    want = rng.normal(size=(17, 1000)).astype(np.float32)
    assert cs.within(cs.errors(want.copy(), want), rms_tol, max_tol)
    got = want.copy()
    if where == "max":
        # one logit moved past the max bound, the RMS barely moves
        got[5, 7] += 1.5 * max_tol * np.abs(want).max()
    else:
        # every logit off by 1.5x the RMS bound of its own size
        got *= 1 + 1.5 * rms_tol
    err = cs.errors(got, want)
    assert not err["bit_exact"]
    assert not cs.within(err, rms_tol, max_tol)
