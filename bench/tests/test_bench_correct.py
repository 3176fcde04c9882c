"""CPU checks of the comparison that decides ``correct``, at a size a
test run holds (a two-layer DeiT, Pallas kernels in interpret mode).

* the plain reference computes what the program's 'sim' oracle and its
  kernel path compute;
* the control, the reference at bfloat16 in the program's place, fails
  the cell's ``ops_rel_rms`` limit;
* a run with the chip check skipped and the timed path broken underneath
  comes out ``correct: false``, once per fault a classification cell can
  have; an unbroken run comes out ``correct: true``.

    PYTHONPATH=src JAX_PLATFORMS=cpu python -m pytest -q bench/tests
"""
from __future__ import annotations

import sys
from pathlib import Path

import numpy as np
import pytest

sys.path[:0] = [str(Path(__file__).resolve().parent),
                str(Path(__file__).resolve().parents[1])]

import control  # noqa: E402
import tiny  # noqa: E402
from bench.harness import check, program, weights as W  # noqa: E402

SEED = 2 ** 35 + 11


@pytest.fixture(scope="module")
def tree():
    return W.make(tiny.CONFIG, SEED)


# the paper's W6A8 datapath, and another point of its design space
DATAPATHS = {
    "w6a8": None,
    "w4a6": {"weight_mant_bits": 4, "weight_block": 32, "act_mant_bits": 6,
             "act_block": 32, "layernorm_lut_bits": 4, "gelu_domain": 4.0,
             "gelu_lut_bits": 4, "softmax_r_bits": 3},
}


@pytest.mark.parametrize("datapath", list(DATAPATHS))
def test_reference_is_the_program_datapath(tree, datapath):
    import dataclasses
    import jax
    from repro.models import build_model

    cfg = dict(tiny.CONFIG, datapath=DATAPATHS[datapath]
               or tiny.CONFIG["datapath"])
    kernel = program.model(cfg)
    sim = build_model(dataclasses.replace(
        kernel.cfg, quant=dataclasses.replace(kernel.cfg.quant, mode="sim")))
    images = np.random.default_rng(1).standard_normal(
        (5, 32, 32, 3)).astype(np.float32)
    with jax.default_matmul_precision("highest"):
        want = np.asarray(jax.jit(sim.logits)(W.to_program(tree, sim), images))
    got = check.reference_logits(cfg, tree, images, "highest")
    np.testing.assert_array_equal(got, want)


def test_control_fails_the_op_limit(tree):
    limits = {"ops_rel_rms": tiny.cell("bulk").workload["limits"]["ops_rel_rms"]}
    engine, _ = program.scheduler(tiny.CONFIG, tree, 8)
    layer = check.layer0(tree)
    x = check.op_inputs(tiny.CONFIG, 8, SEED)
    want = check.reference_ops(tiny.CONFIG, layer, x, "highest")
    ctl = check.ops_numbers(
        check.reference_ops(tiny.CONFIG, layer, x, "bfloat16"), want)
    program_ops = check.ops_numbers(program.ops(engine, x), want)
    assert not check.verdict({"ops_rel_rms": max(ctl.values())}, limits)[0]
    assert check.verdict({"ops_rel_rms": max(program_ops.values())},
                         limits)[0]


def _break(monkeypatch, fault):
    real = program.scheduler

    def broken(cfg, weights, batch):
        engine, sched = real(cfg, weights, batch)
        good = engine.logits_batch
        monkeypatch.setattr(engine, "logits_batch", fault(good))
        return engine, sched
    monkeypatch.setattr(program, "scheduler", broken)


@pytest.mark.parametrize("fault", [None, *control.FAULTS])
def test_broken_timed_path_is_not_correct(monkeypatch, fault):
    cell = tiny.cell("bulk")
    if fault is not None:
        _break(monkeypatch, control.FAULTS[fault])
    res = tiny.execute(cell, seconds=1.0)
    assert res["correct"] is (fault is None), res["checks"]
    assert list(res)[-1] == "checks" and res["attempted"] > 0
