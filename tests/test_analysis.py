"""Self-tests for the static-analysis passes (DESIGN.md §13).

Two layers: (1) every deliberately violating fixture must FIRE its rule
(a rule that cannot flag its own counterexample is dead code) and the
real tree must be clean; (2) the ``tools/repro_lint.py`` CLI must mirror
that in its exit codes — 0 on the tree, non-zero per fixture (the
acceptance contract; subprocess-marked ``slow``).
"""
import subprocess
import sys
from pathlib import Path

import jax.numpy as jnp
import pytest

import repro.analysis as AN
from repro.analysis import cost_model as CM
from repro.analysis import grid_semantics as GS
from repro.analysis import kernel_contracts as KC
from repro.analysis import source_rules as SR
from repro.analysis import trace_lint as TL
from repro.analysis.fixtures import FIXTURE_RULES, FIXTURES, run_fixture

ROOT = Path(__file__).resolve().parents[1]
LINT = ROOT / "tools" / "repro_lint.py"


# ---------------------------------------------------------------------------
# fixtures must fire
# ---------------------------------------------------------------------------
@pytest.mark.parametrize("name", sorted(FIXTURES))
def test_fixture_fires(name):
    violations = run_fixture(name)
    assert violations, f"fixture {name!r} reported nothing — dead rule"
    assert any(v.rule == FIXTURE_RULES[name] for v in violations), \
        (name, [v.rule for v in violations])


def test_fixture_messages_name_the_defect():
    msgs = " ".join(str(v) for v in run_fixture("vmem-over-budget"))
    assert "VMEM" in msgs and "cap" in msgs
    msgs = " ".join(str(v) for v in run_fixture("uncovered-output-block"))
    assert "never writes" in msgs


# ---------------------------------------------------------------------------
# the real tree is clean (the same passes CI runs, in-process)
# ---------------------------------------------------------------------------
def _errors(violations):
    return [v for v in violations if v.severity == AN.ERROR]


def test_source_rules_clean_on_tree():
    assert _errors(SR.run(ROOT)) == []


def test_kernel_contracts_clean_on_tree():
    caps = KC.sweep_captures()
    assert len(caps) >= 8, "sweep shrank — kernels or recorder moved"
    assert _errors(KC.check_captures(caps)) == []


def test_trace_invariants_clean_on_tree():
    assert _errors(TL.run(ROOT)) == []


def test_grid_semantics_clean_on_tree():
    """Every swept pallas_call declares dimension_semantics consistent
    with its revisit/gate evidence (ISSUE 8 acceptance)."""
    caps = KC.sweep_captures()
    assert _errors(GS.check_captures_semantics(caps)) == [], \
        [str(v) for v in GS.check_captures_semantics(caps)]


def test_all_captures_declare_semantics():
    for cap in KC.sweep_captures():
        assert cap.dimension_semantics is not None, cap.label
        assert len(cap.dimension_semantics) == len(cap.grid), cap.label


def test_grid_semantics_sees_the_accumulator_gates():
    """The AST scan resolves gates through partials AND the flash
    kernels' helper call — the evidence the race check rests on."""
    caps = {c.label: c for c in KC.sweep_captures()}
    for label, axis in (("ln-matmul-bench", 1), ("flash-bench", 2),
                        ("flash-decode", 2)):
        facts = GS.kernel_body_facts(caps[label])
        assert facts.src_ok, label
        assert axis in {g.axis for g in facts.gates}, (label, facts.gates)
    # the single-K-tile matmul writes each output tile once: no gate
    facts = GS.kernel_body_facts(caps["matmul-bench"])
    assert facts.src_ok and not facts.gates, facts.gates


def test_cost_model_clean_on_tree():
    assert _errors(CM.run(ROOT)) == [], [str(v) for v in CM.run(ROOT)]


def test_cost_model_reproduces_deit_fusion_saving():
    """The static model must reproduce the ~23% LN->qkv HBM saving the
    bench's analytic counters claim (ISSUE 8 acceptance)."""
    fus = CM.fusion_study()
    assert 20.0 <= fus["saving_pct"] <= 26.0, fus["saving_pct"]
    assert fus["fused_bytes"] < fus["unfused_bytes"]


def test_cost_model_counts_planes_separately():
    """Mantissa and exponent planes appear as separate int8 operands."""
    rows = {r["label"]: r for r in CM.build_table()}
    ops = rows["ln-matmul-bench"]["operands"]
    int8 = [o for o in ops if o["dtype"] == "int8"]
    assert len(int8) == 2, ops
    assert {o["bytes_unique"] for o in int8} == {768 * 768, 24 * 768}


# ---------------------------------------------------------------------------
# pass mechanics
# ---------------------------------------------------------------------------
def test_suppression_comment_waives_and_scopes():
    bad = ("import jax.numpy as jnp\n"
           "def f(x):\n"
           "    return jnp.exp(x)\n")
    rel = "src/repro/models/somewhere.py"
    assert SR.check_source(bad, rel)
    ok = bad.replace(
        "    return jnp.exp(x)",
        "    # repro-lint: allow[models-float-nonlinear] test reason\n"
        "    return jnp.exp(x)")
    assert SR.check_source(ok, rel) == []
    # a suppression naming a DIFFERENT rule does not waive
    wrong = bad.replace(
        "    return jnp.exp(x)",
        "    # repro-lint: allow[neg-inf-literal] wrong rule\n"
        "    return jnp.exp(x)")
    assert SR.check_source(wrong, rel)


def test_models_scope_only():
    """The float-nonlinear rule only binds inside src/repro/models/."""
    bad = "import jax\ny = jax.nn.softmax\n\ndef f(x):\n    return jax.nn.softmax(x)\n"
    assert SR.check_source(bad, "src/repro/models/m.py")
    assert SR.check_source(bad, "src/repro/datapath/b.py") == []
    assert SR.check_source(bad, "tests/t.py") == []


def test_neg_inf_literal_allowed_only_at_home():
    text = "NEG_INF = -2.0e38\n"
    assert SR.check_source(text, "src/repro/core/mx_types.py") == []
    assert SR.check_source(text, "src/repro/kernels/ops.py")


def test_capture_returns_real_blockspecs():
    caps = KC.sweep_captures()
    byk = {c.kernel for c in caps}
    assert {"_mxint_matmul_kernel", "_mxint_layernorm_kernel",
            "_mxint_softmax_kernel", "_mxint_gelu_kernel",
            "_mxint_ln_matmul_kernel", "_flash_kernel",
            "_decode_kernel"} <= byk
    ln = next(c for c in caps if c.kernel == "_mxint_ln_matmul_kernel")
    # the documented model-dtype scratch contract is actually visible
    assert ln.scratch[0].dtype == ln.inputs[0].dtype


def test_trace_lint_flags_xla_backend_with_pallas():
    """forbid_pallas fires when an XLA-mode trace lowers a kernel."""
    from repro.kernels import ops

    rules = TL.TraceRules(forbid_pallas=True)
    x = jnp.zeros((8, 128), jnp.float32)
    vs = TL.lint_fn(lambda a: ops.mxint_softmax_op(a), (x,), rules,
                    "fixture:pallas-in-xla")
    assert any("pallas_call" in v.message for v in vs)


def test_slot_step_target_within_pallas_budget():
    """ISSUE 7: the slot scheduler's mixed slot-prefill + decode step
    lints clean — exactly 17 pallas_calls (8 prefill + 9 decode).  A
    drift means the per-row index plumbing dropped or duplicated a
    kernel.  (Prefill's float online-softmax is by design — see the
    target's docstring; decode-phase nonlinear denial is pinned by the
    decode-step target.)"""
    vs = TL._slot_step_kernel_target()
    assert vs == [], [str(v) for v in vs]


def test_registry_rejects_duplicates():
    with pytest.raises(ValueError):
        AN.register_rule("kernel-contracts", "dup")(lambda root: [])


# ---------------------------------------------------------------------------
# the CLI contract (subprocess — slow lane)
# ---------------------------------------------------------------------------
def _run_lint(*args):
    return subprocess.run(
        [sys.executable, str(LINT), *args], cwd=ROOT, capture_output=True,
        text=True, timeout=900)


@pytest.mark.slow
def test_repro_lint_exits_zero_on_tree():
    r = _run_lint()
    assert r.returncode == 0, r.stderr
    assert "clean" in r.stdout


@pytest.mark.slow
@pytest.mark.parametrize("name", sorted(FIXTURES))
def test_repro_lint_fixture_exits_nonzero(name):
    r = _run_lint("--fixture", name)
    assert r.returncode != 0, (name, r.stdout, r.stderr)
    assert FIXTURE_RULES[name] in r.stderr


@pytest.mark.slow
def test_repro_lint_lists_all_rules():
    r = _run_lint("--list")
    assert r.returncode == 0
    for rule in ("kernel-contracts", "grid-semantics", "cost-model",
                 "trace-invariants", "source-rules", "dispatch-seam",
                 "docs-links"):
        assert rule in r.stdout


@pytest.mark.slow
def test_repro_lint_json_roofline_table():
    """--only cost-model --json emits the machine-readable roofline the
    CI lanes archive and benchmarks/roofline.py ingests."""
    import json

    r = _run_lint("--only", "cost-model", "--json")
    assert r.returncode == 0, r.stderr
    payload = json.loads(r.stdout)
    rows = {row["label"]: row for row in payload["cost_model"]["rows"]}
    assert "ln-matmul-bench" in rows and "flash-deit" in rows
    for row in rows.values():
        assert row["hbm_bytes"] > 0 and row["vmem_bytes"] > 0
    fusion = payload["cost_model"]["fusion"]
    assert 20.0 <= fusion["saving_pct"] <= 26.0
