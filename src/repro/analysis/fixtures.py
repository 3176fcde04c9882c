"""Deliberately violating fixtures — the analysis passes' self-tests.

Each fixture is a callable returning the violations its pass reports for
a KNOWN-BAD input; ``tests/test_analysis.py`` asserts every fixture
fires (non-empty, right rule name) and ``tools/repro_lint.py --fixture
NAME`` exits non-zero on each, which is the acceptance contract: a rule
that cannot flag its own counterexample is dead code, not a guarantee.

The kernel fixtures go through the REAL capture machinery (a fabricated
``pallas_call`` under abstract eval), not hand-built capture records, so
they also pin the recorder itself.
"""
from __future__ import annotations

from typing import Callable, Dict, List

import jax
import jax.numpy as jnp

from repro.analysis.kernel_contracts import (capture_pallas_calls,
                                             check_captures)
from repro.analysis.registry import ERROR, Violation
from repro.analysis.source_rules import check_source
from repro.analysis.trace_lint import (KERNEL_NL_DENY, TraceRules, lint_fn)


def _noop_kernel(*refs):
    pass


def _capture_2d(shape, block, *, out_block=None, grid=None,
                index_map=None, out_index_map=None, dtype=jnp.float32,
                kernel=_noop_kernel, scratch=(), compiler_params=None):
    """Fabricate one 2-D pallas_call capture with the given specs."""
    from jax.experimental import pallas as pl

    grid = grid or tuple(d // b for d, b in zip(shape, block))
    index_map = index_map or (lambda i, j: (i, j))
    out_index_map = out_index_map or index_map
    out_block = out_block or block

    def fn(x):
        return pl.pallas_call(
            kernel,
            grid=grid,
            in_specs=[pl.BlockSpec(block, index_map)],
            out_specs=pl.BlockSpec(out_block, out_index_map),
            out_shape=jax.ShapeDtypeStruct(shape, dtype),
            scratch_shapes=list(scratch),
            compiler_params=compiler_params,
            interpret=True)(x)

    return capture_pallas_calls(fn, jax.ShapeDtypeStruct(shape, dtype),
                                label="fixture")


def vmem_over_budget() -> List[Violation]:
    """A (2048, 2048) f32 block is 16 MiB; double-buffered in+out blows
    the whole per-core budget several times over."""
    return check_captures(_capture_2d((4096, 2048), (2048, 2048)))


def misaligned_tile() -> List[Violation]:
    """Minormost tiled block of 100 lanes (not a 128 multiple)."""
    return check_captures(_capture_2d((64, 400), (8, 100)))


def uncovered_output_block() -> List[Violation]:
    """A constant output index map over a tiled output: 3 of 4 row-blocks
    of the result are never written."""
    return check_captures(_capture_2d(
        (512, 128), (128, 128), grid=(4,),
        index_map=lambda i: (i, 0), out_index_map=lambda i: (0, 0)))


def wrong_scratch_dtype() -> List[Violation]:
    """A kernel posing as mxint_ln_matmul whose LN scratch is f32 while
    the model dtype is bf16 — the model-dtype scratch contract."""
    from jax.experimental.pallas import tpu as pltpu

    def _mxint_ln_matmul_kernel(*refs):
        pass

    return check_captures(_capture_2d(
        (128, 256), (128, 256), dtype=jnp.bfloat16,
        kernel=_mxint_ln_matmul_kernel,
        scratch=(pltpu.VMEM((128, 256), jnp.float32),)))


def float_softmax_in_kernel_trace() -> List[Violation]:
    """jax.nn.softmax traced under kernel-mode rules: denied rank-2 exp,
    a structural softmax chain, and a blown (>=1) pallas budget."""
    rules = TraceRules(deny_outside_pallas=KERNEL_NL_DENY,
                       forbid_softmax_chain=True, pallas_budget=(1, 1))
    return lint_fn(lambda x: jax.nn.softmax(x, axis=-1),
                   (jnp.zeros((8, 16), jnp.float32),), rules,
                   "fixture:float-softmax")


def f64_leak() -> List[Violation]:
    """An f64 upcast mid-trace (x64 enabled only inside the fixture —
    the default f32 canonicalisation would silently hide the leak)."""
    with jax.enable_x64(True):
        return lint_fn(
            lambda x: (x.astype(jnp.float64) * 2.0).astype(jnp.float32),
            (jnp.zeros((4, 4), jnp.float32),), TraceRules(),
            "fixture:f64-leak")


# ---------------------------------------------------------------------------
# grid-semantics fixtures (DESIGN.md §14) — file-defined accumulator
# kernels so the AST gate scan sees real source
# ---------------------------------------------------------------------------
def _acc_kernel(x_ref, o_ref, acc_ref):
    from jax.experimental import pallas as pl

    @pl.when(pl.program_id(1) == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    acc_ref[...] += x_ref[...]

    @pl.when(pl.program_id(1) == 1)
    def _flush():
        o_ref[...] = acc_ref[...].astype(o_ref.dtype)


def _reversed_acc_kernel(x_ref, o_ref, acc_ref):
    from jax.experimental import pallas as pl

    @pl.when(pl.program_id(1) == 1)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    acc_ref[...] += x_ref[...]

    @pl.when(pl.program_id(1) == 0)
    def _flush():
        o_ref[...] = acc_ref[...].astype(o_ref.dtype)


def _inplace_kernel(x_ref, o_ref):
    o_ref[...] = o_ref[...] + x_ref[...]


def _acc_capture(kernel, compiler_params):
    from jax.experimental.pallas import tpu as pltpu

    # grid (4, 2); the output map ignores axis 1, so each output block is
    # written on both of its steps — a revisiting axis by construction
    return _capture_2d(
        (512, 256), (128, 256), grid=(4, 2),
        index_map=lambda i, j: (i, 0),
        kernel=kernel, scratch=(pltpu.VMEM((128, 256), jnp.float32),),
        compiler_params=compiler_params)


def missing_dim_semantics() -> List[Violation]:
    """An accumulator grid with no dimension_semantics declaration."""
    from repro.analysis.grid_semantics import check_captures_semantics

    return check_captures_semantics(_acc_capture(_acc_kernel, None))


def race_parallel_accumulator() -> List[Violation]:
    """The revisiting/gated accumulator axis declared "parallel" — the
    data race the checker exists for."""
    from jax.experimental.pallas import tpu as pltpu

    from repro.analysis.grid_semantics import check_captures_semantics

    return check_captures_semantics(_acc_capture(
        _acc_kernel, pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel"))))


def reversed_init_flush() -> List[Violation]:
    """Init gated on the LAST step and flush on the FIRST: early steps
    accumulate into uninitialised scratch and a partial sum leaves."""
    from jax.experimental.pallas import tpu as pltpu

    from repro.analysis.grid_semantics import check_captures_semantics

    return check_captures_semantics(_acc_capture(
        _reversed_acc_kernel, pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary"))))


def unaliased_inplace_output() -> List[Violation]:
    """A kernel reading its output ref with no input_output_aliases —
    the first visit of each block reads uninitialised VMEM."""
    from jax.experimental.pallas import tpu as pltpu

    from repro.analysis.grid_semantics import check_captures_semantics

    return check_captures_semantics(_capture_2d(
        (512, 256), (128, 256), kernel=_inplace_kernel,
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel"))))


def cost_model_regression() -> List[Violation]:
    """The current tree diffed against a baseline whose byte counts are
    10% smaller — every row regresses past the 2% CI threshold."""
    from repro.analysis.cost_model import build_table, compare_to_baseline

    rows = build_table()
    deflated = {"rows": {
        r["label"]: {"hbm_bytes": int(r["hbm_bytes"] * 0.9)}
        for r in rows}}
    return compare_to_baseline(rows, deflated)


def raw_neg_inf_literal() -> List[Violation]:
    return check_source(
        "MASK_VALUE = -2.0e38\n",
        "src/repro/models/bad_sentinel.py")


def exp_in_models() -> List[Violation]:
    return check_source(
        "import jax.numpy as jnp\n"
        "def f(x):\n"
        "    return jnp.exp(x)\n",
        "src/repro/models/bad_exp.py")


def interpret_literal_in_src() -> List[Violation]:
    return check_source(
        "def f(q, k, v, flash):\n"
        "    return flash(q, k, v, interpret=True)\n",
        "src/repro/serving/bad_interpret.py")


def override_branch_outside_seam() -> List[Violation]:
    """Per-layer override plumbing consulted outside the seam: a models/
    helper iterating the override pairs and branching on the mode string
    by hand — both of which must go through ``q.scoped`` /
    ``datapath.resolve`` (DESIGN.md §16).  Goes through the REAL
    ``tools/check_dispatch.check_text`` scanner so the fixture also pins
    the extended rule itself."""
    import importlib.util
    from pathlib import Path

    root = Path(__file__).resolve().parents[3]
    spec = importlib.util.spec_from_file_location(
        "_check_dispatch_for_fixture", root / "tools" / "check_dispatch.py")
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    # the seam tokens are split so THIS file's source does not trip the
    # tree-wide scan the fixture exercises
    bad = ("def pick_backend(q, scope):\n"
           "    for pattern, ov in q.over" "rides:\n"
           "        if q.mo" "de == 'kernel':\n"
           "            return ov\n")
    return [Violation("dispatch-seam", "fixture", p)
            for p in mod.check_text(bad, "src/repro/models/bad_scoping.py")]


def adhoc_timing_in_src() -> List[Violation]:
    """Hand-rolled perf_counter deltas in library code — the timing that
    belongs in a ``telemetry.span`` (DESIGN.md §15)."""
    return check_source(
        "import time\n"
        "def f(fn):\n"
        "    t0 = time.perf_counter()\n"
        "    fn()\n"
        "    return time.perf_counter() - t0\n",
        "src/repro/serving/bad_timing.py")


FIXTURES: Dict[str, Callable[[], List[Violation]]] = {
    "vmem-over-budget": vmem_over_budget,
    "misaligned-tile": misaligned_tile,
    "uncovered-output-block": uncovered_output_block,
    "wrong-scratch-dtype": wrong_scratch_dtype,
    "float-softmax-kernel-trace": float_softmax_in_kernel_trace,
    "f64-leak": f64_leak,
    "raw-neg-inf-literal": raw_neg_inf_literal,
    "exp-in-models": exp_in_models,
    "interpret-literal-in-src": interpret_literal_in_src,
    "adhoc-timing-in-src": adhoc_timing_in_src,
    "override-branch-outside-seam": override_branch_outside_seam,
    "missing-dim-semantics": missing_dim_semantics,
    "race-parallel-accumulator": race_parallel_accumulator,
    "reversed-init-flush": reversed_init_flush,
    "unaliased-inplace-output": unaliased_inplace_output,
    "cost-model-regression": cost_model_regression,
}

# the rule each fixture must trip (self-test assertion)
FIXTURE_RULES: Dict[str, str] = {
    "vmem-over-budget": "kernel-contracts",
    "misaligned-tile": "kernel-contracts",
    "uncovered-output-block": "kernel-contracts",
    "wrong-scratch-dtype": "kernel-contracts",
    "float-softmax-kernel-trace": "trace-invariants",
    "f64-leak": "trace-invariants",
    "raw-neg-inf-literal": "neg-inf-literal",
    "exp-in-models": "models-float-nonlinear",
    "interpret-literal-in-src": "interpret-literal",
    "adhoc-timing-in-src": "no-adhoc-timing",
    "override-branch-outside-seam": "dispatch-seam",
    "missing-dim-semantics": "grid-semantics",
    "race-parallel-accumulator": "grid-semantics",
    "reversed-init-flush": "grid-semantics",
    "unaliased-inplace-output": "grid-semantics",
    "cost-model-regression": "cost-model",
}


def run_fixture(name: str) -> List[Violation]:
    errors = [v for v in FIXTURES[name]() if v.severity == ERROR]
    return errors
