"""repro.analysis — static kernel-contract + trace-invariant checking
(DESIGN.md §13-14).

Four passes over the MXInt datapath's load-bearing invariants that
Mosaic's own compile does not check (it checks VMEM, the (8, 128) block
rule and scratch layouts; ``tests/test_v5e_compile.py`` runs it):

* :mod:`repro.analysis.kernel_contracts` — abstract-eval capture of
  every ``pallas_call`` over a shape sweep, and index-map coverage
  (inputs in bounds, every output block written).
* :mod:`repro.analysis.grid_semantics` — per-axis
  ``dimension_semantics`` race checker over the same captures:
  accumulator axes must be ``"arbitrary"``, independent tile axes
  ``"parallel"``, init/flush gates in order, in-place outputs aliased.
* :mod:`repro.analysis.trace_lint` — jaxpr allow/deny lists per datapath
  mode (no float softmax/f64 outside ``pallas_call`` in kernel mode, no
  ``pallas_call`` in XLA modes, per-block pallas budgets).
* :mod:`repro.analysis.source_rules` — AST rules (single NEG_INF
  sentinel, no bare float nonlinears in ``models/``, no
  ``interpret=True`` literals in ``src/``).

Importing this package registers every rule; run them with
``tools/repro_lint.py`` (CI) or :func:`repro.analysis.run_rules`
(tier-1 via ``tests/test_analysis.py``).
"""
from repro.analysis.registry import (ERROR, WARN, Rule, Violation,
                                     get_rule, register_rule, rules,
                                     run_rules)
from repro.analysis import grid_semantics, kernel_contracts
from repro.analysis import source_rules, trace_lint
from repro.analysis import fixtures

__all__ = [
    "ERROR", "WARN", "Rule", "Violation", "get_rule", "register_rule",
    "rules", "run_rules", "kernel_contracts", "grid_semantics",
    "source_rules", "trace_lint", "fixtures",
]
