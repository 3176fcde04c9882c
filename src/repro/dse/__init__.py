"""repro.dse — per-layer design-space exploration (DESIGN.md §16).

The paper's method layer: search per-layer-group MXInt configurations
(mantissa widths, block sizes, backend choice, LUT widths) and emit
accuracy-proxy vs hardware-cost Pareto frontiers — the Fig. 1b curve
and the Table V greedy search as two drivers over one space.

    space    SearchSpace / GroupSpace — the declarative knob grammar
    evaluate Evaluator — cached accuracy proxy + static cost scoring
    drivers  exhaustive / greedy / random / evolutionary
    report   Pareto extraction + the archived JSON report

Runnable: ``python -m repro.dse`` (Fig. 1b-style DeiT-Tiny sweep).
"""
from repro.dse.drivers import (GreedyResult, evolutionary_search,
                               exhaustive_search, greedy_search,
                               random_search)
from repro.dse.evaluate import (CandidateCost, EvalResult, Evaluator,
                                weight_groups)
from repro.dse.report import (DEFAULT_OBJECTIVES, build_report, dominates,
                              objective_vector, pareto_front, write_report)
from repro.dse.space import (GroupSpace, Knob, SearchSpace, point_key)

__all__ = [
    "SearchSpace", "GroupSpace", "Knob", "point_key",
    "Evaluator", "EvalResult", "CandidateCost", "weight_groups",
    "exhaustive_search", "greedy_search", "random_search",
    "evolutionary_search", "GreedyResult",
    "dominates", "pareto_front", "objective_vector", "DEFAULT_OBJECTIVES",
    "build_report", "write_report",
]
