"""Rule registry for the static-analysis passes (DESIGN.md §13).

Every pass registers one or more named :class:`Rule` objects; the unified
runner (``tools/repro_lint.py``) and the tier-1 self-tests
(``tests/test_analysis.py``) iterate the registry rather than hard-coding
pass lists, so a new invariant is one ``@register_rule`` away from CI.

Severity: ``error`` violations fail the run; ``warn`` violations are
printed but do not affect the exit code (e.g. a grid too large for
kernel_contracts to enumerate its index maps).
"""
from __future__ import annotations

import dataclasses
from pathlib import Path
from typing import Callable, Dict, List, Optional, Tuple

ERROR = "error"
WARN = "warn"


@dataclasses.dataclass(frozen=True)
class Violation:
    """One finding.  ``where`` is ``path:line`` for source rules and a
    target/kernel label for the abstract-eval passes."""

    rule: str
    where: str
    message: str
    severity: str = ERROR

    def __str__(self) -> str:  # the runner's one-line report format
        return f"[{self.rule}] {self.where}: {self.message}"


@dataclasses.dataclass(frozen=True)
class Rule:
    """A registered analysis pass entry.

    ``run(root)`` receives the repo root and returns the violations it
    found (empty list == clean).  Rules must be side-effect free and
    runnable in any order.
    """

    name: str
    description: str
    run: Callable[[Path], List[Violation]]


_RULES: "Dict[str, Rule]" = {}


def register_rule(name: str, description: str):
    """Decorator: register ``fn(root) -> list[Violation]`` under ``name``."""

    def deco(fn):
        if name in _RULES:
            raise ValueError(f"duplicate analysis rule {name!r}")
        _RULES[name] = Rule(name=name, description=description, run=fn)
        return fn

    return deco


def rules() -> Tuple[Rule, ...]:
    return tuple(_RULES.values())


def get_rule(name: str) -> Rule:
    return _RULES[name]


def run_rules(root: Path, only: Optional[List[str]] = None,
              skip: Tuple[str, ...] = ()) -> List[Violation]:
    """Run the selected rules over ``root`` and pool their violations."""
    out: List[Violation] = []
    for rule in rules():
        if only is not None and rule.name not in only:
            continue
        if rule.name in skip:
            continue
        out.extend(rule.run(Path(root)))
    return out
