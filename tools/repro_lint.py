#!/usr/bin/env python3
"""Unified static-analysis runner (DESIGN.md §13) — THE lint entrypoint.

Folds every registered ``repro.analysis`` pass (kernel contracts, trace
invariants, AST source rules) together with the two legacy tree checks
(``check_dispatch`` dispatch-seam scan, ``check_docs`` DESIGN-citation
scan) behind one command.  CI runs exactly this; tier-1 runs the same
registry in-process via ``tests/test_analysis.py``::

    PYTHONPATH=src python tools/repro_lint.py            # whole tree
    python tools/repro_lint.py --list                    # show rules
    python tools/repro_lint.py --only source-rules       # subset
    python tools/repro_lint.py --fixture f64-leak        # must exit 1
    python tools/repro_lint.py --fixtures                # list fixtures
    python tools/repro_lint.py --json report.json        # violations report

Exit code 0 iff no error-severity violation (``warn`` findings print but
do not fail).  ``--fixture NAME`` runs one deliberately violating
fixture through its pass and exits non-zero when it fires — the
self-test that every rule can flag its own counterexample.
"""
from __future__ import annotations

import argparse
import importlib.util
import sys
from pathlib import Path

ROOT = Path(__file__).resolve().parents[1]
if str(ROOT / "src") not in sys.path:
    sys.path.insert(0, str(ROOT / "src"))


def _load_tool(name: str):
    spec = importlib.util.spec_from_file_location(
        name, ROOT / "tools" / f"{name}.py")
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def _register_legacy_rules():
    """Adapt the standalone tree checks into the rule registry."""
    from repro.analysis import Violation, register_rule

    dispatch = _load_tool("check_dispatch")
    docs = _load_tool("check_docs")

    @register_rule("dispatch-seam",
                   "quant-mode branching only inside repro/datapath/ "
                   "(tools/check_dispatch.py)")
    def _dispatch(root):
        return [Violation("dispatch-seam", "tree", p)
                for p in dispatch.check(Path(root))]

    @register_rule("docs-links",
                   "DESIGN.md §N citations resolve; README keeps the "
                   "tier-1 command (tools/check_docs.py)")
    def _docs(root):
        return [Violation("docs-links", "tree", p)
                for p in docs.check(Path(root))]


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--list", action="store_true",
                    help="list registered rules and exit")
    ap.add_argument("--only", help="comma-separated rule subset to run")
    ap.add_argument("--skip", default="",
                    help="comma-separated rules to skip")
    ap.add_argument("--fixture",
                    help="run one violating fixture; exits non-zero when "
                         "it fires (self-test)")
    ap.add_argument("--fixtures", action="store_true",
                    help="list fixture names and exit")
    ap.add_argument("--json", nargs="?", const="-", default=None,
                    metavar="PATH",
                    help="emit a machine-readable violations report "
                         "to PATH or stdout")
    args = ap.parse_args(argv)

    import repro.analysis as AN
    _register_legacy_rules()

    if args.list:
        for rule in AN.rules():
            print(f"{rule.name:24s} {rule.description}")
        return 0

    if args.fixtures:
        from repro.analysis.fixtures import FIXTURES
        for name in FIXTURES:
            print(name)
        return 0

    if args.fixture:
        from repro.analysis.fixtures import FIXTURES, run_fixture
        if args.fixture not in FIXTURES:
            print(f"repro_lint: unknown fixture {args.fixture!r} "
                  f"(try --fixtures)", file=sys.stderr)
            return 2
        violations = run_fixture(args.fixture)
        for v in violations:
            print(f"repro_lint: {v}", file=sys.stderr)
        if not violations:
            print(f"repro_lint: fixture {args.fixture!r} did NOT fire — "
                  f"its rule is dead", file=sys.stderr)
            return 0   # exit 0 == self-test FAILURE (tests assert != 0)
        return 1

    only = args.only.split(",") if args.only else None
    skip = tuple(s for s in args.skip.split(",") if s)
    violations = AN.run_rules(ROOT, only=only, skip=skip)
    errors = [v for v in violations if v.severity == AN.ERROR]
    warns = [v for v in violations if v.severity != AN.ERROR]

    if args.json is not None:
        import json as _json

        payload = {
            "rules": [r.name for r in AN.rules()],
            "violations": [
                {"rule": v.rule, "where": v.where, "severity": v.severity,
                 "message": v.message} for v in violations],
            "errors": len(errors),
        }
        text = _json.dumps(payload, indent=1, sort_keys=True)
        if args.json == "-":
            print(text)
        else:
            Path(args.json).write_text(text + "\n")
            print(f"repro_lint: wrote {args.json}", file=sys.stderr)
            # the CI log should still show findings inline, not only
            # inside the archived artifact
            for v in violations:
                print(f"repro_lint: {v}", file=sys.stderr)
        return 1 if errors else 0

    for v in warns:
        print(f"repro_lint: warning {v}", file=sys.stderr)
    for v in errors:
        print(f"repro_lint: {v}", file=sys.stderr)
    if errors:
        print(f"repro_lint: {len(errors)} violation(s)", file=sys.stderr)
        return 1
    print(f"repro_lint: clean ({len(AN.rules())} rules"
          + (f", {len(warns)} warning(s)" if warns else "") + ")")
    return 0


if __name__ == "__main__":
    sys.exit(main())
