"""Runnable Fig. 1b-style sweep: ``python -m repro.dse`` (DESIGN.md §16).

Sweeps weight mantissa width (and optionally a per-group space) on a
randomly-initialized DeiT against a synthetic calibration batch and
writes the Pareto JSON report.  With random weights the accuracy proxy
is agreement against the float forward of the SAME weights — the
datapath-fidelity signal the paper's software emulation measures, not
ImageNet accuracy (not shipped in the container).  CI runs this as the
DSE smoke (one block, tiny space, exhaustive driver) and archives the
report in both lanes.
"""
from __future__ import annotations

import argparse
import dataclasses
import sys


def _space(args):
    from repro.core.mx_types import MXFormat, QuantConfig
    from repro.dse.space import GroupSpace, SearchSpace

    base = QuantConfig(mode=args.base_mode, quantize_nonlinear=True,
                       weight_fmt=MXFormat(mant_bits=8, block_size=256),
                       act_fmt=MXFormat(mant_bits=8, block_size=16))
    widths = tuple(int(b) for b in args.weight_bits.split(","))
    if args.per_group:
        groups = (GroupSpace(scope="block/*/attn",
                             weight_mant_bits=widths),
                  GroupSpace(scope="block/*/ffn",
                             weight_mant_bits=widths))
    else:
        groups = (GroupSpace(scope="*", weight_mant_bits=widths),)
    return SearchSpace(base=base, groups=groups)


def main(argv=None) -> dict:
    p = argparse.ArgumentParser(
        prog="python -m repro.dse",
        description="per-layer DSE sweep: accuracy proxy vs static "
                    "hardware cost (paper Fig. 1b / Table V)")
    p.add_argument("--arch", default="deit_tiny",
                   help="configs.deit.BY_NAME entry (default deit_tiny)")
    p.add_argument("--layers", type=int, default=0,
                   help="truncate to N encoder blocks (0 = full depth)")
    p.add_argument("--batch", type=int, default=8)
    p.add_argument("--mode", dest="base_mode", default="sim",
                   help="base execution mode for candidates")
    p.add_argument("--weight-bits", default="3,4,6,8",
                   help="comma list of weight mantissa widths to sweep")
    p.add_argument("--per-group", action="store_true",
                   help="sweep attn and ffn groups independently")
    p.add_argument("--driver", default="exhaustive",
                   choices=("exhaustive", "greedy", "random", "evolve"))
    p.add_argument("--budget", type=float, default=0.01,
                   help="greedy accuracy-loss budget")
    p.add_argument("--samples", type=int, default=16,
                   help="random-driver sample count")
    p.add_argument("--seed", type=int, default=0)
    p.add_argument("--out", default="dse_report.json")
    args = p.parse_args(argv)

    import jax

    from repro.configs.deit import BY_NAME
    from repro.data.pipeline import SyntheticImageData
    from repro.dse import (Evaluator, build_report, evolutionary_search,
                           exhaustive_search, greedy_search, random_search,
                           write_report)
    from repro.models import build_model

    cfg = BY_NAME[args.arch]
    if args.layers:
        cfg = dataclasses.replace(cfg, n_layers=args.layers)
    model = build_model(cfg)
    params = model.init(jax.random.PRNGKey(args.seed))
    data = SyntheticImageData(n_classes=cfg.n_classes, batch=args.batch,
                              image_size=cfg.image_size, seed=args.seed)
    images = data.next_batch()["images"]

    space = _space(args)
    ev = Evaluator(space, cfg, params, images)
    if args.driver == "exhaustive":
        results = exhaustive_search(space, ev)
    elif args.driver == "random":
        results = random_search(space, ev, n=args.samples, seed=args.seed)
    elif args.driver == "evolve":
        results = evolutionary_search(space, ev, seed=args.seed)
    else:
        results = greedy_search(space, ev, budget=args.budget).results

    report = build_report(space, results, driver=args.driver,
                          n_evaluations=ev.n_evaluated)
    path = write_report(args.out, report)

    print(f"# {args.arch} layers={cfg.n_layers} batch={args.batch} "
          f"driver={args.driver} space={space.size()} "
          f"evaluated={ev.n_evaluated}")
    print(f"{'pareto':>6} {'w_bits':>7} {'acc':>6} {'fid':>6}")
    for row in report["candidates"]:
        c = row["cost"]
        print(f"{'*' if row['pareto'] else '':>6} "
              f"{c['weight_bits']:>7.2f} {row['accuracy']:>6.3f} "
              f"{row['fidelity']:>6.3f}")
    print(f"report -> {path}")
    return report


if __name__ == "__main__":
    sys.exit(0 if main() else 1)
