"""Benchmark harness: one module per paper table/figure.

Prints ``name,us_per_call,derived`` CSV.  Modules:

  table5_quantization  — Table V / Fig 1b format sweep + outlier microbench
  per_op_tables        — Tables II/III/IV + Figs 4/7/8/9 datapath DSE
  table6_lut_savings   — Table VI LUT-entry savings (>=16x claim)
  greedy_search_bench  — Table V greedy bitwidth search
"""
from __future__ import annotations

import sys
import traceback

MODULES = [
    "table5_quantization",
    "per_op_tables",
    "table6_lut_savings",
    "greedy_search_bench",
]


def main() -> None:
    only = sys.argv[1:] or MODULES
    print("name,us_per_call,derived")
    failed = []
    for mod_name in MODULES:
        if mod_name not in only:
            continue
        try:
            mod = __import__(f"benchmarks.{mod_name}",
                             fromlist=["run"])
            for name, us, derived in mod.run():
                print(f"{name},{us},{derived}", flush=True)
        except Exception:
            failed.append(mod_name)
            print(f"{mod_name},ERROR,{traceback.format_exc()[-300:]!r}",
                  flush=True)
    if failed:
        raise SystemExit(f"benchmark modules failed: {failed}")


if __name__ == "__main__":
    main()
