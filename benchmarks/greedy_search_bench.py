"""The paper's greedy bitwidth search (§III-A): determine the minimal
weight-mantissa width per tensor group under the 1% accuracy-loss budget.

The paper reports W6/A8 as the lossless point for DeiT; here the same
greedy loop runs on the trained synthetic-task DeiT with argmax-agreement
as the budgeted metric and reports the per-group result + mean bits.

Hosted by ``repro.dse`` since ISSUE 10: the groups are proper per-layer
scopes ("block/*/attn" / "block/*/ffn" / "head") on a SearchSpace over a
weight-QDQ base config with near-lossless 16-bit activations, and the
loop is ``dse.drivers.greedy_search`` — the re-hosted
``core.search.greedy_bitwidth_search`` accept rule (so the old ad-hoc
leaf-requantizing loop and the subsystem cannot drift apart).  Row names
are unchanged.
"""
from __future__ import annotations

import time

from benchmarks import common
from repro.core.mx_types import MXFormat, QuantConfig
from repro.data.pipeline import SyntheticImageData
from repro.dse import Evaluator, GroupSpace, SearchSpace, greedy_search

# bench row group -> model scope glob (row names are the stable API)
ROW_SCOPES = (("attn_w", "block/*/attn"),
              ("ffn_w", "block/*/ffn"),
              ("head_w", "head"))
WIDTHS = tuple(range(10, 2, -1))        # 10 (reference) down to 3


def run():
    model, params = common.trained_deit_micro()
    data = SyntheticImageData(batch=256, seed=500, **common._TASK)
    batch = data.next_batch()

    # weight-only QDQ sweep: activations at 16 bits are lossless on this
    # task, so the budget binds on the weight mantissas (paper Table V)
    base = QuantConfig(mode="fake",
                       weight_fmt=MXFormat(mant_bits=10, block_size=256),
                       act_fmt=MXFormat(mant_bits=16, block_size=16))
    space = SearchSpace(base=base, groups=tuple(
        GroupSpace(scope=scope, weight_mant_bits=WIDTHS)
        for _, scope in ROW_SCOPES))
    ev = Evaluator(space, model.cfg, params, batch["images"])

    t0 = time.perf_counter()
    res = greedy_search(space, ev, budget=0.01,
                        order=[scope for _, scope in ROW_SCOPES])
    us = (time.perf_counter() - t0) * 1e6
    rows = [(f"greedy/{g}_bits", 0.0, str(res.bits[scope]))
            for g, scope in ROW_SCOPES]
    rows.append(("greedy/mean_bits", round(us, 0),
                 f"{res.mean_bits:.2f} (paper: W6 for DeiT) "
                 f"steps={len(res.trace)}"))
    return rows


if __name__ == "__main__":
    for r in run():
        print(",".join(map(str, r)))
