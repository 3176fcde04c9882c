"""Serve an LM with packed MXInt weights + continuous batching.

Weights are stored as int8 mantissa planes + shared exponents (the paper's
format, W8 block-256), the KV cache and scheduler come from repro.serving.
Uses the llama3-family smoke config so it runs on CPU; pass --arch to pick
any assigned architecture.  ``--kernel`` switches the model to
QuantConfig(mode='kernel'): every linear eats the packed planes in a
Pallas kernel and each decode step scores the KV cache ring through the
fused `flash_attention_decode` datapath (DESIGN.md §11) — interpret mode
on CPU, so it is slower here but is the TPU deployment path.

Run:  PYTHONPATH=src python examples/serve_llm_mxint.py [--arch llama3_8b]
                                                        [--kernel]
"""
import argparse
import dataclasses
import time

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs import smoke_config
from repro.core.mx_types import MXINT8_WEIGHT, QuantConfig
from repro.launch.compile_cache import use_persistent_compile_cache
from repro.models import build_model
from repro.serving.engine import ServeConfig, ServingEngine
from repro.serving.scheduler import BatchScheduler, Request


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="llama3_8b")
    ap.add_argument("--requests", type=int, default=6)
    ap.add_argument("--new-tokens", type=int, default=12)
    ap.add_argument("--kernel", action="store_true",
                    help="mode='kernel': Pallas linears + fused decode "
                         "attention over the cache ring")
    args = ap.parse_args()
    use_persistent_compile_cache()

    cfg = smoke_config(args.arch)
    if args.kernel:
        cfg = dataclasses.replace(
            cfg, quant=QuantConfig(mode="kernel", quantize_nonlinear=True))
    model = build_model(cfg)
    params = model.init(jax.random.key(0))

    print(f"arch={cfg.name}: packing weights to MXInt8 (block 256)...")
    eng = ServingEngine(model, params,
                        ServeConfig(max_len=128, batch=2, pack_weights=True,
                                    weight_fmt=MXINT8_WEIGHT))
    sched = BatchScheduler(eng, batch_size=2)

    rng = np.random.default_rng(0)
    for uid in range(args.requests):
        sched.submit(Request(
            uid=uid,
            prompt=rng.integers(0, cfg.vocab, 8).astype(np.int32),
            max_new_tokens=args.new_tokens))

    t0 = time.time()
    done = []
    steps = 0
    while (any(not r.done for r in sched.active if r) or sched.queue) and \
            steps < 500:
        sched.step()
        steps += 1
        for i, r in enumerate(sched.active):
            if r is not None and r.done and r not in done:
                done.append(r)
                print(f"  req {r.uid}: {len(r.generated)} tokens -> "
                      f"{r.generated[:8]}...")
                sched.active[i] = None
    dt = time.time() - t0
    total_toks = sum(len(r.generated) for r in done)
    print(f"\n{len(done)} requests, {total_toks} tokens in {dt:.2f}s "
          f"({total_toks/max(dt,1e-9):.1f} tok/s, CPU, continuous batching)")


if __name__ == "__main__":
    main()
