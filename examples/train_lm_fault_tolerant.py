"""Train an LM with the full production loop: checkpoints, crash recovery,
heartbeats, metrics — then kill it mid-run and watch it resume.

Default config is CPU-sized; --arch picks any assigned architecture's smoke
config, --steps/--batch scale it up (the same loop + sharding machinery is
what the multi-pod dry-run compiles at the 512-chip mesh).

Run:  PYTHONPATH=src python examples/train_lm_fault_tolerant.py
"""
import argparse
import shutil
from pathlib import Path

import jax
import jax.numpy as jnp

from repro.configs import smoke_config
from repro.data.pipeline import SyntheticLMData
from repro.launch.compile_cache import use_persistent_compile_cache
from repro.models import build_model
from repro.optim.adamw import AdamWConfig
from repro.optim.schedules import cosine_schedule
from repro.train.loop import LoopConfig, TrainLoop
from repro.train.state import make_train_state
from repro.train.step import make_train_step


def build(arch, tmpdir, total_steps, batch, seq):
    cfg = smoke_config(arch)
    model = build_model(cfg)
    state = make_train_state(model, jax.random.key(0))
    data = SyntheticLMData(vocab=cfg.vocab, batch=batch, seq_len=seq, seed=7)
    lr_fn = lambda s: cosine_schedule(s, peak=3e-3, warmup_steps=10,
                                      total_steps=total_steps)
    step = jax.jit(make_train_step(model, lr_fn=lr_fn,
                                   opt_cfg=AdamWConfig(weight_decay=0.01)))
    lcfg = LoopConfig(total_steps=total_steps, checkpoint_every=10,
                      log_every=5, checkpoint_dir=str(tmpdir / "ckpt"),
                      metrics_path=str(tmpdir / "metrics.jsonl"),
                      heartbeat_path=str(tmpdir / "heartbeat.json"))
    return TrainLoop(train_step=step, state=state, data=data, cfg=lcfg)


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="llama3_8b")
    ap.add_argument("--steps", type=int, default=40)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--seq", type=int, default=32)
    args = ap.parse_args()
    use_persistent_compile_cache()

    tmpdir = Path("/tmp/repro_train_demo")
    shutil.rmtree(tmpdir, ignore_errors=True)
    tmpdir.mkdir(parents=True)

    print(f"=== phase 1: train to step {args.steps // 2}, then 'crash' ===")
    loop = build(args.arch, tmpdir, args.steps // 2, args.batch, args.seq)
    m1 = loop.run()
    print(f"   loss {m1[0]['loss']:.3f} -> {m1[-1]['loss']:.3f}; "
          f"checkpoint committed at step {loop.ckpt.latest_step()}")
    del loop  # the 'crash'

    print(f"=== phase 2: fresh process resumes from the checkpoint ===")
    loop2 = build(args.arch, tmpdir, args.steps, args.batch, args.seq)
    resumed = loop2.try_resume()
    print(f"   resumed from step {resumed} "
          f"(data stream index {loop2.data.state.next_index})")
    m2 = loop2.run(start_step=resumed)
    print(f"   final loss {m2[-1]['loss']:.3f} at step {m2[-1]['step']}")
    print(f"   metrics in {tmpdir}/metrics.jsonl, "
          f"heartbeat in {tmpdir}/heartbeat.json")
    assert m2[-1]["loss"] < m1[0]["loss"]
    print("fault-tolerant training demo OK")


if __name__ == "__main__":
    main()
