"""Shared benchmark helpers: a trained micro-DeiT and its accuracy.

The container has no ImageNet, so accuracy numbers come from a DeiT of the
same family trained on a synthetic 10-class task (class-conditional blobs,
repro.data).  Quantization is then *post-training* exactly as in the paper,
and every table reports the accuracy DELTA against the float model — the
quantity the paper budgets (<1%).
"""
from __future__ import annotations

import functools
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs.deit import DEIT_MICRO
from repro.data.pipeline import SyntheticImageData
from repro.models import build_model
from repro.models.model_api import unwrap, Param, is_param
from repro.optim.adamw import AdamWConfig
from repro.train.state import make_train_state
from repro.train.step import make_train_step

CACHE = Path(__file__).resolve().parent / "_cache"
CACHE.mkdir(exist_ok=True)


# A HARD task config: 100 thin-margin classes, heavy noise, outlier image
# channels (the activation-outlier phenomenon that breaks per-tensor int
# quantization on real ViTs).  The float model lands well below 100%, so
# quantization formats separate — the paper's Table V regime.
import dataclasses as _dc
BENCH_DEIT = _dc.replace(DEIT_MICRO, n_classes=100)
_TASK = dict(n_classes=100, image_size=32, noise=1.0, class_sep=0.25,
             outlier_channels=False)


@functools.lru_cache(maxsize=1)
def trained_deit_micro(steps: int = 700):
    """Train (or load cached) micro-DeiT on the hard synthetic task."""
    model = build_model(BENCH_DEIT)
    params = model.init(jax.random.key(0))
    cache_file = CACHE / f"deit_micro_h{steps}.npz"
    flat, treedef = jax.tree_util.tree_flatten(unwrap(params))
    if cache_file.exists():
        data = np.load(cache_file)
        leaves = [jnp.asarray(data[f"leaf_{i}"]) for i in range(len(flat))]
        vals = jax.tree_util.tree_unflatten(treedef, leaves)
        params = jax.tree_util.tree_map(
            lambda p, v: Param(v, p.axes), params, vals, is_leaf=is_param)
        return model, params
    data = SyntheticImageData(batch=64, seed=0, **_TASK)
    state = make_train_state(model, jax.random.key(0))
    step = jax.jit(make_train_step(
        model, lr_fn=lambda s: jnp.asarray(1e-3, jnp.float32),
        opt_cfg=AdamWConfig(weight_decay=0.01)))
    for _ in range(steps):
        state, metrics = step(state, data.next_batch())
    params = state.params
    leaves = jax.tree_util.tree_leaves(unwrap(params))
    np.savez(cache_file, **{f"leaf_{i}": np.asarray(l)
                            for i, l in enumerate(leaves)})
    return model, params


def eval_accuracy(model, params, n_batches: int = 8, seed: int = 99) -> float:
    data = SyntheticImageData(batch=128, seed=seed, **_TASK)
    acc_fn = jax.jit(model.accuracy)
    accs = []
    for _ in range(n_batches):
        b = data.next_batch()
        accs.append(float(acc_fn(params, b)))
    return float(np.mean(accs))

