"""Train / eval step builders.

make_train_step returns a pure (state, batch) -> (state, metrics) function
suitable for jit with in/out shardings.  Options:

  * microbatches=N      — gradient accumulation via lax.scan over N slices
                          of the global batch (activation memory / N).
  * grad_compression    — MXInt-compress the *pod-axis* gradient reduction
                          (beyond-paper; DESIGN.md §3).  Implemented with
                          a fully manual jax.shard_map that splits the
                          'pod' axis; the inter-pod wire format is int8
                          mantissa + shared exponents with error feedback.
"""
from __future__ import annotations

import functools
from typing import Any, Callable, Optional

import jax
import jax.numpy as jnp

from repro.core import gradient_compression as gc
from repro.optim.adamw import AdamWConfig, adamw_update
from repro.train.state import TrainState


def _microbatch_value_and_grad(loss_fn, params, batch, n_micro: int):
    """Accumulate grads over n_micro slices of the leading batch dim."""
    def slice_batch(b, i):
        return jax.tree_util.tree_map(
            lambda x: jax.lax.dynamic_slice_in_dim(
                x, i * (x.shape[0] // n_micro), x.shape[0] // n_micro, 0), b)

    def body(carry, i):
        loss_acc, grad_acc = carry
        mb = slice_batch(batch, i)
        loss, grads = jax.value_and_grad(loss_fn)(params, mb)
        grad_acc = jax.tree_util.tree_map(jnp.add, grad_acc, grads)
        return (loss_acc + loss, grad_acc), None

    zeros = jax.tree_util.tree_map(
        lambda p: jnp.zeros(p.shape, jnp.float32), params)
    (loss_sum, grads), _ = jax.lax.scan(
        body, (jnp.zeros((), jnp.float32), zeros), jnp.arange(n_micro))
    scale = 1.0 / n_micro
    return loss_sum * scale, jax.tree_util.tree_map(
        lambda g: g * scale, grads)


def make_train_step(model, *, lr_fn: Callable, opt_cfg: AdamWConfig = None,
                    microbatches: int = 1,
                    grad_compression: bool = False,
                    mesh=None) -> Callable:
    opt_cfg = opt_cfg or AdamWConfig()

    def loss_fn(params, batch):
        return model.loss(params, batch).astype(jnp.float32)

    def _compute_grads(params, batch):
        if microbatches > 1:
            return _microbatch_value_and_grad(loss_fn, params, batch,
                                              microbatches)
        return jax.value_and_grad(loss_fn)(params, batch)

    use_compression = (grad_compression and mesh is not None
                       and "pod" in mesh.axis_names)

    def train_step(state: TrainState, batch) -> tuple:
        if use_compression:
            loss, grads, err_fb = _pod_compressed_grads(
                _compute_grads, state.params, batch, state.err_fb, mesh)
        else:
            loss, grads = _compute_grads(state.params, batch)
            err_fb = state.err_fb
        lr = lr_fn(state.step)
        new_params, new_opt, gnorm = adamw_update(
            grads, state.opt, state.params, lr, opt_cfg)
        metrics = {"loss": loss, "grad_norm": gnorm, "lr": lr,
                   "step": state.step}
        return TrainState(new_params, new_opt, state.step + 1,
                          err_fb), metrics

    return train_step


def _pod_compressed_grads(compute_grads, params, batch, err_fb, mesh):
    """Per-pod gradients + MXInt-compressed mean over the 'pod' axis.

    Error-feedback residuals carry a leading n_pods axis (sharded
    P('pod')) — each pod keeps its own residual, the EF-SGD requirement.
    """
    from jax.sharding import PartitionSpec as P
    n_pods = mesh.shape["pod"]

    def per_pod(p, pod_batch, pod_err):
        err = jax.tree_util.tree_map(lambda e: e[0], pod_err)
        loss, grads = compute_grads(p, pod_batch)
        red, new_err = gc.compressed_psum(grads, "pod", err)
        grads = jax.tree_util.tree_map(lambda g: g / n_pods, red)
        loss = jax.lax.pmean(loss, "pod")
        return loss, grads, jax.tree_util.tree_map(
            lambda e: e[None], new_err)

    in_specs = (P(), P("pod"), P("pod"))
    out_specs = (P(), P(), P("pod"))
    # Fully manual over every mesh axis: partial-manual (manual 'pod',
    # auto 'data'/'model') aborts in XLA's HLO pass pipeline.  The pod
    # axis is split; inside a pod every device sees the replicated
    # pod-local arrays.  Replication checking is off: scan carries inside
    # the model init are pod-unvarying zeros while their outputs vary
    # with the pod-local batch, and the explicit psum makes the reduction
    # correct by construction.
    mapped = jax.shard_map(per_pod, mesh=mesh, in_specs=in_specs,
                           out_specs=out_specs, check_vma=False)
    return mapped(params, batch, err_fb)


def make_eval_step(model) -> Callable:
    def eval_step(params, batch):
        return model.loss(params, batch)
    return eval_step
