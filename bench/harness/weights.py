"""Seeded weights of a configuration, made on the device in one jitted call.

The tree is the family's ``shapes(cfg)`` (``bench/families/<family>.py``):
the program's leaf names and shapes, with plain float32 arrays as leaves,
so the reference reads it directly and ``to_program`` only wraps each
leaf in the program's ``Param``.  Values are drawn by each leaf's kind,
the way a trained model's are scaled: matrices N(0, 1/fan_in), scales
1 + N(0, 0.1^2), shifts N(0, 0.1^2), biases and embeddings N(0, 0.02^2).
Nonzero shifts and biases make every term of the datapath do work.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

from bench.harness import manifest


def jax_key(seed: int):
    """A JAX key from any non-negative integer seed (wider than 32 bits
    too): the seed goes through numpy's SeedSequence."""
    return jax.random.key(int(np.random.SeedSequence(seed).generate_state(1)[0]))


def _leaf(key, shape, kind):
    z = jax.random.normal(key, shape, jnp.float32)
    if kind == "matrix":
        return z * shape[-2] ** -0.5
    if kind == "scale":
        return 1.0 + 0.1 * z
    if kind == "shift":
        return 0.1 * z
    return 0.02 * z


@functools.partial(jax.jit, static_argnames=("family", "dims"))
def _make(key, family, dims):
    spec = manifest.family(family).shapes(dict(dims))
    leaves, tree = jax.tree_util.tree_flatten(
        spec, is_leaf=lambda x: isinstance(x, tuple) and isinstance(x[1], str))
    keys = jax.random.split(key, len(leaves))
    made = [_leaf(k, shape, kind) for k, (shape, kind) in zip(keys, leaves)]
    return jax.tree_util.tree_unflatten(tree, made)


def make(cfg: dict, seed: int) -> dict:
    """The weight tree of ``cfg`` for ``seed``, on the default device."""
    family = cfg["family"]
    return _make(jax_key(seed), family=family, dims=tuple(
        (k, cfg[k]) for k in manifest.family(family).DIMS))


def to_program(tree: dict, model) -> dict:
    """Wrap the leaves in the program's ``Param`` tree (leaf axes from
    ``model.init`` traced abstractly); every shape must match."""
    from repro.models.model_api import is_param

    abstract = jax.eval_shape(model.init, jax.random.key(0))
    mine = dict(jax.tree_util.tree_flatten_with_path(tree)[0])
    out = []
    flat, treedef = jax.tree_util.tree_flatten_with_path(abstract,
                                                         is_leaf=is_param)
    for path, p in flat:
        v = mine.pop(path, None)
        if v is None or v.shape != p.value.shape:
            raise ValueError(f"weight {jax.tree_util.keystr(path)}: program "
                             f"wants {p.value.shape}, benchmark has "
                             f"{None if v is None else v.shape}")
        out.append(p._replace(value=v))
    if mine:
        raise ValueError(f"weights the program does not take: "
                         f"{[jax.tree_util.keystr(k) for k in mine]}")
    return jax.tree_util.tree_unflatten(treedef, out)
