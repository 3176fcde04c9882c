"""Seeded DeiT weights, made on the device in one jitted call.

The tree uses the program's leaf names and shapes (``ViT.init``), with
plain float32 arrays as leaves, so the reference reads it directly and
``to_program`` only wraps each leaf in the program's ``Param``.  Values
are drawn the way a trained DeiT's are scaled: matrices N(0, 1/fan_in),
LayerNorm scales 1 + N(0, 0.1^2), shifts, biases, the class token and
the position table N(0, 0.02^2) or N(0, 0.1^2).  Nonzero shifts and
biases make every term of the datapath do work.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np


def jax_key(seed: int):
    """A JAX key from any non-negative integer seed (wider than 32 bits
    too): the seed goes through numpy's SeedSequence."""
    return jax.random.key(int(np.random.SeedSequence(seed).generate_state(1)[0]))


def shapes(cfg: dict) -> dict:
    """Leaf name -> (shape, kind) of a DeiT with the config's sizes."""
    d, ff, L = cfg["hidden_size"], cfg["intermediate_size"], cfg["num_hidden_layers"]
    p, c = cfg["patch_size"], cfg["num_labels"]
    tokens = (cfg["image_size"] // p) ** 2 + 1
    return {
        "patch_proj": ((3 * p * p, d), "matrix"),
        "patch_bias": ((d,), "bias"),
        "cls_token": ((1, 1, d), "bias"),
        "pos_embed": ((tokens, d), "bias"),
        "blocks": {
            "ln1_g": ((L, d), "scale"), "ln1_b": ((L, d), "shift"),
            "attn": {n: ((L, d, d), "matrix") for n in ("wq", "wk", "wv", "wo")},
            "ln2_g": ((L, d), "scale"), "ln2_b": ((L, d), "shift"),
            "ffn": {"wi": ((L, d, ff), "matrix"), "bi": ((L, ff), "bias"),
                    "wo": ((L, ff, d), "matrix"), "bo": ((L, d), "bias")},
        },
        "final_ln_g": ((d,), "scale"), "final_ln_b": ((d,), "shift"),
        "head": ((d, c), "matrix"), "head_b": ((c,), "bias"),
    }


def _leaf(key, shape, kind):
    z = jax.random.normal(key, shape, jnp.float32)
    if kind == "matrix":
        return z * shape[-2] ** -0.5
    if kind == "scale":
        return 1.0 + 0.1 * z
    if kind == "shift":
        return 0.1 * z
    return 0.02 * z


DIMS = ("hidden_size", "intermediate_size", "num_hidden_layers",
        "patch_size", "image_size", "num_labels")


@functools.partial(jax.jit, static_argnames="dims")
def _make(key, dims):
    spec = shapes(dict(dims))
    leaves, tree = jax.tree_util.tree_flatten(
        spec, is_leaf=lambda x: isinstance(x, tuple) and isinstance(x[1], str))
    keys = jax.random.split(key, len(leaves))
    made = [_leaf(k, shape, kind) for k, (shape, kind) in zip(keys, leaves)]
    return jax.tree_util.tree_unflatten(tree, made)


def make(cfg: dict, seed: int) -> dict:
    """The weight tree of ``cfg`` for ``seed``, on the default device."""
    return _make(jax_key(seed), dims=tuple((k, cfg[k]) for k in DIMS))


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
