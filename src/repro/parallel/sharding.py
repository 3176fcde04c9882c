"""Logical-axis sharding rules: map Param axes onto mesh axes.

The production mesh has axes ("pod", "data", "model") (multi-pod) or
("data", "model") (single pod).  Logical axis names used by the model zoo:

  batch      -> (pod, data)        activations / inputs
  seq        -> None by default; 'data' under sequence-parallel decode
  embed      -> None               d_model stays replicated across TP
  q_heads    -> model              attention heads (TP)
  kv_heads   -> model              KV heads (TP; replicated if fewer heads
                                   than shards — GSPMD handles the remainder)
  mlp        -> model              FFN hidden
  vocab      -> model              embedding / unembedding tables
  expert     -> model              MoE expert dim (EP)
  lru        -> model              recurrent channel dim
  layers     -> None               stacked-scan leading dim
  fsdp       -> data               optional ZeRO-style param shard (hillclimb)

Rules are a dataclass so perf iterations can swap assignments per run.
"""
from __future__ import annotations

import dataclasses
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P


@dataclasses.dataclass(frozen=True)
class ShardingRules:
    batch: Optional[Tuple[str, ...]] = ("pod", "data")
    seq: Optional[str] = None
    embed: Optional[str] = None
    q_heads: Optional[str] = "model"
    kv_heads: Optional[str] = "model"
    heads: Optional[str] = "model"
    mlp: Optional[str] = "model"
    vocab: Optional[str] = "model"
    expert: Optional[str] = "model"
    lru: Optional[str] = "model"
    layers: Optional[str] = None
    kv_seq: Optional[str] = None           # sequence-parallel KV (long ctx)
    patch: Optional[str] = None
    classes: Optional[str] = None
    conv: Optional[str] = None
    pods: Optional[str] = "pod"            # per-pod state (error feedback)
    cap: Optional[Tuple[str, ...]] = ("pod", "data")  # MoE dispatch capacity
    fsdp: Optional[str] = None             # set to "data" for ZeRO-style

    def get(self, name: Optional[str]):
        if name is None:
            return None
        return getattr(self, name)


LOGICAL_RULES = ShardingRules()


def _filter_axes(assignment, mesh_axis_names):
    """Drop mesh axes absent from the current mesh (single-pod drops 'pod')."""
    if assignment is None:
        return None
    if isinstance(assignment, str):
        return assignment if assignment in mesh_axis_names else None
    kept = tuple(a for a in assignment if a in mesh_axis_names)
    return kept if kept else None


def logical_to_pspec(axes: Tuple[Optional[str], ...],
                     rules: ShardingRules,
                     mesh_axis_names,
                     shape: Optional[Tuple[int, ...]] = None,
                     mesh_shape: Optional[dict] = None) -> P:
    """Logical axes tuple -> PartitionSpec.

    Drops mesh axes absent from the current mesh, de-duplicates (a mesh axis
    may appear once per spec), and — when ``shape`` is given — prunes mesh
    axes that do not divide the dimension (e.g. vocab=49155 over model=16,
    MQA kv_heads=1): the longest divisible prefix of the assignment is kept,
    so a (pod, data) batch assignment degrades gracefully to (pod,) or
    replication for small dims."""
    used = set()
    out = []
    for i, name in enumerate(axes):
        a = _filter_axes(rules.get(name), mesh_axis_names)
        if a is None:
            out.append(None)
            continue
        names = (a,) if isinstance(a, str) else a
        names = tuple(n for n in names if n not in used)
        if shape is not None and mesh_shape is not None and i < len(shape):
            while names:
                prod = 1
                for n in names:
                    prod *= mesh_shape[n]
                if prod > 0 and shape[i] % prod == 0:
                    break
                names = names[:-1]
        used.update(names)
        if not names:
            out.append(None)
        elif len(names) == 1:
            out.append(names[0])
        else:
            out.append(names)
    return P(*out)


def params_pspecs(axes_pytree, rules: ShardingRules, mesh: Mesh):
    """Map an axes pytree (from model_api.axes_tree) to PartitionSpecs."""
    names = mesh.axis_names
    return jax.tree_util.tree_map(
        lambda axes: logical_to_pspec(axes, rules, names),
        axes_pytree,
        is_leaf=lambda x: isinstance(x, tuple) and all(
            a is None or isinstance(a, str) for a in x))


def named_sharding_tree(axes_pytree, rules: ShardingRules, mesh: Mesh):
    specs = params_pspecs(axes_pytree, rules, mesh)
    return jax.tree_util.tree_map(
        lambda s: NamedSharding(mesh, s), specs,
        is_leaf=lambda x: isinstance(x, P))


# ---------------------------------------------------------------------------
# tensor-parallel sharding of packed MXInt planes (serving; DESIGN.md §10)
# ---------------------------------------------------------------------------
def _tp_decision(value, n_shards: int, strategy: str):
    """Which axis of a packed weight shards under ``strategy``, or None.

    value: an MXTensor whose planes may still be ShapeDtypeStructs
    (abstract dry-run packing).  Returns (axis, tp_mode) with axis an
    index into the mantissa shape, or None when the leaf must stay
    replicated (not packed, not divisible, or the split would straddle a
    shared-exponent block).
    """
    from repro.core.quantize import MXTensor
    if not isinstance(value, MXTensor):
        return None
    shape = value.mantissa.shape
    if len(shape) < 2:
        return None
    scale_axis = value.scale_axis % len(shape)
    if strategy == "column":
        axis, mode = len(shape) - 1, "gather"
        if axis == scale_axis:
            return None          # output axis carries the shared-exponent
                                 # blocks (embedding tables): cannot
                                 # column-shard without splitting blocks
    elif strategy == "row":
        axis, mode = scale_axis, "psum"
        if axis != len(shape) - 2:
            # mxint_linear contracts the second-to-last plane axis; leaves
            # whose blocks run elsewhere (embedding/unembedding tables:
            # last axis) are consumed via dequantize, not the kernel —
            # sharding them here would silently mismatch.  Replicate.
            return None
        # the exponent plane must split evenly too: block boundaries may
        # not straddle shards (pack with tp_shards=n_shards to guarantee)
        if (shape[axis] // value.block_size) % n_shards:
            return None
    else:
        raise ValueError(f"unknown tp strategy {strategy!r}")
    if shape[axis] % n_shards:
        return None
    return axis, mode


def tp_shard_packed_params(packed_params, n_shards: int,
                           axis_name: str = "model",
                           strategy: str = "column"):
    """Mark packed Param leaves for tensor parallelism and build in_specs.

    packed_params: a Param tree from ``pack_params_mxint`` (MXTensor
    values on the large matmul weights, plain arrays elsewhere).
    n_shards: size of the ``axis_name`` mesh axis.
    strategy:
      'column' — shard every packed weight along its OUTPUT (last) axis;
        each shard contracts the full K and `mxint_linear` all_gathers
        the column slices.  Bit-exact vs single-device by construction
        (collectives only move data).  The serving default.
      'row'    — shard along the contraction/block axis (Megatron
        row-parallel); `mxint_linear` slices the replicated activations
        and psums partial products.  Halves the activation traffic but
        the f32 psum re-orders accumulation: close, NOT bit-exact.
        Pack with ``pack_params_mxint(..., tp_shards=n_shards)`` so block
        boundaries never straddle shards (DESIGN.md §8).

    Returns ``(marked_params, in_specs)``: the same tree with
    ``tp_axis``/``tp_mode`` stamped on the sharded MXTensor leaves, and a
    PartitionSpec tree (one spec per Param position — the exponent plane
    inherits the mantissa plane's spec, their ranks match) usable as
    shard_map in_specs or for ``NamedSharding`` device placement.
    Everything that is not a shardable packed weight (norm scales,
    biases, positional tables) is replicated: biases are added after the
    collective inside ``mxint_linear``, so they stay full-width.
    """
    from repro.models.model_api import Param, is_param

    def mark(p: Param) -> Param:
        d = _tp_decision(p.value, n_shards, strategy)
        if d is None:
            return p
        return Param(p.value._replace(tp_axis=axis_name, tp_mode=d[1]),
                     p.axes)

    def spec(p: Param) -> P:
        d = _tp_decision(p.value, n_shards, strategy)
        if d is None:
            return P()
        axis, _ = d
        ndim = len(p.value.mantissa.shape)
        return P(*(axis_name if i == axis else None for i in range(ndim)))

    marked = jax.tree_util.tree_map(mark, packed_params, is_leaf=is_param)
    specs = jax.tree_util.tree_map(spec, packed_params, is_leaf=is_param)
    return marked, specs


def ambient_mesh():
    """The mesh the current trace runs under (``jax.set_mesh``), or None."""
    m = jax.sharding.get_abstract_mesh()
    return None if m.empty else m


def maybe_constraint(x: jnp.ndarray, axes: Tuple[Optional[str], ...]):
    """with_sharding_constraint when tracing under a mesh, else identity."""
    env_mesh = ambient_mesh()
    if env_mesh is None:
        return x
    spec = logical_to_pspec(axes, LOGICAL_RULES, env_mesh.axis_names)
    try:
        return jax.lax.with_sharding_constraint(x, spec)
    except Exception:
        return x
