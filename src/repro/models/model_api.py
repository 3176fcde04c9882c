"""Model API: configs, parameter wrappers with logical sharding axes,
and the common protocol every architecture implements.

Parameters are created as ``Param(value, axes)`` where ``axes`` is a tuple of
*logical* axis names (e.g. ("embed", "q_heads")).  The parallel layer
(repro.parallel.sharding) maps logical names onto mesh axes.  Keeping the
axes on the leaf makes init the single source of truth — no drift between a
separate spec tree and the real params.
"""
from __future__ import annotations

import dataclasses
from typing import Any, Callable, Dict, NamedTuple, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp

from repro.core.mx_types import QuantConfig


class Param(NamedTuple):
    """A parameter leaf plus its logical sharding axes (aux data)."""
    value: Any               # jnp.ndarray | ShapeDtypeStruct | MXTensor
    axes: Tuple[Optional[str], ...]


jax.tree_util.register_pytree_node(
    Param,
    lambda p: ((p.value,), (p.axes,)),
    lambda aux, leaves: Param(leaves[0], aux[0]),
)


def is_param(x) -> bool:
    return isinstance(x, Param)


def unwrap(tree):
    """Strip Param wrappers -> raw value pytree."""
    return jax.tree_util.tree_map(lambda p: p.value, tree, is_leaf=is_param)


def axes_tree(tree):
    """Param tree -> logical-axes pytree (same structure as unwrap)."""
    return jax.tree_util.tree_map(lambda p: p.axes, tree, is_leaf=is_param)


def wrap_like(values, params_with_axes):
    """Re-attach axes from a Param tree onto a matching value tree."""
    return jax.tree_util.tree_map(
        lambda v, p: Param(v, p.axes), values, params_with_axes,
        is_leaf=lambda x: False)


# ---------------------------------------------------------------------------
# configs
# ---------------------------------------------------------------------------
@dataclasses.dataclass(frozen=True)
class MoEConfig:
    num_experts: int = 8
    top_k: int = 2
    capacity_factor: float = 1.25
    router_aux_loss: float = 0.01


@dataclasses.dataclass(frozen=True)
class ModelConfig:
    """One config drives every architecture family.

    unit / n_units / tail describe the layer stack as a repeating pattern so
    heterogeneous models (recurrentgemma's R-R-A, xlstm's 7xM+S) scan over
    *units* with stacked params — HLO stays O(1) in depth.
    Block kinds: 'attn', 'rec' (RG-LRU), 'mlstm', 'slstm'.
    """

    name: str = "model"
    family: str = "dense"          # dense|moe|hybrid|ssm|vlm|audio|vit|sam
    n_layers: int = 4
    d_model: int = 512
    n_heads: int = 8
    n_kv_heads: int = 8
    d_ff: int = 2048
    vocab: int = 32000
    head_dim: Optional[int] = None
    # layer pattern
    unit: Tuple[str, ...] = ("attn",)
    n_units: Optional[int] = None          # default n_layers / len(unit)
    tail: Tuple[str, ...] = ()
    # mixer details
    rope_theta: float = 10000.0
    qk_norm: bool = False
    window: int = 0                        # sliding-window size; 0 = full
    local_attn_window: int = 0             # for hybrid local-attn blocks
    # ffn
    ffn_kind: str = "swiglu"               # swiglu|geglu|gelu|moe|none
    moe: Optional[MoEConfig] = None
    # recurrent details
    lru_width: Optional[int] = None        # RG-LRU width (default d_model)
    conv_width: int = 4
    # enc-dec
    is_encoder_decoder: bool = False
    n_encoder_layers: int = 0
    # vlm / audio stubs
    vision_tokens: int = 0                 # prefix positions fed by projector
    vision_dim: int = 1024
    audio_frames: bool = False             # encoder input is frame embeddings
    # vit
    image_size: int = 224
    patch_size: int = 16
    n_classes: int = 1000
    pool: str = "cls"
    # sam (Segment Anything image encoder): window attention of
    # attn_window_size^2 tokens, the last layer of every
    # global_attn_period layers global, a neck of neck_dim channels
    attn_window_size: int = 0
    global_attn_period: int = 0
    neck_dim: int = 0
    # numerics / runtime
    dtype: Any = jnp.bfloat16
    norm_eps: float = 1e-6
    tie_embeddings: bool = False
    quant: QuantConfig = dataclasses.field(default_factory=QuantConfig)
    remat: str = "none"                    # none|block|full
    max_cache_len: int = 4096

    @property
    def hd(self) -> int:
        return self.head_dim or self.d_model // self.n_heads

    @property
    def resolved_n_units(self) -> int:
        if self.n_units is not None:
            return self.n_units
        assert (self.n_layers - len(self.tail)) % len(self.unit) == 0, \
            (self.n_layers, self.unit, self.tail)
        return (self.n_layers - len(self.tail)) // len(self.unit)

    def validate(self):
        assert self.resolved_n_units * len(self.unit) + len(self.tail) == \
            self.n_layers, "unit pattern must tile n_layers"
        assert self.n_heads % self.n_kv_heads == 0
        if self.ffn_kind == "moe":
            assert self.moe is not None
        return self


@dataclasses.dataclass(frozen=True)
class ShapeConfig:
    """One of the assigned input-shape cells."""
    name: str
    seq_len: int
    global_batch: int
    kind: str                  # train|prefill|decode


TRAIN_4K = ShapeConfig("train_4k", 4096, 256, "train")
PREFILL_32K = ShapeConfig("prefill_32k", 32768, 32, "prefill")
DECODE_32K = ShapeConfig("decode_32k", 32768, 128, "decode")
LONG_500K = ShapeConfig("long_500k", 524288, 1, "decode")
ALL_SHAPES = (TRAIN_4K, PREFILL_32K, DECODE_32K, LONG_500K)


def shape_by_name(name: str) -> ShapeConfig:
    for s in ALL_SHAPES:
        if s.name == name:
            return s
    raise KeyError(name)


# ---------------------------------------------------------------------------
# init helpers
# ---------------------------------------------------------------------------
def dense_init(key, shape, axes, scale=None, dtype=jnp.float32) -> Param:
    fan_in = shape[-2] if len(shape) >= 2 else shape[-1]
    scale = scale if scale is not None else fan_in ** -0.5
    v = jax.random.normal(key, shape, dtype=jnp.float32) * scale
    return Param(v.astype(dtype), axes)


def zeros_init(shape, axes, dtype=jnp.float32) -> Param:
    return Param(jnp.zeros(shape, dtype=dtype), axes)


def ones_init(shape, axes, dtype=jnp.float32) -> Param:
    return Param(jnp.ones(shape, dtype=dtype), axes)
