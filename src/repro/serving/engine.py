"""Serving: packed-MXInt weights, prefill/decode step builders, engines.

``pack_params_mxint`` converts linear/embedding Param leaves to MXTensor
planes (int8 mantissas + int8 shared exponents) — the paper's weight
format.  The serving dry-run lowers with these packed leaves, so
``memory_analysis()`` shows the real ~4x HBM reduction (DESIGN.md §8).

``ViTServingEngine`` additionally serves SHARDED: given a mesh with a
'model' axis, the packed planes are partitioned over the shards
(mantissa and exponent planes with the same PartitionSpec — they shard
together by construction) and every linear runs ``mxint_linear`` on its
local planes under ``shard_map``, bit-identical to the single-device
kernel/sim path (DESIGN.md §10).  A 'data' mesh axis composes: batch
rows shard over it (trivially bit-exact) so one engine scales both TP
and DP (DESIGN.md §12).  Continuous batching for classification lives
in ``repro.serving.scheduler.ClassifyScheduler`` (DESIGN.md §7).

Token engines batch SLOT-level: ``make_slot_prefill_step`` admits one
request into one row of a live cache, enabled by the per-row
``cache['index']`` vector (DESIGN.md §7).  Because that index is
batch-local — row i's cache state never reads another row's index —
the same 'data'-axis composition applies to LM serving: batch rows
(and their index entries) shard over 'data' with no cross-shard
traffic, bit-exact by construction.
"""
from __future__ import annotations

import dataclasses
from typing import Any, Callable, Optional

import jax
import jax.numpy as jnp

from repro import telemetry as T
from repro.core.mx_types import MXFormat, QuantConfig
from repro.core.quantize import MXTensor, pack_weight
from repro.models.model_api import Param, is_param


def _note_recompiles(engine) -> None:
    """Fold the engine's ``jit_cache_size()`` into the
    ``serving/recompiles`` counter (DESIGN.md §15).

    The first observation on an engine sets its baseline without
    counting — warmup compiles are expected; every later POSITIVE delta
    is a recompile and increments the counter.  The counter is created
    eagerly so a warm, recompile-free run still exports it at 0 (the
    continuous-batching contract the metrics snapshot now witnesses).
    Engines whose jax build hides cache stats (size -1) keep the
    counter at 0 rather than guessing.
    """
    counter = T.counter("serving/recompiles")
    probe = getattr(engine, "jit_cache_size", None)
    size = probe() if probe is not None else -1
    if size < 0:                  # stats hidden (or a stub engine)
        return
    seen = getattr(engine, "_jit_cache_seen", None)
    engine._jit_cache_seen = size
    if seen is not None and size > seen:
        counter.inc(size - seen)


@dataclasses.dataclass(frozen=True)
class ServeConfig:
    """Engine knobs.

    max_len: KV-cache capacity (token engines only).
    batch: the fixed jit batch shape — requests are padded/packed to it.
    pack_weights / weight_fmt: pack large matmul weights to MXInt planes.
    temperature: 0 = greedy decode.
    tp_strategy: how ``ViTServingEngine`` splits packed planes when given
      a mesh — 'column' (output-axis shards + all_gather, bit-exact) or
      'row' (contraction-axis shards + psum, faster on real
      interconnects but re-orders the f32 accumulation; DESIGN.md §10).
    """
    max_len: int = 4096
    batch: int = 8
    pack_weights: bool = False
    weight_fmt: MXFormat = None
    temperature: float = 0.0          # 0 = greedy
    tp_strategy: str = "column"

    def __post_init__(self):
        if self.pack_weights and self.weight_fmt is None:
            from repro.core.mx_types import MXINT6_WEIGHT
            object.__setattr__(self, "weight_fmt", MXINT6_WEIGHT)
        if self.tp_strategy not in ("column", "row"):
            raise ValueError(self.tp_strategy)


# ---------------------------------------------------------------------------
# weight packing
# ---------------------------------------------------------------------------
_PACK_MIN_SIZE = 1 << 14       # don't pack tiny tensors (norm scales, biases)


def _should_pack(p: Param) -> bool:
    v = p.value
    shape = getattr(v, "shape", ())
    axes = p.axes
    if axes and axes[_contraction_axis(p)] is None:
        return False            # no logical contraction axis: positional
                                # tables (pos_embed, cls_token) are added,
                                # not matmul'd — never pack
    # the logical kernel excludes a leading stacked-layers dim
    eff = shape[1:] if axes and axes[0] == "layers" else shape
    if len(eff) < 2:
        return False            # norm scales / biases stay un-packed
    size = 1
    for s in shape:
        size *= s
    if size < _PACK_MIN_SIZE:
        return False
    # blocks along a tiny contraction dim (e.g. width-4 conv taps) are
    # pointless and would leave a degenerate exponent plane
    return shape[_contraction_axis(p)] >= 16


def _contraction_axis(p: Param) -> int:
    """Blocks run along the reduction dim of the consuming matmul:
      * expert-stacked kernels (E, d_in, d_out): axis 1;
      * embedding/unembedding tables (vocab, d): axis 1 (rows are looked up
        whole; unembed contracts d);
      * plain 2-D kernels (d_in, d_out): axis 0.
    Never a sharded-output axis, so shared exponents never straddle shards
    (DESIGN.md §8)."""
    axes = p.axes
    if axes and axes[0] == "expert":
        return 1
    if axes and axes[0] in ("vocab", "classes"):
        return len(axes) - 1
    return max(len(axes) - 2, 0)


_TP_LOGICAL = ("q_heads", "kv_heads", "heads", "mlp", "vocab", "expert",
               "lru")


def pack_params_mxint(params, fmt: MXFormat, abstract: bool = False,
                      tp_shards: int = 1):
    """Param tree -> Param tree with MXTensor values on large matmul
    weights.  ``abstract=True`` produces ShapeDtypeStruct planes for the
    dry-run (no allocation).

    A packed (d_in, d_out) kernel becomes two planes: an int8 mantissa
    plane of the original shape and an int8 shared-exponent plane of
    shape (d_in / block, d_out) — blocks always run along the
    contraction axis (``_contraction_axis``), so both planes partition
    identically along any non-block axis.  Norm scales, biases and
    positional tables stay un-packed (``_should_pack``).

    ``tp_shards``: when the contraction axis is tensor-parallel (row-
    parallel wo/down projections; ``ServeConfig(tp_strategy='row')``),
    the block size is clamped to the PER-SHARD contraction length so
    shared exponents never straddle shard boundaries (DESIGN.md §8) and
    the exponent plane shards exactly like the mantissa plane.  The
    column-parallel serving default shards output axes only and packs
    with ``tp_shards=1`` — byte-identical to single-device packing.
    """
    import dataclasses as _dc
    from repro.core.quantize import _resolve_block

    def pack(p: Param) -> Param:
        if not _should_pack(p):
            return p
        axis = _contraction_axis(p)
        v = p.value
        k_len = v.shape[axis]
        eff_fmt = fmt
        if tp_shards > 1 and p.axes[axis] in _TP_LOGICAL and \
                k_len % tp_shards == 0:
            per_shard = k_len // tp_shards
            block = _resolve_block(per_shard, fmt.block_size)
            eff_fmt = _dc.replace(fmt, block_size=block)
        if abstract:
            block = _resolve_block(k_len, eff_fmt.block_size)
            eshape = list(v.shape)
            eshape[axis] //= block
            mx = MXTensor(
                jax.ShapeDtypeStruct(v.shape, eff_fmt.mant_dtype),
                jax.ShapeDtypeStruct(tuple(eshape), jnp.int8),
                axis - len(v.shape), eff_fmt.mant_bits, block)
        else:
            mx = pack_weight(v.astype(jnp.float32), eff_fmt, axis=axis)
        return Param(mx, p.axes)

    return jax.tree_util.tree_map(pack, params, is_leaf=is_param)


def packed_param_axes(params):
    """Axes prefix tree for packed params: MXTensor has two leaves
    (mantissa, exponent); the exponent inherits the mantissa's axes with the
    block axis shrunk — the same PartitionSpec applies to both, so the Param
    level prefix works unchanged."""
    from repro.models.model_api import axes_tree
    return axes_tree(params)


# ---------------------------------------------------------------------------
# step builders
# ---------------------------------------------------------------------------
def make_prefill_step(model) -> Callable:
    cfg = model.cfg

    def prefill_step(params, batch, cache):
        if cfg.is_encoder_decoder:
            return model.prefill(params, batch["frames"], batch["tokens"],
                                 cache)
        return model.prefill(params, batch["tokens"], cache,
                             batch.get("vision_embeds"))

    return prefill_step


def make_slot_prefill_step(model, max_len: int) -> Callable:
    """Prefill ONE request into ONE slot of a LIVE batch cache.

    The slot-level admission primitive (DESIGN.md §7): runs a batch-1
    prefill of the right-padded prompt ``tokens`` (1, P) with real
    length ``length`` into a fresh temporary cache, then scatters every
    temporary leaf into row ``slot`` of the live ``cache`` along its
    'batch' axis (found via ``model.cache_axes()``), leaving the other
    rows' state untouched — which is exactly what the per-row
    ``cache['index']`` contract makes sound.  Returns ``(tok, cache)``
    where ``tok`` (1,) is the greedy first generated token.

    Shapes are fixed per P, so one jit specialization serves every
    (slot, length) pair — zero recompiles after warmup.
    """
    axes = model.cache_axes()

    def slot_prefill(params, tokens, length, slot, cache):
        tmp = model.cache_init(1, max_len)
        logits, tmp = model.prefill(params, tokens, tmp,
                                    lengths=jnp.reshape(length, (1,)))
        leaves, treedef = jax.tree_util.tree_flatten(cache)
        tmp_leaves = treedef.flatten_up_to(tmp)
        ax_leaves = treedef.flatten_up_to(axes)
        out = []
        for dst, src, ax in zip(leaves, tmp_leaves, ax_leaves):
            bi = ax.index("batch")
            starts = tuple(slot if j == bi else jnp.int32(0)
                           for j in range(dst.ndim))
            out.append(jax.lax.dynamic_update_slice(
                dst, src.astype(dst.dtype), starts))
        tok = jnp.argmax(logits[:, -1], -1).astype(jnp.int32)
        return tok, jax.tree_util.tree_unflatten(treedef, out)

    return slot_prefill


def make_decode_step(model, temperature: float = 0.0) -> Callable:
    def decode_step(params, tokens, cache, rng=None):
        logits, cache = model.decode_step(params, tokens, cache)
        if temperature > 0.0 and rng is not None:
            nxt = jax.random.categorical(
                rng, logits[:, -1].astype(jnp.float32) / temperature)
        else:
            nxt = jnp.argmax(logits[:, -1], axis=-1)
        return nxt.astype(jnp.int32)[:, None], cache

    return decode_step


# ---------------------------------------------------------------------------
# engine (host-side loop; used by examples and integration tests)
# ---------------------------------------------------------------------------
class ServingEngine:
    def __init__(self, model, params, serve_cfg: ServeConfig):
        self.model = model
        self.cfg = serve_cfg
        if serve_cfg.pack_weights:
            params = pack_params_mxint(params, serve_cfg.weight_fmt)
        self.params = params
        self._prefill = jax.jit(make_prefill_step(model))
        self._decode = jax.jit(make_decode_step(model,
                                                serve_cfg.temperature))
        self._prefill_slot = jax.jit(
            make_slot_prefill_step(model, serve_cfg.max_len))

    def jit_cache_size(self) -> int:
        """Total jit specializations of the decode + slot-prefill steps
        (-1 when this jax build hides cache stats).  The slot-level
        batching contract: flat after warmup for ANY request mix —
        decode always sees the one (batch, 1) shape, slot prefill one
        shape per prompt-length bucket (tests/test_scheduler_properties)."""
        total = 0
        for fn in (self._decode, self._prefill_slot):
            cs = getattr(fn, "_cache_size", None)
            if cs is None:
                return -1
            total += int(cs())
        return total

    def generate(self, batch, max_new_tokens: int = 16):
        bsz, plen = batch["tokens"].shape[:2]
        T.histogram("serving/batch_size",
                    T.DEFAULT_SIZE_BUCKETS).record(bsz)
        T.histogram("serving/prefill_len",
                    T.DEFAULT_SIZE_BUCKETS).record(plen)
        with T.span("serving/generate", batch=bsz, new_tokens=max_new_tokens):
            cache = self.model.cache_init(bsz, self.cfg.max_len)
            logits, cache = self._prefill(self.params, batch, cache)
            tok = jnp.argmax(logits[:, -1], -1).astype(jnp.int32)[:, None]
            out = [tok]
            for _ in range(max_new_tokens - 1):
                tok, cache = self._decode(self.params, tok, cache)
                out.append(tok)
            result = jnp.concatenate(out, axis=1)
        _note_recompiles(self)
        return result


# ---------------------------------------------------------------------------
# ViT classification engine (the paper's deployment scenario)
# ---------------------------------------------------------------------------
class ViTServingEngine:
    """Batched image-classification serving for ViT/DeiT models.

    The token engines above are prefill/decode state machines; a classifier
    is a stateless batched forward, so this engine only needs weight packing
    plus fixed-shape batching (requests are padded to ``serve_cfg.batch`` so
    one jit specialization serves every request size).

    With ``pack_weights=True`` and a model config in ``mode='kernel'`` this
    is the paper's full deployment: packed int8 planes in HBM, every linear
    and non-linear op on the accelerator through the Pallas MXInt kernels.

    Sharded serving: pass a ``mesh`` with a 'model' axis (e.g.
    ``repro.launch.mesh.make_tp_mesh(2)``).  The packed planes are
    device_put pre-sharded over the mesh — per-device HBM holds 1/S of
    the packed bytes — and ``classify`` runs one ``shard_map``-wrapped
    jit in which each shard feeds its local int8 planes to
    ``mxint_linear``.  With the default ``tp_strategy='column'`` the
    sharded forward is BIT-IDENTICAL to the single-device ``mode='sim'``
    oracle (asserted by tests/test_sharded_serving.py; design and
    exactness argument in DESIGN.md §10).

    Data parallelism composes: a mesh with a 'data' axis (e.g.
    ``repro.launch.mesh.make_serving_mesh(dp, tp)``) additionally shards
    the BATCH dimension — each data shard classifies ``batch/dp`` images
    through the full (model-sharded) forward.  Batch rows are
    independent everywhere in the datapath (row-wise quantizer blocks,
    per-row norms/softmax), so data sharding is trivially bit-exact and
    one engine scales both TP and DP (DESIGN.md §10/§12).  Requires
    ``serve_cfg.batch % dp == 0``; the params stay replicated over
    'data' (their PartitionSpecs name only 'model').
    """

    def __init__(self, model, params, serve_cfg: ServeConfig, mesh=None):
        self.model = model
        self.cfg = serve_cfg
        self.mesh = mesh
        tp = mesh.shape.get("model", 1) if mesh is not None else 1
        dp = mesh.shape.get("data", 1) if mesh is not None else 1
        if tp > 1 or dp > 1:
            if not serve_cfg.pack_weights:
                raise ValueError("sharded serving shards the PACKED planes; "
                                 "set ServeConfig(pack_weights=True)")
            if serve_cfg.batch % dp:
                raise ValueError(
                    f"data sharding needs batch % dp == 0, got "
                    f"batch={serve_cfg.batch} dp={dp}")
            self.params, self._logits = self._build_sharded(
                model, params, serve_cfg, mesh, tp, dp)
            return
        if serve_cfg.pack_weights:
            params = pack_params_mxint(params, serve_cfg.weight_fmt)
        self.params = params
        self._logits = jax.jit(model.logits)

    @staticmethod
    def _build_sharded(model, params, serve_cfg: ServeConfig, mesh, tp: int,
                       dp: int = 1):
        """Pack -> mark/shard planes -> device_put -> shard_map'd jit."""
        from jax.sharding import NamedSharding, PartitionSpec as P
        from repro.parallel.sharding import tp_shard_packed_params
        strategy = serve_cfg.tp_strategy
        packed = pack_params_mxint(
            params, serve_cfg.weight_fmt,
            # row-parallel splits the contraction axis: clamp block sizes
            # to the per-shard length so shared exponents never straddle
            # shard boundaries.  Column-parallel never splits blocks, so
            # packing stays byte-identical to the single-device engine.
            tp_shards=tp if strategy == "row" else 1)
        if tp > 1:
            marked, specs = tp_shard_packed_params(packed, tp, "model",
                                                   strategy)
        else:
            # data-only mesh: planes stay whole and replicated (marking
            # them for a 1-way 'model' axis would emit collectives over
            # an axis the mesh may not even carry)
            marked = packed
            specs = jax.tree_util.tree_map(lambda p: P(), packed,
                                           is_leaf=is_param)

        def put(p: Param, spec) -> Param:
            ns = NamedSharding(mesh, spec)
            v = p.value
            if isinstance(v, MXTensor):
                v = v._replace(mantissa=jax.device_put(v.mantissa, ns),
                               exponent=jax.device_put(v.exponent, ns))
            else:
                v = jax.device_put(v, ns)
            return Param(v, p.axes)

        placed = jax.tree_util.tree_map(put, marked, specs, is_leaf=is_param)
        # batch sharding over 'data' (replicated when the mesh has no data
        # axis): every data shard runs the identical model-sharded forward
        # on its batch/dp rows
        img_spec = P("data") if dp > 1 else P()
        # replication checking off: the collectives mxint_linear inserts
        # make the outputs replicated over 'model' by construction
        fwd = jax.shard_map(lambda p, imgs: model.logits(p, imgs),
                            mesh=mesh, in_specs=(specs, img_spec),
                            out_specs=img_spec, check_vma=False)
        return placed, jax.jit(fwd)

    def jit_cache_size(self) -> int:
        """Number of jit specializations of the classify forward (-1 when
        this jax build does not expose cache stats).  The continuous-
        batching contract: stays at 1 after warmup for ANY request-size
        mix (tests/test_sharded_serving.py)."""
        fn = getattr(self._logits, "_cache_size", None)
        return int(fn()) if fn is not None else -1

    def logits_batch(self, chunk) -> jnp.ndarray:
        """One jitted forward on a FIXED-shape (cfg.batch, H, W, 3) chunk.

        The single funnel into ``self._logits`` — both ``classify`` and
        ``ClassifyScheduler`` go through it with an identical argument
        signature (shape/dtype/sharding), which is what keeps the jit
        cache at one specialization across arbitrary request mixes.
        """
        return self._logits(self.params, jnp.asarray(chunk))

    def classify(self, images: jnp.ndarray):
        """(n, H, W, 3) images -> (labels (n,), logits (n, classes)).

        ``n`` is arbitrary: requests are served in fixed ``cfg.batch``
        chunks, the final partial chunk zero-padded (and the padding rows
        dropped from the result).
        """
        images = jnp.asarray(images)
        n = images.shape[0]
        batch = self.cfg.batch
        T.histogram("serving/batch_size",
                    T.DEFAULT_SIZE_BUCKETS).record(batch)
        with T.span("serving/classify", images=n):
            chunks = []
            for i in range(0, n, batch):
                chunk = images[i:i + batch]
                pad = batch - chunk.shape[0]
                if pad:
                    chunk = jnp.concatenate(
                        [chunk, jnp.zeros((pad,) + chunk.shape[1:],
                                          chunk.dtype)])
                logits = self.logits_batch(chunk)
                chunks.append(logits[:batch - pad] if pad else logits)
            logits = jnp.concatenate(chunks, axis=0)
        _note_recompiles(self)
        return jnp.argmax(logits, axis=-1), logits


def make_engine(model, params, serve_cfg: ServeConfig, mesh=None):
    """Family-aware engine constructor.  ``mesh`` enables sharded serving
    for the ViT family (token engines are single-device for now)."""
    if getattr(model.cfg, "family", None) == "vit":
        return ViTServingEngine(model, params, serve_cfg, mesh=mesh)
    return ServingEngine(model, params, serve_cfg)
