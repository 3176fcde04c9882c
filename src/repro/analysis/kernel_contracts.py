"""Pallas index-map coverage checker and the kernel capture sweep
(DESIGN.md §13).

Every ``pallas_call`` in ``repro/kernels/`` is captured by ABSTRACT
evaluation — the wrapper runs under ``jax.eval_shape`` with
``pl.pallas_call`` swapped for a recorder that grabs the grid, the
Block Specs, the scratch shapes and the operand avals, then returns
zero-filled outputs of the declared ``out_shape`` (no kernel body ever
executes).  The captures serve two checks Mosaic does not make:

* **index_map coverage** (this module) — every input index map,
  enumerated over the full grid with concrete ints, must stay in
  bounds; every OUTPUT block must be produced by at least one grid step
  (a constant out map over a tiled output silently leaves garbage
  blocks, and compiles).
* **grid semantics** (:mod:`repro.analysis.grid_semantics`) — races
  between the declared ``dimension_semantics`` and the kernel's
  accumulator axes.

VMEM budgets, the (8, 128) block rule and scratch layouts are checked
by Mosaic itself: ``tests/test_v5e_compile.py`` compiles the kernels for
a described v5e.

The built-in sweep (:func:`sweep_captures`) drives every kernel in
``repro/kernels/`` through a few stand-alone shapes plus the padded
DeiT shapes the model path produces.
"""
from __future__ import annotations

import dataclasses
import itertools
from pathlib import Path
from typing import Callable, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp

from repro.analysis.registry import WARN, Violation, register_rule

# keep index-map enumeration cheap; none of the swept kernels comes close
MAX_GRID_POINTS = 65536


@dataclasses.dataclass(frozen=True)
class BlockUse:
    """One operand (or output) of a captured pallas_call."""

    name: str
    shape: Tuple[int, ...]
    dtype: jnp.dtype
    block_shape: Tuple[int, ...]
    index_map: Optional[Callable]


@dataclasses.dataclass(frozen=True)
class ScratchUse:
    shape: Tuple[int, ...]
    dtype: jnp.dtype


@dataclasses.dataclass(frozen=True)
class PallasCapture:
    label: str                  # sweep entry that produced this call
    kernel: str                 # kernel function __name__
    grid: Tuple[int, ...]
    inputs: Tuple[BlockUse, ...]
    outputs: Tuple[BlockUse, ...]
    scratch: Tuple[ScratchUse, ...]
    # grid-semantics capture (DESIGN.md §14): the declared per-axis
    # dimension_semantics (None == the call declared nothing), any
    # input->output aliasing, and the kernel callable itself (possibly a
    # functools.partial — grid_semantics AST-inspects its source and
    # resolves comparator names from the partial's keywords)
    dimension_semantics: Optional[Tuple[str, ...]] = None
    input_output_aliases: Tuple[Tuple[int, int], ...] = ()
    kernel_fn: Optional[Callable] = dataclasses.field(
        default=None, compare=False)


def _dimension_semantics(compiler_params) -> Optional[Tuple[str, ...]]:
    """Extract dimension_semantics from a ``compiler_params`` kwarg in any
    of the forms pallas_call accepts (CompilerParams dataclass, flat
    dict, or the legacy {"mosaic": {...}} nesting)."""
    if compiler_params is None:
        return None
    if isinstance(compiler_params, dict):
        inner = compiler_params.get("mosaic", compiler_params)
        ds = inner.get("dimension_semantics") if isinstance(inner, dict) \
            else getattr(inner, "dimension_semantics", None)
    else:
        ds = getattr(compiler_params, "dimension_semantics", None)
    return tuple(ds) if ds is not None else None


# ---------------------------------------------------------------------------
# capture
# ---------------------------------------------------------------------------
def _as_tuple(x):
    if x is None:
        return ()
    if isinstance(x, (list, tuple)):
        return tuple(x)
    return (x,)


def _kernel_name(kernel) -> str:
    return getattr(getattr(kernel, "func", kernel), "__name__", str(kernel))


def capture_pallas_calls(fn, *args, label: str = "?",
                         **kwargs) -> List[PallasCapture]:
    """Abstractly evaluate ``fn(*args, **kwargs)`` recording every
    ``pallas_call`` it stages.  ``args`` may be arrays or
    ``ShapeDtypeStruct``s; nothing is executed.

    The pjit trace cache is cleared first: a jit-wrapped kernel wrapper
    whose jaxpr is already cached would be inlined WITHOUT re-running its
    Python body, and the recorder would silently miss the call.
    """
    import jax.experimental.pallas as plmod

    records: List[PallasCapture] = []
    real = plmod.pallas_call

    def spy(kernel, out_shape=None, **kw):
        osh = kw.get("out_shape", out_shape)
        grid = kw.get("grid", ())
        in_specs = _as_tuple(kw.get("in_specs"))
        out_specs = _as_tuple(kw.get("out_specs"))
        scratch = _as_tuple(kw.get("scratch_shapes", ()))
        out_sds = _as_tuple(osh)
        dim_sem = _dimension_semantics(kw.get("compiler_params"))
        aliases = tuple(sorted(
            (int(a), int(b))
            for a, b in dict(kw.get("input_output_aliases") or {}).items()))

        def runner(*operands):
            ins = tuple(
                BlockUse(name=f"in{i}", shape=tuple(jnp.shape(o)),
                         dtype=jnp.dtype(o.dtype),
                         block_shape=tuple(s.block_shape)
                         if s.block_shape is not None else tuple(jnp.shape(o)),
                         index_map=s.index_map)
                for i, (s, o) in enumerate(zip(in_specs, operands)))
            outs = tuple(
                BlockUse(name=f"out{i}", shape=tuple(sd.shape),
                         dtype=jnp.dtype(sd.dtype),
                         block_shape=tuple(s.block_shape)
                         if s.block_shape is not None else tuple(sd.shape),
                         index_map=s.index_map)
                for i, (s, sd) in enumerate(zip(out_specs, out_sds)))
            scr = tuple(ScratchUse(shape=tuple(s.shape),
                                   dtype=jnp.dtype(s.dtype)) for s in scratch)
            records.append(PallasCapture(
                label=label, kernel=_kernel_name(kernel),
                grid=tuple(grid) if isinstance(grid, (list, tuple))
                else (grid,),
                inputs=ins, outputs=outs, scratch=scr,
                dimension_semantics=dim_sem,
                input_output_aliases=aliases, kernel_fn=kernel))
            return jax.tree_util.tree_map(
                lambda s: jnp.zeros(s.shape, s.dtype), osh)

        return runner

    jax.clear_caches()
    plmod.pallas_call = spy
    try:
        jax.eval_shape(lambda *a: fn(*a, **kwargs), *args)
    finally:
        plmod.pallas_call = real
        jax.clear_caches()     # drop jaxprs traced against the spy
    return records


# ---------------------------------------------------------------------------
# index-map coverage
# ---------------------------------------------------------------------------
def _where(cap: PallasCapture) -> str:
    return f"{cap.label}/{cap.kernel}"


def _iter_grid(grid: Tuple[int, ...]):
    return itertools.product(*[range(g) for g in grid])


def check_capture(cap: PallasCapture) -> List[Violation]:
    out: List[Violation] = []
    points = 1
    for g in cap.grid:
        points *= g
    if points > MAX_GRID_POINTS:
        out.append(Violation(
            "kernel-contracts", _where(cap),
            f"grid {cap.grid} has {points} steps (> {MAX_GRID_POINTS}); "
            f"index-map coverage not enumerated", severity=WARN))
        return out
    for use in cap.inputs + cap.outputs:
        if use.index_map is None:
            continue
        nblocks = tuple(-(-dim // blk) for dim, blk
                        in zip(use.shape, use.block_shape))
        if any(b == 0 for b in nblocks):
            continue  # a zero-sized axis has no blocks to cover
        seen = set()
        for idx in _iter_grid(cap.grid):
            bid = use.index_map(*idx)
            bid = tuple(bid) if isinstance(bid, (list, tuple)) else (bid,)
            if len(bid) != len(nblocks):
                out.append(Violation(
                    "kernel-contracts", _where(cap),
                    f"{use.name}: index_map returns rank {len(bid)} for a "
                    f"rank-{len(nblocks)} blocked operand"))
                break
            if any(not (0 <= int(b) < n) for b, n in zip(bid, nblocks)):
                out.append(Violation(
                    "kernel-contracts", _where(cap),
                    f"{use.name}: index_map{idx} -> {tuple(int(b) for b in bid)} "
                    f"out of bounds for {nblocks} blocks "
                    f"(array {use.shape}, block {use.block_shape})"))
                break
            seen.add(tuple(int(b) for b in bid))
        else:
            if use.name.startswith("out"):
                every = set(itertools.product(*[range(n) for n in nblocks]))
                missing = sorted(every - seen)
                if missing:
                    out.append(Violation(
                        "kernel-contracts", _where(cap),
                        f"{use.name}: index_map never writes output "
                        f"block(s) {missing[:4]}{'...' if len(missing) > 4 else ''} "
                        f"of {len(every)} — uncovered blocks hold garbage"))
    return out



# ---------------------------------------------------------------------------
# the built-in sweep (stand-alone shapes + padded DeiT shapes)
# ---------------------------------------------------------------------------
def _sds(shape, dtype=jnp.float32):
    return jax.ShapeDtypeStruct(tuple(shape), dtype)


def _sweep_matmul() -> List[PallasCapture]:
    from repro.kernels.mxint_matmul import mxint_matmul
    from repro.kernels.ops import linear_row_tile
    caps = []
    # 128x1024 @ 1024x512, paper W-block 256
    caps += capture_pallas_calls(
        lambda x, m, e: mxint_matmul.__wrapped__(
            x, m, e, w_block=256, act_block=16, act_mant_bits=8,
            quantize_act=True, bm=128, bn=128, interpret=True,
            out_dtype=jnp.float32),
        _sds((128, 1024)), _sds((1024, 512), jnp.int8),
        _sds((4, 512), jnp.int8), label="matmul-1024")
    # the two other operand paths at that shape: activations not
    # quantized (f32 as they arrive, no scratch) and 10-bit act
    # mantissas (an f32 operand scratch)
    caps += capture_pallas_calls(
        lambda x, m, e: mxint_matmul.__wrapped__(
            x, m, e, w_block=256, quantize_act=False, bm=128, bn=128,
            interpret=True, out_dtype=jnp.float32),
        _sds((128, 1024)), _sds((1024, 512), jnp.int8),
        _sds((4, 512), jnp.int8), label="matmul-raw")
    caps += capture_pallas_calls(
        lambda x, m, e: mxint_matmul.__wrapped__(
            x, m, e, w_block=256, act_block=16, act_mant_bits=10,
            quantize_act=True, bm=128, bn=128, interpret=True,
            out_dtype=jnp.float32),
        _sds((128, 1024)), _sds((1024, 512), jnp.int8),
        _sds((4, 512), jnp.int8), label="matmul-f32-operand")
    # OCP-32 weight blocks over K=1024: the exponent plane's (32, 128)
    # block is int8's native tile
    caps += capture_pallas_calls(
        lambda x, m, e: mxint_matmul.__wrapped__(
            x, m, e, w_block=32, act_block=16, act_mant_bits=8,
            quantize_act=True, bm=128, bn=128, interpret=False,
            out_dtype=jnp.float32),
        _sds((128, 1024)), _sds((1024, 768), jnp.int8),
        _sds((32, 768), jnp.int8), label="matmul-compiled")
    # DeiT-Tiny model-path linear: 2x197 tokens padded to 400 rows,
    # d=192 contraction, lanes padded to 256, OCP-32 weight blocks —
    # the config ops.mxint_linear launches for the qkv/proj/FFN
    # projections
    caps += capture_pallas_calls(
        lambda x, m, e: mxint_matmul.__wrapped__(
            x, m, e, w_block=32, act_block=16, act_mant_bits=8,
            quantize_act=True, bm=linear_row_tile(394, 192), bn=128,
            interpret=True, out_dtype=jnp.float32),
        _sds((400, 192)), _sds((192, 256), jnp.int8),
        _sds((6, 256), jnp.int8), label="matmul-deit")
    # DeiT-Base fc2 in a batch of 32 images: 6,304 rows, K=3072, the
    # paper's W-block 256 — a ragged last row block (25 x 256 rows)
    caps += capture_pallas_calls(
        lambda x, m, e: mxint_matmul.__wrapped__(
            x, m, e, w_block=256, act_block=16, act_mant_bits=8,
            quantize_act=True, bm=linear_row_tile(6304, 3072), bn=128,
            interpret=False, out_dtype=jnp.float32),
        _sds((6304, 3072)), _sds((3072, 768), jnp.int8),
        _sds((12, 768), jnp.int8), label="matmul-deit-bulk")
    return caps


def _sweep_rowwise() -> List[PallasCapture]:
    from repro.kernels.mxint_gelu import mxint_gelu
    from repro.kernels.mxint_layernorm import mxint_layernorm
    from repro.kernels.mxint_softmax import mxint_softmax
    caps = []
    x = _sds((256, 768))
    g = _sds((768,))
    caps += capture_pallas_calls(
        lambda a, b, c: mxint_layernorm.__wrapped__(
            a, b, c, act_block=16, mant_bits=8, lut_bits=5,
            block_rows=128, interpret=True),
        x, g, g, label="layernorm-768")
    caps += capture_pallas_calls(
        lambda a: mxint_softmax.__wrapped__(
            a, act_block=16, mant_bits=8, r_bits=2, block_rows=128,
            interpret=True),
        x, label="softmax-768")
    caps += capture_pallas_calls(
        lambda a: mxint_gelu.__wrapped__(
            a, act_block=16, mant_bits=8, lut_bits=5, block_rows=128,
            interpret=True),
        x, label="gelu-768")
    # DeiT-Tiny model-path rows: 2*197 tokens padded to 400, d=192
    caps += capture_pallas_calls(
        lambda a, b, c: mxint_layernorm.__wrapped__(
            a, b, c, act_block=16, mant_bits=8, lut_bits=5,
            block_rows=16, interpret=True),
        _sds((400, 192)), _sds((192,)), _sds((192,)), label="layernorm-deit")
    return caps


def _sweep_ln_matmul() -> List[PallasCapture]:
    from repro.kernels.mxint_ln_matmul import mxint_ln_matmul
    from repro.kernels.ops import linear_row_tile
    caps = capture_pallas_calls(
        lambda x, g, b, m, e: mxint_ln_matmul.__wrapped__(
            x, g, b, m, e, w_block=32, act_block=16, mant_bits=8,
            lut_bits=5, bm=128, bn=128, interpret=True),
        _sds((256, 768)), _sds((768,)), _sds((768,)),
        _sds((768, 768), jnp.int8), _sds((24, 768), jnp.int8),
        label="ln-matmul-768")
    # DeiT-Base LN->fc1 in a batch of 32 images: 6,304 rows in 25 row
    # blocks of 256, the last ragged
    caps += capture_pallas_calls(
        lambda x, g, b, m, e: mxint_ln_matmul.__wrapped__(
            x, g, b, m, e, w_block=256, act_block=16, mant_bits=8,
            lut_bits=5, bm=linear_row_tile(6304, 768), bn=128,
            interpret=False),
        _sds((6304, 768)), _sds((768,)), _sds((768,)),
        _sds((768, 3072), jnp.int8), _sds((3, 3072), jnp.int8),
        label="ln-matmul-deit-bulk")
    return caps


def _sweep_flash() -> List[PallasCapture]:
    from repro.kernels.flash_attention import (flash_attention,
                                               flash_attention_decode)
    caps = []
    # causal, (4, 256, 128): two 128-key blocks
    caps += capture_pallas_calls(
        lambda q, k, v: flash_attention.__wrapped__(
            q, k, v, causal=True, block_q=128, block_k=128, interpret=True),
        _sds((4, 256, 128)), _sds((4, 256, 128)), _sds((4, 256, 128)),
        label="flash-causal-256")
    # DeiT padded attention shape the model path produces:
    # (b*h, 197->200, 64->128), kv padded to 256
    caps += capture_pallas_calls(
        lambda q, k, v: flash_attention.__wrapped__(
            q, k, v, causal=False, block_q=8, block_k=128, kv_len=197,
            interpret=True),
        _sds((6, 200, 128)), _sds((6, 256, 128)), _sds((6, 256, 128)),
        label="flash-deit")
    # decode over a 128-slot ring, GQA heads folded to sublane rows,
    # per-row (B, W) ring validity (slot-level batching contract)
    caps += capture_pallas_calls(
        lambda q, k, v, m: flash_attention_decode.__wrapped__(
            q, k, v, m, block_k=128, w_len=128, interpret=True),
        _sds((2, 2, 8, 128)), _sds((2, 128, 2, 128)),
        _sds((2, 128, 2, 128)), _sds((2, 128), jnp.bool_),
        label="flash-decode")
    return caps


SWEEP: Tuple[Callable[[], List[PallasCapture]], ...] = (
    _sweep_matmul, _sweep_rowwise, _sweep_ln_matmul, _sweep_flash)

# two rules (kernel-contracts, grid-semantics) walk the same sweep;
# captures are immutable, so one abstract-eval pass serves both within a
# process
_SWEEP_MEMO: List[PallasCapture] = []


def sweep_captures(refresh: bool = False) -> List[PallasCapture]:
    if _SWEEP_MEMO and not refresh:
        return list(_SWEEP_MEMO)
    caps: List[PallasCapture] = []
    for builder in SWEEP:
        caps.extend(builder())
    _SWEEP_MEMO[:] = caps
    return list(caps)


def check_captures(caps: Sequence[PallasCapture]) -> List[Violation]:
    out: List[Violation] = []
    for cap in caps:
        out.extend(check_capture(cap))
    return out


@register_rule(
    "kernel-contracts",
    "Pallas index-map coverage (inputs in bounds, every output block "
    "written) over the stand-alone + DeiT shape sweep")
def run(root: Path) -> List[Violation]:
    caps = sweep_captures()
    out = check_captures(caps)
    if not caps:
        out.append(Violation("kernel-contracts", "sweep",
                             "sweep captured no pallas_calls — the "
                             "recorder or the kernels moved"))
    return out
