"""Static per-pallas_call FLOPs / HBM-bytes / VMEM cost model (DESIGN.md §14).

Everything is derived from the captured call alone — grid, BlockSpecs,
dtypes and the kernel's ``functools.partial`` configuration — with no
execution:

* **HBM traffic** — Pallas walks the grid in lexicographic order (last
  axis fastest) and re-fetches an operand block only when its
  ``index_map`` value changes between consecutive steps.  The model
  counts those transitions per operand (``bytes_traffic``) and also the
  distinct-block footprint (``bytes_unique`` — what an ideal
  infinite-VMEM schedule would move, and what the analytic counters in
  ``kernel_bench`` count).  Mantissa and exponent planes are separate
  operands, so their bytes are accounted separately, at 1 byte/element —
  the paper's packed-plane memory win is visible per row.
* **FLOPs** — closed-form per kernel family from block shapes and the
  partial's config (dot products 2·m·k·n; select-chain LUT lookups
  2·elements·2^bits; O(10)·elements vector work for the rowwise
  datapaths).  Formulas are in DESIGN.md §14; they feed the arithmetic-
  intensity column of the roofline table, while the BYTE columns are the
  CI-guarded quantity.
* **VMEM residency** — ``2 × (in+out block bytes) + scratch`` (the same
  double-buffering model the kernel-contracts VMEM cap uses).

The ``cost-model`` rule (a) cross-validates the model against
``benchmarks.kernel_bench._ln_linear_hbm_bytes`` — the analytic counter
the bench already publishes — at the bench LN→linear shape and on the
DeiT-tiny LN→qkv fusion study (the fused datapath must reproduce the
~23% byte saving), and (b) diffs every sweep row against the committed
baseline ``benchmarks/_cache/cost_model_baseline.json``, failing on
>2% traffic-byte regressions (refresh with
``tools/repro_lint.py --update-cost-baseline`` after an intentional
tiling change).
"""
from __future__ import annotations

import functools
import itertools
import json
from pathlib import Path
from typing import Callable, Dict, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp

from repro.analysis.kernel_contracts import (DOUBLE_BUFFER, MAX_GRID_POINTS,
                                             BlockUse, PallasCapture,
                                             _nbytes, capture_pallas_calls,
                                             sweep_captures)
from repro.analysis.registry import ERROR, WARN, Violation, register_rule

BASELINE_RELPATH = Path("benchmarks/_cache/cost_model_baseline.json")
REGRESSION_THRESHOLD = 0.02     # CI fails on >2% traffic-byte growth
CROSS_VAL_RTOL = 0.02           # model vs analytic counter agreement
# gamma/beta/LUT sidecar operands the analytic counter ignores stay
# within CROSS_VAL_RTOL of the plane+activation total on every shape we
# validate; a bigger gap means the model or the kernel changed shape


# ---------------------------------------------------------------------------
# traffic
# ---------------------------------------------------------------------------
def _block_bytes(use: BlockUse) -> int:
    return _nbytes(use.block_shape, use.dtype)


def operand_traffic(use: BlockUse,
                    grid: Tuple[int, ...]) -> Optional[Tuple[int, int]]:
    """(consecutive re-fetches, distinct blocks) for one operand.

    Returns None when the grid is too large to enumerate (none of the
    swept kernels is)."""
    points = 1
    for g in grid:
        points *= g
    if points > MAX_GRID_POINTS:
        return None
    im = use.index_map
    fetches = 0
    prev: object = object()
    uniq = set()
    for idx in itertools.product(*[range(g) for g in grid]):
        if im is None:
            bid: Tuple[int, ...] = ()
        else:
            raw = im(*idx)
            raw = raw if isinstance(raw, (list, tuple)) else (raw,)
            bid = tuple(int(b) for b in raw)
        if bid != prev:
            fetches += 1
            prev = bid
        uniq.add(bid)
    return fetches, len(uniq)


# ---------------------------------------------------------------------------
# FLOPs (formulas: DESIGN.md §14)
# ---------------------------------------------------------------------------
def _partial_kwargs(cap: PallasCapture) -> Dict[str, object]:
    kw: Dict[str, object] = {}
    fn = cap.kernel_fn
    while isinstance(fn, functools.partial):
        kw.update(fn.keywords or {})
        fn = fn.func
    return kw


def _steps(grid: Tuple[int, ...]) -> int:
    n = 1
    for g in grid:
        n *= g
    return n


def _flops_matmul(cap, kw) -> int:
    bm, bk = cap.inputs[0].block_shape
    bn = cap.outputs[0].block_shape[-1]
    per = 2 * bm * bk * bn + bk * bn          # dot + exponent scale
    if kw.get("quantize_act"):
        per += 6 * bm * bk                    # in-register act quantize
    return _steps(cap.grid) * per


def _flops_ln_matmul(cap, kw) -> int:
    bm, d = cap.inputs[0].block_shape
    bn = cap.outputs[0].block_shape[-1]
    lut = 2 ** int(kw.get("lut_bits", 5))
    dot = _steps(cap.grid) * 2 * bm * d * bn
    ln = cap.grid[0] * (12 * bm * d + 2 * bm * lut)   # j == 0 only
    return dot + ln


def _flops_layernorm(cap, kw) -> int:
    br, d = cap.inputs[0].block_shape
    lut = 2 ** int(kw.get("lut_bits", 5))
    return _steps(cap.grid) * (12 * br * d + 2 * br * lut)


def _flops_softmax(cap, kw) -> int:
    br, n = cap.inputs[0].block_shape
    lut = 2 ** int(kw.get("r_bits", 2))
    return _steps(cap.grid) * (10 * br * n + 2 * br * n * lut)


def _flops_gelu(cap, kw) -> int:
    br, d = cap.inputs[0].block_shape
    lut = 2 ** int(kw.get("index_bits", 5))
    return _steps(cap.grid) * (8 * br * d + 2 * br * d * lut)


def _flops_flash(cap, kw) -> int:
    q = cap.inputs[0].block_shape       # (1, bq, d) / (1, 1, g, d)
    rows, d = q[-2], q[-1]
    bk = cap.inputs[1].block_shape[1]   # (1, bk, d) / (1, bk, 1, d)
    per = 4 * rows * bk * d + 10 * rows * bk   # qk + pv dots + update
    if kw.get("exp_mode") == "mxint":
        per += 2 * rows * bk * 2 ** int(kw.get("r_bits", 2))
    return _steps(cap.grid) * per


FLOPS: Dict[str, Callable[[PallasCapture, Dict[str, object]], int]] = {
    "_mxint_matmul_kernel": _flops_matmul,
    "_mxint_ln_matmul_kernel": _flops_ln_matmul,
    "_mxint_layernorm_kernel": _flops_layernorm,
    "_mxint_softmax_kernel": _flops_softmax,
    "_mxint_gelu_kernel": _flops_gelu,
    "_flash_kernel": _flops_flash,
    "_decode_kernel": _flops_flash,
}


# ---------------------------------------------------------------------------
# per-capture row
# ---------------------------------------------------------------------------
def capture_costs(cap: PallasCapture) -> Dict[str, object]:
    operands = []
    traffic_total = 0
    unique_total = 0
    for use in cap.inputs + cap.outputs:
        t = operand_traffic(use, cap.grid)
        bb = _block_bytes(use)
        if t is None:
            fetches, uniq = _steps(cap.grid), _steps(cap.grid)
        else:
            fetches, uniq = t
        operands.append({
            "name": use.name,
            "dtype": str(jnp.dtype(use.dtype)),
            "block": list(use.block_shape),
            "bytes_traffic": fetches * bb,
            "bytes_unique": uniq * bb,
        })
        traffic_total += fetches * bb
        unique_total += uniq * bb
    vmem = (DOUBLE_BUFFER * sum(_block_bytes(u)
                                for u in cap.inputs + cap.outputs)
            + sum(_nbytes(s.shape, s.dtype) for s in cap.scratch))
    flops_fn = FLOPS.get(cap.kernel)
    flops = flops_fn(cap, _partial_kwargs(cap)) if flops_fn else 0
    return {
        "label": cap.label,
        "kernel": cap.kernel,
        "grid": list(cap.grid),
        "flops": int(flops),
        "hbm_bytes": int(traffic_total),
        "unique_bytes": int(unique_total),
        "vmem_bytes": int(vmem),
        "intensity": round(flops / traffic_total, 3) if traffic_total else 0.0,
        "operands": operands,
    }


def build_table(caps: Optional[Sequence[PallasCapture]] = None
                ) -> List[Dict[str, object]]:
    if caps is None:
        caps = sweep_captures()
    return [capture_costs(c) for c in caps]


# ---------------------------------------------------------------------------
# query API (the repro.dse evaluator's entry point, DESIGN.md §16)
# ---------------------------------------------------------------------------
_TABLE_MEMO: List[Dict[str, object]] = []


def table(refresh: bool = False) -> List[Dict[str, object]]:
    """The standard-sweep cost table, memoized (the sweep itself is
    already memoized in kernel_contracts; this skips re-deriving rows).
    Rows are shallow copies — treat operand entries as read-only."""
    if refresh or not _TABLE_MEMO:
        _TABLE_MEMO[:] = build_table()
    return [dict(r) for r in _TABLE_MEMO]


def query(labels: Optional[Sequence[str]] = None
          ) -> Dict[str, Dict[str, object]]:
    """Label-keyed cost rows; with ``labels`` given, KeyError on any
    unknown label naming the known ones (typo-proof for callers keying
    off telemetry probe labels)."""
    rows = {r["label"]: r for r in table()}
    if labels is None:
        return rows
    missing = sorted(set(labels) - set(rows))
    if missing:
        raise KeyError(f"unknown cost-model labels {missing}; known: "
                       f"{sorted(rows)}")
    return {label: rows[label] for label in labels}


# ---------------------------------------------------------------------------
# DeiT LN->qkv fusion study (logical, unpadded shapes — what the bench's
# analytic counter accounts; the interpret wrapper's padding is a CPU
# artefact, not datapath traffic)
# ---------------------------------------------------------------------------
_FUSION_MEMO: Dict[str, Dict[str, object]] = {}


def _sds(shape, dtype=jnp.float32):
    return jax.ShapeDtypeStruct(tuple(shape), dtype)


def fusion_study(arch: str = "deit_tiny") -> Dict[str, object]:
    """Model bytes for fused vs unfused LN->qkv at DeiT logical shapes."""
    if arch in _FUSION_MEMO:
        return dict(_FUSION_MEMO[arch])
    from repro.configs.deit import BY_NAME
    from repro.kernels.mxint_layernorm import mxint_layernorm
    from repro.kernels.mxint_ln_matmul import mxint_ln_matmul
    from repro.kernels.mxint_matmul import mxint_matmul

    cfg = BY_NAME[arch]
    d = cfg.d_model
    M = (cfg.image_size // cfg.patch_size) ** 2 + 1
    w_block, n_linears = 32, 3
    bn = 64 if d % 64 == 0 else d

    fused_caps = capture_pallas_calls(
        lambda x, g, b, m, e: mxint_ln_matmul.__wrapped__(
            x, g, b, m, e, w_block=w_block, act_block=16, mant_bits=8,
            lut_bits=5, bm=1, bn=bn, interpret=True),
        _sds((M, d)), _sds((d,)), _sds((d,)),
        _sds((d, d), jnp.int8), _sds((d // w_block, d), jnp.int8),
        label=f"{arch}-lnqkv-fused")
    ln_caps = capture_pallas_calls(
        lambda x, g, b: mxint_layernorm.__wrapped__(
            x, g, b, act_block=16, mant_bits=8, lut_bits=5,
            quantize_out=True, block_rows=1, interpret=True),
        _sds((M, d)), _sds((d,)), _sds((d,)),
        label=f"{arch}-lnqkv-unfused-ln")
    mm_caps = capture_pallas_calls(
        lambda x, m, e: mxint_matmul.__wrapped__(
            x, m, e, w_block=w_block, act_block=16, act_mant_bits=8,
            quantize_act=True, bm=1, bn=bn, interpret=True,
            out_dtype=jnp.float32),
        _sds((M, d)), _sds((d, d), jnp.int8),
        _sds((d // w_block, d), jnp.int8),
        label=f"{arch}-lnqkv-unfused-linear")

    rows = build_table(fused_caps + ln_caps + mm_caps)
    by_label = {r["label"]: r for r in rows}
    fused = n_linears * by_label[f"{arch}-lnqkv-fused"]["unique_bytes"]
    unfused = (by_label[f"{arch}-lnqkv-unfused-ln"]["unique_bytes"]
               + n_linears
               * by_label[f"{arch}-lnqkv-unfused-linear"]["unique_bytes"])
    result = {
        "arch": arch,
        "rows_tokens": M, "d_model": d, "w_block": w_block,
        "n_linears": n_linears,
        "fused_bytes": int(fused),
        "unfused_bytes": int(unfused),
        "saving_pct": round(100.0 * (unfused - fused) / unfused, 2),
        "rows": rows,
    }
    _FUSION_MEMO[arch] = result
    return dict(result)


def report(root: Path) -> Dict[str, object]:
    """The machine-readable roofline table (repro_lint --json payload)."""
    fusion = fusion_study()
    return {
        "rows": build_table(),
        "fusion": {k: v for k, v in fusion.items() if k != "rows"},
        "fusion_rows": fusion["rows"],
    }


# ---------------------------------------------------------------------------
# baseline diff + analytic cross-validation
# ---------------------------------------------------------------------------
def baseline_payload() -> Dict[str, object]:
    fusion = fusion_study()
    return {
        "version": 1,
        "threshold_pct": 100 * REGRESSION_THRESHOLD,
        "rows": {r["label"]: {k: r[k] for k in
                              ("hbm_bytes", "unique_bytes", "flops",
                               "vmem_bytes")}
                 for r in build_table()},
        "fusion": {fusion["arch"]: {k: fusion[k] for k in
                                    ("fused_bytes", "unfused_bytes",
                                     "saving_pct")}},
    }


def write_baseline(root: Path) -> Path:
    path = root / BASELINE_RELPATH
    path.parent.mkdir(parents=True, exist_ok=True)
    path.write_text(json.dumps(baseline_payload(), indent=1,
                               sort_keys=True) + "\n")
    return path


def compare_to_baseline(rows: Sequence[Dict[str, object]],
                        baseline: Dict[str, object],
                        threshold: float = REGRESSION_THRESHOLD
                        ) -> List[Violation]:
    out: List[Violation] = []
    current = {r["label"]: r for r in rows}
    base_rows = baseline.get("rows", {})
    for label, base in sorted(base_rows.items()):
        cur = current.get(label)
        if cur is None:
            out.append(Violation(
                "cost-model", label,
                "baseline row has no current counterpart — the sweep "
                "shrank; refresh the baseline if intentional"))
            continue
        b, c = int(base["hbm_bytes"]), int(cur["hbm_bytes"])
        if c > b * (1 + threshold):
            out.append(Violation(
                "cost-model", label,
                f"HBM traffic regression: {c} bytes vs baseline {b} "
                f"(+{100.0 * (c - b) / b:.1f}% > "
                f"{100 * threshold:.0f}%) — a BlockSpec/tiling change "
                f"reinflated the datapath; fix it or refresh the "
                f"baseline (--update-cost-baseline)"))
        elif c < b * (1 - threshold):
            out.append(Violation(
                "cost-model", label,
                f"HBM traffic improved {100.0 * (b - c) / b:.1f}% vs "
                f"baseline ({c} vs {b}) — refresh the baseline to guard "
                f"the win", severity=WARN))
    for label in sorted(set(current) - set(base_rows)):
        out.append(Violation(
            "cost-model", label,
            "row missing from the committed baseline — refresh it "
            "(--update-cost-baseline)", severity=WARN))
    return out


def _analytic_counter(root: Path):
    import sys
    if str(root) not in sys.path:
        sys.path.insert(0, str(root))
    from benchmarks.kernel_bench import _ln_linear_hbm_bytes
    return _ln_linear_hbm_bytes


def cross_validate(root: Path) -> List[Violation]:
    """Model vs the bench's analytic byte counters."""
    out: List[Violation] = []
    try:
        analytic = _analytic_counter(root)
    except Exception as exc:   # pragma: no cover - import environment
        return [Violation(
            "cost-model", "cross-validation",
            f"cannot import benchmarks.kernel_bench analytic counter: "
            f"{exc!r}")]

    def _check(where, model, want, rtol=CROSS_VAL_RTOL):
        if not (abs(model - want) <= rtol * want):
            out.append(Violation(
                "cost-model", where,
                f"model bytes {model} vs analytic {want} "
                f"(|Δ| > {100 * rtol:.0f}%) — the static model and the "
                f"bench counter disagree"))

    # bench LN->linear shape: one fused call, rows=256, d=n=768, OCP-32
    rows = build_table()
    ln = next((r for r in rows if r["label"] == "ln-matmul-bench"), None)
    if ln is None:
        out.append(Violation("cost-model", "ln-matmul-bench",
                             "sweep lost the fused LN->matmul row"))
    else:
        _check("ln-matmul-bench", ln["unique_bytes"],
               analytic(256, 768, 768, 32, 1, fused=True))

    # DeiT-tiny LN->qkv fusion: totals and the headline saving
    fus = fusion_study()
    M, d, wb, nl = (fus["rows_tokens"], fus["d_model"], fus["w_block"],
                    fus["n_linears"])
    want_fused = analytic(M, d, d, wb, nl, fused=True)
    want_unfused = analytic(M, d, d, wb, nl, fused=False)
    _check("deit-lnqkv-fused", fus["fused_bytes"], want_fused)
    _check("deit-lnqkv-unfused", fus["unfused_bytes"], want_unfused)
    want_saving = 100.0 * (want_unfused - want_fused) / want_unfused
    if abs(fus["saving_pct"] - want_saving) > 1.5 or not (
            20.0 <= fus["saving_pct"] <= 26.0):
        out.append(Violation(
            "cost-model", "deit-lnqkv-saving",
            f"fused LN->qkv byte saving {fus['saving_pct']}% does not "
            f"reproduce the bench's ~{want_saving:.1f}% claim"))
    return out


@register_rule(
    "cost-model",
    "Static FLOPs/HBM-bytes/VMEM roofline per pallas_call, cross-"
    "validated against kernel_bench's analytic counters and diffed "
    "against benchmarks/_cache/cost_model_baseline.json (>2% byte "
    "regressions fail)")
def run(root: Path) -> List[Violation]:
    out = cross_validate(root)
    path = root / BASELINE_RELPATH
    if not path.exists():
        out.append(Violation(
            "cost-model", str(BASELINE_RELPATH),
            "committed cost-model baseline missing — generate it with "
            "tools/repro_lint.py --update-cost-baseline"))
        return out
    try:
        baseline = json.loads(path.read_text())
    except ValueError as exc:
        out.append(Violation("cost-model", str(BASELINE_RELPATH),
                             f"baseline is not valid JSON: {exc}"))
        return out
    out.extend(compare_to_baseline(build_table(), baseline))
    return out
