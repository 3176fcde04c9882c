"""Cached candidate evaluator: accuracy proxy + static hardware cost
(DESIGN.md §16).

Accuracy side — the ``core/search.py`` proxies, unchanged: top-1 argmax
*agreement* with the float (mode='off') model on a calibration batch
(the paper's 1%-budget stand-in without ImageNet in the container) and
cosine fidelity of the logits.

Cost side — static only, no execution:

* ``weight_bits`` / ``act_bits`` — element-count-weighted mean
  ``MXFormat.bits_per_element`` over the model's weight groups, each
  group priced under its SCOPED config (``q.scoped(scope)``), so a
  per-layer override shows up exactly in proportion to the parameters
  it covers (the paper's Fig. 1b x-axis).  Groups whose scoped mode is
  'off' are priced at float32.
* ``lut_entries`` — total LUT provisioning: the per-table MAX across
  scopes (shared hardware must fit the widest requested table), summed
  over the three §III-B tables.

Every evaluation is cached on the canonical point key and counted in
telemetry (``dse/evaluations``, ``dse/cache_hits``, ``span/dse/eval``).
"""
from __future__ import annotations

import dataclasses
from typing import Dict, List, Optional, Sequence, Tuple

from repro.core.mx_types import QuantConfig
from repro.core.search import argmax_agreement, cosine_fidelity
from repro.dse.space import Point, SearchSpace, point_key
from repro.telemetry import metrics
from repro.telemetry.tracing import span

FLOAT_BITS = 32.0


@dataclasses.dataclass(frozen=True)
class CandidateCost:
    """Static hardware-cost vector of one candidate."""

    weight_bits: float          # weighted mean bits/element, weights
    act_bits: float             # weighted mean bits/element, activations
    weight_bytes: int           # total packed weight footprint
    lut_entries: int

    def as_dict(self) -> dict:
        return dataclasses.asdict(self)


@dataclasses.dataclass(frozen=True)
class EvalResult:
    key: tuple                  # canonical point key (space.point_key)
    point: Dict[Tuple[str, str], object]
    accuracy: float             # argmax agreement vs float model
    fidelity: float             # cosine fidelity of logits
    cost: CandidateCost

    def as_dict(self) -> dict:
        return {
            "point": [{"scope": s, "knob": n, "value": v}
                      for (s, n), v in sorted(self.point.items())],
            "accuracy": self.accuracy,
            "fidelity": self.fidelity,
            "cost": self.cost.as_dict(),
        }


# ---------------------------------------------------------------------------
# weight groups: (scope tag, element count) per quantizable weight
# ---------------------------------------------------------------------------
def weight_groups(cfg, params) -> List[Tuple[str, int]]:
    """(scope, n_elements) for every quantized weight tensor, under the
    same scope tags the model's forward passes to ``q.scoped``."""
    if cfg.family == "vit":
        return _vit_weight_groups(cfg, params)
    # generic fallback: every large matrix under the un-scoped tag
    total = sum(int(_leaf_size(p)) for p in _matmul_leaves(params))
    return [("*", total)]


def _leaf_size(p) -> int:
    v = getattr(p, "value", p)
    mant = getattr(v, "mantissa", None)
    return int(mant.size if mant is not None else v.size)


def _matmul_leaves(tree):
    import jax

    from repro.models.model_api import is_param
    for p in jax.tree_util.tree_leaves(tree, is_leaf=is_param):
        v = getattr(p, "value", p)
        mant = getattr(v, "mantissa", None)
        nd = mant.ndim if mant is not None else getattr(v, "ndim", 0)
        if nd >= 2 and _leaf_size(p) > 256:
            yield p

def _vit_weight_groups(cfg, params) -> List[Tuple[str, int]]:
    n = cfg.n_layers
    attn = sum(_leaf_size(params["blocks"]["attn"][k])
               for k in ("wq", "wk", "wv", "wo")) // n
    ffn = sum(_leaf_size(params["blocks"]["ffn"][k])
              for k in ("wi", "wo")) // n
    out = [("patch", _leaf_size(params["patch_proj"]))]
    for i in range(n):
        out.append((f"block/{i}/attn", attn))
        out.append((f"block/{i}/ffn", ffn))
    out.append(("head", _leaf_size(params["head"])))
    return out


# ---------------------------------------------------------------------------
# static cost
# ---------------------------------------------------------------------------
def _fmt_bits(q: QuantConfig, which: str) -> float:
    if not q.enabled:
        return FLOAT_BITS
    return getattr(q, which).bits_per_element


def static_cost(space: SearchSpace, point: Point,
                groups: Sequence[tuple]) -> CandidateCost:
    q = space.to_config(point)
    scopes = [s for s, _ in groups]
    total = sum(n for _, n in groups) or 1
    w_bits = sum(n * _fmt_bits(q.scoped(s), "weight_fmt")
                 for s, n in groups) / total
    a_bits = sum(n * _fmt_bits(q.scoped(s), "act_fmt")
                 for s, n in groups) / total

    lut = 0
    for entries in ("ln_lut_entries", "gelu_lut_entries",
                    "softmax_lut_entries"):
        per_scope = []
        for s in scopes:
            qs = q.scoped(s)
            if qs.quantize_nonlinear and qs.nonlinear is not None:
                per_scope.append(getattr(qs.nonlinear, entries))
        lut += max(per_scope, default=0)

    return CandidateCost(
        weight_bits=round(float(w_bits), 4),
        act_bits=round(float(a_bits), 4),
        weight_bytes=int(round(sum(n for _, n in groups) * w_bits / 8.0)),
        lut_entries=lut,
    )


# ---------------------------------------------------------------------------
# the evaluator
# ---------------------------------------------------------------------------
class Evaluator:
    """Score SearchSpace points, memoized on the canonical point key.

    cfg/params: the model (any family with scope-tagged call sites; the
    ViT/DeiT family is the first-class citizen).  ``images`` is the
    calibration batch.  The float reference (mode='off') is computed
    once, lazily.
    """

    def __init__(self, space: SearchSpace, cfg, params, images, *,
                 registry: Optional[metrics.Registry] = None):
        self.space = space
        self.cfg = cfg
        self.params = params
        self.images = images
        self.groups = weight_groups(cfg, params)
        self.registry = registry or metrics.default_registry()
        self._cache: Dict[tuple, EvalResult] = {}
        self._logits_cache: Dict[tuple, object] = {}
        self._ref = None

    def _logits(self, q: QuantConfig):
        import dataclasses as dc

        from repro.models import build_model
        model = build_model(dc.replace(self.cfg, quant=q))
        return model.logits(self.params, self.images)

    @property
    def reference(self):
        if self._ref is None:
            self._ref = self._logits(QuantConfig(mode="off"))
        return self._ref

    def logits_for(self, point: Point):
        """Candidate logits on the calibration batch, memoized — the
        greedy driver compares candidates AGAINST EACH OTHER with these
        (the ``core.search`` accept rule), not just against float."""
        key = point_key(point)
        got = self._logits_cache.get(key)
        if got is None:
            self.registry.counter("dse/evaluations").inc()
            with span("dse/eval", registry=self.registry):
                got = self._logits(self.space.to_config(point))
            self._logits_cache[key] = got
        return got

    def __call__(self, point: Point) -> EvalResult:
        key = point_key(point)
        hit = self._cache.get(key)
        if hit is not None:
            self.registry.counter("dse/cache_hits").inc()
            return hit
        out = self.logits_for(point)
        result = EvalResult(
            key=key,
            point=dict(point),
            accuracy=argmax_agreement(out, self.reference),
            fidelity=cosine_fidelity(out, self.reference),
            cost=static_cost(self.space, point, self.groups),
        )
        self._cache[key] = result
        return result

    @property
    def n_evaluated(self) -> int:
        return len(self._cache)
