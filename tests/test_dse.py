"""Tests for repro.dse (DESIGN.md §16) and the per-layer scoped-config
plumbing it rides on.

The two ISSUE-10 acceptance criteria live here:

* a uniform (no-override) SearchSpace point is BIT-IDENTICAL to the
  global QuantConfig for mode in {'sim', 'kernel'} on DeiT-Tiny — both
  the identity short-circuit (the uniform point materializes the base
  config object itself) and the forced-unroll case (a same-value
  override switches the ViT from lax.scan to the per-layer loop, which
  must not change a single bit);
* the exhaustive driver on a <=16-point space returns a Pareto set in
  which membership is verifiably correct, backed by a randomized
  property test on the dominance check itself.
"""
import dataclasses
import importlib.util
import json
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs.deit import DEIT_MICRO, DEIT_TINY
from repro.core.mx_types import MXFormat, QuantConfig, QuantOverride
from repro.dse import (Evaluator, GroupSpace, SearchSpace, exhaustive_search,
                       greedy_search, point_key)
from repro.dse.report import (DEFAULT_OBJECTIVES, build_report, dominates,
                              objective_vector, pareto_front)
from repro.models import build_model
from repro.serving.engine import pack_params_mxint
from repro.telemetry import metrics

ROOT = Path(__file__).resolve().parents[1]

SIM = QuantConfig(mode="sim", quantize_nonlinear=True)
KERNEL = QuantConfig(mode="kernel", quantize_nonlinear=True)


def _images(n, size, seed=0):
    rng = np.random.default_rng(seed)
    return jnp.asarray(rng.normal(size=(n, size, size, 3)).astype(np.float32))


# ---------------------------------------------------------------------------
# QuantConfig.scoped — the override resolution the whole subsystem rides on
# ---------------------------------------------------------------------------
class TestScopedConfig:
    def test_later_overrides_win_per_field(self):
        q = QuantConfig(
            mode="sim",
            overrides=(
                ("block/*", QuantOverride(weight_fmt=MXFormat(4, 256),
                                          act_fmt=MXFormat(6, 16))),
                ("block/1/*", QuantOverride(weight_fmt=MXFormat(8, 256))),
            ))
        q1 = q.scoped("block/1/attn")
        # block/1 matches both patterns: weight_fmt from the later entry,
        # act_fmt inherited from the earlier one
        assert q1.weight_fmt.mant_bits == 8
        assert q1.act_fmt.mant_bits == 6
        q0 = q.scoped("block/0/ffn")
        assert q0.weight_fmt.mant_bits == 4
        assert q0.act_fmt.mant_bits == 6
        # non-matching scope keeps the base fields
        qh = q.scoped("head")
        assert qh.weight_fmt == q.weight_fmt and qh.act_fmt == q.act_fmt

    def test_scoped_strips_overrides_and_caches(self):
        q = QuantConfig(overrides=(("head", QuantOverride(mode="sim")),))
        qs = q.scoped("head")
        assert qs.mode == "sim" and not qs.has_overrides
        assert q.scoped("head") is qs          # per-instance cache
        assert qs.scoped("head") is qs         # idempotent

    def test_no_overrides_and_none_scope_are_identity(self):
        q = QuantConfig(mode="sim")
        assert q.scoped(None) is q
        assert q.scoped("block/3/ffn") is q
        qo = QuantConfig(overrides=(("head", QuantOverride(mode="sim")),))
        assert qo.scoped(None) is qo

    def test_scoped_mode_override_switches_datapath(self):
        q = QuantConfig(mode="kernel", quantize_nonlinear=True,
                        overrides=(("block/*/ffn",
                                    QuantOverride(mode="sim")),))
        assert q.datapath.name == "pallas_kernel"
        assert q.scoped("block/2/ffn").datapath.name == "mxint_sim"
        assert q.scoped("block/2/attn").datapath.name == "pallas_kernel"

    def test_override_validation(self):
        with pytest.raises(ValueError, match="pairs"):
            QuantConfig(overrides=(("head",),))
        with pytest.raises(ValueError, match="pattern"):
            QuantConfig(overrides=(("", QuantOverride(mode="sim")),))
        with pytest.raises(TypeError, match="QuantOverride"):
            QuantConfig(overrides=(("head", {"mode": "sim"}),))

    def test_describe_is_json_serializable(self):
        q = QuantConfig(mode="kernel", quantize_nonlinear=True)
        d = json.loads(json.dumps(q.describe()))
        assert d["mode"] == "kernel"
        assert d["weight_fmt"]["mant_bits"] == q.weight_fmt.mant_bits
        assert d["nonlinear"]["ln_lut_bits"] == q.nonlinear.ln_lut_bits


# ---------------------------------------------------------------------------
# SearchSpace grammar
# ---------------------------------------------------------------------------
class TestSearchSpace:
    def _space(self):
        return SearchSpace(
            base=QuantConfig(mode="fake"),
            groups=(GroupSpace(scope="block/*",
                               weight_mant_bits=(6, 4),
                               act_mant_bits=(8,)),
                    GroupSpace(scope="head", weight_mant_bits=(6, 3))))

    def test_size_and_points(self):
        space = self._space()
        assert space.size() == 2 * 1 * 2
        pts = list(space.points())
        assert len(pts) == 4
        assert len({point_key(p) for p in pts}) == 4

    def test_baseline_point_materializes_base_itself(self):
        space = self._space()
        p = space.baseline_point()
        # base weight mant is 6 (MXINT6_WEIGHT), act mant 8 (MXINT8_ACT):
        # every knob has its base value among the candidates
        assert p[("block/*", "weight_mant_bits")] == 6
        assert p[("head", "weight_mant_bits")] == 6
        assert space.to_config(p) is space.base

    def test_to_config_drops_base_equal_assignments(self):
        space = self._space()
        p = space.baseline_point()
        p[("head", "weight_mant_bits")] = 3
        q = space.to_config(p)
        assert len(q.overrides) == 1
        assert q.overrides[0][0] == "head"
        assert q.scoped("head").weight_fmt.mant_bits == 3
        assert q.scoped("block/0/attn").weight_fmt.mant_bits == 6

    def test_non_candidate_value_rejected(self):
        space = self._space()
        p = space.baseline_point()
        p[("head", "weight_mant_bits")] = 5
        with pytest.raises(ValueError, match="not a candidate"):
            space.to_config(p)

    def test_mutate_changes_exactly_one_knob(self):
        space = self._space()
        rng = np.random.default_rng(0)
        p = space.baseline_point()
        for _ in range(20):
            m = space.mutate(p, rng)
            diff = [k for k in p if m[k] != p[k]]
            assert len(diff) == 1
            scope, name = diff[0]
            g = next(g for g in space.groups if g.scope == scope)
            assert m[diff[0]] in getattr(g, name)

    def test_duplicate_knob_rejected(self):
        with pytest.raises(ValueError, match="declared twice"):
            SearchSpace(base=QuantConfig(),
                        groups=(GroupSpace(scope="head",
                                           weight_mant_bits=(4, 6)),
                                GroupSpace(scope="head",
                                           weight_mant_bits=(3,))))

    def test_override_carrying_base_rejected(self):
        base = QuantConfig(overrides=(("head", QuantOverride(mode="sim")),))
        with pytest.raises(ValueError, match="override-free"):
            SearchSpace(base=base, groups=())

    def test_duplicate_candidates_rejected(self):
        with pytest.raises(ValueError, match="duplicate"):
            GroupSpace(scope="head", weight_mant_bits=(4, 4))


# ---------------------------------------------------------------------------
# acceptance: uniform point bit-identity on DeiT-Tiny, sim AND kernel
# ---------------------------------------------------------------------------
class TestUniformPointBitIdentity:
    """ISSUE 10 acceptance: the no-override point of a SearchSpace is
    bit-identical to today's global QuantConfig — including when a
    same-value override FORCES the unrolled per-layer model path that
    per-layer configs require (scan vs unroll must agree bitwise)."""

    def _setup(self):
        cfg = dataclasses.replace(DEIT_TINY, n_layers=2, n_classes=100)
        params = build_model(dataclasses.replace(cfg, quant=SIM)).init(
            jax.random.key(0))
        packed = pack_params_mxint(params, KERNEL.weight_fmt)
        imgs = _images(2, cfg.image_size)
        return cfg, params, packed, imgs

    def test_uniform_point_is_the_base_config(self):
        for base in (SIM, KERNEL):
            space = SearchSpace(
                base=base,
                groups=(GroupSpace(scope="block/*",
                                   weight_mant_bits=(6, 4)),))
            assert space.to_config(space.baseline_point()) is base

    @pytest.mark.parametrize("mode", ["sim", "kernel"])
    def test_same_value_override_unroll_bit_exact(self, mode):
        """A same-value override resolves to the base fields everywhere
        but flips the ViT from lax.scan to the unrolled loop — the
        logits must not move by a single bit."""
        cfg, params, packed, imgs = self._setup()
        base = SIM if mode == "sim" else KERNEL
        p = params if mode == "sim" else packed
        forced = dataclasses.replace(
            base, overrides=(("block/*",
                              QuantOverride(weight_fmt=base.weight_fmt)),))
        assert forced.has_overrides
        want = np.asarray(
            jax.jit(build_model(dataclasses.replace(cfg, quant=base)).logits)(
                p, imgs))
        got = np.asarray(
            jax.jit(build_model(dataclasses.replace(cfg, quant=forced)).logits)(
                p, imgs))
        np.testing.assert_array_equal(got, want)

    def test_mixed_backend_kernel_with_sim_ffn_bit_exact(self):
        """kernel base + sim FFN override on PACKED params: sim is the
        bit-exact oracle of the kernels on these shapes, so the mixed
        model must equal the full-kernel model bitwise — one model, two
        live backends (the §16 headline)."""
        cfg, params, packed, imgs = self._setup()
        mixed = dataclasses.replace(
            KERNEL, overrides=(("block/*/ffn", QuantOverride(mode="sim")),))
        want = np.asarray(
            build_model(dataclasses.replace(cfg, quant=KERNEL)).logits(
                packed, imgs))
        got = np.asarray(
            build_model(dataclasses.replace(cfg, quant=mixed)).logits(
                packed, imgs))
        np.testing.assert_array_equal(got, want)

    def test_effective_override_actually_changes_logits(self):
        """Guard that the scope tags reach the layers: a 3-bit FFN
        weight override must move the logits (else the bit-identity
        tests above prove nothing)."""
        cfg, params, _, imgs = self._setup()
        base = QuantConfig(mode="fake")
        narrow = dataclasses.replace(
            base, overrides=(("block/*/ffn",
                              QuantOverride(weight_fmt=MXFormat(3, 256))),))
        a = np.asarray(build_model(dataclasses.replace(cfg, quant=base))
                       .logits(params, imgs))
        b = np.asarray(build_model(dataclasses.replace(cfg, quant=narrow))
                       .logits(params, imgs))
        assert np.abs(a - b).max() > 0


# ---------------------------------------------------------------------------
# evaluator + drivers on a micro model (fake mode: cheap float QDQ)
# ---------------------------------------------------------------------------
def _micro_setup(n_layers=1):
    cfg = dataclasses.replace(DEIT_MICRO, n_layers=n_layers, n_classes=10)
    base = QuantConfig(mode="fake",
                       weight_fmt=MXFormat(mant_bits=8, block_size=256),
                       act_fmt=MXFormat(mant_bits=16, block_size=16))
    params = build_model(dataclasses.replace(cfg, quant=base)).init(
        jax.random.key(1))
    imgs = _images(4, cfg.image_size, seed=11)
    return cfg, base, params, imgs


class TestEvaluator:
    def test_cache_and_telemetry_counters(self):
        cfg, base, params, imgs = _micro_setup()
        space = SearchSpace(base=base, groups=(
            GroupSpace(scope="block/*", weight_mant_bits=(8, 4)),))
        reg = metrics.Registry()
        ev = Evaluator(space, cfg, params, imgs,
                       registry=reg)
        p = space.baseline_point()
        r1 = ev(p)
        r2 = ev(p)
        assert r2 is r1
        assert ev.n_evaluated == 1
        assert reg.counter("dse/evaluations").value == 1
        assert reg.counter("dse/cache_hits").value == 1
        # logits memo is shared with __call__: no new forward
        ev.logits_for(p)
        assert reg.counter("dse/evaluations").value == 1
        # the uniform point agrees with itself-as-float only partially,
        # but accuracy/fidelity are well-defined probabilities
        assert 0.0 <= r1.accuracy <= 1.0
        assert -1.0 <= r1.fidelity <= 1.0

    def test_static_cost_weights_by_group_size(self):
        cfg, base, params, imgs = _micro_setup(n_layers=2)
        space = SearchSpace(base=base, groups=(
            GroupSpace(scope="block/*", weight_mant_bits=(8, 4)),))
        ev = Evaluator(space, cfg, params, imgs,
                       registry=metrics.Registry())
        wide = ev(space.baseline_point())
        p = space.baseline_point()
        p[("block/*", "weight_mant_bits")] = 4
        narrow = ev(p)
        assert narrow.cost.weight_bits < wide.cost.weight_bits
        # blocks shrank but patch/head stayed at 8 bits, so the weighted
        # mean sits strictly between the two uniform widths
        assert narrow.cost.weight_bits > MXFormat(4, 256).bits_per_element
        assert narrow.cost.weight_bytes < wide.cost.weight_bytes


class TestDrivers:
    def test_exhaustive_pareto_acceptance(self):
        """ISSUE 10 acceptance: exhaustive on a <=16-point space; every
        front member is undominated, every non-member is dominated by a
        front member, and the archived report is self-consistent."""
        cfg, base, params, imgs = _micro_setup()
        space = SearchSpace(base=base, groups=(
            GroupSpace(scope="block/*/attn", weight_mant_bits=(8, 3)),
            GroupSpace(scope="block/*/ffn", weight_mant_bits=(8, 3)),
            GroupSpace(scope="head", weight_mant_bits=(8, 3))))
        assert space.size() == 8 <= 16
        ev = Evaluator(space, cfg, params, imgs,
                       registry=metrics.Registry())
        results = exhaustive_search(space, ev)
        assert len(results) == 8
        front = pareto_front(results)
        assert front
        vecs = [objective_vector(r) for r in results]
        for i in front:
            assert not any(dominates(vecs[j], vecs[i])
                           for j in range(len(vecs)) if j != i)
        for i in range(len(vecs)):
            if i not in front:
                assert any(dominates(vecs[j], vecs[i]) for j in front)

        report = build_report(space, results, driver="exhaustive",
                              n_evaluations=ev.n_evaluated)
        blob = json.loads(json.dumps(report))     # must serialize
        assert blob["schema"] == 1
        assert blob["n_candidates"] == 8
        assert blob["pareto"] == sorted(front)
        flags = [c["pareto"] for c in blob["candidates"]]
        assert [i for i, f in enumerate(flags) if f] == sorted(front)

    def test_exhaustive_limit_guard(self):
        cfg, base, params, imgs = _micro_setup()
        space = SearchSpace(base=base, groups=(
            GroupSpace(scope="block/*", weight_mant_bits=(8, 6, 4)),))
        ev = Evaluator(space, cfg, params, imgs,
                       registry=metrics.Registry())
        with pytest.raises(ValueError, match="exhaustive limit"):
            exhaustive_search(space, ev, limit=2)

    def test_greedy_loose_budget_reaches_narrowest(self):
        cfg, base, params, imgs = _micro_setup()
        space = SearchSpace(base=base, groups=(
            GroupSpace(scope="block/*", weight_mant_bits=(8, 6, 4)),))
        ev = Evaluator(space, cfg, params, imgs,
                       registry=metrics.Registry())
        res = greedy_search(space, ev, budget=1.0)
        assert res.bits == {"block/*": 4}
        assert res.mean_bits == 4.0
        assert [t[:2] for t in res.trace] == [("block/*", 6),
                                              ("block/*", 4)]
        assert all(ok for *_, ok in res.trace)
        # reference (widest) point + both lowerings were evaluated
        assert ev.n_evaluated == 3

    def test_greedy_impossible_budget_keeps_widest(self):
        cfg, base, params, imgs = _micro_setup()
        space = SearchSpace(base=base, groups=(
            GroupSpace(scope="block/*", weight_mant_bits=(8, 6, 4)),))
        ev = Evaluator(space, cfg, params, imgs,
                       registry=metrics.Registry())
        res = greedy_search(space, ev, budget=-1.0)
        assert res.bits == {"block/*": 8}
        assert len(res.trace) == 1 and res.trace[0][3] is False

    def test_greedy_unswept_knob_rejected(self):
        cfg, base, params, imgs = _micro_setup()
        space = SearchSpace(base=base, groups=(
            GroupSpace(scope="block/*", weight_mant_bits=(8, 4)),))
        ev = Evaluator(space, cfg, params, imgs,
                       registry=metrics.Registry())
        with pytest.raises(ValueError, match="act_mant_bits"):
            greedy_search(space, ev, knob="act_mant_bits")


# ---------------------------------------------------------------------------
# dominance property test (pure vectors — no model in the loop)
# ---------------------------------------------------------------------------
class _Vec:
    """Minimal EvalResult stand-in for the report-layer functions."""

    def __init__(self, v):
        self.v = tuple(float(x) for x in v)


_VOBJ = tuple((f"o{i}", +1, (lambda i: lambda r: r.v[i])(i))
              for i in range(3))


class TestDominance:
    def test_strictness_and_ties(self):
        assert dominates((1.0, 1.0), (0.0, 1.0))
        assert not dominates((1.0, 1.0), (1.0, 1.0))   # ties never dominate
        assert not dominates((0.0, 2.0), (1.0, 1.0))   # trade-off
        with pytest.raises(ValueError, match="arity"):
            dominates((1.0,), (1.0, 2.0))

    def test_sense_flips_sign(self):
        r = _Vec((0.9, 6.0, 100.0))
        objs = (("acc", +1, lambda x: x.v[0]),
                ("bits", -1, lambda x: x.v[1]))
        assert objective_vector(r, objs) == (0.9, -6.0)

    def test_duplicate_points_all_stay_on_front(self):
        results = [_Vec((1, 2, 3)) for _ in range(4)]
        assert pareto_front(results, _VOBJ) == [0, 1, 2, 3]

    def test_front_membership_property(self):
        """Randomized (fixed-seed) property: on integer grids full of
        ties, front members are undominated and every non-member is
        dominated by some front member."""
        rng = np.random.default_rng(0)
        for _ in range(50):
            n = int(rng.integers(1, 24))
            vecs = rng.integers(0, 4, size=(n, 3))
            results = [_Vec(v) for v in vecs]
            front = pareto_front(results, _VOBJ)
            assert front, "front of a non-empty set cannot be empty"
            vs = [objective_vector(r, _VOBJ) for r in results]
            for i in front:
                assert not any(dominates(vs[j], vs[i])
                               for j in range(n) if j != i)
            for i in set(range(n)) - set(front):
                assert any(dominates(vs[j], vs[i]) for j in front)


# ---------------------------------------------------------------------------
# the extended dispatch-seam rule (satellite 6)
# ---------------------------------------------------------------------------
def _check_dispatch():
    spec = importlib.util.spec_from_file_location(
        "check_dispatch", ROOT / "tools" / "check_dispatch.py")
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


class TestDispatchSeamOverrideRule:
    def test_override_read_flagged_outside_seam(self):
        cd = _check_dispatch()
        bad = "for pattern, ov in q.overrides:\n    pass\n"
        probs = cd.check_text(bad, "src/repro/models/foo.py")
        assert len(probs) == 1 and "DESIGN.md §16" in probs[0]

    def test_override_read_allowed_inside_seam(self):
        cd = _check_dispatch()
        text = "for pattern, ov in q.overrides:\n    pass\n"
        assert cd.check_text(text, "src/repro/datapath/foo.py") == []
        assert cd.check_text(text, "src/repro/core/mx_types.py") == []

    def test_has_overrides_gate_stays_free(self):
        cd = _check_dispatch()
        assert cd.check_text("if quant.has_overrides:\n    pass\n",
                             "src/repro/models/vit.py") == []

    def test_mode_branch_still_flagged(self):
        cd = _check_dispatch()
        probs = cd.check_text("if q.mode == 'kernel':\n    pass\n",
                              "src/repro/models/foo.py")
        assert len(probs) == 1 and "DESIGN.md §12" in probs[0]
