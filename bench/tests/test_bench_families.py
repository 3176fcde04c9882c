"""CPU checks of the family seam: a configuration names its architecture
in ``"family"``, and everything the benchmark knows of that architecture
lives in ``bench/families/<family>.py``.

* the ``vit`` family reproduces, bit for bit, the weights and the
  readings that the benchmark gave before the seam existed (recorded
  below from that code, on the tiny DeiT of ``tiny.py``);
* a second family enters as new files only, in a copy of ``bench/``;
* the generic harness names no leaf or key of one family.

    PYTHONPATH=src JAX_PLATFORMS=cpu python -m pytest -q bench/tests
"""
from __future__ import annotations

import hashlib
import io
import json
import os
import re
import shutil
import subprocess
import sys
import time
import tokenize
from pathlib import Path
from types import SimpleNamespace

import jax
import numpy as np
import pytest

sys.path.insert(0, str(Path(__file__).resolve().parent))

import tiny  # noqa: E402
from bench import run  # noqa: E402
from bench.harness import check, load, manifest, program  # noqa: E402
from bench.harness import weights as W  # noqa: E402

SEED = 2 ** 35 + 11
SIZES = (8, 5, 12, 1, 20, 7, 16)     # requests served, 69 images

# recorded with the harness as it was before the seam
WEIGHTS_SHA = "64023ab815b47ce70b1b4f7f5954afee4e2f4cdd2fc8a12459e5a3ea05eef937"
REFERENCE_SHA = "ebf70733da8dfedc8928142fc6b67bff12550fe5e7a92e67ece1f938f54af6ab"
NUMBERS = {"ops_rel_rms": 8.468459289690694e-08, "logits_rel_rms": 0.0}
PER_OP = {"attention": 8.468459289690694e-08, "gelu": 0.0, "layernorm": 0.0,
          "linear": 0.0, "ln_linear": 0.0}
CONTROL_OPS = {"attention": 0.01610501028121597, "gelu": 0.0,
               "layernorm": 0.0, "linear": 0.0, "ln_linear": 0.0}
CONTROL_OUTPUT = 0.21130853123281804


def served(s: load.Setup) -> list:
    """``SIZES`` through the cell's scheduler, as finished requests."""
    reqs, start = [], 0
    for uid, n in enumerate(SIZES):
        s.sched.submit(program.request(uid, s.pool[start:start + n]))
        reqs.append(load.Request(uid=uid, arrival=0.0, start=start, n=n))
        start += n
    s.sched.run()
    for q in s.sched.finished:
        reqs[q.uid].finish, reqs[q.uid].output = 1.0, q.logits
    return reqs


def test_vit_weights_are_bit_identical():
    tree = W.make(tiny.CONFIG, SEED)
    flat = jax.tree_util.tree_flatten_with_path(tree)[0]
    h = hashlib.sha256()
    for path, leaf in flat:
        h.update(jax.tree_util.keystr(path).encode())
        h.update(np.asarray(leaf).tobytes())
    assert h.hexdigest() == WEIGHTS_SHA


def test_vit_checks_read_what_they_read_before_the_seam():
    cell = tiny.cell("bulk")
    fam = manifest.family(cell.config["family"])
    s = load.setup(cell, SEED, time.perf_counter())
    reqs = served(s)
    numbers, per_op = run.judge(s, run=type(
        "Run", (), {"requests": reqs, "in_window": len(reqs)}))
    assert numbers == NUMBERS and per_op == PER_OP

    images = np.concatenate([s.pool[r.start:r.start + r.n] for r in reqs])
    want = check.reference_output(tiny.CONFIG, s.weights, images, "highest")
    assert want.shape == (69, 10)
    assert hashlib.sha256(want.tobytes()).hexdigest() == REFERENCE_SHA
    low = check.reference_output(tiny.CONFIG, s.weights, images, "bfloat16")
    assert check.output_number(tiny.CONFIG, s.weights, s.pool, reqs,
                               served=low) == CONTROL_OUTPUT
    layer = fam.layer0(s.weights)
    x = fam.op_inputs(tiny.CONFIG, s.batch, SEED)
    assert check.ops_numbers(
        fam.reference_ops(tiny.CONFIG, layer, x, "bfloat16"),
        fam.reference_ops(tiny.CONFIG, layer, x, "highest")) == CONTROL_OPS


def test_output_number_takes_every_element_of_an_image(monkeypatch):
    # a family whose output per image is a (3, 4) map of ones
    fam = SimpleNamespace(forward=lambda cfg, w, images, precision:
                          np.ones((len(images), 3, 4), np.float32))
    monkeypatch.setattr(check.manifest, "family", lambda name: fam)
    pool = np.zeros((3, 2, 2, 3), np.float32)
    reqs = [SimpleNamespace(start=0, n=1), SimpleNamespace(start=1, n=2)]
    got = np.ones((3, 3, 4))
    got[2, 2, 3] = 3.0                 # one element of image 2 off by 2
    number = check.output_number({"family": "map"}, {}, pool, reqs,
                                 served=got)
    assert number == pytest.approx(np.sqrt(4 / 12))


# A mean-pooled ViT: the program's ViT with ``pool="mean"``, its reference
# head on the mean of all tokens; everything else is the vit family's.
MEAN_FAMILY = '''
import dataclasses
import functools

import jax
import jax.numpy as jnp

from bench.harness import manifest, reference as R

vit = manifest.family("vit")
OUTPUT, DIMS, shapes = vit.OUTPUT, vit.DIMS, vit.shapes
op_inputs, layer0 = vit.op_inputs, vit.layer0
program_ops, reference_ops = vit.program_ops, vit.reference_ops


def model(cfg):
    from repro.models import build_model
    return build_model(dataclasses.replace(vit.model(cfg).cfg, pool="mean"))


@functools.partial(jax.jit,
                   static_argnames=("heads", "patch", "dp", "precision"))
def _logits(params, images, *, heads, patch, dp, precision):
    x = R.linear(vit.patchify(images.astype(jnp.float32), patch),
                 params["patch_proj"], params["patch_bias"], dp, precision)
    cls = jnp.broadcast_to(params["cls_token"], (x.shape[0], 1, x.shape[-1]))
    x = jnp.concatenate([cls, x], axis=1) + params["pos_embed"][None]
    x, _ = jax.lax.scan(
        lambda x, bp: (vit.block(x, bp, heads, dp, precision), None),
        x, params["blocks"])
    x = R.layernorm(x, params["final_ln_g"], params["final_ln_b"], dp)
    return R.linear(x.mean(1), params["head"], params["head_b"], dp,
                    precision)


def forward(cfg, weights, images, precision):
    return _logits(weights, images, heads=cfg["num_attention_heads"],
                   patch=cfg["patch_size"], dp=R.Datapath.of(cfg),
                   precision=precision)
'''

# set-up, a short window and the judgement of a cell of that family, in
# a process whose ``bench`` is the copy
DRIVE = '''
import json, sys, time
import numpy as np
sys.path[:0] = [sys.argv[1], sys.argv[2]]
from bench import run
from bench.harness import check, load, manifest
B = manifest.BENCH
cfg = manifest.load_json(B / "configs" / "vit_mean_test.json")
wl = dict(manifest.load_json(B / "workloads" / "deit_base.bulk.json"),
          batch=8, sample_images=24)
cell = manifest.Cell(name="vit_mean_test.bulk", chips=1, config=cfg,
                     traffic=manifest.load_json(B / "traffic" / "bulk.json"),
                     workload=wl, end_to_end=[], per_layer=[])
peaks = {"int8_ops_per_s": 393e12, "hbm_bytes_per_s": 819e9}
s = load.setup(cell, 2 ** 36 + 5, time.perf_counter())
r = load.window(s, 1.0, False, peaks)
numbers, per_op = run.judge(s, r)
reqs = check.sample(r.requests[:r.in_window],
                    np.random.default_rng([s.seed, 4]), wl["sample_images"])
as_cls = check.output_number(dict(cfg, family="vit"), s.weights, s.pool, reqs)
ok, checks = check.verdict(numbers, wl["limits"])
print(json.dumps({"bench": str(B), "correct": ok, "numbers": numbers,
                  "as_cls": as_cls, "limits": wl["limits"],
                  "in_window": r.in_window}))
'''


def test_a_second_family_enters_as_files_only(tmp_path):
    copy = tmp_path / "bench"
    shutil.copytree(manifest.BENCH, copy,
                    ignore=shutil.ignore_patterns("__pycache__"))
    before = {p: p.read_bytes() for p in copy.rglob("*") if p.is_file()}
    (copy / "families" / "vit_mean.py").write_text(MEAN_FAMILY)
    (copy / "configs" / "vit_mean_test.json").write_text(json.dumps(
        dict(tiny.CONFIG, name="vit_mean_test", family="vit_mean")))
    script = tmp_path / "drive.py"
    script.write_text(DRIVE)
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    p = subprocess.run([sys.executable, str(script), str(tmp_path),
                        str(manifest.ROOT / "src")], capture_output=True,
                       text=True, env=env, cwd=tmp_path, timeout=600)
    assert p.returncode == 0, p.stderr[-4000:]
    res = json.loads(p.stdout.splitlines()[-1])
    assert res["bench"] == str(copy) and res["in_window"] > 0
    assert res["correct"], res
    assert set(res["numbers"]) == {"logits_rel_rms", "ops_rel_rms"}
    # the mean head was served and judged: the class-token reference of
    # the same weights and images fails the same limit
    assert res["as_cls"] > res["limits"]["logits_rel_rms"], res
    after = {p: p.read_bytes() for p in copy.rglob("*")
             if p.is_file() and "__pycache__" not in p.parts}
    assert {p: after[p] for p in before} == before


GENERIC = ["harness/*.py", "run.py", "control.py"]


def _keys(tree):
    for k, v in tree.items():
        yield k
        if isinstance(v, dict):
            yield from _keys(v)


def test_generic_harness_names_no_family_key():
    vit = manifest.family("vit")
    leaves = set(_keys(vit.shapes(tiny.CONFIG)))
    files = [p for g in GENERIC for p in sorted(manifest.BENCH.glob(g))]
    assert len(files) > 5
    for path in files:
        text = path.read_text()
        for word in ("cls_token", "num_labels"):
            assert not re.search(rf"\b{word}\b", text), (path, word)
        assert not re.search(r"\*\*\s*2\s*\+\s*1", text), path
        for tok in tokenize.generate_tokens(io.StringIO(text).readline):
            if tok.type == tokenize.STRING and not tok.string.startswith(
                    ('f"', "f'")):
                assert eval(tok.string) not in leaves, (path, tok.string)
