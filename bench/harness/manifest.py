"""Finds everything a cell needs by the names in ``BENCHMARK.json``.

A cell ``<config>.<traffic>`` is assembled from data files only:

* ``bench/configs/<config>.json``: the model's sizes, source and cuts;
* ``bench/traffic/<traffic>.json``: the traffic mix (loop, sizes, ...);
* ``bench/workloads/<cell>.json``: what belongs to the cell alone (engine
  batch, correctness sample and limits);
* ``bench/metrics/<metric>.py``: one reader per metric, ``read(run)``;
* ``bench/work/<family>.py``: operations and bytes from shapes;
* ``bench/peaks.json``: the chip's published peaks by ``device_kind``.

So a later cell, configuration, traffic mix or metric is new files and
new ``BENCHMARK.json`` entries, never an edit.
"""
from __future__ import annotations

import dataclasses
import importlib.util
import json
from pathlib import Path

BENCH = Path(__file__).resolve().parents[1]
ROOT = BENCH.parent


def load_json(path: Path) -> dict:
    with open(path) as f:
        return json.load(f)


def benchmark() -> dict:
    return load_json(ROOT / "BENCHMARK.json")


@dataclasses.dataclass
class Cell:
    name: str
    chips: int
    config: dict
    traffic: dict
    workload: dict
    end_to_end: list
    per_layer: list


def _applies(metric: dict, cell: str) -> bool:
    return "workloads" not in metric or cell in metric["workloads"]


def cell(name: str, manifest: dict | None = None) -> Cell:
    """The cell called ``name`` with its files loaded; KeyError if the
    manifest has no such cell."""
    m = manifest or benchmark()
    entry = {w["name"]: w for w in m["workloads"]}[name]
    return Cell(
        name=name,
        chips=entry["chips"],
        config=load_json(BENCH / "configs" / f"{entry['config']}.json"),
        traffic=load_json(BENCH / "traffic" / f"{entry['traffic']}.json"),
        workload=load_json(BENCH / "workloads" / f"{name}.json"),
        end_to_end=[x for x in m["end_to_end"] if _applies(x, name)],
        per_layer=[x for x in m["per_layer"] if _applies(x, name)],
    )


def _module(path: Path):
    spec = importlib.util.spec_from_file_location(
        f"bench_{path.parent.name}_{path.stem.replace('.', '_')}", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def metric_reader(name: str):
    """``read(run) -> float | None`` of ``bench/metrics/<name>.py``."""
    return _module(BENCH / "metrics" / f"{name}.py").read


def work(family: str):
    """The module ``bench/work/<family>.py``."""
    return _module(BENCH / "work" / f"{family}.py")


def peaks(device_kind: str) -> dict:
    """Published peaks of one chip of ``device_kind``; an unknown kind is
    an error, never a default."""
    table = load_json(BENCH / "peaks.json")["devices"]
    if device_kind not in table:
        raise KeyError(f"no peaks for device kind {device_kind!r} in "
                       f"bench/peaks.json (have {sorted(table)})")
    return table[device_kind]
