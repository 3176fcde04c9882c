"""Snapshot exporters (DESIGN.md §15).

Two surfaces over one :meth:`Registry.snapshot`:

* :func:`json_snapshot` — the machine-readable dump CI archives and
  ``examples/serve_deit_mxint.py --metrics-json`` writes;
* :func:`prometheus_text` — Prometheus text exposition (counters,
  gauges, cumulative-bucket histograms) for scrape endpoints.
"""
from __future__ import annotations

import json
import re
from pathlib import Path
from typing import List, Optional, Union

from repro.telemetry import metrics


def json_snapshot(snapshot: Optional[dict] = None,
                  path: Union[str, Path, None] = None,
                  extra: Optional[dict] = None,
                  registry=None) -> dict:
    """Snapshot (default registry unless given) as a json-ready dict;
    ``extra`` keys are merged top-level; ``path`` also writes the file."""
    if snapshot is None:
        snapshot = (registry or metrics.default_registry()).snapshot()
    payload = dict(snapshot)
    if extra:
        payload.update(extra)
    if path is not None:
        Path(path).write_text(json.dumps(payload, indent=1,
                                         sort_keys=True) + "\n")
    return payload


# ---------------------------------------------------------------------------
# Prometheus text exposition
# ---------------------------------------------------------------------------
def _prom_name(name: str) -> str:
    return "repro_" + re.sub(r"[^a-zA-Z0-9_]", "_", name).strip("_")


def _fmt(v: float) -> str:
    return repr(float(v)) if isinstance(v, float) else str(v)


def prometheus_text(snapshot: Optional[dict] = None, registry=None) -> str:
    """Prometheus 0.0.4 text format.  Histograms use cumulative bucket
    counts with ``le`` labels plus ``_sum``/``_count`` series."""
    if snapshot is None:
        snapshot = (registry or metrics.default_registry()).snapshot()
    out: List[str] = []
    for name, value in snapshot.get("counters", {}).items():
        p = _prom_name(name) + "_total"
        out += [f"# TYPE {p} counter", f"{p} {value}"]
    for name, value in snapshot.get("gauges", {}).items():
        p = _prom_name(name)
        out += [f"# TYPE {p} gauge", f"{p} {_fmt(value)}"]
    for name, h in snapshot.get("histograms", {}).items():
        p = _prom_name(name)
        out.append(f"# TYPE {p} histogram")
        cum = 0
        for bound, count in zip(h["buckets"], h["counts"]):
            cum += count
            out.append(f'{p}_bucket{{le="{_fmt(bound)}"}} {cum}')
        cum += h["counts"][-1]
        out.append(f'{p}_bucket{{le="+Inf"}} {cum}')
        out.append(f"{p}_sum {_fmt(h['sum'])}")
        out.append(f"{p}_count {h['count']}")
    return "\n".join(out) + "\n"
