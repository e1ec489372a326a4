"""``BENCHMARK.json`` and the files it names, found by name.

A cell names a configuration and a traffic mix; the configuration's
``file`` gives its sizes, the mix is ``chipbench/traffic/<traffic>.json``
and each per-layer metric's reader is ``chipbench/metrics/<name>.py``,
which defines ``read(record, trace) -> float | None``.  Adding a cell, a
mix, a configuration or a metric adds files and entries; no code here
names one.
"""

from __future__ import annotations

import importlib.util
import json
from pathlib import Path

import numpy as np

def load_cell(root: Path, name: str) -> dict:
    """Everything one cell needs: its entry, configuration, traffic mix and
    the end-to-end and per-layer metrics it reports."""
    spec = json.loads((root / "BENCHMARK.json").read_text())
    cells = {w["name"]: w for w in spec["workloads"]}
    if name not in cells:
        raise KeyError(f"no workload {name!r} in BENCHMARK.json; known: "
                       f"{sorted(cells)}")
    cell = cells[name]
    configs = {c["name"]: c for c in spec["configs"]}
    config = json.loads((root / configs[cell["config"]]["file"]).read_text())
    mix = json.loads((root / "chipbench" / "traffic" /
                      f"{cell['traffic']}.json").read_text())

    def mine(metrics):
        return [m for m in metrics
                if "workloads" not in m or name in m["workloads"]]

    return {"cell": cell, "config": config, "traffic": mix,
            "end_to_end": mine(spec["end_to_end"]),
            "per_layer": mine(spec["per_layer"])}


def reader(root: Path, name: str):
    """The ``read`` function of per-layer metric ``name``."""
    path = root / "chipbench" / "metrics" / f"{name}.py"
    mod_spec = importlib.util.spec_from_file_location(
        f"chipbench_metric_{name.replace('.', '_')}", path)
    if mod_spec is None or not path.is_file():
        raise FileNotFoundError(f"no reader for metric {name!r} at {path}")
    mod = importlib.util.module_from_spec(mod_spec)
    mod_spec.loader.exec_module(mod)
    return mod.read


def percentile(values, p: float) -> float:
    """The p-th percentile of all values, interpolated linearly between
    order statistics (numpy's default, Hyndman-Fan type 7)."""
    v = np.sort(np.asarray(values, np.float64))
    if v.size == 0:
        raise ValueError("percentile of no values")
    pos = (v.size - 1) * p / 100.0
    lo = int(np.floor(pos))
    hi = min(lo + 1, v.size - 1)
    return float(v[lo] + (v[hi] - v[lo]) * (pos - lo))
