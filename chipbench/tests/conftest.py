"""Fixtures: a checkout of tiny cells that runs on the CPU.

``tiny_root`` copies ``chipbench/`` into a temporary checkout beside a
link to the program's ``src/`` and writes a ``BENCHMARK.json`` whose
configurations keep the real ones' shape at a size the CPU runs in
seconds (Pallas kernels in interpret mode).  Only the tests relax the
harness's look for a chip, by handing ``run.main`` the CPU devices.
"""

from __future__ import annotations

import json
import shutil
import sys
from pathlib import Path

import pytest

REPO = Path(__file__).resolve().parents[2]
if str(REPO) not in sys.path:
    sys.path.insert(0, str(REPO))
if str(REPO / "src") not in sys.path:
    sys.path.insert(0, str(REPO / "src"))

TINY_CORPUS = {"generator": "blobs", "n": 2048, "d": 16, "dtype": "float32",
               "metric": "euclidean", "n_queries": 96, "n_centers": 8,
               "center_scale": 3.0, "noise_scale": 1.0, "geometry_seed": 7}


def tiny_configs() -> dict:
    """Tiny stand-ins for the two real configurations."""
    return {
        "tiny-bruteforce": {
            "name": "tiny-bruteforce", "corpus": TINY_CORPUS,
            "index": {"algo": "BruteForce", "build": {"backend": "pallas"},
                      "query": {}},
            "engine": {"batch_size": 16},
            "guarantees": {"exact": True, "recall_epsilon": 0.001,
                           "recall_floor": {"default": 1.0}},
            "check": {"dist_err": 5e-5}},
        "tiny-ivf": {
            "name": "tiny-ivf", "corpus": TINY_CORPUS,
            "index": {"algo": "IVF",
                      "build": {"n_clusters": 16, "rerank_kernel": True},
                      "query": {"n_probes": 4, "max_probes": 4}},
            "engine": {"batch_size": 16},
            "pump": {"max_wait_ms": 2.0, "max_queue": 1024},
            "guarantees": {"exact": False, "recall_epsilon": 0.001,
                           "recall_floor": {"n_probes=2": 0.5,
                                            "n_probes=4": 0.6}},
            "check": {"dist_err": 5e-5}},
    }


TINY_TRAFFIC = {
    "tiny-bulk": {"mode": "closed", "k": 5,
                  "groups": [{"query_params": {}, "share": 1.0}]},
    "tiny-bulk-p4": {"mode": "closed", "k": 5,
                     "groups": [{"query_params": {"n_probes": 4},
                                 "share": 1.0}]},
    "tiny-open": {"mode": "open", "k": 5, "rate_per_s": 40.0,
                  "deadline_ms": 60000, "drain_s": 30,
                  "groups": [{"query_params": {"n_probes": 2}, "share": 0.5},
                             {"query_params": {"n_probes": 4},
                              "share": 0.5}]},
}

TINY_CELLS = [
    {"name": "tiny-bf-bulk", "config": "tiny-bruteforce",
     "traffic": "tiny-bulk", "chips": 1, "why": "test"},
    {"name": "tiny-ivf-open", "config": "tiny-ivf", "traffic": "tiny-open",
     "chips": 1, "why": "test"},
    {"name": "tiny-ivf-bulk", "config": "tiny-ivf", "traffic": "tiny-bulk-p4",
     "chips": 1, "why": "test"},
]


def make_root(tmp: Path, cells=TINY_CELLS, traffic=TINY_TRAFFIC) -> Path:
    """A checkout in ``tmp``: chipbench/ copied, src/ linked, tiny
    configurations and mixes written as new files, BENCHMARK.json's
    metrics kept and its cells replaced."""
    shutil.copytree(REPO / "chipbench", tmp / "chipbench",
                    ignore=shutil.ignore_patterns("__pycache__", "tests"))
    (tmp / "src").symlink_to(REPO / "src")
    spec = json.loads((REPO / "BENCHMARK.json").read_text())
    configs = []
    for name, cfg in tiny_configs().items():
        f = tmp / "chipbench" / "configs" / f"{name}.json"
        f.write_text(json.dumps(cfg))
        configs.append({"name": name, "source": "test",
                        "file": f"chipbench/configs/{name}.json",
                        "reduced": [], "why": "test"})
    for name, mix in traffic.items():
        (tmp / "chipbench" / "traffic" / f"{name}.json").write_text(
            json.dumps(mix))
    names = [c["name"] for c in cells]
    spec["configs"] = configs
    spec["workloads"] = list(cells)
    bulk = [c["name"] for c in cells if "bulk" in c["traffic"]]
    opened = [c["name"] for c in cells if "open" in c["traffic"]]
    for m in spec["end_to_end"] + spec["per_layer"]:
        if "workloads" in m:
            m["workloads"] = {"qps": bulk, "p50_ms": opened,
                              "p99_ms": opened}.get(m["name"], names)
    (tmp / "BENCHMARK.json").write_text(json.dumps(spec))
    return tmp


def cpu_devices(chips):
    import jax

    return jax.devices()[:chips]


@pytest.fixture
def tiny_root(tmp_path):
    return make_root(tmp_path)


@pytest.fixture
def run_cell(capsys):
    """Run one cell in this process on the CPU; returns the result line
    (a dict) and the exit code."""
    from chipbench import run

    def go(root, cell, seed=123, seconds=1.0, trace=0):
        rc = run.main(["--workload", cell, "--seed", str(seed),
                       "--seconds", str(seconds), "--trace", str(trace)],
                      root=root, accelerator=cpu_devices)
        out = capsys.readouterr().out.strip().splitlines()
        return rc, (json.loads(out[-1]) if rc == 0 and out else None)

    return go
