"""Tiny cells run end to end on the CPU, found by name from new files."""

import json

import pytest


@pytest.mark.parametrize("cell", ["tiny-bf-bulk", "tiny-ivf-bulk",
                                  "tiny-ivf-open"])
def test_tiny_cell_runs_and_is_correct(tiny_root, run_cell, cell):
    rc, line = run_cell(tiny_root, cell)
    assert rc == 0
    assert line["correct"] is True, line["checks"]
    assert line["failed"] == 0 and line["attempted"] > 0
    assert list(line)[-1] == "checks"
    assert line["device"]["platform"] == "cpu"
    spec = json.loads((tiny_root / "BENCHMARK.json").read_text())
    want = {m["name"] for m in spec["end_to_end"]
            if cell in m.get("workloads", [cell])}
    assert set(line["metrics"]) == want
    assert line["metrics"]["recall"]["value"] >= 0.6


def test_new_cell_is_new_files_only(tiny_root, run_cell):
    """A cell, a traffic mix and a per-layer metric added as new files and
    entries are found by name; no harness file changes."""
    from chipbench import bench

    mix = {"mode": "closed", "k": 3,
           "groups": [{"query_params": {}, "share": 1.0}]}
    (tiny_root / "chipbench" / "traffic" / "tiny-bulk-k3.json").write_text(
        json.dumps(mix))
    (tiny_root / "chipbench" / "metrics" / "answers_per_batch.py").write_text(
        "def read(record, trace):\n"
        "    return record['window']['attempted'] / "
        "record['device_batches']\n")
    spec = json.loads((tiny_root / "BENCHMARK.json").read_text())
    spec["workloads"].append({"name": "tiny-bf-k3", "config":
                              "tiny-bruteforce", "traffic": "tiny-bulk-k3",
                              "chips": 1, "why": "added by a test"})
    for m in spec["end_to_end"]:
        if m["name"] in ("qps", "recall"):
            m["workloads"].append("tiny-bf-k3")
    spec["per_layer"].append({"name": "answers_per_batch", "unit": "rows",
                              "better": "higher", "source": "host_clock",
                              "layer": "engine", "moves": "qps",
                              "workloads": ["tiny-bf-k3"]})
    (tiny_root / "BENCHMARK.json").write_text(json.dumps(spec))
    rc, line = run_cell(tiny_root, "tiny-bf-k3")
    assert rc == 0 and line["correct"] is True
    assert set(line["metrics"]) == {"qps", "recall", "setup_s"}
    cell = bench.load_cell(tiny_root, "tiny-bf-k3")
    assert [m["name"] for m in cell["per_layer"]][-1] == "answers_per_batch"
    read = bench.reader(tiny_root, "answers_per_batch")
    assert read({"window": {"attempted": 32}, "device_batches": 2},
                None) == 16


def test_same_seed_same_inputs():
    import numpy as np

    from chipbench import data
    from conftest import TINY_CORPUS

    a = [np.asarray(x) for x in data.make(TINY_CORPUS, 2**31 + 9)]
    b = [np.asarray(x) for x in data.make(TINY_CORPUS, 2**31 + 9)]
    c = [np.asarray(x) for x in data.make(TINY_CORPUS, 2**31 + 10)]
    d = [np.asarray(x) for x in data.make(TINY_CORPUS, 2**40 + 2**31 + 9)]
    assert all(np.array_equal(x, y) for x, y in zip(a, b))
    assert not np.array_equal(a[1], c[1])
    assert not np.array_equal(a[1], d[1])
    # a seed flips coordinate signs of one fixed geometry: |X| is shared
    np.testing.assert_array_equal(np.abs(a[0]), np.abs(c[0]))
