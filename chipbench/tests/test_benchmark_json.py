"""BENCHMARK.json keeps to the contract's names, units and files, and every
file it names is where the harness looks for it."""

import json
import re
from pathlib import Path

import pytest

ROOT = Path(__file__).resolve().parents[2]
SPEC = json.loads((ROOT / "BENCHMARK.json").read_text())
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.-]{1,16}$")
E2E = {m["name"] for m in SPEC["end_to_end"]}
CELLS = {w["name"]: w for w in SPEC["workloads"]}


def all_names():
    for group in ("configs", "workloads", "end_to_end", "per_layer"):
        for entry in SPEC[group]:
            yield group, entry["name"]
    for w in SPEC["workloads"]:
        yield "config", w["config"]
        yield "traffic", w["traffic"]
    for c in SPEC["configs"]:
        for key in c["reduced"]:
            yield "reduced", key


def test_top_level_keys():
    assert set(SPEC) == {"command", "paths", "run_seconds", "configs",
                         "workloads", "end_to_end", "per_layer"}
    assert 1 <= SPEC["run_seconds"] <= 51
    assert SPEC["command"][1].startswith(SPEC["paths"][0] + "/")


@pytest.mark.parametrize("group,name", list(all_names()))
def test_name_characters(group, name):
    assert NAME.match(name), (group, name)


def test_names_unique():
    for group in ("configs", "workloads"):
        names = [e["name"] for e in SPEC[group]]
        assert len(names) == len(set(names))
    metrics = [m["name"] for m in SPEC["end_to_end"] + SPEC["per_layer"]]
    assert len(metrics) == len(set(metrics))


@pytest.mark.parametrize("metric", SPEC["end_to_end"] + SPEC["per_layer"],
                         ids=lambda m: m["name"])
def test_metric_entry(metric):
    assert UNIT.match(metric["unit"]), metric
    assert metric["better"] in ("lower", "higher")
    for cell in metric.get("workloads", []):
        assert cell in CELLS
    if "bound" in metric:
        assert metric["source"] in ("host_clock", "device_trace")
        assert 0.01 <= metric["bound"] <= 0.25
    else:
        assert metric["source"] in ("device_trace", "program_span",
                                    "program_counter", "host_clock")
        assert metric["moves"] in E2E
        assert "\n" not in metric["layer"] and metric["layer"]
        assert (ROOT / "chipbench" / "metrics" /
                f"{metric['name']}.py").is_file()
        moved = next(m for m in SPEC["end_to_end"]
                     if m["name"] == metric["moves"])
        for cell in metric.get("workloads", CELLS):
            assert cell in moved.get("workloads", CELLS), (metric, cell)
    if "roofline" in metric["name"] or "mfu" in metric["name"]:
        assert metric["unit"] == "%"


@pytest.mark.parametrize("cell", SPEC["workloads"], ids=lambda w: w["name"])
def test_cell_files_and_metrics(cell):
    configs = {c["name"]: c for c in SPEC["configs"]}
    cfg = configs[cell["config"]]
    assert (ROOT / cfg["file"]).is_file()
    assert cfg["file"].startswith(SPEC["paths"][0] + "/")
    assert (ROOT / "chipbench" / "traffic" /
            f"{cell['traffic']}.json").is_file()
    assert cell["chips"] in (1, 4)
    assert 1 <= len(cell["why"]) <= 200
    reported = [m["name"] for m in SPEC["end_to_end"]
                if cell["name"] in m.get("workloads", CELLS)]
    assert "setup_s" in reported and len(reported) >= 2
    assert any(cell["name"] in m.get("workloads", CELLS)
               for m in SPEC["per_layer"])


def test_pairs_unique_and_configs_used():
    pairs = [(w["config"], w["traffic"]) for w in SPEC["workloads"]]
    assert len(pairs) == len(set(pairs))
    used = {w["config"] for w in SPEC["workloads"]}
    assert used == {c["name"] for c in SPEC["configs"]}


def test_config_files_hold_their_source():
    for c in SPEC["configs"]:
        cfg = json.loads((ROOT / c["file"]).read_text())
        assert cfg["name"] == c["name"] and cfg["source"] == c["source"]
        assert cfg["reduced"] == c["reduced"]
        assert 1 <= len(c["source"]) <= 200 and 1 <= len(c["why"]) <= 200


def test_size_limit():
    assert (ROOT / "BENCHMARK.json").stat().st_size <= 64 * 1024
