"""The reduction from a trace summary to busy time, idle share, the
breakdown and roofline shares."""

import json
from pathlib import Path

import pytest

from chipbench import bench, trace, work

DATA = Path(__file__).resolve().parent / "data"
ROOT = Path(__file__).resolve().parents[2]

# a window of 100 ns: device ops overlap at 10-30, 20-40 and run at 60-70;
# one harness span 5-45 (the search) and one 50-95 (host work)
SYNTH = {
    "window": [0, 100],
    "devices": {"/device:TPU:0": [["fusion", 10, 20], ["kernel", 20, 20],
                                  ["copy", 60, 10], ["late", 95, 30]]},
    "spans": [["cb.search", 5, 45], ["cb.other", 50, 95]],
}


def test_union_and_overlap():
    assert trace.union([(3, 5), (0, 2), (1, 4), (7, 7)]) == [[0, 5]]
    merged = trace.union([(0, 2), (4, 6)])
    assert trace.overlap(merged, 1, 5) == 2
    assert trace.intersect(merged, [[1, 5]]) == 2


def test_reduce_synthetic():
    r = trace.reduce(SYNTH)
    # busy: 10-40 (30) + 60-70 (10) + 95-100 clipped (5) = 45 ns
    assert r["busy_s"] == pytest.approx(45e-9)
    assert r["window_s"] == pytest.approx(100e-9)
    ops = dict(r["device_ops"])
    assert ops["fusion"] == pytest.approx(20e-9)
    assert ops["late"] == pytest.approx(5e-9)
    spans = {n: (dur, busy) for n, dur, busy in r["spans"]}
    assert spans["cb.search"] == pytest.approx((40e-9, 30e-9))
    assert spans["cb.other"] == pytest.approx((45e-9, 10e-9))
    idle = dict(r["idle_gaps"])
    # idle: 0-10, 40-60, 70-95 = 55 ns; cb.search covers 5-10 and 40-45,
    # cb.other 50-60 and 70-95; 0-5 and 45-50 lie in no span
    assert idle["cb.search"] == pytest.approx(10e-9)
    assert idle["cb.other"] == pytest.approx(35e-9)
    assert idle["(no harness span)"] == pytest.approx(10e-9)


def test_reduce_averages_over_devices():
    two = dict(SYNTH, devices={"/device:TPU:0": [["a", 0, 100]],
                               "/device:TPU:1": [["a", 0, 50]]})
    assert trace.reduce(two)["busy_s"] == pytest.approx(75e-9)


def test_reduce_refuses_an_empty_device_trace():
    with pytest.raises(RuntimeError):
        trace.reduce(dict(SYNTH, devices={}))


def _record(algo, mode, batches, batch, groups=()):
    cfg = json.loads((ROOT / "chipbench" / "configs" / (
        "sift1m-bruteforce.json" if algo == "BruteForce"
        else "sift1m-ivf1000.json")).read_text())
    return {"config": cfg, "traffic": {"mode": mode},
            "device_kind": "TPU v5 lite", "device_batches": batches,
            "batch_size": batch, "groups": list(groups),
            "setup": {"build_s": 1.5}, "serve_counters": {}}


def test_scan_roofline_arithmetic():
    rec = _record("BruteForce", "closed", 100, 256)
    # 512,000,000 bytes at 819e9 B/s = 0.62515 ms; 2*256*1e6*128 flops at
    # 197e12 = 0.3327 ms: the bytes bound it
    assert work.scan_need_s(rec) == pytest.approx(100 * 512e6 / 819e9)
    read = bench.reader(ROOT, "scan_roofline")
    assert read(rec, {"busy_s": 2.0}) == pytest.approx(
        100 * 100 * 512e6 / 819e9 / 2.0)
    assert read(_record("IVF", "closed", 1, 64), {"busy_s": 1.0}) is None


def test_rerank_roofline_arithmetic():
    groups = [{"query_params": {"n_probes": 4}, "answered": 10},
              {"query_params": {"n_probes": 8}, "answered": 30}]
    rec = _record("IVF", "open", 2, 64, groups)
    # per query n_probes * 1000 rows * 512 B; per batch the 1000 centroids
    need_bytes = (10 * 4 + 30 * 8) * 1000 * 512 + 2 * 1000 * 512
    assert work.ivf_need_s(rec) == pytest.approx(need_bytes / 819e9)
    read_open = bench.reader(ROOT, "rerank_roofline.open")
    read_bulk = bench.reader(ROOT, "rerank_roofline.bulk")
    assert read_open(rec, {"busy_s": 0.5}) == pytest.approx(
        100 * need_bytes / 819e9 / 0.5)
    assert read_bulk(rec, {"busy_s": 0.5}) is None


def test_peaks_keyed_by_device_kind():
    assert work.peaks("TPU v5 lite")["hbm_bytes_per_s"] == 819e9
    with pytest.raises(KeyError):
        work.peaks("cpu")


def test_idle_and_host_readers():
    rec = _record("BruteForce", "closed", 2, 256)
    red = trace.reduce(SYNTH)
    idle = bench.reader(ROOT, "device_idle.bulk")(rec, red)
    assert idle == pytest.approx(55.0)
    assert bench.reader(ROOT, "device_idle.open")(rec, red) is None
    host = bench.reader(ROOT, "engine_host_ms.bulk")(rec, red)
    assert host == pytest.approx(1e3 * 10e-9)


@pytest.mark.parametrize("path", sorted(DATA.glob("*.summary.json")),
                         ids=lambda p: p.name)
def test_recorded_trace(path):
    """A summary recorded on the chip reduces to sane numbers: busy time
    inside the window, every search span holding device work."""
    summary = json.loads(path.read_text())
    r = trace.reduce(summary)
    assert 0 < r["busy_s"] <= r["window_s"]
    assert r["device_ops"] and r["device_ops"][0][1] > 0
    searches = [s for s in r["spans"] if s[0] == "cb.search"]
    assert searches and all(0 < busy <= dur for _, dur, busy in searches)
    expected = json.loads(path.with_suffix("").with_suffix(
        ".expected.json").read_text())
    assert r["busy_s"] == pytest.approx(expected["busy_s"], rel=1e-12)
    assert r["window_s"] == pytest.approx(expected["window_s"], rel=1e-12)


def test_extract_recorded_xplane():
    """The profiler's own file, recorded on the chip, reads back: one TPU
    plane of ops, the three harness search spans inside the window."""
    s = trace.extract(str(DATA / "bf-65536-b256-k10.xplane.pb"))
    assert list(s["devices"]) == ["/device:TPU:0"]
    names = {n for n, _, _ in s["devices"]["/device:TPU:0"]}
    assert "stream_topk_pallas.1" in names
    assert [n for n, _, _ in s["spans"]] == ["cb.search"] * 3
    w0, w1 = s["window"]
    assert all(w0 <= a < b <= w1 for _, a, b in s["spans"])
    r = trace.reduce(s)
    assert 0 < r["busy_s"] < r["window_s"]
    assert r["device_ops"][0][0] == "stream_topk_pallas.1"
