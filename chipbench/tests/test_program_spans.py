"""The per-layer metrics read from the program's own spans
(``repro.serve.tracing``): a traced tiny cell reports them, and a reader
whose window counts exceed the spans recorded reports nothing.

The CPU's trace has no device plane and the peak table no CPU row, so
these traced runs stand one device operation and the v5e's peaks in for
them (the span readers read neither)."""

import math
import sys

import pytest

from chipbench import bench, trace, work

BULK = ("engine_launch_ms.bulk", "engine_fetch_ms.bulk")
OPEN = ("pump.queue_wait_p50_ms", "pump.queue_wait_p99_ms",
        "pump.service_ms")


@pytest.fixture
def cpu_trace(monkeypatch):
    real = trace.extract

    def extract(path):
        summary = real(path)
        w1 = summary["window"][1]
        summary["devices"] = {"/device:TEST:0": [["op", 0, w1 // 2]]}
        return summary

    monkeypatch.setattr(trace, "extract", extract)
    peaks = work.peaks
    monkeypatch.setattr(work, "peaks", lambda kind: peaks("TPU v5 lite"))


@pytest.mark.parametrize("cell,names", [("tiny-bf-bulk", BULK),
                                        ("tiny-ivf-bulk", BULK),
                                        ("tiny-ivf-open", OPEN)])
def test_traced_cell_reports_span_metrics(tiny_root, run_cell, cpu_trace,
                                          cell, names):
    rc, line = run_cell(tiny_root, cell, trace=1)
    assert rc == 0 and line["correct"] is True
    for name in names:
        m = line["metrics"][name]
        assert m["unit"] == "ms"
        assert math.isfinite(m["value"]) and m["value"] >= 0.0
    others = BULK if names == OPEN else OPEN
    assert not set(others) & set(line["metrics"])


def _record(mode, device_batches, served, batches):
    return {"traffic": {"mode": mode}, "device_batches": device_batches,
            "serve_counters": {"served": served, "batches": batches}}


ONE_EACH = [("repro.engine.launch", 0, 10**6, {}),
            ("repro.engine.fetch", 0, 10**6, {}),
            ("repro.pump.queue", 0, 10**6, {}),
            ("repro.pump.batch", 0, 10**6, {})]


@pytest.mark.parametrize("case", ["past_the_log", "no_module", "dropped"])
@pytest.mark.parametrize("name", BULK + OPEN)
def test_reader_reports_nothing_without_the_window(tiny_root, monkeypatch,
                                                   name, case):
    """None, not an error: when the window counts more spans than were
    recorded, when the program has no span module (an older program), and
    when the log dropped spans."""
    from repro.serve import tracing

    read = bench.reader(tiny_root, name)
    mode = "closed" if name in BULK else "open"
    monkeypatch.setattr(tracing, "recorded", lambda: list(ONE_EACH))
    monkeypatch.setattr(tracing, "_dropped", 0)
    assert read(_record(mode, 1, 1, 1), None) == 1.0
    n = 1
    if case == "past_the_log":
        n = 2
    elif case == "no_module":
        monkeypatch.setitem(sys.modules, "repro.serve.tracing", None)
        monkeypatch.delattr("repro.serve.tracing")
    else:
        monkeypatch.setattr(tracing, "_dropped", 1)
    assert read(_record(mode, n, n, n), None) is None
