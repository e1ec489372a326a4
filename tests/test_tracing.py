"""Spans of the serving path (``repro.serve.tracing``): nothing recorded
with the profiler off; under ``jax.profiler.trace`` one queue span per
served request, engine spans inside their micro-batch, and every span in
the profile's ``.xplane.pb`` at one clock offset from the log's."""

import glob
import os
import sys
import threading

import jax
import numpy as np
import pytest

from repro.serve import AsyncEngine, Engine, tracing

ENGINE_SPANS = ("repro.engine.launch", "repro.engine.wait",
                "repro.engine.fetch")


def _engine():
    X = np.random.default_rng(0).normal(size=(512, 16)).astype(np.float32)
    return Engine.build("IVF", X, metric="euclidean",
                        build_params={"n_clusters": 8},
                        query_params={"n_probes": 2, "max_probes": 4},
                        k=5, batch_size=8)


@pytest.fixture(scope="module")
def profiled(tmp_path_factory):
    """One profile around a bulk ``Engine.search`` and an ``AsyncEngine``
    serving two ``n_probes`` groups; the spans it logged, its xplane
    events, and the counters of what ran."""
    eng = _engine()
    Q = np.random.default_rng(1).normal(size=(40, 16)).astype(np.float32)
    eng.search(Q[:8])
    srv = AsyncEngine(eng, max_wait_ms=2.0)
    for p in (2, 4):
        srv.submit(Q[0], n_probes=p).result(timeout=60)
    served0 = srv.metrics.counter("served")
    batches0 = srv.metrics.counter("batches")
    n0 = len(tracing.recorded())
    stats0 = eng.stats["batches"]
    d = str(tmp_path_factory.mktemp("profile"))
    with jax.profiler.trace(d):
        eng.search(Q[:20])                  # micro-batches of 8, 8, 4
        tickets = [srv.submit(q, n_probes=2 + 2 * (i % 2))
                   for i, q in enumerate(Q[20:])]
        for t in tickets:
            t.result(timeout=60)
    srv.close()
    (path,) = glob.glob(os.path.join(d, "**", "*.xplane.pb"), recursive=True)
    events = []
    for plane in jax.profiler.ProfileData.from_file(path).planes:
        for line in plane.lines:
            events += [(ev.name, float(ev.start_ns), dict(ev.stats))
                       for ev in line.events if ev.name.startswith("repro.")]
    return {"spans": tracing.recorded()[n0:], "events": events,
            "tickets": {int(t) for t in tickets},
            "served": srv.metrics.counter("served") - served0,
            "pump_batches": srv.metrics.counter("batches") - batches0,
            "engine_batches": eng.stats["batches"] - stats0}


def _named(spans, name):
    return [s for s in spans if s[0] == name]


@pytest.mark.parametrize("site", ["span", "batch", "record"])
def test_profiler_off_records_nothing(site):
    assert not jax.profiler.TraceAnnotation.is_enabled()
    n0 = len(tracing.recorded())
    if site == "record":
        assert tracing.record("repro.test", 0, 1, batch=0) is None
    else:
        ctx = getattr(tracing, site)(*(["repro.test"] if site == "span"
                                       else []))
        assert ctx is tracing._NULL
        with ctx as got:
            assert got is None
    assert len(tracing.recorded()) == n0


def test_one_queue_span_per_served_request(profiled):
    queue = _named(profiled["spans"], "repro.pump.queue")
    assert profiled["served"] == len(profiled["tickets"]) == 20
    assert len(queue) == profiled["served"]
    assert {s[3]["ticket"] for s in queue} == profiled["tickets"]
    assert all(e >= s for _, s, e, _ in queue)


def test_queue_spans_name_a_batch_span(profiled):
    batches = {s[3]["batch"]: s
               for s in _named(profiled["spans"], "repro.pump.batch")}
    assert len(batches) == profiled["pump_batches"] > 1
    for _, start, end, ids in _named(profiled["spans"], "repro.pump.queue"):
        batch = batches[ids["batch"]]
        assert end <= batch[1]
    rows = {b: 0 for b in batches}
    for s in _named(profiled["spans"], "repro.pump.queue"):
        rows[s[3]["batch"]] += 1
    assert all(rows[b] == s[3]["rows"] for b, s in batches.items())
    assert {s[3]["n_probes"] for s in batches.values()} == {2, 4}


@pytest.mark.parametrize("name", ENGINE_SPANS)
def test_engine_spans_lie_inside_their_batch(profiled, name):
    spans = profiled["spans"]
    batches = {s[3]["batch"]: s for s in _named(spans, "repro.pump.batch")}
    mine = _named(spans, name)
    # one per micro-batch: three of the bulk search, one per pump batch
    assert len(mine) == profiled["engine_batches"] == 3 + len(batches)
    assert len({s[3]["batch"] for s in mine}) == len(mine)
    inside = 0
    for _, start, end, ids in mine:
        if ids["batch"] in batches:
            _, b0, b1, _ = batches[ids["batch"]]
            assert b0 <= start <= end <= b1
            inside += 1
    assert inside == len(batches)


def test_engine_spans_of_a_batch_run_in_order(profiled):
    by_batch = {}
    for name, start, end, ids in profiled["spans"]:
        if name in ENGINE_SPANS:
            by_batch.setdefault(ids["batch"], {})[name] = (start, end)
    for spans in by_batch.values():
        ends = [spans[n] for n in ENGINE_SPANS]
        assert all(a[1] <= b[0] for a, b in zip(ends, ends[1:]))


def test_launch_count_equals_engine_batches(profiled):
    launch = _named(profiled["spans"], "repro.engine.launch")
    assert len(launch) == profiled["engine_batches"]


def test_every_span_in_the_profile_once_at_one_offset(profiled):
    events = {}
    for name, start, stats in profiled["events"]:
        events.setdefault((name, stats.get("batch")), []).append(start)
    offsets = []
    opened = [s for s in profiled["spans"] if s[0] != "repro.pump.queue"]
    assert len(opened) == 3 * profiled["engine_batches"] \
        + profiled["pump_batches"]
    for name, start, _, ids in opened:
        (xstart,) = events[(name, ids["batch"])]
        offsets.append(xstart - start)
    assert max(offsets) - min(offsets) < 50e3          # ns


def test_full_log_counts_what_it_drops_until_cleared(monkeypatch, tmp_path):
    monkeypatch.setattr(tracing, "CAPACITY", len(tracing.recorded()) + 2)
    monkeypatch.setattr(tracing, "_dropped", 0)     # restored afterwards
    with jax.profiler.trace(str(tmp_path)):
        for i in range(5):
            with tracing.span("repro.test", i=i):
                pass
    assert tracing.dropped() == 3
    assert [s[3]["i"] for s in tracing.recorded()[-2:]] == [0, 1]
    monkeypatch.setattr(tracing, "_log", [])        # restored afterwards
    tracing.record("repro.test", 0, 1)              # profiler off: no-op
    tracing.clear()
    assert tracing.recorded() == [] and tracing.dropped() == 0


def test_threads_lose_no_span_and_share_no_batch_id(monkeypatch, tmp_path):
    """More writer threads than cores, switching often, into a log that
    fills halfway: every span kept or counted as dropped, never past the
    capacity, every batch id distinct, each span tagged with its own
    thread's id."""
    n_threads, per = (os.cpu_count() or 2) + 2, 200
    total = n_threads * per
    n0 = len(tracing.recorded())
    monkeypatch.setattr(tracing, "CAPACITY", n0 + total // 2)
    monkeypatch.setattr(tracing, "_dropped", 0)
    seen = {}

    def work(t):
        for i in range(per):
            with tracing.batch() as b:
                seen[(t, i)] = b
                with tracing.span("repro.test.stress", t=t, i=i):
                    pass

    interval = sys.getswitchinterval()
    sys.setswitchinterval(1e-6)
    try:
        with jax.profiler.trace(str(tmp_path)):
            threads = [threading.Thread(target=work, args=(t,))
                       for t in range(n_threads)]
            for th in threads:
                th.start()
            for th in threads:
                th.join(timeout=60)
    finally:
        sys.setswitchinterval(interval)
    assert not any(th.is_alive() for th in threads)
    spans = tracing.recorded()[n0:]
    assert len(spans) == total // 2
    assert tracing.dropped() == total - total // 2
    assert len(set(seen.values())) == total
    assert all(ids["batch"] == seen[(ids["t"], ids["i"])]
               for _, _, _, ids in spans)
