"""Serve engine: fixed-shape micro-batching, request stream, checkpoints."""

import numpy as np
import pytest

from repro.serve import CHECKPOINT_VERSION, CheckpointError, Engine
from repro.serve.engine import load_state, save_state


@pytest.fixture(scope="module")
def engine(small_dataset):
    return Engine.build("IVF", small_dataset.train, metric="euclidean",
                        build_params={"n_clusters": 30},
                        query_params={"n_probes": 8}, k=10, batch_size=16)


def test_micro_batching_matches_direct_search(engine, small_dataset):
    """Padded fixed-shape micro-batches must not change results, for any
    request size (including sizes that don't divide batch_size)."""
    from repro.ann import ivf

    state = engine.state
    for nq in (1, 7, 16, 19):
        dists, ids = engine.search(small_dataset.test[:nq])
        assert ids.shape == (nq, 10)
        want_d, want = ivf.search(state, small_dataset.test[:nq], k=10,
                                  n_probes=8)
        np.testing.assert_array_equal(ids, np.asarray(want))
        np.testing.assert_allclose(dists, np.asarray(want_d), rtol=1e-5)
    # empty request batches answer empty instead of crashing the loop
    dists, ids = engine.search(small_dataset.test[:0])
    assert dists.shape == (0, 10) and ids.shape == (0, 10)
    # every device call used the same padded shape => single trace
    assert engine.stats["padded"] > 0


def test_submit_flush_ticket_stream(engine, small_dataset):
    tickets = [engine.submit(q) for q in small_dataset.test[:5]]
    engine.flush()
    _, batch_ids = engine.search(small_dataset.test[:5])
    for i, t in enumerate(tickets):
        dists, ids = engine.result(t)
        np.testing.assert_array_equal(ids, batch_ids[i])
    with pytest.raises(KeyError):
        engine.result(tickets[0])           # tickets are single-use


def test_checkpoint_roundtrip_identical(engine, small_dataset, tmp_path):
    path = tmp_path / "ivf.ckpt"
    engine.save(path)
    restored = Engine.load(path)
    assert restored.k == engine.k
    assert restored.batch_size == engine.batch_size
    assert restored.query_params["n_probes"] == 8
    _, a = engine.search(small_dataset.test)
    _, b = restored.search(small_dataset.test)
    np.testing.assert_array_equal(a, b)


def test_checkpoint_rejects_stale_version(engine, tmp_path, monkeypatch):
    import repro.serve.checkpoint as ckpt_mod

    path = tmp_path / "stale.ckpt"
    monkeypatch.setattr(ckpt_mod, "CHECKPOINT_VERSION",
                        CHECKPOINT_VERSION + 1)
    engine.save(path)
    monkeypatch.undo()
    with pytest.raises(CheckpointError, match="format version"):
        Engine.load(path)


def test_checkpoint_rejects_garbage(tmp_path):
    missing = tmp_path / "nope.ckpt"
    with pytest.raises(CheckpointError, match="no checkpoint"):
        load_state(missing)
    garbage = tmp_path / "garbage.ckpt"
    garbage.write_bytes(b"definitely not a checkpoint")
    with pytest.raises(CheckpointError):
        load_state(garbage)
    # an .npz that is not an engine checkpoint is rejected with a clear
    # message instead of a KeyError deep in numpy
    alien = tmp_path / "alien.ckpt"
    np.savez(open(alien, "wb"), something=np.arange(3))
    with pytest.raises(CheckpointError, match="not an Engine checkpoint"):
        load_state(alien)


def test_state_save_load_roundtrip_tuple_arrays(tmp_path, small_dataset):
    """Tuple-valued array entries (HNSW's per-level adjacency) survive."""
    from repro.ann import hnsw

    state = hnsw.build(small_dataset.train[:400], metric="euclidean",
                       M=8, ef_construction=32)
    path = tmp_path / "hnsw.ckpt"
    save_state(state, path)
    restored, _ = load_state(path)
    assert restored.static == state.static
    assert len(restored["layers"]) == len(state["layers"])
    _, a = hnsw.search(state, small_dataset.test[:8], k=5, ef=32)
    _, b = hnsw.search(restored, small_dataset.test[:8], k=5, ef=32)
    np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def test_engine_traced_knob_no_retrace(small_dataset):
    """IVF's n_probes as a traced knob under a static max_probes cap: the
    knob sweeps recall/QPS with no recompilation and matches the static
    path at every setting."""
    import jax.numpy as jnp

    from repro.ann import ivf

    eng = Engine.build("IVF", small_dataset.train, metric="euclidean",
                       build_params={"n_clusters": 30},
                       query_params={"max_probes": 30, "n_probes": 2},
                       traced_params=("n_probes",), k=10, batch_size=16)
    state = eng.state
    for p in (1, 8, 30):
        _, got = eng.search(small_dataset.test, n_probes=jnp.int32(p))
        _, want = ivf.search(state, small_dataset.test, k=10, n_probes=p)
        np.testing.assert_array_equal(got, np.asarray(want))


def test_engine_traced_knob_survives_checkpoint(small_dataset, tmp_path):
    """traced_params is engine configuration: a restored engine must keep
    serving traced knob values instead of re-pinning them static."""
    import jax.numpy as jnp

    eng = Engine.build("IVF", small_dataset.train, metric="euclidean",
                       build_params={"n_clusters": 30},
                       query_params={"max_probes": 30, "n_probes": 2},
                       traced_params=("n_probes",), k=10, batch_size=16)
    path = tmp_path / "traced.ckpt"
    eng.save(path)
    restored = Engine.load(path)
    assert restored.traced_params == ("n_probes",)
    _, a = eng.search(small_dataset.test, n_probes=jnp.int32(8))
    _, b = restored.search(small_dataset.test, n_probes=jnp.int32(8))
    np.testing.assert_array_equal(a, b)


def test_engine_per_request_overrides_do_not_retrace(small_dataset):
    """A pinned max_probes cap auto-demotes n_probes to a traced knob:
    per-request overrides through search() AND the submit()/flush() ticket
    stream sweep the knob with exactly ONE jit trace."""
    from repro.ann import functional, ivf

    eng = Engine.build("IVF", small_dataset.train, metric="euclidean",
                       build_params={"n_clusters": 30},
                       query_params={"max_probes": 30, "n_probes": 2},
                       k=10, batch_size=16)
    assert "n_probes" in eng.traced_params     # auto-traced via the cap
    functional.TRACE_COUNTS.clear()
    for p in (1, 8, 30):
        _, got = eng.search(small_dataset.test[:20], n_probes=p)
        _, want = ivf.search(eng.state, small_dataset.test[:20], k=10,
                             n_probes=p)
        np.testing.assert_array_equal(got, np.asarray(want))
    # ticket stream: interleaved per-request knobs, answered in override
    # groups, still zero new traces
    tickets = [(engq, p) for p in (1, 8, 30, 8)
               for engq in [eng.submit(small_dataset.test[0], n_probes=p)]]
    eng.flush()
    for t, p in tickets:
        _, ids = eng.result(t)
        _, want = ivf.search(eng.state, small_dataset.test[:1], k=10,
                             n_probes=p)
        np.testing.assert_array_equal(ids, np.asarray(want)[0])
    assert functional.TRACE_COUNTS["IVF"] == 1, (
        f"engine retraced: {functional.TRACE_COUNTS['IVF']} traces")


def test_engine_rejects_override_above_cap(small_dataset):
    """A traced knob above its static cap would be silently clamped by the
    in-kernel mask; the engine must reject it instead of serving degraded
    results as if they were the requested setting."""
    eng = Engine.build("IVF", small_dataset.train, metric="euclidean",
                       build_params={"n_clusters": 30},
                       query_params={"max_probes": 8, "n_probes": 2},
                       k=10, batch_size=16)
    with pytest.raises(ValueError, match="exceeds the engine's static"):
        eng.search(small_dataset.test[:4], n_probes=9)
    # a bad override fails its own submit() — queued tickets of other
    # clients are untouched and still redeemable
    good = eng.submit(small_dataset.test[0], n_probes=4)
    with pytest.raises(ValueError, match="exceeds the engine's static"):
        eng.submit(small_dataset.test[1], n_probes=9)
    eng.flush()
    dists, ids_one = eng.result(good)
    assert ids_one.shape == (10,)
    _, ids = eng.search(small_dataset.test[:4], n_probes=8)   # at cap: fine
    assert ids.shape == (4, 10)


def test_engine_checkpoint_roundtrips_static_caps(small_dataset, tmp_path):
    """The static max_* cap is engine configuration: it must survive a
    checkpoint round-trip so a restored engine keeps serving traced knob
    values under the same cap."""
    eng = Engine.build("IVF", small_dataset.train, metric="euclidean",
                       build_params={"n_clusters": 30},
                       query_params={"max_probes": 30, "n_probes": 2},
                       k=10, batch_size=16)
    path = tmp_path / "capped.ckpt"
    eng.save(path)
    restored = Engine.load(path)
    assert restored.query_params["max_probes"] == 30
    assert restored.query_params["n_probes"] == 2
    assert "n_probes" in restored.traced_params
    _, a = eng.search(small_dataset.test[:8], n_probes=12)
    _, b = restored.search(small_dataset.test[:8], n_probes=12)
    np.testing.assert_array_equal(a, b)


def test_engine_recall_gate(small_dataset):
    """The serve-smoke contract: a few hundred micro-batched queries
    through the Engine reach recall >= 0.9, via the shared metrics path."""
    from repro.ann import distances as D
    from repro.core.metrics import recall_from_arrays

    eng = Engine.build("IVF", small_dataset.train, metric="euclidean",
                       build_params={"n_clusters": 30},
                       query_params={"n_probes": 8}, k=10, batch_size=64)
    rng = np.random.default_rng(0)
    sel = rng.integers(0, len(small_dataset.test), 320)
    Q = small_dataset.test[sel]
    _, ids = eng.search(Q)
    dists = D.pairwise_rows(Q, small_dataset.train, ids, "euclidean")
    rec = float(np.mean(recall_from_arrays(
        dists, small_dataset.distances[sel], 10, neighbors=ids)))
    assert rec >= 0.9
    assert eng.stats["queries"] == 320


def test_compile_is_the_served_program(small_dataset):
    """``Engine.compile`` builds the micro-batch program ahead of time:
    its output for a padded batch equals what ``search`` serves, per-
    request overrides included, and over-cap overrides are refused."""
    import jax

    eng = Engine.build("IVF", small_dataset.train, metric="euclidean",
                       build_params={"n_clusters": 30},
                       query_params={"n_probes": 4, "max_probes": 8},
                       k=10, batch_size=16)
    Q = small_dataset.test[:16]
    compiled = eng.compile(Q, n_probes=8)
    assert isinstance(compiled, jax.stages.Compiled)
    params = dict(eng.query_params, n_probes=8)
    _, ids = compiled(eng.state, Q, **{k: v for k, v in params.items()
                                      if k in eng.traced_params})
    _, want = eng.search(Q, n_probes=8)
    np.testing.assert_array_equal(np.asarray(ids), want)
    with pytest.raises(ValueError, match="exceeds"):
        eng.compile(Q, n_probes=9)
