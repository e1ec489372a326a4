#!/usr/bin/env python3
"""Chip smoke test: serve a SIFT-1M-shaped index on TPU through the normal
entry points, with compiled Pallas kernels, and check the answers.

    python chip_smoke.py              # phases A, B, C on one chip
    python chip_smoke.py --chips 4    # only the sharded path, four chips

The deployment is the SIFT-1M shape of the ANN-Benchmarks paper (Table 1):
n = 1,000,000 vectors, d = 128, float32, euclidean, 10,000 queries, k = 10
and k = 100, generated from the seeded ``blobs-euclidean-1000000-d128``
builder (nothing is downloaded; the corpus is 512 MB on the device).

One chip:

  A. ``Engine`` batch mode over ``BruteForce(backend="pallas")`` (the
     ``distance_topk`` kernel): 256-query micro-batches at k=10 and k=100.
  B. ``AsyncEngine`` open-loop Poisson traffic over
     ``IVF(n_clusters=1000, rerank_kernel=True)`` (the ``rerank_topk``
     kernel), with a traced ``n_probes`` under a ``max_probes`` cap: every
     query is asked at two probe counts, one compiled program.
  C. ``BruteForce`` with PQ codes (m=16, 8 bits), ``adc_kernel=True`` and
     ``rerank_kernel=True`` (the ``adc_scan`` and ``rerank_topk`` kernels),
     ``n_cand=1000``.

Each phase prints one JSON line: device kind, compile seconds, whether the
compiled search program holds a Pallas kernel (``tpu_custom_call``),
queries served and failed / degraded / timed-out tickets, and recall@k
against float64 exact neighbours computed with numpy on the host for a
sample of 512 queries.  A phase fails unless its kernel is present, no
ticket failed, degraded or timed out, and recall reaches the floor stated
below.  QPS is printed as a smoke reading, not a measurement.

Four chips (``--chips 4``): ``python -m repro.launch.serve --shards 4``'s
setup for ``ShardedBruteForce`` and ``ShardedIVF`` over the same corpus;
the sharded ids must be bitwise equal to the single-device search in the
same process, and each device's share of the corpus bytes is printed.

The last line of standard output is the result,
``{"ok": true, "device": {"platform": "tpu", "kind": ..., "count": N}}``,
printed only when every phase passed.  Without a TPU, or without the repo's
``src/`` next to this file, the script exits non-zero and prints no result.
It starts no child process.
"""

from __future__ import annotations

import argparse
import json
import sys
import time
import traceback
from pathlib import Path

import numpy as np

ROOT = Path(__file__).resolve().parent
DATASET = "blobs-euclidean-1000000-d128"
N_SAMPLE = 512           # host-reference sample (>= 500 queries)
BATCH = 256              # served micro-batch for phases A and C
B_BATCH = 64             # phase B micro-batch (open-loop latency)
N_LISTS = 1000           # IVF inverted lists
N_CAND = 1000            # phase C: ADC survivors reranked exactly
SEED = 0                 # sample selection and Poisson arrivals

# Phase A is exact search: recall@k against the float64 reference misses
# only where float32 and float64 order a near tie differently.
FLOOR_A = 0.99
# Phases B and C: the recall@10 of the same index on a CPU run at the same
# n and sample (XLA fold paths, which return the kernels' ids), less a
# margin for the chip's different float rounding in k-means and PQ
# training, which moves the lists and codebooks:
#   IVF n_probes=4: 0.9385, n_probes=8: 0.9988
#   PQ m=16 + exact rerank of n_cand=1000: 0.4082 — the blobs' noise is
#   isotropic, so 16 code bytes keep little of the order inside a cluster
N_PROBES = (4, 8)
MAX_PROBES = 8
FLOOR_B = {4: 0.91, 8: 0.97}
FLOOR_C = 0.38


def _fail(msg: str) -> None:
    print(f"chip_smoke: {msg}", file=sys.stderr)
    sys.exit(2)


def host_reference(train: np.ndarray, Q: np.ndarray, k: int) -> np.ndarray:
    """Exact k nearest ids by float64 numpy on the host (independent of
    every device path)."""
    X = train.astype(np.float64)
    xsq = np.einsum("nd,nd->n", X, X)
    out = np.empty((len(Q), k), np.int64)
    for s in range(0, len(Q), 32):
        q = Q[s:s + 32].astype(np.float64)
        d = xsq[None, :] - 2.0 * (q @ X.T) + np.einsum("bd,bd->b", q, q)[:, None]
        part = np.argpartition(d, k, axis=1)[:, :k]
        order = np.argsort(np.take_along_axis(d, part, axis=1), axis=1)
        out[s:s + 32] = np.take_along_axis(part, order, axis=1)
    return out


def recall(ids: np.ndarray, ref: np.ndarray, k: int) -> float:
    hits = sum(len(set(a[:k].tolist()) & set(b[:k].tolist()))
               for a, b in zip(np.asarray(ids), ref))
    return hits / (len(ref) * k)


def compiled_info(eng, Q, **overrides):
    """(compile seconds, whether the program runs a Pallas kernel)."""
    t0 = time.perf_counter()
    compiled = eng.compile(Q, **overrides)
    return time.perf_counter() - t0, "tpu_custom_call" in compiled.as_text()


def serve(eng, Q, k: int) -> tuple[np.ndarray, dict]:
    """``Engine.search`` one micro-batch at a time: (ids, counts), where a
    batch that raises counts its queries as failed (timed out, for
    ``DeadlineExceeded``) and leaves their ids -1."""
    from repro.serve import DeadlineExceeded

    ids = np.full((len(Q), k), -1, np.int64)
    counts = {"served": 0, "failed": 0, "timed_out": 0}
    for s in range(0, len(Q), eng.batch_size):
        blk = Q[s:s + eng.batch_size]
        try:
            _, ids[s:s + len(blk)] = eng.search(blk)
            counts["served"] += len(blk)
        except DeadlineExceeded:
            counts["timed_out"] += len(blk)
        except Exception:                          # noqa: BLE001
            traceback.print_exc()
            counts["failed"] += len(blk)
    return ids, counts


def report(line: dict, ok: bool) -> bool:
    line["ok"] = bool(ok)
    print(json.dumps(line), flush=True)
    return ok


def phase_a(ds, sample, refs, kind) -> bool:
    from repro.serve import Engine

    Q = ds.test[sample]
    t0 = time.perf_counter()
    base = Engine.build("BruteForce", ds.train, metric=ds.metric,
                        build_params={"backend": "pallas"}, k=10,
                        batch_size=BATCH)
    build_s = time.perf_counter() - t0
    ok = True
    for k in (10, 100):
        eng = Engine(base.state, k=k, batch_size=BATCH)
        compile_s, kernel = compiled_info(eng, Q)
        eng.search(Q[:BATCH])                    # warm: first call
        t0 = time.perf_counter()
        ids, counts = serve(eng, Q, k)
        dt = time.perf_counter() - t0
        r = recall(ids, refs[k], k)
        ok &= report({
            "phase": "A", "index": "BruteForce(backend=pallas)",
            "device_kind": kind, "k": k, "build_s": build_s,
            "compile_s": compile_s, "tpu_custom_call": kernel, **counts,
            "degraded": eng.stats["degraded"], f"recall@{k}": r,
            "floor": FLOOR_A, "qps": len(ids) / dt,
        }, kernel and r >= FLOOR_A and eng.stats["degraded"] == 0
            and counts["failed"] == counts["timed_out"] == 0)
    return ok


def phase_b(ds, sample, refs, kind) -> bool:
    from repro.serve import AsyncEngine, DeadlineExceeded, Engine

    k = 10
    Q = ds.test[sample]
    t0 = time.perf_counter()
    eng = Engine.build("IVF", ds.train, metric=ds.metric,
                       build_params={"n_clusters": N_LISTS,
                                     "rerank_kernel": True},
                       query_params={"n_probes": N_PROBES[0],
                                     "max_probes": MAX_PROBES},
                       k=k, batch_size=B_BATCH)
    build_s = time.perf_counter() - t0
    compile_s, kernel = compiled_info(eng, Q)
    for p in N_PROBES:                           # warm: one program
        eng.search(Q[:eng.batch_size], n_probes=p)
    rng = np.random.default_rng(SEED)
    order = [(i, p) for i in range(len(Q)) for p in N_PROBES]
    order = [order[j] for j in rng.permutation(len(order))]
    rate = 500.0                                  # Poisson arrivals / s
    gaps = rng.exponential(1.0 / rate, len(order))
    srv = AsyncEngine(eng, max_wait_ms=5.0, max_queue=4096,
                      default_deadline_ms=60_000)
    t0 = time.perf_counter()
    tickets = []
    for (i, p), gap in zip(order, gaps):
        tickets.append((srv.submit(Q[i], n_probes=p), i, p))
        time.sleep(gap)
    ids = {p: np.full((len(Q), k), -1, np.int64) for p in N_PROBES}
    failed = degraded = timed_out = 0
    for ticket, i, p in tickets:
        try:
            _, row = ticket.result(timeout=120)
        except DeadlineExceeded:
            timed_out += 1
            continue
        except Exception:                          # noqa: BLE001
            failed += 1
            continue
        if ticket.partial:
            degraded += 1
        ids[p][i] = row
    dt = time.perf_counter() - t0
    srv.close()
    lat = srv.metrics.snapshot()["latency_ms"]
    ok = True
    for p in N_PROBES:
        r = recall(ids[p], refs[k], k)
        ok &= report({
            "phase": "B", "index": f"IVF(n_clusters={N_LISTS},rerank_kernel)",
            "device_kind": kind, "k": k, "n_probes": p,
            "max_probes": MAX_PROBES, "build_s": build_s,
            "compile_s": compile_s, "tpu_custom_call": kernel,
            "requests": len(tickets),
            "served": len(tickets) - failed - timed_out,
            "failed": failed, "degraded": degraded,
            "timed_out": timed_out, "micro_batches":
                srv.metrics.counter("batches"),
            f"recall@{k}": r, "floor": FLOOR_B[p],
            "qps": len(tickets) / dt, "p50_ms": lat["p50"],
            "p99_ms": lat["p99"],
        }, kernel and r >= FLOOR_B[p]
            and failed == degraded == timed_out == 0)
    return ok


def phase_c(ds, sample, refs, kind) -> bool:
    from repro.serve import Engine

    k = 10
    Q = ds.test[sample]
    t0 = time.perf_counter()
    eng = Engine.build(
        "BruteForce", ds.train, metric=ds.metric,
        build_params={"quantize": {"pq": {"m": 16, "bits": 8}},
                      "adc_kernel": True, "rerank_kernel": True},
        query_params={"n_cand": N_CAND}, k=k, batch_size=BATCH)
    build_s = time.perf_counter() - t0
    compile_s, kernel = compiled_info(eng, Q)
    eng.search(Q[:BATCH])                        # warm: first call
    t0 = time.perf_counter()
    ids, counts = serve(eng, Q, k)
    dt = time.perf_counter() - t0
    r = recall(ids, refs[k], k)
    return report({
        "phase": "C", "index": "BruteForce(pq m=16 bits=8, adc_kernel, "
                               "rerank_kernel)",
        "device_kind": kind, "k": k, "n_cand": N_CAND, "build_s": build_s,
        "compile_s": compile_s, "tpu_custom_call": kernel, **counts,
        "degraded": eng.stats["degraded"], f"recall@{k}": r,
        "floor": FLOOR_C, "qps": len(ids) / dt,
    }, kernel and r >= FLOOR_C and eng.stats["degraded"] == 0
        and counts["failed"] == counts["timed_out"] == 0)


def device_bytes(state) -> dict:
    """Bytes of the state's arrays held by each device."""
    import jax

    out: dict = {}
    for leaf in jax.tree_util.tree_leaves(state.arrays):
        for shard in getattr(leaf, "addressable_shards", ()):
            dev = shard.device.id
            out[dev] = out.get(dev, 0) + int(shard.data.nbytes)
    return out


def sharded(ds, sample, kind, n_chips) -> bool:
    """``launch.serve --shards N`` setup for both shard plans, each
    checked bitwise against the single-device index in this process."""
    from repro.launch import serve
    from repro.serve import Engine

    Q = ds.test[sample]
    ok = True
    for algo, build, query in (
            ("BruteForce", [], []),
            ("IVF", [f"n_clusters={N_LISTS}"], [f"n_probes={MAX_PROBES}"])):
        args = serve.parse_args(
            ["--dataset", DATASET, "--algorithm", algo,
             "--shards", str(n_chips), "--count", "10",
             "--batch-size", str(BATCH), "--n-batches", "2",
             "--build", *build, "--query", *query])
        t0 = time.perf_counter()
        eng, _ = serve.setup(args, ds)
        build_s = time.perf_counter() - t0
        recall_agg = serve.batch_loop(eng, ds, args)
        _, ids = eng.search(Q)
        single = Engine.build(algo, ds.train, metric=ds.metric,
                              build_params=serve.parse_build(build),
                              query_params=serve.parse_kv(query),
                              k=10, batch_size=BATCH)
        _, ref = single.search(Q)
        equal = bool(np.array_equal(ids, ref))
        per_dev = device_bytes(eng.state)
        corpus = {d: 0 for d in per_dev}
        for shard in eng.state["X"].addressable_shards:
            corpus[shard.device.id] += int(shard.data.nbytes)
        total = sum(corpus.values())
        ok &= report({
            "phase": "sharded", "index": eng.state.algo,
            "device_kind": kind, "n_shards": n_chips, "build_s": build_s,
            "served": len(ids), "ids_bitwise_equal_single_device": equal,
            "mismatched_rows": int((ids != ref).any(axis=1).sum()),
            "dataset_recall@10": recall_agg,
            "corpus_bytes_share": {str(d): b / total
                                   for d, b in sorted(corpus.items())},
            "state_bytes_per_device": {str(d): b
                                       for d, b in sorted(per_dev.items())},
        }, equal and total > 0
            and max(corpus.values()) <= 0.3 * total)
    return ok


def main(argv=None) -> int:
    p = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    p.add_argument("--chips", type=int, default=1, choices=(1, 4),
                   help="4: run only the sharded path across four chips")
    args = p.parse_args(argv)

    if not (ROOT / "src" / "repro").is_dir():
        _fail(f"the repo's src/repro is not next to {Path(__file__).name}")
    sys.path.insert(0, str(ROOT / "src"))

    from repro.core import compile_cache

    cache = compile_cache.enable()
    import jax

    devices = jax.devices()
    platform = devices[0].platform
    if platform != "tpu":
        _fail(f"needs a TPU, but JAX found platform {platform!r} "
              f"({len(devices)} {devices[0].device_kind} device(s))")
    if len(devices) < args.chips:
        _fail(f"--chips {args.chips} needs {args.chips} TPU devices, "
              f"found {len(devices)}")
    kind = devices[0].device_kind
    print(json.dumps({"phase": "start", "device_kind": kind,
                      "devices": len(devices), "compile_cache": str(cache)}),
          flush=True)

    from repro.data.datasets import build_dataset

    t0 = time.perf_counter()
    ds = build_dataset(DATASET)
    build_s = time.perf_counter() - t0
    sample = np.sort(np.random.default_rng(SEED).choice(
        len(ds.test), N_SAMPLE, replace=False))
    print(json.dumps({
        "phase": "data", "dataset": DATASET, "n": ds.n, "d": ds.dimension,
        "queries": len(ds.test), "build_s": build_s, "sample": N_SAMPLE}),
        flush=True)

    if args.chips == 1:
        t0 = time.perf_counter()
        refs = {k: host_reference(ds.train, ds.test[sample], k)
                for k in (10, 100)}
        print(json.dumps({"phase": "host_reference",
                          "seconds": time.perf_counter() - t0}), flush=True)
        phases = [phase_a, phase_b, phase_c]
        run = [lambda f=f: f(ds, sample, refs, kind) for f in phases]
    else:
        # the sharded ids are checked against the single-device index
        run = [lambda: sharded(ds, sample, kind, args.chips)]
    ok = True
    for fn in run:
        try:
            ok &= fn()
        except Exception:                          # noqa: BLE001
            traceback.print_exc()
            ok = False
    if not ok:
        print("chip_smoke: a phase failed (see its line above)",
              file=sys.stderr)
        return 1
    print(json.dumps({"ok": True, "device": {
        "platform": platform, "kind": kind, "count": len(devices)}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
