"""Fused candidate-rerank benchmark (ISSUE 5 acceptance).

Candidate *verification* dominates query cost across the LSH / tree /
inverted-file families (Li et al. 2016) — and the seed's rerank
materialized the full [b, C, d] gathered candidate tensor before a dense
einsum, which blows up exactly at the high-probe operating points the
recall/QPS frontier cares about.  Three paths are timed per algorithm on
the SAME built index at a high-probe query setting, warm (the rerank is
the steady-state serving hot loop):

  * **materialized** — the candidate window reranked in ONE chunk
    (``rerank_block`` >= C): gather-all + one-shot ``topk_unique``, the
    seed behaviour.  Peak memory O(b * C * d).
  * **stream_fold**  — the shared XLA streaming fold with the autotuned
    candidate block: peak memory O(b * (block + k)) running state plus one
    [b, block, d] gathered chunk.
  * **kernel**       — the fused Pallas kernel path (``rerank_kernel``
    build flag): gather DMA'd row-by-row into VMEM scratch.  Timed on a
    reduced query batch — on a CPU backend it runs in interpret mode
    (every DMA is emulated), so its wall-clock there is a correctness
    proxy, not a perf claim.

Gates (CI smoke lane):

  * equal recall by construction — materialized and stream_fold neighbor
    ids are asserted bit-identical per algorithm;
  * kernel parity — kernel ids bit-identical to the fold (and distances
    bit-identical for hamming's integer popcounts; float modes to 1e-6,
    the dot-shape ulp documented in ``kernels/rerank_topk/ops.py``);
  * ``>= 1.3x`` equal-recall speedup (stream_fold vs materialized) on at
    least two algorithms.

    PYTHONPATH=src python benchmarks/bench_rerank.py [--smoke]
"""

from __future__ import annotations

import time

import jax
import numpy as np

try:
    from benchmarks.common import Row, write_bench_json
except ModuleNotFoundError:          # direct script invocation
    import pathlib
    import sys
    sys.path.insert(0, str(pathlib.Path(__file__).resolve().parents[1]))
    from benchmarks.common import Row, write_bench_json
from repro.ann.functional import get_functional
from repro.data import get_dataset

K = 10
MIN_SPEEDUP = 1.3
MIN_WINNERS = 2
KERNEL_NQ = 16            # interpret-mode kernel: parity on a small batch

# algorithm -> (dataset template, build params, high-probe query params, nq)
# Shapes are picked so the materialized gather is the dominant cost: many
# probed lists / tables / flips, wide per-probe windows, d wide enough
# that [b, C, d] dwarfs the [b, C] id window.
CASES = {
    "IVF": ("blobs-euclidean-{n}-d128", {"n_clusters": 64},
            {"n_probes": 64}, 256),
    "HyperplaneLSH": ("blobs-angular-{n}-d128",
                      {"n_tables": 8, "n_bits": 8, "cap": 128},
                      {"n_probes": 8}, 128),
    "E2LSH": ("blobs-euclidean-{n}-d128",
              {"n_tables": 8, "n_hashes": 8, "width": 2.0, "cap": 128},
              {"n_probes": 8}, 128),
    "RPForest": ("blobs-euclidean-{n}-d128",
                 {"n_trees": 10, "leaf_size": 64}, {"probe": 8}, 128),
    "MultiIndexHashing": ("random-hamming-{n}-b128",
                         {"n_chunks": 16, "cap": 64}, {"radius": 2}, 128),
}

SCALE_N = {"smoke": 2000, "default": 20000, "full": 100000}
HAMMING_N = {"smoke": 1500, "default": 15000, "full": 50000}


def _timed(fn, n: int = 5, warmup: int = 2) -> float:
    for _ in range(warmup):
        jax.block_until_ready(fn())
    best = float("inf")
    for _ in range(n):
        t0 = time.perf_counter()
        jax.block_until_ready(fn())
        best = min(best, time.perf_counter() - t0)
    return best


def _peak_model_mb(b: int, C: int, d: int, k: int, block: int,
                   itemsize: int) -> tuple[float, float]:
    """(materialized, streaming) peak rerank memory in MB: the O(b*C*d)
    gathered tensor vs the O(b*(block + k)) fold state + one gathered
    chunk."""
    mat = b * C * d * itemsize
    fold = b * ((block + 3 * k) * 4 + block * d * itemsize)
    return mat / 2**20, fold / 2**20


def run(scale: str = "default"):
    """Harness contract: ``run(scale) -> list[Row]``."""
    rows, _ = run_with_summary(scale)
    return rows


def run_with_summary(scale: str = "default"):
    from repro.kernels.rerank_topk.ops import pick_rerank_block

    rows = []
    winners = 0
    summary = {}
    for name, (ds_tmpl, build_params, query_params, nq) in CASES.items():
        n = (HAMMING_N if "hamming" in ds_tmpl else SCALE_N)[scale]
        ds = get_dataset(ds_tmpl.format(n=n))
        spec = get_functional(name)
        Q = ds.test
        while Q.shape[0] < nq:                 # small smoke test splits
            Q = np.concatenate([Q, Q])
        Q = Q[:nq]

        mat = spec.build(ds.train, metric=ds.metric, rerank_block=1 << 30,
                         **build_params)
        fold = spec.build(ds.train, metric=ds.metric, **build_params)
        kern = spec.build(ds.train, metric=ds.metric, rerank_kernel=True,
                          **build_params)

        jq_mat, jq_fold, jq_kern = (spec.jit_search() for _ in range(3))
        t_mat = _timed(lambda: jq_mat(mat, Q, k=K, **query_params))
        t_fold = _timed(lambda: jq_fold(fold, Q, k=K, **query_params))
        d_mat, i_mat = jq_mat(mat, Q, k=K, **query_params)
        d_fold, i_fold = jq_fold(fold, Q, k=K, **query_params)

        # equal recall by construction: identical neighbors (float dists
        # agree to the ulp across blockings; hamming exactly)
        np.testing.assert_array_equal(
            np.asarray(i_mat), np.asarray(i_fold),
            err_msg=f"{name}: stream fold changed the neighbor set")
        if ds.metric == "hamming":
            np.testing.assert_array_equal(np.asarray(d_mat),
                                          np.asarray(d_fold))
        else:
            np.testing.assert_allclose(np.asarray(d_mat),
                                       np.asarray(d_fold),
                                       rtol=1e-6, atol=1e-5)

        # kernel parity gate on a reduced batch (interpret-mode DMAs)
        Qk = Q[:KERNEL_NQ]
        d_k, i_k = jq_kern(kern, Qk, k=K, **query_params)
        t_kern = _timed(lambda: jq_kern(kern, Qk, k=K, **query_params),
                        n=1, warmup=1)
        d_f, i_f = jq_fold(fold, Qk, k=K, **query_params)
        np.testing.assert_array_equal(
            np.asarray(i_k), np.asarray(i_f),
            err_msg=f"{name}: kernel path != XLA fold (ids)")
        if ds.metric == "hamming":
            np.testing.assert_array_equal(np.asarray(d_k), np.asarray(d_f))
        else:
            np.testing.assert_allclose(np.asarray(d_k), np.asarray(d_f),
                                       rtol=1e-6, atol=1e-5)

        # shapes + the memory model the fold buys
        d_dim = ds.train.shape[1]
        C = _candidate_width(name, mat, build_params, query_params)
        block = pick_rerank_block(nq, C, d_dim, K)
        mb_mat, mb_fold = _peak_model_mb(nq, C, d_dim, K, block,
                                         ds.train.dtype.itemsize)
        x = t_mat / t_fold
        winners += x >= MIN_SPEEDUP
        shape = f"b={nq};C={C};d={d_dim}"
        summary[name] = {"speedup": round(x, 3), "qps": round(nq / t_fold),
                         "qps_materialized": round(nq / t_mat),
                         "block": block, "C": C,
                         "peak_mb_materialized": round(mb_mat, 1),
                         "peak_mb_fold": round(mb_fold, 1),
                         "equal_recall": True}
        rows.append(Row(f"rerank/{name}/materialized", t_mat * 1e6,
                        f"{shape};qps={nq / t_mat:.0f};"
                        f"peak_mb={mb_mat:.1f}"))
        rows.append(Row(f"rerank/{name}/stream_fold", t_fold * 1e6,
                        f"{shape};qps={nq / t_fold:.0f};x={x:.2f};"
                        f"block={block};peak_mb={mb_fold:.1f};"
                        f"equal_recall=True"))
        rows.append(Row(f"rerank/{name}/kernel", t_kern * 1e6,
                        f"b={KERNEL_NQ};C={C};interpret=True;"
                        f"parity=ids_bitwise"))

    assert winners >= MIN_WINNERS, (
        f"only {winners} algorithms reached {MIN_SPEEDUP}x equal-recall "
        f"speedup over the materialized rerank (need {MIN_WINNERS})")
    summary["winners_ge_1.3x"] = winners
    return rows, summary


def _candidate_width(name, state, build_params, query_params) -> int:
    """The [b, C] rerank window width at the benchmarked setting."""
    if name == "IVF":
        return query_params["n_probes"] * state.stat("pad")
    if name in ("HyperplaneLSH", "E2LSH"):
        return (build_params["n_tables"] * query_params["n_probes"]
                * build_params["cap"])
    if name == "RPForest":
        return (build_params["n_trees"] * query_params["probe"]
                * build_params["leaf_size"])
    # MIH: all chunk codes within the probe radius, per chunk
    import math
    bits = state.stat("chunk_bits")
    probes = sum(math.comb(bits, r)
                 for r in range(query_params["radius"] + 1))
    return build_params["n_chunks"] * probes * build_params["cap"]


if __name__ == "__main__":
    import argparse

    p = argparse.ArgumentParser()
    p.add_argument("--smoke", action="store_true",
                   help="tiny dataset (CI smoke lane)")
    p.add_argument("--scale", default=None,
                   choices=["smoke", "default", "full"])
    args = p.parse_args()
    scale = args.scale or ("smoke" if args.smoke else "default")
    rows, summary = run_with_summary(scale)
    print("name,us_per_call,derived")
    for row in rows:
        print(row.csv())
    path = write_bench_json("rerank", rows, scale=scale, extra=summary)
    print(f"wrote {path}")
