"""Public wrappers for the fused candidate-rerank primitive.

``rerank_topk``   the entry point for a window of arbitrary row ids (LSH
                  schemes, RPForest, the Hamming indexes, quantized IVF's
                  ADC survivors, IVF over tombstones or remapped ids): a
                  [b, C] window of candidate row ids is reduced to the k
                  best distinct ids without ever materializing the
                  [b, C, d] gathered tensor.  Two device paths with
                  identical select semantics:

                  * **XLA streaming fold** (default) — the candidate axis is
                    scanned in autotuned blocks folded through the canonical
                    unique top-k (``repro.ann.topk.chunked_topk(unique=
                    True)``), peak memory O(b * (block + k)) id/dist state
                    plus one [b, block, d] gathered chunk;
                  * **Pallas kernel** (``use_kernel=True``) — the same fold
                    with the gather DMA'd row-by-row into VMEM scratch, so
                    the gathered rows never round-trip through HBM at all.
                    Compiled on a TPU, interpreted on a CPU backend; the
                    XLA fold is the reference it is gated against, and is
                    taken instead only for an empty window.

``rerank_lists``  the entry point for runs of a cluster-major corpus
                  (IVF's probed lists): per (query, probe) a start and a
                  size, and the kernel copies each run as chunked slices,
                  so a list costs a few DMAs instead of one per row and
                  the padding past its end is never read.

Both paths return exactly what ``topk_unique`` over the materialized gather
returns (``ref.rerank_topk_ref``): masked (-1) candidates never win,
duplicate ids — including duplicates spanning block boundaries — collapse
to their best distance, and rows with fewer than k distinct finite
candidates pad with (+inf, -1).  Parity granularity: neighbor *ids* are
bit-identical across materialized / fold / kernel in every mode (the
canonical-select contract the traced-knob sweep machinery of PRs 3-4
rests on), and hamming distances are bit-identical too (integer
popcounts); float distances agree only to the ulp across paths — blocking
changes the dot shapes XLA vectorizes over, which can reassociate the
contraction.
"""

from __future__ import annotations

import collections
from typing import Optional

import jax
import jax.numpy as jnp

from repro.kernels import interpret_mode
from repro.kernels.rerank_topk.rerank_topk import (rerank_lists_pallas,
                                                   rerank_topk_pallas)

_FOLD_BUDGET = 32 << 20     # XLA fold: gathered-chunk working set (HBM-ish)
_KERNEL_BUDGET = 4 << 20    # kernel: [bq, bc, d] VMEM gather scratch
_LIST_BUDGET = 8 << 20      # list kernel: two [bq, bc, d] row chunks

#: trace-time count of the rerank path each trace took: ``"rows"`` for a
#: window of row ids (:func:`rerank_topk`), ``"lists"`` for runs of a
#: cluster-major corpus (:func:`rerank_lists`).  Tests reset and read it.
PATH_COUNTS: "collections.Counter[str]" = collections.Counter()


def pick_rerank_block(b: int, C: int, d: int, k: int, *,
                      itemsize: int = 4,
                      budget: int = _FOLD_BUDGET) -> int:
    """Autotuned candidate-block size for the streaming fold.

    Largest power-of-two block (128..4096) whose per-fold working set —
    the [b, block, d] gathered rows plus the [b, block + 3k] merge state —
    fits ``budget``.  Small windows collapse to a single one-shot fold
    (block >= C), which is exactly the materialized path minus the perils,
    so the fold is never slower than one-shot on shapes where one-shot was
    fine.
    """
    block = 4096

    def working_set(blk: int) -> int:
        return itemsize * max(1, b) * (blk * (d + 2) + 3 * k)

    while block > 128 and block >= 2 * max(1, C):
        block //= 2                 # window fits a smaller block: one-shot
    while block > 128 and working_set(block) > budget:
        block //= 2
    return block


def _pick_kernel_block(bq: int, C: int, d: int, k: int,
                       block: Optional[int]) -> int:
    bc = block if block else pick_rerank_block(
        bq, C, d, k, budget=_KERNEL_BUDGET)
    return max(8, min(int(bc), 1024, _ceil_to(C, 8)))


def _pick_list_block(bq: int, d: int) -> int:
    """Rows of a list-kernel chunk: the largest power of two (128..1024)
    whose two [bq, bc, d] f32 chunk buffers fit ``_LIST_BUDGET``.  Longer
    chunks mean fewer steps of the select per list: 1024 rows took 2.3 ms
    a 64-query SIFT-1M batch against 2.8 at 512 and 3.8 at 256 (TPU v5e,
    8 probes)."""
    bc = 1024
    while bc > 128 and 2 * bq * bc * d * 4 > _LIST_BUDGET:
        bc //= 2
    return bc


def _ceil_to(x: int, m: int) -> int:
    return -(-x // m) * m


def _pad_cols(a, width: int, value):
    pad = width - a.shape[1]
    if pad == 0:
        return a
    return jnp.pad(a, ((0, 0), (0, pad)), constant_values=value)


def _pad_rows(a, rows: int, value):
    pad = rows - a.shape[0]
    if pad == 0:
        return a
    widths = ((0, pad),) + ((0, 0),) * (a.ndim - 1)
    return jnp.pad(a, widths, constant_values=value)


def _chunk_distances(Q, X, qsq, xsq, cand, bad, row_ids, metric: str):
    """Exact (dist, id) for one candidate chunk — the distance formulation
    the XLA fold and the kernel wrapper's penalty operand share
    (``ref.rerank_topk_ref`` mirrors it independently, as the kernels
    convention requires of an oracle; keep the expression trees in sync or
    the bitwise-id parity gates will catch the drift)."""
    safe = jnp.maximum(cand, 0)
    x = X[safe]                                           # [b, c, d]
    if metric == "hamming":
        xor = jax.lax.bitwise_xor(x, Q[:, None, :].astype(jnp.uint32))
        pen = jnp.where(bad, jnp.inf, 0.0).astype(jnp.float32)
        d = jnp.sum(jax.lax.population_count(xor),
                    axis=-1).astype(jnp.float32) + pen
    elif metric == "euclidean":
        cross = jnp.einsum("bcd,bd->bc", x, Q,
                           precision=jax.lax.Precision.HIGHEST)
        pen = jnp.where(bad, jnp.inf, xsq[safe]).astype(jnp.float32)
        d = (qsq - 2.0 * cross) + pen
    else:                                                 # angular
        pen = jnp.where(bad, jnp.inf, 0.0).astype(jnp.float32)
        d = (1.0 - jnp.einsum("bcd,bd->bc", x, Q,
                           precision=jax.lax.Precision.HIGHEST)) + pen
    ids = cand if row_ids is None else row_ids[safe].astype(jnp.int32)
    return d, jnp.where(bad, -1, ids)


def rerank_topk(Q, X, cand, *, k: int, metric: str, xsq=None, row_ids=None,
                valid=None, block: Optional[int] = None,
                use_kernel: bool = False,
                interpret: Optional[bool] = None):
    """(dists [b, kk], ids [b, kk]) of the k best DISTINCT candidates.

    ``cand [b, C]``  int32 row indices into ``X``; -1 marks a masked slot.
    ``valid``        optional extra [b, C] bool mask — this is where the
                     traced-knob validity windows (``n_probes`` / ``scan``
                     / ``tables`` / ``trees``) flow in.
    ``row_ids``      optional [n] row -> output-id map (IVF's cluster-major
                     corpus); identity when omitted (LSH/forest windows
                     carry corpus ids directly).
    ``xsq``          cached [n] squared norms (required for euclidean —
                     every euclidean build stores it).
    ``block``        candidate-block override; autotuned from the shapes
                     when None (``pick_rerank_block``).
    ``use_kernel``   route through the fused Pallas kernel (the
                     ``rerank_kernel`` build flag); the XLA fold answers
                     only the shapes the kernel cannot take (empty
                     windows).
    ``interpret``    Pallas interpret mode; None decides from the backend
                     (:func:`repro.kernels.interpret_mode`).

    kk = min(k, C); rows with fewer than kk distinct finite candidates pad
    with (+inf, -1), exactly like ``topk_unique``.
    """
    # deferred: repro.ann.lsh/ivf/hamming import this module, and importing
    # repro.ann.topk initializes the repro.ann package (import cycle)
    from repro.ann.topk import chunked_topk

    if metric == "euclidean" and xsq is None:
        raise ValueError("euclidean rerank needs the cached xsq table "
                         "(build-time jnp.sum(X**2, axis=1))")
    interpret = interpret_mode() if interpret is None else interpret
    PATH_COUNTS["rows"] += 1
    cand = jnp.asarray(cand, jnp.int32)
    b, C = cand.shape
    kk = min(int(k), C)
    if C == 0:                         # empty window: nothing to rerank
        return (jnp.full((b, 0), jnp.inf, jnp.float32),
                jnp.full((b, 0), -1, jnp.int32))
    Q = jnp.asarray(Q)
    if metric == "hamming":
        Q = Q.astype(jnp.uint32)
        qsq = None
    else:
        Q = Q.astype(jnp.float32)
        qsq = jnp.sum(Q * Q, axis=1, keepdims=True) \
            if metric == "euclidean" else None
    bad = cand < 0
    if valid is not None:
        bad = bad | ~valid

    if use_kernel and C > 0 and b > 0:
        return _rerank_kernel_path(Q, X, qsq, xsq, cand, bad, row_ids,
                                   metric, kk, block, interpret)

    blk = block if block else pick_rerank_block(b, C, Q.shape[1], kk)

    def chunk(s, size):
        return _chunk_distances(Q, X, qsq, xsq, cand[:, s:s + size],
                                bad[:, s:s + size], row_ids, metric)

    return chunked_topk(C, kk, blk, chunk, unique=True)


def _rerank_kernel_path(Q, X, qsq, xsq, cand, bad, row_ids, metric: str,
                        kk: int, block: Optional[int], interpret: bool):
    """Pad shapes to kernel tiles and pre-fold masking into the penalty
    operand (+inf sentinels, the same treatment as ``distance_topk``)."""
    b, C = cand.shape
    bq = 8
    bc = _pick_kernel_block(bq, C, Q.shape[1], kk, block)
    Cp = _ceil_to(C, bc)
    bp = _ceil_to(b, bq)

    safe = jnp.maximum(cand, 0)
    ids = cand if row_ids is None else row_ids[safe].astype(jnp.int32)
    ids = jnp.where(bad, -1, ids)
    if metric == "euclidean":
        pen = jnp.where(bad, jnp.inf, xsq[safe]).astype(jnp.float32)
    else:
        pen = jnp.where(bad, jnp.inf, 0.0).astype(jnp.float32)
    if qsq is None:
        qsq = jnp.zeros((b, 1), jnp.float32)

    mode = {"euclidean": "l2sq", "angular": "cos", "hamming": "ham"}[metric]
    vals, idx = rerank_topk_pallas(
        _pad_rows(_pad_cols(safe, Cp, 0), bp, 0),
        _pad_rows(Q, bp, 0),
        _pad_rows(qsq, bp, 0.0),
        _pad_rows(_pad_cols(ids, Cp, -1), bp, -1),
        _pad_rows(_pad_cols(pen, Cp, jnp.inf), bp, jnp.inf),
        X, mode=mode, k=kk, bq=bq, bc=bc, interpret=interpret)
    return vals[:b], idx[:b]


def rerank_lists(Q, X, starts, sizes, *, k: int, metric: str, ids, xsq=None,
                 n_probes=None, scan=None, window: int,
                 block: Optional[int] = None,
                 interpret: Optional[bool] = None):
    """(dists [b, kk], ids [b, kk]) over runs of a cluster-major corpus.

    Query i's candidates are the rows ``[starts[i, p], starts[i, p] +
    sizes[i, p])`` of ``X`` for each probe p, cut to the first ``window``
    rows (and to ``scan``, a traced budget, when given); probes at or past
    the traced ``n_probes`` hold none.  ``ids [n]`` gives each row's output
    id, ``xsq [n]`` its cached squared norm (euclidean).  The answer is
    the row kernel's over the same window (``rerank_topk`` with
    ``cand = starts + arange(window)``, the rest masked): kk = min(k, P *
    window), ids bit-identical, rows with too few candidates padded with
    (+inf, -1).

    The kernel reads each run in 128-aligned chunks of ``block`` rows
    (1024 at d <= 128, fewer at larger d): per (query, chunk) a few
    power-of-two slices of the run's rows and one copy each of the
    chunk's norms and ids, the next chunk's copies in flight while this
    one is scored.  Chunks past a run's end or the scan budget, and dead
    probes, are never read.
    """
    if metric not in ("euclidean", "angular"):
        raise ValueError(f"list rerank scores euclidean or angular, "
                         f"not {metric!r}")
    if metric == "euclidean" and xsq is None:
        raise ValueError("euclidean rerank needs the cached xsq table "
                         "(build-time jnp.sum(X**2, axis=1))")
    interpret = interpret_mode() if interpret is None else interpret
    PATH_COUNTS["lists"] += 1
    b, P = starts.shape
    n = X.shape[0]
    kk = min(int(k), P * window)
    bq = 8
    bc = block if block else _pick_list_block(bq, X.shape[1])
    bc = min(_ceil_to(bc, 128), _ceil_to(window + 127, 128))
    n_chunks = -(-(window + 127) // bc)

    starts = jnp.asarray(starts, jnp.int32)
    lens = jnp.minimum(jnp.asarray(sizes, jnp.int32), window)
    if scan is not None:
        lens = jnp.minimum(lens, jnp.maximum(scan, 1))
    if n_probes is not None:
        lens = jnp.where(jnp.arange(P) < n_probes, lens, 0)
    bp = _ceil_to(b, bq)
    starts = _pad_rows(starts, bp, 0)
    lens = _pad_rows(lens, bp, 0)
    # the plan of each query tile: how many (probe, chunk) items hold a
    # live row for any of its queries, then those items, p * n_chunks + c
    chunks = jnp.where(lens > 0, -(-(starts % 128 + lens) // bc), 0)
    chunks = chunks.reshape(bp // bq, bq, P).max(axis=1)     # [tiles, P]
    live = (jnp.arange(n_chunks)[None, None, :] < chunks[..., None])
    live = live.reshape(bp // bq, P * n_chunks)
    items = jnp.argsort(~live, axis=1, stable=True).astype(jnp.int32)
    plan = jnp.concatenate(
        [jnp.sum(live, axis=1, keepdims=True, dtype=jnp.int32), items], 1)
    plan = jnp.repeat(plan, bq, axis=0)                      # [bp, 1 + T]

    # norms and ids as rows of 128 lanes, padded so a chunk's lane rows
    # never run past the end
    rows = -(-n // 128) + bc // 128

    def lane_rows(a, dtype):
        a = jnp.asarray(a, dtype)
        return jnp.pad(a, (0, rows * 128 - n)).reshape(rows, 128)

    Q = jnp.asarray(Q, jnp.float32)
    if metric == "euclidean":
        mode = "l2sq"
        qsq = jnp.sum(Q * Q, axis=1, keepdims=True)
        xsq = lane_rows(xsq, jnp.float32)
    else:                               # cos mode reads no norms
        mode = "cos"
        qsq = jnp.zeros((b, 1), jnp.float32)
        xsq = jnp.zeros((8, 128), jnp.float32)
    vals, idx = rerank_lists_pallas(
        starts, lens, plan, _pad_rows(Q, bp, 0), _pad_rows(qsq, bp, 0.0),
        X, xsq, lane_rows(ids, jnp.int32), mode=mode, k=kk, bq=bq, bc=bc,
        n_chunks=n_chunks, interpret=interpret)
    return vals[:b], idx[:b]
