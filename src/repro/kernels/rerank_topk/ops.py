"""Public wrappers for the fused candidate-rerank primitive.

``rerank_topk``   ONE entry point for every candidate-rerank call site in
                  the suite (LSH schemes, RPForest, IVF, the Hamming
                  indexes): a [b, C] window of candidate row ids is reduced
                  to the k best distinct ids without ever materializing the
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

from typing import Optional

import jax
import jax.numpy as jnp

from repro.kernels import interpret_mode
from repro.kernels.rerank_topk.rerank_topk import rerank_topk_pallas

_FOLD_BUDGET = 32 << 20     # XLA fold: gathered-chunk working set (HBM-ish)
_KERNEL_BUDGET = 4 << 20    # kernel: [bq, bc, d] VMEM gather scratch


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
