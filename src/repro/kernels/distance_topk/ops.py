"""Public wrappers for the streaming fused distance+top-k kernel.

``stream_topk``      one kernel launch: queries/corpus padded to tiles,
                     corpus sentinels masked in-kernel via the xsq penalty
                     row, exact (dists, ids) out.
``stream_topk_batched``  query-block streaming driver: millions of queries
                     in fixed memory — each block is one kernel launch, so
                     peak HBM is O(X + qblock * (d + k)) regardless of nq.
"""

from __future__ import annotations

import jax.numpy as jnp
import numpy as np

from repro.kernels import interpret_mode
from repro.kernels.distance_topk.distance_topk import stream_topk_pallas

_METRIC_TO_MODE = {"euclidean": "l2sq", "angular": "cos", "ip": "ip",
                   "l2sq": "l2sq", "cos": "cos"}


def _round8(x: int) -> int:
    return -(-x // 8) * 8


def pick_tiles(nq: int, n: int, d: int, k: int,
               vmem_budget: int = 8 * 1024 * 1024):
    """(bq, bn, bd) aligned to the native 8-sublane granularity (bn to the
    full 128 lanes) that fit the VMEM budget; inputs are padded up to tile
    multiples by the wrapper.

    Working set per grid step ~ 4B * (bq*bd + bn*bd + bq*bn cross scratch
    + bq*(bn + 3k) merge state).
    """
    bq = min(128, _round8(max(8, nq)))
    bd = 128 if d >= 128 else _round8(max(8, d))
    bn = 1024

    def vmem(bn):
        return 4 * (bq * bd + bn * bd + 2 * bq * bn + 3 * bq * k)

    while vmem(bn) > vmem_budget and bn > 128:
        bn //= 2
    return bq, bn, bd


def _pad_to(a, axis, multiple):
    pad = (-a.shape[axis]) % multiple
    if pad == 0:
        return a
    widths = [(0, 0)] * a.ndim
    widths[axis] = (0, pad)
    return jnp.pad(a, widths)


def _resolve_tiles(nq, n, d, k, bq, bn, bd):
    abq, abn, abd = pick_tiles(nq, n, d, k)
    bq, bn, bd = bq or abq, bn or abn, bd or abd
    bq = min(bq, _round8(max(8, nq)))
    bn = min(bn, max(128, -(-n // 128) * 128))
    bd = min(bd, _round8(max(8, d)))
    return bq, bn, bd


def _prep_corpus(X, mode: str, bn: int, bd: int):
    """Pad X to tiles and build the xsq operand (squared norms for l2sq, a
    0/+inf penalty row otherwise; +inf on padded rows in every mode)."""
    n = X.shape[0]
    Xp = _pad_to(_pad_to(jnp.asarray(X, jnp.float32), 0, bn), 1, bd)
    if mode == "l2sq":
        xsq = jnp.sum(Xp * Xp, axis=1)[None, :]
    else:
        xsq = jnp.zeros((1, Xp.shape[0]), jnp.float32)
    if Xp.shape[0] != n:
        # sentinel penalty: padded rows always lose, in every mode
        mask = jnp.arange(Xp.shape[0]) >= n
        xsq = jnp.where(mask[None, :], jnp.inf, xsq)
    return Xp, xsq


def _prep_queries(Q, mode: str, bq: int, bd: int):
    Qp = _pad_to(_pad_to(jnp.asarray(Q, jnp.float32), 0, bq), 1, bd)
    if mode == "l2sq":
        qsq = jnp.sum(Qp * Qp, axis=1, keepdims=True)
    else:
        qsq = jnp.zeros((Qp.shape[0], 1), jnp.float32)
    return Qp, qsq


def stream_topk(Q, X, *, k: int, metric: str = "euclidean",
                row_ids=None, valid=None,
                bq: int | None = None, bn: int | None = None,
                bd: int | None = None, interpret: bool | None = None):
    """(dists [nq,k], ids [nq,k]) of the k nearest corpus rows per query.

    ``metric="angular"`` expects pre-normalised inputs (the index layer
    normalises at fit time).  Exact in every mode: padded corpus rows carry
    a +inf penalty through the kernel's xsq operand and can never win.

    ``valid`` (optional [n] bool) masks corpus rows through the same
    penalty channel — a sharded index's pad rows ride in here without any
    kernel change.  ``row_ids`` (optional [n] int32) remaps the returned
    row indices to global ids (-1 for empty / masked-out slots).
    """
    interpret = interpret_mode() if interpret is None else interpret
    mode = _METRIC_TO_MODE[metric]
    if k < 1:
        raise ValueError(f"k must be >= 1, got {k}")
    nq, d = Q.shape
    n = X.shape[0]
    k = min(k, n)
    bq, bn, bd = _resolve_tiles(nq, n, d, k, bq, bn, bd)
    Qp, qsq = _prep_queries(Q, mode, bq, bd)
    Xp, xsq = _prep_corpus(X, mode, bn, bd)
    if valid is not None:
        keep = jnp.zeros(Xp.shape[0], bool).at[:n].set(
            jnp.asarray(valid, bool))
        xsq = jnp.where(keep[None, :], xsq, jnp.inf)
    vals, idx = stream_topk_pallas(Qp, Xp, qsq, xsq, mode=mode, k=k,
                                   bq=bq, bn=bn, bd=bd, interpret=interpret)
    vals, idx = vals[:nq], idx[:nq]
    if row_ids is not None:
        alive = jnp.isfinite(vals)
        gl = jnp.asarray(row_ids, jnp.int32)[jnp.clip(idx, 0, n - 1)]
        idx = jnp.where(alive, gl, -1)
    return vals, idx


def stream_topk_batched(Q, X, *, k: int, metric: str = "euclidean",
                        query_block: int = 4096,
                        interpret: bool | None = None,
                        materialize: bool = True):
    """Query-streaming mode: process Q in fixed-size blocks so arbitrarily
    many queries run in constant device memory (beyond the inherent
    O(nq * k) result).  The corpus is padded and its norm/sentinel operand
    built ONCE, outside the block loop; the final partial block is padded
    up to ``query_block`` to keep a single compiled kernel shape.

    ``materialize=False`` returns device arrays without a host sync, so
    index-layer callers can keep the host transfer off the benchmark clock
    (paper §3.5)."""
    interpret = interpret_mode() if interpret is None else interpret
    mode = _METRIC_TO_MODE[metric]
    if k < 1:
        raise ValueError(f"k must be >= 1, got {k}")
    Q = np.asarray(Q)
    nq, d = Q.shape
    n = X.shape[0]
    k = min(k, n)
    query_block = max(1, min(query_block, nq))
    bq, bn, bd = _resolve_tiles(query_block, n, d, k, None, None, None)
    Xp, xsq = _prep_corpus(X, mode, bn, bd)
    vals_out, ids_out = [], []
    for s in range(0, nq, query_block):
        blk = Q[s:s + query_block]
        pad = query_block - blk.shape[0]
        if pad:
            blk = np.concatenate(
                [blk, np.zeros((pad,) + blk.shape[1:], blk.dtype)])
        Qp, qsq = _prep_queries(blk, mode, bq, bd)
        v, i = stream_topk_pallas(Qp, Xp, qsq, xsq, mode=mode, k=k,
                                  bq=bq, bn=bn, bd=bd, interpret=interpret)
        if materialize:
            vals_out.append(np.asarray(v[:query_block - pad]))
            ids_out.append(np.asarray(i[:query_block - pad]))
        else:
            vals_out.append(v[:query_block - pad])
            ids_out.append(i[:query_block - pad])
    if materialize:
        return np.concatenate(vals_out), np.concatenate(ids_out)
    return jnp.concatenate(vals_out), jnp.concatenate(ids_out)
