"""Pure-jnp oracle for the fused candidate rerank: materialize the whole
[b, C, d] gathered candidate tensor, compute distances, one-shot canonical
``topk_unique``.  This is both the correctness reference the tests assert
against and the memory-hungry baseline ``benchmarks/bench_rerank.py`` times
the streaming paths against.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp


def rerank_topk_ref(Q, X, cand, *, k: int, metric: str, xsq=None,
                    row_ids=None, valid=None):
    """(dists [b, kk], ids [b, kk]) over a [b, C] candidate window.

    ``cand`` holds row indices into ``X`` (-1 = masked); ``valid`` is an
    optional extra mask (traced-knob dead windows); ``row_ids`` optionally
    maps rows to output ids (IVF's cluster-major layout); ``xsq`` is the
    cached per-row squared-norm table (euclidean).  kk = min(k, C).
    """
    from repro.ann.topk import topk_unique   # deferred: import cycle

    cand = jnp.asarray(cand, jnp.int32)
    bad = cand < 0
    if valid is not None:
        bad = bad | ~valid
    safe = jnp.maximum(cand, 0)
    x = X[safe]                                          # [b, C, d]
    if metric == "hamming":
        xor = jax.lax.bitwise_xor(x, Q[:, None, :].astype(jnp.uint32))
        pen = jnp.where(bad, jnp.inf, 0.0).astype(jnp.float32)
        d = jnp.sum(jax.lax.population_count(xor),
                    axis=-1).astype(jnp.float32) + pen
    elif metric == "euclidean":
        if xsq is None:
            xsq = jnp.sum(X.astype(jnp.float32) ** 2, axis=1)
        qsq = jnp.sum(Q * Q, axis=1, keepdims=True)
        cross = jnp.einsum("bcd,bd->bc", x, Q,
                           precision=jax.lax.Precision.HIGHEST)
        pen = jnp.where(bad, jnp.inf, xsq[safe]).astype(jnp.float32)
        d = (qsq - 2.0 * cross) + pen
    else:                                                # angular
        pen = jnp.where(bad, jnp.inf, 0.0).astype(jnp.float32)
        d = (1.0 - jnp.einsum("bcd,bd->bc", x, Q,
                           precision=jax.lax.Precision.HIGHEST)) + pen
    ids = cand if row_ids is None else row_ids[safe].astype(jnp.int32)
    ids = jnp.where(bad, -1, ids)
    return topk_unique(d, ids, min(k, cand.shape[1]))
