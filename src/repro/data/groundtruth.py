"""Exact ground-truth computation (paper §3.2: dataset files ship the true
k=100 neighbors + distances).

Blocked brute force on device: query blocks x corpus blocks with a running
top-k merge, so GT for n=10^6-scale corpora never materialises the full
distance matrix.  The corpus goes to the device once; every distance is
computed at full fp32 precision (``repro.ann.distances`` runs its matmuls
at ``Precision.HIGHEST``: a TPU's default precision would make one bf16
pass and misorder near neighbours).
"""

from __future__ import annotations

import numpy as np

import jax
import jax.numpy as jnp

from repro.ann import distances as D


def exact_knn(
    train: np.ndarray,
    test: np.ndarray,
    k: int,
    metric: str,
    query_block: int = 512,
    corpus_block: int = 65536,
) -> tuple[np.ndarray, np.ndarray]:
    """Return (neighbors [nq,k], distances [nq,k]) exactly, blocked."""
    n = train.shape[0]
    k = min(k, n)
    nq = test.shape[0]
    all_idx = np.empty((nq, k), np.int64)
    all_dst = np.empty((nq, k), np.float32)

    corpus_blocks = [
        (s, min(s + corpus_block, n)) for s in range(0, n, corpus_block)
    ]

    @jax.jit
    def block_topk(q, x):
        d = D.distance_matrix(q, x, metric)  # [bq, bn]
        kk = min(k, x.shape[0])
        neg, idx = jax.lax.top_k(-d, kk)
        return -neg, idx

    Xd = [jnp.asarray(train[s:e]) for (s, e) in corpus_blocks]
    for qs in range(0, nq, query_block):
        qe = min(qs + query_block, nq)
        q = jnp.asarray(test[qs:qe])
        best_d = np.full((qe - qs, k), np.inf, np.float32)
        best_i = np.full((qe - qs, k), -1, np.int64)
        for (s, e), x in zip(corpus_blocks, Xd):
            d, i = block_topk(q, x)
            d = np.asarray(d, np.float32)
            i = np.asarray(i, np.int64) + s
            # merge running top-k with this block's top-k
            cd = np.concatenate([best_d, d], axis=1)
            ci = np.concatenate([best_i, i], axis=1)
            order = np.argsort(cd, axis=1, kind="stable")[:, :k]
            best_d = np.take_along_axis(cd, order, axis=1)
            best_i = np.take_along_axis(ci, order, axis=1)
        all_idx[qs:qe] = best_i
        all_dst[qs:qe] = best_d
    return all_idx, all_dst
