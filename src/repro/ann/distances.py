"""Distance functions shared by algorithms, ground truth and the results
layer.  Conventions follow ann-benchmarks:

    euclidean : l2 norm  ||q - x||
    angular   : 1 - cos(q, x)            (in [0, 2])
    hamming   : popcount(q XOR x)        (packed uint32 words)

``distance_matrix`` is the jnp building block (used inside jitted code);
``pairwise_rows`` is the numpy-facing re-computation entry used by the
framework after each run (paper §3.6: "the experiment loop independently
re-computes distance values after the query has otherwise finished").
"""

from __future__ import annotations

import numpy as np

import jax
import jax.numpy as jnp

METRICS = ("euclidean", "angular", "hamming")


def _cross(Q: jnp.ndarray, X: jnp.ndarray) -> jnp.ndarray:
    """Q @ X.T in full fp32: at default precision a TPU makes one bf16 pass
    over f32 operands, which reorders near neighbours."""
    return jnp.matmul(Q, X.T, precision=jax.lax.Precision.HIGHEST)


def sq_l2_matrix(Q: jnp.ndarray, X: jnp.ndarray,
                 x_sqnorm: jnp.ndarray | None = None) -> jnp.ndarray:
    """Squared L2 distances via the MXU-friendly expansion
    ||q||^2 - 2 q.x + ||x||^2, fp32 products and accumulation."""
    Q = Q.astype(jnp.float32)
    X = X.astype(jnp.float32)
    qn = jnp.sum(Q * Q, axis=1, keepdims=True)
    xn = jnp.sum(X * X, axis=1)[None, :] if x_sqnorm is None else x_sqnorm[None, :]
    return jnp.maximum(qn - 2.0 * _cross(Q, X) + xn, 0.0)


def angular_matrix(Q: jnp.ndarray, X: jnp.ndarray,
                   normalized: bool = False) -> jnp.ndarray:
    Q = Q.astype(jnp.float32)
    X = X.astype(jnp.float32)
    if not normalized:
        Q = Q / jnp.maximum(jnp.linalg.norm(Q, axis=1, keepdims=True), 1e-12)
        X = X / jnp.maximum(jnp.linalg.norm(X, axis=1, keepdims=True), 1e-12)
    return 1.0 - _cross(Q, X)


def hamming_matrix(Q: jnp.ndarray, X: jnp.ndarray) -> jnp.ndarray:
    """Popcount distances between packed uint32 codes; returns float32."""
    x = jax.lax.bitwise_xor(Q[:, None, :].astype(jnp.uint32),
                            X[None, :, :].astype(jnp.uint32))
    return jnp.sum(jax.lax.population_count(x), axis=-1).astype(jnp.float32)


def masked_rows_to(X: jnp.ndarray, q: jnp.ndarray, ids: jnp.ndarray,
                   metric: str) -> jnp.ndarray:
    """Distances from ONE query to the gathered rows ``X[ids]``; entries
    with ``ids < 0`` come back +inf (gather-safe).  Squared L2 for
    euclidean — the beam-search comparator the graph algorithms share.
    """
    x = X[jnp.maximum(ids, 0)]
    if metric == "angular":
        d = 1.0 - jnp.matmul(x, q, precision=jax.lax.Precision.HIGHEST)
    else:
        diff = x - q[None, :]
        d = jnp.sum(diff * diff, axis=-1)
    return jnp.where(ids >= 0, d, jnp.inf)


def distance_matrix(Q, X, metric: str) -> jnp.ndarray:
    if metric == "euclidean":
        return jnp.sqrt(sq_l2_matrix(Q, X))
    if metric == "angular":
        return angular_matrix(Q, X)
    if metric == "hamming":
        return hamming_matrix(Q, X)
    raise ValueError(f"unknown metric {metric!r}")


def single(q, x, metric: str) -> float:
    return float(distance_matrix(jnp.asarray(q)[None, :],
                                 jnp.asarray(x)[None, :], metric)[0, 0])


def pairwise_rows(test: np.ndarray, train: np.ndarray,
                  neighbors: np.ndarray, metric: str) -> np.ndarray:
    """distances[i, j] = dist(test[i], train[neighbors[i, j]]); inf where
    neighbors is -1 padding.  Blocked to bound memory."""
    nq, k = neighbors.shape
    out = np.full((nq, k), np.inf, np.float32)
    block = max(1, 4_000_000 // max(k * train.shape[1], 1))
    fn = jax.jit(_rows_kernel, static_argnames=("metric",))
    for s in range(0, nq, block):
        e = min(s + block, nq)
        idx = np.clip(neighbors[s:e], 0, train.shape[0] - 1)
        d = fn(jnp.asarray(test[s:e]), jnp.asarray(train), jnp.asarray(idx),
               metric=metric)
        d = np.array(d, np.float32, copy=True)
        d[neighbors[s:e] < 0] = np.inf
        out[s:e] = d
    return out


def _rows_kernel(q, train, idx, *, metric):
    cand = train[idx]                      # [b, k, d]
    if metric == "euclidean":
        diff = cand.astype(jnp.float32) - q[:, None, :].astype(jnp.float32)
        return jnp.sqrt(jnp.maximum(jnp.sum(diff * diff, axis=-1), 0.0))
    if metric == "angular":
        qn = q / jnp.maximum(jnp.linalg.norm(q, axis=1, keepdims=True), 1e-12)
        cn = cand / jnp.maximum(
            jnp.linalg.norm(cand, axis=2, keepdims=True), 1e-12)
        return 1.0 - jnp.einsum("bd,bkd->bk", qn.astype(jnp.float32),
                                cn.astype(jnp.float32),
                                precision=jax.lax.Precision.HIGHEST)
    if metric == "hamming":
        x = jax.lax.bitwise_xor(cand.astype(jnp.uint32),
                                q[:, None, :].astype(jnp.uint32))
        return jnp.sum(jax.lax.population_count(x), axis=-1).astype(jnp.float32)
    raise ValueError(metric)
