"""Pure-jnp oracle for the distance-matrix kernel."""

from __future__ import annotations

import jax
import jax.numpy as jnp


def distance_matrix_ref(Q, X, *, mode: str = "l2sq") -> jnp.ndarray:
    Q = Q.astype(jnp.float32)
    X = X.astype(jnp.float32)
    cross = jnp.matmul(Q, X.T, precision=jax.lax.Precision.HIGHEST)
    if mode == "l2sq":
        qsq = jnp.sum(Q * Q, axis=1, keepdims=True)
        xsq = jnp.sum(X * X, axis=1)[None, :]
        return jnp.maximum(qsq - 2.0 * cross + xsq, 0.0)
    if mode == "ip":
        return -cross
    if mode == "cos":
        return 1.0 - cross
    raise ValueError(mode)
