"""Public jit'd wrapper for the tiled distance kernel.

Pads inputs to tile multiples (queries with zero rows, corpus with rows
whose distance is forced to +inf by the caller via slicing), picks VMEM-
fitting tile sizes, and slices the result back.
"""

from __future__ import annotations

import jax.numpy as jnp

from repro.kernels import interpret_mode
from repro.kernels.distance.distance import distance_matrix_pallas


def _pad_to(a: jnp.ndarray, axis: int, multiple: int) -> jnp.ndarray:
    size = a.shape[axis]
    pad = (-size) % multiple
    if pad == 0:
        return a
    widths = [(0, 0)] * a.ndim
    widths[axis] = (0, pad)
    return jnp.pad(a, widths)


def pick_tiles(nq: int, n: int, d: int,
               vmem_budget: int = 8 * 1024 * 1024):
    """Pick (bq, bn, bd) multiples of 128(8) that fit the VMEM budget.

    Working set per grid step ~ 4B * (bq*bd + bn*bd + 2*bq*bn).
    """
    bq = min(128, max(8, nq))
    bd = 128 if d >= 128 else max(8, d)
    bn = 512
    while 4 * (bq * bd + bn * bd + 2 * bq * bn) > vmem_budget and bn > 128:
        bn //= 2
    return bq, bn, bd


def distance_matrix(Q, X, *, mode: str = "l2sq",
                    interpret: bool | None = None) -> jnp.ndarray:
    """D[nq, n] distances; mode in {"l2sq", "ip", "cos"}."""
    interpret = interpret_mode() if interpret is None else interpret
    nq, d = Q.shape
    n = X.shape[0]
    bq, bn, bd = pick_tiles(nq, n, d)
    Qp = _pad_to(_pad_to(jnp.asarray(Q, jnp.float32), 0, bq), 1, bd)
    Xp = _pad_to(_pad_to(jnp.asarray(X, jnp.float32), 0, bn), 1, bd)
    qsq = jnp.sum(Qp * Qp, axis=1, keepdims=True)
    xsq = jnp.sum(Xp * Xp, axis=1)[None, :]
    out = distance_matrix_pallas(Qp, Xp, qsq, xsq, mode=mode, bq=bq, bn=bn,
                                 bd=bd, interpret=interpret)
    return out[:nq, :n]
