"""Public wrapper for the embedding-bag kernel.

``embedding_bag(table, indices, bags, weights, n_bags)`` — sorts lookups by
bag id if needed (the kernel's layout contract) and handles empty bags
(rows never written get zeros via a final mask).
"""

from __future__ import annotations

import jax.numpy as jnp

from repro.kernels import interpret_mode
from repro.kernels.embedbag.embedbag import embedding_bag_pallas


def embedding_bag(table, indices, bags, weights=None, *, n_bags: int,
                  assume_sorted: bool = False,
                  interpret: bool | None = None):
    interpret = interpret_mode() if interpret is None else interpret
    indices = jnp.asarray(indices, jnp.int32)
    bags = jnp.asarray(bags, jnp.int32)
    if weights is None:
        weights = jnp.ones(indices.shape, jnp.float32)
    weights = jnp.asarray(weights, jnp.float32)
    if not assume_sorted:
        order = jnp.argsort(bags, stable=True)
        indices, bags, weights = indices[order], bags[order], weights[order]
    out = embedding_bag_pallas(indices, bags, weights, table,
                               n_bags=n_bags, interpret=interpret)
    # zero rows for empty bags (never visited by the grid)
    touched = jnp.zeros((n_bags,), bool).at[bags].set(True)
    return jnp.where(touched[:, None], out, 0.0)
