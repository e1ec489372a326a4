"""In-kernel top-k selects shared by the Pallas kernels.

Both are k rounds of pure elementwise/min reductions over a [bq, m]
candidate tile (no sort, top_k or cumsum primitive), so they lower through
Mosaic and run on the VPU inside a kernel.  This module imports only jax,
so any kernel can use it without pulling in :mod:`repro.ann`.

``merge_topk_rounds``         positional: each candidate slot is one
                              entry; a repeated id can be emitted twice.
``merge_topk_unique_rounds``  unique by id: bit-identical to the canonical
                              ``repro.ann.topk.topk_unique`` select.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

NEG_ONE = -1
_I32_MAX = 2**31 - 1


def merge_topk_rounds(cand_d, cand_i, k: int):
    """The k smallest (dist, id) pairs per row from [bq, m] candidates.

    Returns ([bq, k] dists, [bq, k] ids), ascending, id -1 where fewer than
    k finite candidates exist.  Distance ties break toward the earlier
    candidate position.
    """
    bq, _ = cand_d.shape
    col = jax.lax.broadcasted_iota(jnp.int32, (bq, k), 1)
    pos = jax.lax.broadcasted_iota(jnp.int32, cand_d.shape, 1)
    out_d = jnp.full((bq, k), jnp.inf, jnp.float32)
    out_i = jnp.full((bq, k), NEG_ONE, jnp.int32)

    def round_fn(t, state):
        cand_d, out_d, out_i = state
        mval = jnp.min(cand_d, axis=1, keepdims=True)          # [bq, 1]
        # first position holding the minimum: a min over positions, since
        # Mosaic has no cumsum to find it with
        mpos = jnp.min(jnp.where(cand_d == mval, pos, _I32_MAX), axis=1,
                       keepdims=True)
        first = pos == mpos
        midx = jnp.sum(jnp.where(first, cand_i, 0), axis=1, keepdims=True)
        # guard: if mval is inf there is no valid candidate left
        alive = jnp.isfinite(mval)
        midx = jnp.where(alive, midx, NEG_ONE)
        write = col == t
        out_d = jnp.where(write, mval, out_d)
        out_i = jnp.where(write, midx, out_i)
        cand_d = jnp.where(first, jnp.inf, cand_d)
        return cand_d, out_d, out_i

    _, out_d, out_i = jax.lax.fori_loop(0, k, round_fn,
                                        (cand_d, out_d, out_i))
    return out_d, out_i


def merge_topk_unique_rounds(cand_d, cand_i, k: int):
    """k smallest (dist, id) pairs per row with duplicate ids removed.

    Bit-identical to ``topk_unique(cand_d, cand_i, k)``: both order the
    distinct-id candidate set by (dist, id) ascending — dedupe keeps each
    id's smallest distance, distance ties break toward the smaller id, and
    rows with fewer than k finite distinct ids pad with (+inf, -1).

    Invalid candidates must carry (+inf, -1) — the rerank wrappers' penalty
    masking guarantees it.
    """
    bq, _ = cand_d.shape
    col = jax.lax.broadcasted_iota(jnp.int32, (bq, k), 1)
    out_d = jnp.full((bq, k), jnp.inf, jnp.float32)
    out_i = jnp.full((bq, k), NEG_ONE, jnp.int32)

    def round_fn(t, state):
        cand_d, out_d, out_i = state
        mval = jnp.min(cand_d, axis=1, keepdims=True)          # [bq, 1]
        eq = cand_d == mval
        # among distance ties, the smallest id wins (topk_unique's order)
        midx = jnp.min(jnp.where(eq, cand_i, _I32_MAX), axis=1,
                       keepdims=True)
        alive = jnp.isfinite(mval)
        midx = jnp.where(alive, midx, NEG_ONE)
        write = col == t
        out_d = jnp.where(write, mval, out_d)
        out_i = jnp.where(write, midx, out_i)
        # retire EVERY copy of the selected id, not just the winning one —
        # this is what collapses duplicates across block boundaries
        cand_d = jnp.where(alive & (cand_i == midx), jnp.inf, cand_d)
        return cand_d, out_d, out_i

    _, out_d, out_i = jax.lax.fori_loop(0, k, round_fn,
                                        (cand_d, out_d, out_i))
    return out_d, out_i
