"""Embedding-bag gather-reduce kernel (recsys hot path; DESIGN.md §4).

JAX has no native EmbeddingBag; the jnp implementation is
``table[idx]`` (gather) + ``segment_sum``, which materialises the gathered
[N_lookups, D] intermediate in HBM.  This kernel streams table rows through
VMEM one lookup at a time and accumulates directly into the output bag tile
— the TPU analogue of FBGEMM's TBE kernel.

Layout contract (established by the recsys input pipeline): lookups are
sorted by bag id, flattened across the batch:

    indices [N]  int32   row into the table
    bags    [N]  int32   output row (non-decreasing)
    weights [N]  f32     per-sample weights (1.0 for plain sum)

Grid: one step per lookup.  BlockSpec index_maps are *data-dependent* via
scalar prefetch (PrefetchScalarGridSpec): the table block fetched at step i
is row ``indices[i]``; the output block is row ``bags[i]``.  Because bags
are sorted, output-block revisits are consecutive, so the accumulation is a
clean read-modify-write while the tile stays resident in VMEM.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _embedbag_kernel(idx_ref, bag_ref, w_ref, table_ref, out_ref):
    i = pl.program_id(0)
    is_first = jnp.where(
        i == 0, True, bag_ref[jnp.maximum(i - 1, 0)] != bag_ref[i])
    row = table_ref[...] * w_ref[i]

    @pl.when(is_first)
    def _init():
        out_ref[...] = row

    @pl.when(jnp.logical_not(is_first))
    def _acc():
        out_ref[...] += row


@functools.partial(jax.jit, static_argnames=("n_bags", "interpret"))
def embedding_bag_pallas(
    indices: jnp.ndarray,     # [N] int32, sorted by bag
    bags: jnp.ndarray,        # [N] int32 non-decreasing, covers 0..n_bags-1
    weights: jnp.ndarray,     # [N] f32
    table: jnp.ndarray,       # [V, D]
    *,
    n_bags: int,
    interpret: bool,
) -> jnp.ndarray:
    N = indices.shape[0]
    V, Dm = table.shape
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=3,
        grid=(N,),
        in_specs=[
            pl.BlockSpec((1, Dm), lambda i, idx, bag, w: (idx[i], 0)),
        ],
        out_specs=pl.BlockSpec((1, Dm), lambda i, idx, bag, w: (bag[i], 0)),
    )
    return pl.pallas_call(
        _embedbag_kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((n_bags, Dm), table.dtype),
        interpret=interpret,
    )(indices, bags, weights, table)
