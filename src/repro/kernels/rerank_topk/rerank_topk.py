"""Fused candidate-rerank kernel: gather + distance + running unique top-k.

Every candidate-generation algorithm in the suite (LSH, trees, inverted
files) funnels its query time through the same rerank hot path: a [b, C]
window of candidate row ids, gather the rows, exact distances against the
query batch, keep the k best *distinct* ids.  The XLA formulation
materializes the full [b, C, d] gathered tensor in HBM before the distance
einsum — at high probe counts that gather dominates both memory and
bandwidth (candidate verification is the dominant cost across these
families; Li et al. 2016).

This kernel fuses the whole pipeline so gathered rows never round-trip
through HBM:

  * each (query, candidate) tile brings its own [bq, bc] block of row ids
    into SMEM, and those ids drive per-row DMAs of the corpus rows into a
    [bq, bc, d] VMEM scratch tile;
  * distances are computed against the resident query tile in all three
    modes — ``l2sq`` (cached squared norms flow in through the per-candidate
    penalty operand), ``cos`` (dot), ``ham`` (XOR + popcount on packed
    uint32 words);
  * each tile folds into a running per-query (dist, id) top-k accumulator
    in VMEM scratch that is *unique by id*: duplicate candidate ids —
    including duplicates spanning candidate-block boundaries — collapse to
    their best distance, and ``-1`` (masked) ids never win.

Peak memory is O(b * (bc + k)) per query block instead of O(b * C * d);
the output is written once per query tile on the last candidate step.

Grid: (b/bq, C/bc), candidate axis sequential ("arbitrary"), query axis
parallel.  Invalidity (masked candidates, traced-knob dead windows) arrives
pre-folded into the penalty operand as +inf, the same sentinel treatment as
``distance_topk``'s xsq row.

Selection: ``repro.kernels.select.merge_topk_unique_rounds`` —
bit-identical to the canonical ``repro.ann.topk.topk_unique`` select (the
contract the traced-knob parity machinery rests on), built from VPU-only
min/mask reductions so it lowers through Mosaic.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.select import NEG_ONE, merge_topk_unique_rounds


def _rerank_kernel(cand_ref, q_ref, qsq_ref, ids_ref, pen_ref, x_hbm,
                   vals_out, idx_out, xg_ref, vals_ref, idx_ref, sem, *,
                   mode: str, k: int, bq: int, bc: int, n_c_steps: int):
    j = pl.program_id(1)                       # candidate tile

    @pl.when(j == 0)
    def _init_state():
        vals_ref[...] = jnp.full_like(vals_ref, jnp.inf)
        idx_ref[...] = jnp.full_like(idx_ref, NEG_ONE)

    # gather the candidate rows of this tile into VMEM scratch: one row DMA
    # per (query, slot) pair, row ids from this tile's SMEM block.  The
    # start()/wait() pairs are serialized; overlapping them is open work
    # (ROADMAP).
    def _gather(t, carry):
        qi = t // bc
        s = t % bc
        dma = pltpu.make_async_copy(x_hbm.at[cand_ref[qi, s]],
                                    xg_ref.at[qi, s], sem)
        dma.start()
        dma.wait()
        return carry

    jax.lax.fori_loop(0, bq * bc, _gather, 0)

    q = q_ref[...]                              # [bq, d]
    x = xg_ref[...]                             # [bq, bc, d]
    pen = pen_ref[...]                          # [bq, bc] (+inf = masked)
    if mode == "ham":
        xor = jax.lax.bitwise_xor(x, q[:, None, :])
        # Mosaic reduces signed integers only: sum the popcounts as int32
        d = jnp.sum(jax.lax.population_count(xor).astype(jnp.int32),
                    axis=-1).astype(jnp.float32) + pen
    else:
        cross = jax.lax.dot_general(
            x, q, (((2,), (1,)), ((0,), (0,))),
            precision=jax.lax.Precision.HIGHEST,
            preferred_element_type=jnp.float32)  # [bq, bc]
        if mode == "l2sq":
            # pen carries the gathered corpus squared norms (cached xsq)
            d = (qsq_ref[...] - 2.0 * cross) + pen
        else:                                    # cos
            d = (1.0 - cross) + pen

    cand_d = jnp.concatenate([vals_ref[...], d], axis=1)
    cand_i = jnp.concatenate([idx_ref[...], ids_ref[...]], axis=1)
    out_d, out_i = merge_topk_unique_rounds(cand_d, cand_i, k)
    vals_ref[...] = out_d
    idx_ref[...] = out_i

    @pl.when(j == n_c_steps - 1)
    def _flush():
        vals_out[...] = vals_ref[...]
        idx_out[...] = idx_ref[...]


@functools.partial(
    jax.jit, static_argnames=("mode", "k", "bq", "bc", "interpret"))
def rerank_topk_pallas(
    cand_rows: jnp.ndarray,        # [b, C] int32 gather rows (clamped >= 0)
    Q: jnp.ndarray,                # [b, d] f32 (uint32 words for ham)
    Qsq: jnp.ndarray,              # [b, 1] f32 squared norms (l2sq)
    cand_ids: jnp.ndarray,         # [b, C] int32 output ids, -1 masked
    pen: jnp.ndarray,              # [b, C] f32 xsq / 0, +inf where masked
    X: jnp.ndarray,                # [n, d] corpus (stays in HBM, DMA'd)
    *,
    mode: str,
    k: int,
    bq: int = 8,
    bc: int = 256,
    interpret: bool,
):
    b, d = Q.shape
    C = cand_rows.shape[1]
    assert b % bq == 0 and C % bc == 0, (b, C, bq, bc)
    n_c_steps = C // bc
    grid = (b // bq, n_c_steps)
    xg_dtype = X.dtype if mode == "ham" else jnp.float32
    kernel = functools.partial(_rerank_kernel, mode=mode, k=k, bq=bq, bc=bc,
                               n_c_steps=n_c_steps)
    vals, idx = pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((bq, bc), lambda i, j: (i, j),
                         memory_space=pltpu.SMEM),
            pl.BlockSpec((bq, d), lambda i, j: (i, 0)),
            pl.BlockSpec((bq, 1), lambda i, j: (i, 0)),
            pl.BlockSpec((bq, bc), lambda i, j: (i, j)),
            pl.BlockSpec((bq, bc), lambda i, j: (i, j)),
            pl.BlockSpec(memory_space=pl.ANY),
        ],
        out_specs=[
            pl.BlockSpec((bq, k), lambda i, j: (i, 0)),
            pl.BlockSpec((bq, k), lambda i, j: (i, 0)),
        ],
        scratch_shapes=[
            pltpu.VMEM((bq, bc, d), xg_dtype),   # gathered candidate rows
            pltpu.VMEM((bq, k), jnp.float32),    # running top-k dists
            pltpu.VMEM((bq, k), jnp.int32),      # running top-k ids
            pltpu.SemaphoreType.DMA(()),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((b, k), jnp.float32),
            jax.ShapeDtypeStruct((b, k), jnp.int32),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary"),
        ),
        interpret=interpret,
    )(cand_rows, Q, Qsq, cand_ids, pen, X)
    return vals, idx
