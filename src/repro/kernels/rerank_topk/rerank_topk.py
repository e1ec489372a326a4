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

The list kernel (``rerank_lists_pallas``) serves candidates that are runs
of a cluster-major corpus, IVF's probed lists: it takes each (query,
probe)'s start and live length as SMEM blocks, and a per-query-tile plan
of the (probe, chunk) items that hold a live row.  Each item copies, per
query, the run's rows of one 128-aligned chunk as a few power-of-two
slices (never past the run, rounded to 8 rows, nor past row n-1), and the
chunk's cached norms and ids as whole 128-lane rows; the next item's
copies are in flight while this one is scored.  Distances and the select
are the row kernel's.

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


def _lists_kernel(start_ref, len_ref, plan_ref, q_ref, qsq_ref, x_hbm,
                  xsq_hbm, ids_hbm, vals_out, idx_out, xbuf, sbuf, ibuf,
                  vals_ref, idx_ref, sem, *, mode: str, k: int, bq: int,
                  bc: int, n_chunks: int, n: int):
    vals_ref[...] = jnp.full_like(vals_ref, jnp.inf)
    idx_ref[...] = jnp.full_like(idx_ref, NEG_ONE)
    count = plan_ref[0, 0]
    # a run's rows reach VMEM as power-of-two pieces, so no copy reads
    # past the run (rounded to 8 rows) nor past row n-1
    sizes = [1 << e for e in range(bc.bit_length() - 1, -1, -1)
             if (1 << e) <= n and ((1 << e) >= 8 or n % 8)]

    def run(t, qi):
        """(chunk start row, first and last+1 live slot) of query qi in
        plan item t: slot j of the chunk is row base + j, base 128-aligned
        so the chunk's norms and ids are whole lane rows."""
        item = plan_ref[0, 1 + t]
        p = item // n_chunks
        s = start_ref[qi, p]
        end = s + len_ref[qi, p]
        base = pl.multiple_of(s - s % 128 + (item % n_chunks) * bc, 128)
        lo = jnp.maximum(s, base) - base
        hi = jnp.maximum(jnp.minimum(end, base + bc) - base, lo)
        return base, lo, hi

    def each_live_copy(t, slot, act):
        def one_query(qi, carry):
            base, lo, hi = run(t, qi)

            @pl.when(lo < hi)
            def _copy():
                lo8 = pl.multiple_of(lo - lo % 8, 8)
                size = jnp.minimum(hi + (-hi) % 8, n - base) - lo8
                for piece in sizes:
                    off = lo8 + (size & -(2 * piece))

                    @pl.when(size & piece != 0)
                    def _piece():
                        act(pltpu.make_async_copy(
                            x_hbm.at[pl.ds(base + off, piece)],
                            xbuf.at[slot, qi, pl.ds(off, piece)],
                            sem.at[slot]))

                lanes = pl.ds(base // 128, bc // 128)
                act(pltpu.make_async_copy(ids_hbm.at[lanes],
                                          ibuf.at[slot, qi], sem.at[slot]))
                if mode == "l2sq":
                    act(pltpu.make_async_copy(xsq_hbm.at[lanes],
                                              sbuf.at[slot, qi],
                                              sem.at[slot]))
            return carry

        jax.lax.fori_loop(0, bq, one_query, 0)

    @pl.when(count > 0)
    def _first():
        each_live_copy(0, 0, lambda c: c.start())

    lane = jax.lax.broadcasted_iota(jnp.int32, (bq, bc), 1)
    sub = jax.lax.broadcasted_iota(jnp.int32, (bq, bc), 0)

    def step(t, carry):
        slot = t % 2

        @pl.when(t + 1 < count)
        def _prefetch():
            each_live_copy(t + 1, 1 - slot, lambda c: c.start())

        each_live_copy(t, slot, lambda c: c.wait())
        lo = jnp.zeros((bq, bc), jnp.int32)
        hi = jnp.zeros((bq, bc), jnp.int32)
        for qi in range(bq):
            _, lo_q, hi_q = run(t, qi)
            lo = jnp.where(sub == qi, lo_q, lo)
            hi = jnp.where(sub == qi, hi_q, hi)
        valid = (lane >= lo) & (lane < hi)
        cross = jax.lax.dot_general(
            xbuf[slot], q_ref[...], (((2,), (1,)), ((0,), (0,))),
            precision=jax.lax.Precision.HIGHEST,
            preferred_element_type=jnp.float32)   # [bq, bc]
        if mode == "l2sq":
            d = (qsq_ref[...] - 2.0 * cross) + sbuf[slot].reshape(bq, bc)
        else:                                      # cos
            d = 1.0 - cross
        # slots outside the run hold stale or unwritten rows: select, not
        # add, so a NaN there cannot reach the min
        cand_d = jnp.concatenate(
            [vals_ref[...], jnp.where(valid, d, jnp.inf)], axis=1)
        cand_i = jnp.concatenate(
            [idx_ref[...],
             jnp.where(valid, ibuf[slot].reshape(bq, bc), NEG_ONE)], axis=1)
        out_d, out_i = merge_topk_unique_rounds(cand_d, cand_i, k)
        vals_ref[...] = out_d
        idx_ref[...] = out_i
        return carry

    jax.lax.fori_loop(0, count, step, 0)
    vals_out[...] = vals_ref[...]
    idx_out[...] = idx_ref[...]


@functools.partial(
    jax.jit,
    static_argnames=("mode", "k", "bq", "bc", "n_chunks", "interpret"))
def rerank_lists_pallas(
    starts: jnp.ndarray,           # [b, P] int32 first row of each run
    lens: jnp.ndarray,             # [b, P] int32 live rows (0: no work)
    plan: jnp.ndarray,             # [b, 1 + P * n_chunks] int32, per tile
    Q: jnp.ndarray,                # [b, d] f32
    Qsq: jnp.ndarray,              # [b, 1] f32 squared norms (l2sq)
    X: jnp.ndarray,                # [n, d] f32 cluster-major corpus (HBM)
    xsq: jnp.ndarray,              # [R, 128] f32 row norms, row-major
    ids: jnp.ndarray,              # [R, 128] int32 row ids, row-major
    *,
    mode: str,
    k: int,
    bq: int = 8,
    bc: int,
    n_chunks: int,
    interpret: bool,
):
    b, d = Q.shape
    n = X.shape[0]
    P = starts.shape[1]
    assert b % bq == 0 and bc % 128 == 0, (b, bq, bc)
    kernel = functools.partial(_lists_kernel, mode=mode, k=k, bq=bq, bc=bc,
                               n_chunks=n_chunks, n=n)
    smem = functools.partial(pl.BlockSpec, memory_space=pltpu.SMEM)
    vals, idx = pl.pallas_call(
        kernel,
        grid=(b // bq,),
        in_specs=[
            smem((bq, P), lambda i: (i, 0)),
            smem((bq, P), lambda i: (i, 0)),
            smem((bq, plan.shape[1]), lambda i: (i, 0)),
            pl.BlockSpec((bq, d), lambda i: (i, 0)),
            pl.BlockSpec((bq, 1), lambda i: (i, 0)),
            pl.BlockSpec(memory_space=pl.ANY),
            pl.BlockSpec(memory_space=pl.ANY),
            pl.BlockSpec(memory_space=pl.ANY),
        ],
        out_specs=[
            pl.BlockSpec((bq, k), lambda i: (i, 0)),
            pl.BlockSpec((bq, k), lambda i: (i, 0)),
        ],
        scratch_shapes=[
            pltpu.VMEM((2, bq, bc, d), jnp.float32),        # row chunks
            pltpu.VMEM((2, bq, bc // 128, 128), jnp.float32),  # their norms
            pltpu.VMEM((2, bq, bc // 128, 128), jnp.int32),    # their ids
            pltpu.VMEM((bq, k), jnp.float32),         # running top-k dists
            pltpu.VMEM((bq, k), jnp.int32),           # running top-k ids
            pltpu.SemaphoreType.DMA((2,)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((b, k), jnp.float32),
            jax.ShapeDtypeStruct((b, k), jnp.int32),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel",),
        ),
        interpret=interpret,
    )(starts, lens, plan, Q, Qsq, X, xsq, ids)
    return vals, idx
