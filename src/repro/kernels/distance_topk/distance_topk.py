"""Streaming fused distance + top-k kernel.

The hot path of the whole benchmark (brute force and every algorithm's
rerank stage): for each query tile, stream over database tiles, compute the
(bq, bn) distance tile on the MXU, and merge it into a per-query running
top-k accumulator held in VMEM scratch.  The [nq, n] distance matrix is
never written to HBM — the only HBM traffic is one read of Q and X and an
O(nq * k) result write, so ``n`` is bounded by HBM capacity for X alone.

Differences from the older ``topk_scan`` kernel it supersedes:

  * the running (dist, id) state lives in VMEM *scratch*, not in the output
    block — the output is written exactly once per query tile, on the last
    corpus step, instead of being round-tripped every step;
  * the contraction dim is tiled too (bd), with MXU accumulation into a
    VMEM cross-term scratch across the innermost grid axis, so large d
    never blows the VMEM budget;
  * padded corpus rows are masked in *every* mode through the ``xsq``
    operand (squared norms carrying +inf sentinels for "l2sq"; a plain
    additive 0/+inf penalty row for "ip"/"cos"), which makes the result
    exact with no host-side post-filtering.

Grid: (nq/bq, n/bn, d/bd), corpus and contraction axes sequential
("arbitrary"), query axis parallel.

Top-k merge: ``repro.kernels.select.merge_topk_rounds`` — k rounds of
(min, first-argmin, mask-to-inf) VPU reductions over the (bq, k + bn)
concatenation of the running state and the fresh tile.  No sort/top_k
primitives, so it lowers through Mosaic; with bn >> k the MXU matmul still
dominates.  Ties break
toward the smaller corpus id (the running state precedes the fresh tile and
ids ascend within a tile), matching ``jax.lax.top_k``.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.distance.distance import distance_epilogue
from repro.kernels.select import NEG_ONE, merge_topk_rounds


def _stream_topk_kernel(q_ref, x_ref, qsq_ref, xsq_ref, vals_out, idx_out,
                        acc_ref, vals_ref, idx_ref, *, mode: str, k: int,
                        bn: int, n_n_steps: int, n_d_steps: int):
    j = pl.program_id(1)                       # corpus tile
    kd = pl.program_id(2)                      # contraction tile

    @pl.when((j == 0) & (kd == 0))
    def _init_state():
        vals_ref[...] = jnp.full_like(vals_ref, jnp.inf)
        idx_ref[...] = jnp.full_like(idx_ref, NEG_ONE)

    @pl.when(kd == 0)
    def _init_acc():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    q = q_ref[...].astype(jnp.float32)          # [bq, bd]
    x = x_ref[...].astype(jnp.float32)          # [bn, bd]
    acc_ref[...] += jax.lax.dot_general(
        q, x, (((1,), (1,)), ((), ())),
        precision=jax.lax.Precision.HIGHEST,
        preferred_element_type=jnp.float32)      # [bq, bn] on the MXU

    @pl.when(kd == n_d_steps - 1)
    def _merge():
        d = distance_epilogue(acc_ref[...], qsq_ref[...], xsq_ref[...], mode)
        bq = d.shape[0]
        ids = j * bn + jax.lax.broadcasted_iota(jnp.int32, (bq, bn), 1)
        cand_d = jnp.concatenate([vals_ref[...], d], axis=1)
        cand_i = jnp.concatenate([idx_ref[...], ids], axis=1)
        out_d, out_i = merge_topk_rounds(cand_d, cand_i, k)
        vals_ref[...] = out_d
        idx_ref[...] = out_i

    @pl.when((kd == n_d_steps - 1) & (j == n_n_steps - 1))
    def _flush():
        vals_out[...] = vals_ref[...]
        idx_out[...] = idx_ref[...]


@functools.partial(
    jax.jit,
    static_argnames=("mode", "k", "bq", "bn", "bd", "interpret"))
def stream_topk_pallas(
    Q: jnp.ndarray,                # [nq, d]  padded to tiles by ops.py
    X: jnp.ndarray,                # [n, d]
    Qsq: jnp.ndarray,              # [nq, 1] fp32 squared norms (l2sq)
    Xsq: jnp.ndarray,              # [1, n]  squared norms / +inf penalty row
    *,
    mode: str,
    k: int,
    bq: int = 128,
    bn: int = 1024,
    bd: int = 128,
    interpret: bool,
):
    nq, d = Q.shape
    n = X.shape[0]
    assert nq % bq == 0 and n % bn == 0 and d % bd == 0, (nq, n, d)
    n_n_steps = n // bn
    n_d_steps = d // bd
    grid = (nq // bq, n_n_steps, n_d_steps)
    kernel = functools.partial(_stream_topk_kernel, mode=mode, k=k, bn=bn,
                               n_n_steps=n_n_steps, n_d_steps=n_d_steps)
    vals, idx = pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((bq, bd), lambda i, j, kd: (i, kd)),
            pl.BlockSpec((bn, bd), lambda i, j, kd: (j, kd)),
            pl.BlockSpec((bq, 1), lambda i, j, kd: (i, 0)),
            pl.BlockSpec((1, bn), lambda i, j, kd: (0, j)),
        ],
        out_specs=[
            pl.BlockSpec((bq, k), lambda i, j, kd: (i, 0)),
            pl.BlockSpec((bq, k), lambda i, j, kd: (i, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((nq, k), jnp.float32),
            jax.ShapeDtypeStruct((nq, k), jnp.int32),
        ],
        scratch_shapes=[
            pltpu.VMEM((bq, bn), jnp.float32),   # cross-term accumulator
            pltpu.VMEM((bq, k), jnp.float32),    # running top-k dists
            pltpu.VMEM((bq, k), jnp.int32),      # running top-k ids
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary", "arbitrary"),
        ),
        interpret=interpret,
    )(Q, X, Qsq, Xsq)
    return vals, idx
