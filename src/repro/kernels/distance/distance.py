"""Tiled distance-matrix kernel: D = dist(Q, X) over (bq, bn) VMEM tiles.

TPU mapping of the paper's hot loop (every algorithm's candidate rerank and
the brute-force baseline): the cross term Q @ X^T runs on the MXU with fp32
accumulation; the norm epilogue fuses into the same tile while it is still
in VMEM, so HBM traffic is exactly one read of each Q/X tile and one write
of the distance tile.

Grid: (nq/bq, n/bn, d/bd).  The contraction dim d is tiled too (bd), with
accumulation into the output tile across the innermost grid axis; the
epilogue (norms / 1-ip) is applied on the last d-step.  All tile sizes are
multiples of the MXU/VPU native 128 lanes (8 sublanes fp32).

Modes:
    "l2sq" : ||q||^2 - 2 q.x + ||x||^2   (squared L2; monotone for NN)
    "ip"   : - q.x                        (max inner product as min dist)
    "cos"  : 1 - q.x                      (angular distance; pre-normalised
                                           inputs)
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def distance_epilogue(cross, qsq, xsq, mode: str):
    """Turn an accumulated cross tile ``Q @ X^T`` into distances.

    ``qsq`` [bq, 1] / ``xsq`` [1, bn] are the squared norms for "l2sq".  For
    "ip"/"cos" the ``xsq`` row doubles as an additive per-corpus-row penalty
    (0 for valid rows, +inf for padding sentinels), so callers can mask
    padded corpus rows in every mode through the same operand.
    """
    if mode == "l2sq":
        return jnp.maximum(qsq - 2.0 * cross + xsq, 0.0)
    if mode == "ip":
        return -cross + xsq
    if mode == "cos":
        return 1.0 - cross + xsq
    raise ValueError(mode)


def _distance_kernel(q_ref, x_ref, qsq_ref, xsq_ref, out_ref, acc_ref, *,
                     mode: str, n_d_steps: int):
    kd = pl.program_id(2)

    @pl.when(kd == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    q = q_ref[...].astype(jnp.float32)          # [bq, bd]
    x = x_ref[...].astype(jnp.float32)          # [bn, bd]
    acc_ref[...] += jax.lax.dot_general(
        q, x, (((1,), (1,)), ((), ())),
        precision=jax.lax.Precision.HIGHEST,
        preferred_element_type=jnp.float32)      # [bq, bn] on the MXU

    @pl.when(kd == n_d_steps - 1)
    def _epilogue():
        cross = acc_ref[...]
        if mode == "l2sq":
            out_ref[...] = distance_epilogue(cross, qsq_ref[...],
                                             xsq_ref[...], mode)
        else:                                    # "ip" / "cos": no penalty row
            out_ref[...] = distance_epilogue(cross, 0.0, 0.0, mode)


@functools.partial(
    jax.jit,
    static_argnames=("mode", "bq", "bn", "bd", "interpret"))
def distance_matrix_pallas(
    Q: jnp.ndarray,                  # [nq, d]  (padded to tiles by ops.py)
    X: jnp.ndarray,                  # [n, d]
    Qsq: jnp.ndarray,                # [nq, 1] fp32 squared norms
    Xsq: jnp.ndarray,                # [1, n]
    *,
    mode: str = "l2sq",
    bq: int = 128,
    bn: int = 512,
    bd: int = 128,
    interpret: bool,
) -> jnp.ndarray:
    nq, d = Q.shape
    n = X.shape[0]
    assert nq % bq == 0 and n % bn == 0 and d % bd == 0, (nq, n, d)
    n_d_steps = d // bd
    grid = (nq // bq, n // bn, n_d_steps)

    kernel = functools.partial(_distance_kernel, mode=mode,
                               n_d_steps=n_d_steps)
    return pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((bq, bd), lambda i, j, kd: (i, kd)),
            pl.BlockSpec((bn, bd), lambda i, j, kd: (j, kd)),
            pl.BlockSpec((bq, 1), lambda i, j, kd: (i, 0)),
            pl.BlockSpec((1, bn), lambda i, j, kd: (0, j)),
        ],
        out_specs=pl.BlockSpec((bq, bn), lambda i, j, kd: (i, j)),
        out_shape=jax.ShapeDtypeStruct((nq, n), jnp.float32),
        scratch_shapes=[pltpu.VMEM((bq, bn), jnp.float32)],
        interpret=interpret,
    )(Q, X, Qsq, Xsq)
