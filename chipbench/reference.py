"""The plain reference: exact k nearest neighbours by brute force.

It imports nothing of the program and takes nothing the program made; it
sees only the corpus and queries that ``data.make`` drew from the seed.
Candidates come from a blocked scan on the device in float32 at full
precision (six bf16 passes on a TPU), ``MARGIN`` more than asked for, and
are then rescored in float64 on the host from the raw vectors, where
``||q - x||^2`` is summed without cancellation.  A float32 error of the
scan (about 1e-4 on distances near 256) cannot push a true neighbour past
``MARGIN`` places, so the float64 top k is exact.

``precision="bf16x3"`` is the control: the same scan computed as a TPU
computes float32 at ``high`` precision, three bf16 passes (the low-order
product dropped).
Its top k and its distances stand in for the program's answers to show
that the comparison fails them.
"""

from __future__ import annotations

from functools import partial

import numpy as np

import jax
import jax.numpy as jnp

MARGIN = 16
CHUNK = 65536         # corpus rows per scan step
QUERY_BLOCK = 1024    # queries per scan call


def _cross(Q, X, precision: str):
    """Q @ X.T in float32: exact-as-float32, or the three-pass control."""
    if precision == "highest":
        return jnp.matmul(Q, X.T, precision=jax.lax.Precision.HIGHEST)
    if precision != "bf16x3":
        raise ValueError(f"unknown precision {precision!r}")
    if jax.default_backend() == "tpu":
        # the TPU's own three-pass algorithm (``Precision.HIGH``)
        return jnp.matmul(
            Q, X.T, precision=jax.lax.DotAlgorithmPreset.BF16_BF16_F32_X3)
    # elsewhere, where a dot ignores the algorithm and computes in float32,
    # the same three passes spelled out (on a TPU, XLA would take the
    # float32 -> bf16 -> float32 round trip that makes the low parts for
    # excess precision and drop it, leaving one pass)
    bf = jnp.bfloat16

    def split(a):
        hi = a.astype(bf)
        return hi, (a - hi.astype(jnp.float32)).astype(bf)

    qh, ql = split(Q)
    xh, xl = split(X)
    a = jnp.concatenate([qh, qh, ql], axis=1)
    b = jnp.concatenate([xh, xl, xh], axis=1)
    return jnp.matmul(a, b.T, preferred_element_type=jnp.float32)


@partial(jax.jit, static_argnames=("kk", "chunk", "precision"))
def _scan_topk(Q, Xc, xsq, *, kk, chunk, precision):
    """(dists [b, kk], rows [b, kk]) over corpus chunks Xc [c, chunk, d]
    with squared norms xsq [c, chunk] (+inf on padded rows)."""
    qsq = jnp.sum(Q * Q, axis=1, keepdims=True)
    b = Q.shape[0]

    def step(carry, xs):
        best_d, best_i = carry
        X, norms, base = xs
        d = qsq + norms[None, :] - 2.0 * _cross(Q, X, precision)
        rows = base + jnp.arange(chunk, dtype=jnp.int32)
        cat_d = jnp.concatenate([best_d, d], axis=1)
        cat_i = jnp.concatenate([best_i, jnp.broadcast_to(rows, d.shape)],
                                axis=1)
        neg, pos = jax.lax.top_k(-cat_d, kk)
        return (-neg, jnp.take_along_axis(cat_i, pos, axis=1)), None

    init = (jnp.full((b, kk), jnp.inf, jnp.float32),
            jnp.full((b, kk), -1, jnp.int32))
    bases = jnp.arange(Xc.shape[0], dtype=jnp.int32) * chunk
    (best_d, best_i), _ = jax.lax.scan(step, init, (Xc, xsq, bases))
    return best_d, best_i


def _chunked(X):
    """The corpus as [c, chunk, d] with squared norms, +inf on pad rows."""
    n, d = X.shape
    chunk = min(CHUNK, n)
    c = -(-n // chunk)
    pad = c * chunk - n
    Xp = jnp.pad(jnp.asarray(X, jnp.float32), ((0, pad), (0, 0)))
    xsq = jnp.sum(Xp * Xp, axis=1)
    xsq = jnp.where(jnp.arange(c * chunk) < n, xsq, jnp.inf)
    return Xp.reshape(c, chunk, d), xsq.reshape(c, chunk), chunk


def scan(X_dev, Q: np.ndarray, kk: int, precision: str = "highest"):
    """Blocked brute-force scan on the device: (float32 dists [nq, kk],
    rows [nq, kk]) ascending, in ``QUERY_BLOCK`` query blocks (the last one
    padded, so one program serves them all)."""
    Xc, xsq, chunk = _chunked(X_dev)
    kk = min(int(kk), X_dev.shape[0])
    nq = Q.shape[0]
    qb = min(QUERY_BLOCK, nq)
    out_d, out_i = [], []
    for s in range(0, nq, qb):
        blk = np.asarray(Q[s:s + qb], np.float32)
        live = blk.shape[0]
        if live < qb:
            blk = np.concatenate([blk, np.zeros((qb - live, blk.shape[1]),
                                                np.float32)])
        d, i = _scan_topk(jnp.asarray(blk), Xc, xsq, kk=kk, chunk=chunk,
                          precision=precision)
        out_d.append(np.asarray(d)[:live])
        out_i.append(np.asarray(i)[:live])
    return np.concatenate(out_d), np.concatenate(out_i)


def sq_dists64(X: np.ndarray, Q: np.ndarray, qidx: np.ndarray,
               ids: np.ndarray, rows_per_block: int = 1 << 17) -> np.ndarray:
    """Exact squared distances ||Q[qidx[a]] - X[ids[a, j]]||^2 in float64,
    [A, m]; +inf where an id is out of range (a missing answer)."""
    A, m = ids.shape
    block = max(1, rows_per_block // max(m, 1))
    out = np.empty((A, m), np.float64)
    n = X.shape[0]
    for s in range(0, A, block):
        ii = ids[s:s + block]
        ok = (ii >= 0) & (ii < n)
        rows = X[np.where(ok, ii, 0)].astype(np.float64)     # [b, m, d]
        q = Q[qidx[s:s + block]].astype(np.float64)[:, None, :]
        d = np.sum((rows - q) ** 2, axis=-1)
        out[s:s + block] = np.where(ok, d, np.inf)
    return out


def exact(X_host: np.ndarray, X_dev, Q: np.ndarray, qidx: np.ndarray,
          k: int) -> np.ndarray:
    """Exact float64 squared distances of the k nearest corpus rows of each
    query ``Q[qidx]``, ascending, [len(qidx), k]."""
    _, rows = scan(X_dev, Q[qidx], k + MARGIN)
    d64 = sq_dists64(X_host, Q, qidx, rows)
    return np.sort(d64, axis=1)[:, :k]


def control(X_dev, Q: np.ndarray, qidx: np.ndarray, k: int):
    """The control's answers to ``Q[qidx]``: (dists [A, k], ids [A, k]) of
    the three-pass scan, in the program's place."""
    return scan(X_dev, Q[qidx], k, precision="bf16x3")
