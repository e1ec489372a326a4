"""Public wrapper for the Hamming top-k kernel."""

from __future__ import annotations

import jax.numpy as jnp
import numpy as np

from repro.kernels import interpret_mode
from repro.kernels.hamming.hamming import hamming_topk_pallas


def word_major(X):
    """Packed codes [n, w] -> the kernel's word-major corpus [w, n] (corpus
    rows on the lanes).  An index makes it once, at build."""
    return jnp.asarray(X, jnp.uint32).T


def hamming_topk(Q, XT, *, k: int, bq: int = 64, bn: int = 512,
                 interpret: bool | None = None):
    """k nearest by popcount of Q [nq, w] over the word-major corpus
    XT [w, n] (see :func:`word_major`)."""
    interpret = interpret_mode() if interpret is None else interpret
    Q = jnp.asarray(Q, jnp.uint32)
    XT = jnp.asarray(XT, jnp.uint32)
    nq, w = Q.shape
    n = XT.shape[1]
    bq = min(bq, max(8, nq))
    bn = min(bn, max(128, n))
    pad_q = (-nq) % bq
    pad_n = (-n) % bn
    Qp = jnp.pad(Q, ((0, pad_q), (0, 0)))
    XTp = jnp.pad(XT, ((0, 0), (0, pad_n)))
    n_valid = jnp.full((1, 1), n, jnp.int32)
    vals, idx = hamming_topk_pallas(Qp, XTp, n_valid, k=min(k, n), bq=bq,
                                    bn=bn, interpret=interpret)
    return vals[:nq], idx[:nq]
