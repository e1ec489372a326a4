"""Kernel microbenchmarks: Pallas (interpret-mode, correctness-profiled)
vs jnp reference paths, plus the analytically derived TPU roofline time
for each kernel shape (``derived``) — the wall numbers are CPU proxies,
the derived numbers are the TPU claims.
"""

from __future__ import annotations

import numpy as np

import jax
import jax.numpy as jnp

from benchmarks.common import Row, timed
from repro.analysis.roofline import HBM_BW, PEAK_FLOPS


def run(scale: str = "default"):
    rows = []
    rng = np.random.default_rng(0)
    nq, n, d, k = (64, 8192, 128, 10) if scale != "smoke" else \
        (16, 1024, 64, 10)

    Q = jnp.asarray(rng.standard_normal((nq, d)), jnp.float32)
    X = jnp.asarray(rng.standard_normal((n, d)), jnp.float32)

    # two-pass jnp: full matrix then top_k (the memory-bound baseline)
    @jax.jit
    def two_pass(Q, X):
        d2 = (jnp.sum(Q * Q, 1)[:, None] - 2 * Q @ X.T
              + jnp.sum(X * X, 1)[None, :])
        return jax.lax.top_k(-d2, k)

    us = timed(lambda: jax.block_until_ready(two_pass(Q, X)))
    flops = 2 * nq * n * d
    bytes_2p = 4 * (nq * d + n * d + 2 * nq * n)
    t_tpu = max(flops / PEAK_FLOPS, bytes_2p / HBM_BW)
    rows.append(Row("kernel/bruteforce_two_pass_jnp", us,
                    f"tpu_roofline_us={t_tpu * 1e6:.1f}"))

    from repro.kernels.distance_topk import stream_topk

    us = timed(lambda: jax.block_until_ready(
        stream_topk(Q, X, k=k, metric="euclidean")))
    bytes_fused = 4 * (nq * d + n * d + 2 * nq * k)
    t_tpu_f = max(flops / PEAK_FLOPS, bytes_fused / HBM_BW)
    rows.append(Row("kernel/stream_topk_pallas_interpret", us,
                    f"tpu_roofline_us={t_tpu_f * 1e6:.1f};"
                    f"hbm_bytes_saved={(bytes_2p - bytes_fused) / 1e6:.1f}MB"))

    from repro.kernels.distance import distance_matrix

    us = timed(lambda: jax.block_until_ready(
        distance_matrix(Q, X, mode="l2sq")))
    rows.append(Row("kernel/distance_matrix_pallas_interpret", us,
                    f"tpu_roofline_us={t_tpu * 1e6:.1f}"))

    # hamming
    w = 8
    Qh = jnp.asarray(rng.integers(0, 2**32, (nq, w), dtype=np.uint64)
                     .astype(np.uint32))
    Xh = jnp.asarray(rng.integers(0, 2**32, (n, w), dtype=np.uint64)
                     .astype(np.uint32))
    from repro.kernels.hamming import hamming_topk, word_major

    XTh = word_major(Xh)
    us = timed(lambda: jax.block_until_ready(hamming_topk(Qh, XTh, k=k)))
    t_h = 4 * (nq * w + n * w) / HBM_BW
    rows.append(Row("kernel/hamming_topk_pallas_interpret", us,
                    f"tpu_roofline_us={t_h * 1e6:.2f}"))

    # embedding bag
    from repro.kernels.embedbag import embedding_bag

    V, D, N, B = 10000, 64, 4096, 512
    table = jnp.asarray(rng.standard_normal((V, D)), jnp.float32)
    idx = jnp.asarray(rng.integers(0, V, N), jnp.int32)
    bags = jnp.asarray(np.sort(rng.integers(0, B, N)), jnp.int32)
    us = timed(lambda: jax.block_until_ready(
        embedding_bag(table, idx, bags, n_bags=B, assume_sorted=True)))
    t_eb = 4 * (N * D + B * D) / HBM_BW
    rows.append(Row("kernel/embedding_bag_pallas_interpret", us,
                    f"tpu_roofline_us={t_eb * 1e6:.2f}"))

    # decode attention
    from repro.kernels.decode_attn import decode_attention

    Bq, H, KV, S, dh = 4, 8, 4, 2048, 64
    q = jnp.asarray(rng.standard_normal((Bq, H, dh)), jnp.float32)
    kk = jnp.asarray(rng.standard_normal((Bq, S, KV, dh)), jnp.float32)
    vv = jnp.asarray(rng.standard_normal((Bq, S, KV, dh)), jnp.float32)
    us = timed(lambda: jax.block_until_ready(
        decode_attention(q, kk, vv, bs=256)))
    t_da = 4 * 2 * Bq * S * KV * dh / HBM_BW   # KV read dominates
    rows.append(Row("kernel/decode_attention_pallas_interpret", us,
                    f"tpu_roofline_us={t_da * 1e6:.2f}"))
    return rows
