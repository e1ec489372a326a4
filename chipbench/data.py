"""Seeded corpus and queries, made on the device in one jitted call.

The shape follows the repository's ``blobs`` dataset generator
(``src/repro/data/synthetic.py``: a Gaussian mixture of ``sqrt(n) / 4``
centres, centres ~ N(0, 9 I), points = centre + N(0, I)), which stands in
for SIFT descriptors, since the benchmark downloads nothing.  It is copied rather
than imported so that the yardstick cannot move with the program, and it is
made with ``jax.random`` on the device instead of numpy on the host, which
took 10-21 s of every set-up at n = 1,000,000.

Two seeds take part.  The configuration's ``geometry_seed`` fixes the
mixture and every corpus point up to sign; the run's ``--seed`` draws
the queries and flips the sign of each coordinate, of corpus and queries
alike.  A sign flip is an isometry that floating point keeps exactly, so
every seed gives a different corpus with the same neighbourhoods, the same
k-means partition and so the same IVF list sizes: the seed changes the
numbers a run sees, not the work it does.
"""

from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp



def run_key(seed: int) -> jax.Array:
    """A PRNG key from any non-negative whole number; bits above 32 are
    folded in, since a key keeps only the low 32."""
    seed = int(seed)
    if seed < 0:
        raise ValueError(f"--seed must be >= 0, got {seed}")
    key = jax.random.PRNGKey(seed & 0xFFFFFFFF)
    high = seed >> 32
    while high:
        key = jax.random.fold_in(key, high & 0xFFFFFFFF)
        high >>= 32
    return key


@partial(jax.jit, static_argnames=("n", "d", "n_queries", "n_centers",
                                   "center_scale", "noise_scale"))
def _make(geometry_key, key, *, n, d, n_queries, n_centers, center_scale,
          noise_scale):
    kc, ka, kn = jax.random.split(geometry_key, 3)
    centers = center_scale * jax.random.normal(kc, (n_centers, d),
                                               jnp.float32)
    assign = jax.random.randint(ka, (n,), 0, n_centers)
    X = centers[assign] + noise_scale * jax.random.normal(kn, (n, d),
                                                          jnp.float32)
    ks, kq, kqn = jax.random.split(key, 3)
    sign = jnp.where(jax.random.bernoulli(ks, 0.5, (d,)), -1.0, 1.0)
    qa = jax.random.randint(kq, (n_queries,), 0, n_centers)
    Q = centers[qa] + noise_scale * jax.random.normal(kqn, (n_queries, d),
                                                      jnp.float32)
    return X * sign, Q * sign


def make(corpus: dict, seed: int):
    """(X [n, d], Q [n_queries, d]) float32 device arrays for a
    configuration's ``corpus`` block and a run's seed."""
    if corpus.get("generator") != "blobs" or corpus.get("metric") != \
            "euclidean" or corpus.get("dtype") != "float32":
        raise ValueError(f"only float32 euclidean blobs are generated, "
                         f"got {corpus}")
    return _make(jax.random.PRNGKey(int(corpus["geometry_seed"])),
                 run_key(seed),
                 n=int(corpus["n"]), d=int(corpus["d"]),
                 n_queries=int(corpus["n_queries"]),
                 n_centers=int(corpus["n_centers"]),
                 center_scale=float(corpus["center_scale"]),
                 noise_scale=float(corpus["noise_scale"]))
