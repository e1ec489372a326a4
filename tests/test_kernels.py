"""Pallas kernels vs pure-jnp oracles: shape/dtype sweeps in interpret mode
(assignment: "For each Pallas kernel, sweep shapes/dtypes and
assert_allclose against the ref.py pure-jnp oracle")."""

import numpy as np
import pytest

import jax
import jax.numpy as jnp


# ------------------------------------------------------------- distance
@pytest.mark.parametrize("nq,n,d", [(8, 128, 32), (37, 300, 100),
                                    (128, 512, 128), (5, 1000, 17)])
@pytest.mark.parametrize("mode", ["l2sq", "ip", "cos"])
def test_distance_kernel(nq, n, d, mode):
    from repro.kernels.distance import distance_matrix, distance_matrix_ref

    rng = np.random.default_rng(nq * n + d)
    Q = rng.standard_normal((nq, d)).astype(np.float32)
    X = rng.standard_normal((n, d)).astype(np.float32)
    out = distance_matrix(jnp.asarray(Q), jnp.asarray(X), mode=mode)
    ref = distance_matrix_ref(jnp.asarray(Q), jnp.asarray(X), mode=mode)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=1e-5, atol=1e-4)


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_distance_kernel_dtypes(dtype):
    from repro.kernels.distance import distance_matrix, distance_matrix_ref

    rng = np.random.default_rng(0)
    Q = jnp.asarray(rng.standard_normal((16, 64)), dtype)
    X = jnp.asarray(rng.standard_normal((256, 64)), dtype)
    out = distance_matrix(Q, X, mode="l2sq")
    ref = distance_matrix_ref(Q.astype(jnp.float32),
                              X.astype(jnp.float32), mode="l2sq")
    tol = 1e-4 if dtype == jnp.float32 else 5e-2
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=tol, atol=tol * 10)


# ------------------------------------------------- streaming distance+topk
@pytest.mark.parametrize("nq,n,d,k", [(8, 256, 32, 5), (33, 700, 64, 10),
                                      (16, 1024, 300, 100), (3, 999, 17, 7)])
@pytest.mark.parametrize("metric", ["euclidean", "angular", "ip"])
def test_stream_topk_kernel(nq, n, d, k, metric):
    from repro.kernels.distance_topk import stream_topk, stream_topk_ref

    rng = np.random.default_rng(nq + n + k)
    Q = rng.standard_normal((nq, d)).astype(np.float32)
    X = rng.standard_normal((n, d)).astype(np.float32)
    if metric == "angular":
        Q /= np.linalg.norm(Q, axis=1, keepdims=True)
        X /= np.linalg.norm(X, axis=1, keepdims=True)
    mode = {"euclidean": "l2sq", "angular": "cos", "ip": "ip"}[metric]
    v, i = stream_topk(jnp.asarray(Q), jnp.asarray(X), k=k, metric=metric,
                       bn=256)
    rv, ri = stream_topk_ref(jnp.asarray(Q), jnp.asarray(X), k=k, mode=mode)
    # distances must match exactly-ish; ids may differ only on value ties
    np.testing.assert_allclose(np.asarray(v), np.asarray(rv), rtol=1e-4,
                               atol=1e-4)
    assert np.mean(np.asarray(i) == np.asarray(ri)) > 0.99


@pytest.mark.parametrize("mode", ["l2sq", "ip", "cos"])
def test_stream_topk_matches_materialize_then_topk(mode):
    """Equivalence with the two-pass path: distance_matrix + topk_with_ids."""
    from repro.ann.topk import topk_with_ids
    from repro.kernels.distance.ops import distance_matrix
    from repro.kernels.distance_topk import stream_topk

    rng = np.random.default_rng(7)
    Q = jnp.asarray(rng.standard_normal((19, 45)), jnp.float32)
    X = jnp.asarray(rng.standard_normal((531, 45)), jnp.float32)
    metric = {"l2sq": "euclidean", "cos": "angular", "ip": "ip"}[mode]
    v, i = stream_topk(Q, X, k=13, metric=metric, bn=128)
    D = distance_matrix(Q, X, mode=mode)
    ids = jnp.broadcast_to(jnp.arange(X.shape[0], dtype=jnp.int32)[None, :],
                           D.shape)
    mv, mi = topk_with_ids(D, ids, 13)
    np.testing.assert_allclose(np.asarray(v), np.asarray(mv), rtol=1e-4,
                               atol=1e-4)
    assert np.mean(np.asarray(i) == np.asarray(mi)) > 0.99


def test_stream_topk_ties_stable_ids():
    """Exact duplicate corpus rows: ties must break toward the smaller id,
    matching jax.lax.top_k."""
    from repro.kernels.distance_topk import stream_topk, stream_topk_ref

    rng = np.random.default_rng(3)
    base = rng.standard_normal((60, 24)).astype(np.float32)
    X = np.concatenate([base, base, base])          # every row 3x duplicated
    Q = rng.standard_normal((9, 24)).astype(np.float32)
    v, i = stream_topk(jnp.asarray(Q), jnp.asarray(X), k=12,
                       metric="euclidean", bn=128)
    rv, ri = stream_topk_ref(jnp.asarray(Q), jnp.asarray(X), k=12,
                             mode="l2sq")
    np.testing.assert_array_equal(np.asarray(i), np.asarray(ri))


@pytest.mark.parametrize("metric", ["euclidean", "angular"])
def test_stream_topk_valid_mask_and_row_ids(metric):
    """Shard-local plumbing (ISSUE 9): masked rows ride the existing xsq
    penalty channel and never appear, ``row_ids`` remaps winners to global
    ids — bit-parity with a brute-force scan of the kept subset."""
    from repro.kernels.distance_topk import stream_topk

    rng = np.random.default_rng(11)
    X = rng.standard_normal((300, 24)).astype(np.float32)
    Q = rng.standard_normal((7, 24)).astype(np.float32)
    if metric == "angular":
        Q /= np.linalg.norm(Q, axis=1, keepdims=True)
        X /= np.linalg.norm(X, axis=1, keepdims=True)
    valid = rng.random(300) < 0.5
    gids = rng.permutation(10_000)[:300].astype(np.int32)
    v, i = stream_topk(jnp.asarray(Q), jnp.asarray(X), k=10, metric=metric,
                       row_ids=jnp.asarray(gids), valid=jnp.asarray(valid),
                       bn=128)
    # oracle: scan only the kept rows
    kept = np.flatnonzero(valid)
    if metric == "euclidean":
        D = ((Q[:, None, :] - X[None, kept]) ** 2).sum(-1)
    else:
        D = 1.0 - Q @ X[kept].T
    order = np.argsort(D, axis=1)[:, :10]
    want = gids[kept][order]
    assert np.array_equal(np.sort(np.asarray(i)), np.sort(want))
    assert not np.isin(np.asarray(i), gids[~valid]).any()


def test_stream_topk_valid_mask_underfull():
    """Fewer valid rows than k: losing slots pad with (+inf, -1)."""
    from repro.kernels.distance_topk import stream_topk

    rng = np.random.default_rng(12)
    X = rng.standard_normal((64, 8)).astype(np.float32)
    Q = rng.standard_normal((3, 8)).astype(np.float32)
    valid = np.zeros(64, bool)
    valid[:4] = True
    v, i = stream_topk(jnp.asarray(Q), jnp.asarray(X), k=10,
                       metric="euclidean", row_ids=jnp.arange(64,
                                                             dtype=np.int32),
                       valid=jnp.asarray(valid))
    v, i = np.asarray(v), np.asarray(i)
    assert (np.sort(i[:, :4], axis=1) == np.arange(4)).all()
    assert (i[:, 4:] == -1).all()
    assert np.isinf(v[:, 4:]).all()


def test_stream_topk_scan_ref_matches_exact():
    """The pure-JAX streaming scan (the shard-local serving path) is exact."""
    from repro.kernels.distance_topk import (stream_topk_ref,
                                             stream_topk_ref_scan)

    rng = np.random.default_rng(11)
    Q = jnp.asarray(rng.standard_normal((14, 33)), jnp.float32)
    X = jnp.asarray(rng.standard_normal((777, 33)), jnp.float32)
    sv, si = stream_topk_ref_scan(Q, X, k=9, mode="l2sq", bn=100)
    rv, ri = stream_topk_ref(Q, X, k=9, mode="l2sq")
    np.testing.assert_allclose(np.asarray(sv), np.asarray(rv), rtol=1e-4,
                               atol=1e-4)
    assert np.mean(np.asarray(si) == np.asarray(ri)) > 0.99


def test_stream_topk_batched_query_blocks():
    """Query-streaming driver: identical results for any block size,
    including ragged final blocks and k > block interactions."""
    from repro.kernels.distance_topk import (stream_topk_batched,
                                             stream_topk_ref)

    rng = np.random.default_rng(5)
    Q = rng.standard_normal((37, 20)).astype(np.float32)
    X = jnp.asarray(rng.standard_normal((400, 20)), jnp.float32)
    rv, ri = stream_topk_ref(jnp.asarray(Q), X, k=8, mode="l2sq")
    for qb in (5, 16, 37, 64):
        v, i = stream_topk_batched(Q, X, k=8, metric="euclidean",
                                   query_block=qb)
        np.testing.assert_allclose(v, np.asarray(rv), rtol=1e-4, atol=1e-4)
        assert np.mean(i == np.asarray(ri)) > 0.99, qb


def test_stream_topk_k_exceeds_corpus():
    from repro.kernels.distance_topk import stream_topk

    rng = np.random.default_rng(2)
    Q = jnp.asarray(rng.standard_normal((4, 16)), jnp.float32)
    X = jnp.asarray(rng.standard_normal((6, 16)), jnp.float32)
    v, i = stream_topk(Q, X, k=50, metric="euclidean")
    assert v.shape == (4, 6) and i.shape == (4, 6)
    assert np.all(np.asarray(i) >= 0) and np.all(np.asarray(i) < 6)


# --------------------------------------------------------------- hamming
@pytest.mark.parametrize("nq,n,w,k", [(8, 256, 4, 5), (17, 300, 8, 10),
                                      (64, 512, 25, 32)])
def test_hamming_kernel(nq, n, w, k):
    from repro.kernels.hamming import (hamming_topk, hamming_topk_ref,
                                       word_major)

    rng = np.random.default_rng(w)
    Q = rng.integers(0, 2**32, (nq, w), dtype=np.uint64).astype(np.uint32)
    X = rng.integers(0, 2**32, (n, w), dtype=np.uint64).astype(np.uint32)
    v, i = hamming_topk(Q, word_major(X), k=k, bn=128)
    rv, ri = hamming_topk_ref(jnp.asarray(Q), jnp.asarray(X), k=k)
    np.testing.assert_array_equal(np.asarray(v), np.asarray(rv))
    # integer distances tie often; compare distance multisets per row
    np.testing.assert_array_equal(np.sort(np.asarray(v)),
                                  np.sort(np.asarray(rv)))


# -------------------------------------------------------------- embedbag
@pytest.mark.parametrize("V,D,N,B", [(50, 16, 100, 12), (128, 32, 300, 17),
                                     (1000, 8, 64, 64)])
def test_embedbag_kernel(V, D, N, B):
    from repro.kernels.embedbag import embedding_bag, embedding_bag_ref

    rng = np.random.default_rng(V + N)
    table = jnp.asarray(rng.standard_normal((V, D)), jnp.float32)
    idx = rng.integers(0, V, N).astype(np.int32)
    bags = rng.integers(0, B, N).astype(np.int32)        # unsorted on purpose
    w = rng.random(N).astype(np.float32)
    out = embedding_bag(table, idx, bags, w, n_bags=B)
    ref = embedding_bag_ref(jnp.asarray(idx), jnp.asarray(bags),
                            jnp.asarray(w), table, n_bags=B)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=1e-5, atol=1e-5)


def test_embedbag_empty_bags():
    from repro.kernels.embedbag import embedding_bag

    table = jnp.ones((10, 4), jnp.float32)
    idx = np.array([0, 1], np.int32)
    bags = np.array([0, 3], np.int32)      # bags 1, 2 empty
    out = np.asarray(embedding_bag(table, idx, bags, n_bags=5))
    assert np.all(out[1] == 0) and np.all(out[2] == 0) and np.all(out[4] == 0)
    assert np.all(out[0] == 1) and np.all(out[3] == 1)


# ----------------------------------------------------------- decode attn
@pytest.mark.parametrize("B,H,KV,S,dh", [(2, 4, 2, 128, 32),
                                         (3, 8, 4, 257, 64),
                                         (1, 2, 1, 64, 16)])
def test_decode_attn_kernel(B, H, KV, S, dh):
    from repro.kernels.decode_attn import (decode_attention,
                                           decode_attention_ref)

    rng = np.random.default_rng(B * S)
    q = rng.standard_normal((B, H, dh)).astype(np.float32)
    k = rng.standard_normal((B, S, KV, dh)).astype(np.float32)
    v = rng.standard_normal((B, S, KV, dh)).astype(np.float32)
    lengths = rng.integers(1, S + 1, B).astype(np.int32)
    out = decode_attention(jnp.asarray(q), jnp.asarray(k), jnp.asarray(v),
                           jnp.asarray(lengths), bs=64)
    qg = q.reshape(B, KV, H // KV, dh)
    ref = jax.vmap(
        lambda qh, kh, vh: decode_attention_ref(qh, kh, vh,
                                                jnp.asarray(lengths)),
        in_axes=(1, 2, 2), out_axes=1)(
        jnp.asarray(qg), jnp.asarray(k), jnp.asarray(v)).reshape(B, H, dh)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=1e-5, atol=1e-5)


# --------------------------------------------- top-k merge: duplicate ids
def test_merge_topk_rounds_emits_duplicates_unique_variant_does_not():
    """Regression pin for the streaming-mutation merge: when the SAME id
    appears in both merge operands (main index + delta overlap after a
    re-insert), the plain positional ``merge_topk_rounds`` emits it twice
    — one result slot per copy — while ``merge_topk_unique_rounds``
    retires every copy of a selected id and matches the canonical
    ``topk_unique`` contract exactly.  This is why repro.mutate routes
    its main+delta merge through the unique variant."""
    from repro.ann.topk import topk_unique
    from repro.kernels.distance_topk.distance_topk import merge_topk_rounds
    from repro.kernels.rerank_topk import merge_topk_unique_rounds

    # id 7 in both operands (best copy first), plus a distance TIE between
    # the two copies of id 5 — ties must retire together, not fill 2 slots
    cand_d = jnp.asarray([[1.0, 2.0, 1.0, 3.0, 4.0, 4.0]], jnp.float32)
    cand_i = jnp.asarray([[7, 3, 7, 9, 5, 5]], jnp.int32)

    dup_d, dup_i = merge_topk_rounds(cand_d, cand_i, 3)
    assert np.asarray(dup_i).tolist() == [[7, 7, 3]]      # the bug, pinned

    uniq_d, uniq_i = merge_topk_unique_rounds(cand_d, cand_i, 3)
    want_d, want_i = topk_unique(cand_d, cand_i, 3)
    np.testing.assert_array_equal(np.asarray(uniq_i), np.asarray(want_i))
    np.testing.assert_array_equal(np.asarray(uniq_d), np.asarray(want_d))
    assert np.asarray(uniq_i).tolist() == [[7, 3, 9]]

    # wider than the distinct-id count: unique pads with (+inf, -1)
    pad_d, pad_i = merge_topk_unique_rounds(cand_d, cand_i, 6)
    assert np.asarray(pad_i).tolist() == [[7, 3, 9, 5, -1, -1]]
    assert np.isinf(np.asarray(pad_d)[0, 4:]).all()
