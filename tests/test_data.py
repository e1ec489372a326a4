"""Datasets + ground truth."""

import numpy as np
import pytest

from repro.data import exact_knn, get_dataset
from repro.data.datasets import Dataset
from repro.data.graphs import CSRGraph, random_graph, sample_subgraph


def test_groundtruth_matches_naive(rng):
    X = rng.standard_normal((300, 16)).astype(np.float32)
    Q = rng.standard_normal((12, 16)).astype(np.float32)
    nbrs, dists = exact_knn(X, Q, 5, "euclidean", corpus_block=64)
    d_full = np.sqrt(((Q[:, None, :] - X[None, :, :]) ** 2).sum(-1))
    want = np.argsort(d_full, axis=1)[:, :5]
    np.testing.assert_allclose(dists, np.sort(d_full, axis=1)[:, :5],
                               rtol=1e-4, atol=1e-4)
    # ids equal up to ties: compare distances of chosen ids
    chosen = np.take_along_axis(d_full, nbrs, axis=1)
    np.testing.assert_allclose(chosen, np.sort(d_full, axis=1)[:, :5],
                               rtol=1e-4, atol=1e-4)


def test_rand_euclidean_planted_neighbors():
    """The paper's construction: each query's nearest neighbor must be a
    planted point at distance ~0.1 (locally easy)."""
    ds = get_dataset("random-euclidean-3000")
    assert ds.metric == "euclidean"
    np.testing.assert_allclose(ds.distances[:, 0], 0.1, atol=2e-2)
    # and the 10th neighbor at ~0.5
    np.testing.assert_allclose(ds.distances[:, 9], 0.5, atol=6e-2)


def test_dataset_cache_roundtrip(tmp_path):
    ds = get_dataset("blobs-euclidean-500", data_dir=tmp_path)
    again = get_dataset("blobs-euclidean-500", data_dir=tmp_path)
    np.testing.assert_array_equal(ds.train, again.train)
    assert (tmp_path / "blobs-euclidean-500.npz").exists()


def test_hamming_dataset_structure():
    ds = get_dataset("random-hamming-800-b64")
    assert ds.point_type == "bit"
    assert ds.train.dtype == np.uint32
    assert ds.dimension == 64
    # planted near-duplicates: NN distance well below random (~bits/2)
    assert ds.distances[:, 0].mean() < 16


def test_unknown_dataset():
    with pytest.raises(KeyError):
        get_dataset("no-such-dataset-42")


def test_random_graph_csr_consistency():
    g = random_graph(100, 500, 8, 4, seed=3)
    assert g.n_nodes == 100 and g.n_edges == 500
    src, dst = g.edge_list()
    assert len(src) == 500
    deg = np.bincount(dst, minlength=100)
    np.testing.assert_array_equal(deg, g.degrees())


def test_neighbor_sampler_fanout():
    g = random_graph(500, 5000, 8, 4, seed=4)
    rng = np.random.default_rng(0)
    sub = sample_subgraph(g, np.arange(32), (5, 3), rng)
    assert sub["mask"][:32].all() and not sub["mask"][32:].any()
    # edge count bounded by fanout budget
    assert len(sub["src"]) <= 32 * 5 + 32 * 5 * 3
    # all local ids valid
    assert sub["src"].max() < len(sub["feats"])
    assert sub["dst"].max() < len(sub["feats"])
    # sampled edges exist in the original graph
    nodes = np.asarray([k for k in range(len(sub["feats"]))])


def test_dataset_seed_is_a_stable_digest_of_the_name():
    """The same name gives the same data in every process: ``hash`` of a
    str is salted per process, the seed is not."""
    import subprocess
    import sys
    from pathlib import Path

    from repro.data.synthetic import seed_for

    assert seed_for("blobs-euclidean-1000000-d128") == 1213396170
    src = Path(__file__).resolve().parents[1] / "src"
    out = subprocess.run(
        [sys.executable, "-c",
         "from repro.data.synthetic import seed_for;"
         "print(seed_for('blobs-euclidean-1000000-d128'))"],
        env={"PYTHONPATH": str(src), "PYTHONHASHSEED": "12345",
             "JAX_PLATFORMS": "cpu"},
        capture_output=True, text=True, timeout=120)
    assert out.stdout.strip() == "1213396170", out.stderr[-2000:]


@pytest.mark.parametrize("metric", ["euclidean", "angular"])
def test_groundtruth_matches_float64_reference(metric):
    """Ground truth and the jnp scan agree with float64 numpy, on data
    whose large common offset makes a low-precision cross term misorder
    neighbours; the matmuls request full fp32 precision (a TPU's default
    is one bf16 pass)."""
    import jax

    from repro.ann import distances as D

    rng = np.random.default_rng(7)
    X = (rng.standard_normal((4000, 128)) + 3.0).astype(np.float32)
    Q = (rng.standard_normal((40, 128)) + 3.0).astype(np.float32)
    nbrs, dists = exact_knn(X, Q, 10, metric, corpus_block=1024)
    X64, Q64 = X.astype(np.float64), Q.astype(np.float64)
    if metric == "euclidean":
        ref = np.sqrt(((Q64[:, None, :] - X64[None, :, :]) ** 2).sum(-1))
    else:
        Xn = X64 / np.linalg.norm(X64, axis=1, keepdims=True)
        Qn = Q64 / np.linalg.norm(Q64, axis=1, keepdims=True)
        ref = 1.0 - Qn @ Xn.T
    want = np.argsort(ref, axis=1, kind="stable")[:, :10]
    np.testing.assert_array_equal(nbrs, want)
    np.testing.assert_allclose(dists, np.take_along_axis(ref, want, 1),
                               rtol=1e-5, atol=1e-5)
    fn = D.sq_l2_matrix if metric == "euclidean" else D.angular_matrix
    hlo = jax.jit(fn).lower(Q, X).as_text()
    assert "HIGHEST" in hlo
