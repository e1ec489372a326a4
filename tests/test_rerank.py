"""Fused candidate-rerank kernel (ISSUE 5): parity + memory-model harness.

The contract everything rests on: both ``rerank_topk`` paths (the XLA
streaming fold and the Pallas kernel) return exactly what the canonical
``topk_unique`` over the materialized gather returns — masked ``-1``
candidates never win, duplicate ids collapse to their best distance even
when the copies span candidate-block boundaries, and short windows pad
with (+inf, -1).  Parity granularity (documented in
``kernels/rerank_topk/ops.py``): neighbor ids are bit-identical across
materialized / fold / kernel in every mode, hamming distances too
(integer popcounts); float distances agree to the ulp — blocking changes
the dot shapes XLA vectorizes over.

List level: IVF's probed lists are runs of its cluster-major corpus, and
the list-slice path (``rerank_lists``) reads them as slices; it returns
the row path's ids bit for bit over the same window, at the edges of the
run layout (a list longer than a chunk, an empty list, a list ending at
row n-1, starts off the 8-row tiles) and under the traced knobs.

Algorithm level: all six candidate-rerank algorithms (IVF, HyperplaneLSH,
E2LSH, RPForest, BitsamplingAnnoy, MultiIndexHashing) are pinned
materialized == fold == kernel per algorithm, and the kernel path keeps
the one-trace-per-sweep guarantee from tests/test_sweep.py.
"""

import numpy as np
import pytest

import jax.numpy as jnp

from repro.ann import functional
from repro.ann.functional import get_functional, search_sweep
from repro.ann.topk import topk_unique
from repro.kernels.rerank_topk import (PATH_COUNTS, merge_topk_unique_rounds,
                                       pick_rerank_block, rerank_lists,
                                       rerank_topk, rerank_topk_ref)

METRICS = ("euclidean", "angular", "hamming")


def _case(metric, b=9, C=150, n=260, d=18, seed=0, mask_frac=0.15):
    """A candidate window with -1 masks and duplicate ids that straddle
    any block boundary <= 50 (dups at offsets 0..20 vs 50..70 vs C-20..C)."""
    rng = np.random.default_rng(seed)
    if metric == "hamming":
        X = rng.integers(0, 2**32, (n, 4), dtype=np.uint64).astype(np.uint32)
        Q = rng.integers(0, 2**32, (b, 4), dtype=np.uint64).astype(np.uint32)
        xsq = None
    else:
        X = rng.standard_normal((n, d)).astype(np.float32)
        Q = rng.standard_normal((b, d)).astype(np.float32)
        if metric == "angular":
            X /= np.linalg.norm(X, axis=1, keepdims=True)
            Q /= np.linalg.norm(Q, axis=1, keepdims=True)
        xsq = jnp.sum(jnp.asarray(X) ** 2, axis=1) \
            if metric == "euclidean" else None
    cand = rng.integers(0, n, (b, C)).astype(np.int32)
    cand[:, 50:70] = cand[:, 0:20]            # duplicates across blocks
    cand[:, -20:] = cand[:, 0:20]
    cand[rng.random((b, C)) < mask_frac] = -1
    return jnp.asarray(Q), jnp.asarray(X), jnp.asarray(cand), xsq


def _assert_dists(metric, want, got):
    if metric == "hamming":
        np.testing.assert_array_equal(np.asarray(want), np.asarray(got))
    else:
        np.testing.assert_allclose(np.asarray(want), np.asarray(got),
                                   rtol=1e-6, atol=1e-5)


@pytest.mark.parametrize("metric", METRICS)
@pytest.mark.parametrize("block", [32, 50, 128, 1024])
def test_fold_matches_materialized_oracle(metric, block):
    """XLA streaming fold == one-shot topk_unique over the full gather:
    ids bit for bit at any block size (including block > C one-shot),
    distances to the documented granularity."""
    Q, X, cand, xsq = _case(metric)
    rd, ri = rerank_topk_ref(Q, X, cand, k=12, metric=metric, xsq=xsq)
    fd, fi = rerank_topk(Q, X, cand, k=12, metric=metric, xsq=xsq,
                         block=block)
    np.testing.assert_array_equal(np.asarray(ri), np.asarray(fi))
    _assert_dists(metric, rd, fd)


@pytest.mark.parametrize("metric", METRICS)
def test_kernel_matches_fold(metric):
    """Pallas kernel path: ids bit-identical in every mode; distances
    bit-identical for hamming, ulp-close for float modes."""
    Q, X, cand, xsq = _case(metric, seed=3)
    fd, fi = rerank_topk(Q, X, cand, k=11, metric=metric, xsq=xsq, block=64)
    kd, ki = rerank_topk(Q, X, cand, k=11, metric=metric, xsq=xsq, block=64,
                         use_kernel=True)
    np.testing.assert_array_equal(np.asarray(fi), np.asarray(ki))
    if metric == "hamming":
        np.testing.assert_array_equal(np.asarray(fd), np.asarray(kd))
    else:
        np.testing.assert_allclose(np.asarray(fd), np.asarray(kd),
                                   rtol=1e-6, atol=1e-5)


@pytest.mark.parametrize("use_kernel", [False, True])
def test_valid_mask_and_row_ids(use_kernel):
    """Traced-knob-style validity masks flow in as an input; row_ids remap
    gather rows to output ids (IVF's cluster-major layout)."""
    Q, X, cand, xsq = _case("euclidean", seed=5)
    rng = np.random.default_rng(7)
    valid = jnp.asarray(rng.random(cand.shape) > 0.3)
    row_ids = jnp.asarray(rng.permutation(X.shape[0]).astype(np.int32))
    kw = dict(k=10, metric="euclidean", xsq=xsq, valid=valid,
              row_ids=row_ids)
    rd, ri = rerank_topk_ref(Q, X, cand, **kw)
    gd, gi = rerank_topk(Q, X, cand, block=64, use_kernel=use_kernel, **kw)
    np.testing.assert_array_equal(np.asarray(ri), np.asarray(gi))
    np.testing.assert_allclose(np.asarray(rd), np.asarray(gd),
                               rtol=1e-6, atol=1e-5)
    # masked-out ids may never appear in the output
    dead = set(np.asarray(row_ids)[np.asarray(cand)[~np.asarray(valid)
                                                    & (np.asarray(cand) >= 0)]]
               .ravel().tolist())
    live = set(np.asarray(gi).ravel().tolist()) - {-1}
    masked_everywhere = dead - set(
        np.asarray(row_ids)[np.asarray(cand)[np.asarray(valid)
                                             & (np.asarray(cand) >= 0)]]
        .ravel().tolist())
    assert not (live & masked_everywhere)


@pytest.mark.parametrize("use_kernel", [False, True])
def test_short_window_and_all_masked(use_kernel):
    """n_cand < k returns a C-wide result; fully-masked rows pad (+inf,-1)."""
    rng = np.random.default_rng(2)
    X = jnp.asarray(rng.standard_normal((40, 8)).astype(np.float32))
    Q = jnp.asarray(rng.standard_normal((3, 8)).astype(np.float32))
    xsq = jnp.sum(X * X, axis=1)
    cand = jnp.asarray(rng.integers(0, 40, (3, 5)).astype(np.int32))
    rd, ri = rerank_topk_ref(Q, X, cand, k=20, metric="euclidean", xsq=xsq)
    gd, gi = rerank_topk(Q, X, cand, k=20, metric="euclidean", xsq=xsq,
                         use_kernel=use_kernel)
    assert gi.shape == (3, 5)
    np.testing.assert_array_equal(np.asarray(ri), np.asarray(gi))

    dead = jnp.full((3, 6), -1, jnp.int32)
    dd, di = rerank_topk(Q, X, dead, k=4, metric="euclidean", xsq=xsq,
                         use_kernel=use_kernel)
    assert np.all(np.asarray(di) == -1) and np.all(np.isinf(np.asarray(dd)))


def test_euclidean_requires_xsq():
    Q, X, cand, xsq = _case("euclidean")
    with pytest.raises(ValueError, match="xsq"):
        rerank_topk(Q, X, cand, k=5, metric="euclidean")


def test_merge_unique_rounds_equals_topk_unique():
    """The kernel's VPU-only select == the canonical lexsort select, bit
    for bit, under heavy ties and duplicates."""
    rng = np.random.default_rng(11)
    d = rng.integers(0, 4, (6, 64)).astype(np.float32)   # many exact ties
    ids = rng.integers(0, 12, (6, 64)).astype(np.int32)  # many duplicates
    d[ids < 0] = np.inf
    mask = rng.random((6, 64)) < 0.2
    d[mask], ids[mask] = np.inf, -1
    for k in (1, 5, 13):
        wd, wi = topk_unique(jnp.asarray(d), jnp.asarray(ids), k)
        gd, gi = merge_topk_unique_rounds(jnp.asarray(d), jnp.asarray(ids), k)
        np.testing.assert_array_equal(np.asarray(wi), np.asarray(gi))
        np.testing.assert_array_equal(np.asarray(wd), np.asarray(gd))


def test_pick_rerank_block_bounds():
    small = pick_rerank_block(1, 64, 8, 10)
    assert small >= 64                                   # tiny C: one-shot
    big = pick_rerank_block(512, 1 << 20, 512, 100)
    assert 128 <= big <= 4096                            # floors at 128
    assert pick_rerank_block(256, 8192, 128, 10) < 4096  # budget bites


@pytest.mark.parametrize("use_kernel", [False, True])
def test_empty_candidate_window(use_kernel):
    """C == 0: a well-formed empty result, not a crash (both paths)."""
    rng = np.random.default_rng(4)
    X = jnp.asarray(rng.standard_normal((10, 6)).astype(np.float32))
    Q = jnp.asarray(rng.standard_normal((3, 6)).astype(np.float32))
    d, i = rerank_topk(Q, X, jnp.zeros((3, 0), jnp.int32), k=5,
                       metric="euclidean", xsq=jnp.sum(X * X, axis=1),
                       use_kernel=use_kernel)
    assert d.shape == (3, 0) and i.shape == (3, 0)


# ------------------------------------------------- algorithm-level parity
# All six candidate-rerank algorithms: materialized (rerank_block >= C,
# the seed behaviour) == autotuned streaming fold == Pallas kernel path,
# pinned per algorithm on its own index layout.
ALGO_CASES = {
    "IVF": ("small_dataset", {"n_clusters": 20}, {"n_probes": 8}),
    "HyperplaneLSH": ("small_angular",
                      {"n_tables": 4, "n_bits": 8, "cap": 32},
                      {"n_probes": 3}),
    "E2LSH": ("small_dataset",
              {"n_tables": 4, "n_hashes": 6, "width": 2.0, "cap": 32},
              {"n_probes": 3}),
    "RPForest": ("small_dataset", {"n_trees": 4, "leaf_size": 16},
                 {"probe": 2}),
    "BitsamplingAnnoy": ("small_hamming", {"n_trees": 4}, {"probe": 2}),
    "MultiIndexHashing": ("small_hamming", {"n_chunks": 16, "cap": 32},
                          {"radius": 1}),
}


@pytest.mark.parametrize("name", sorted(ALGO_CASES))
def test_algorithm_rerank_paths_agree(name, request):
    fixture, build_params, qp = ALGO_CASES[name]
    ds = request.getfixturevalue(fixture)
    spec = get_functional(name)
    Q = ds.test[:8]
    mat = spec.build(ds.train, metric=ds.metric, rerank_block=1 << 30,
                     **build_params)
    fold = spec.build(ds.train, metric=ds.metric, **build_params)
    kern = spec.build(ds.train, metric=ds.metric, rerank_kernel=True,
                      rerank_block=64, **build_params)
    dm, im = spec.search(mat, Q, k=10, **qp)
    df, if_ = spec.search(fold, Q, k=10, **qp)
    dk, ik = spec.search(kern, Q, k=10, **qp)
    np.testing.assert_array_equal(np.asarray(im), np.asarray(if_),
                                  err_msg=f"{name}: fold != materialized")
    _assert_dists(ds.metric, dm, df)
    np.testing.assert_array_equal(np.asarray(if_), np.asarray(ik),
                                  err_msg=f"{name}: kernel != fold")
    _assert_dists(ds.metric, df, dk)


# ------------------------------------------------- list-slice path
# One corpus for every case, so each metric compiles the list kernel once:
# n = 1003 (off the 8- and 128-row tiles), lists of odd sizes (unaligned
# starts), one of 300 rows (over the 128-row chunk), one empty, the last
# ending at row n-1.
LIST_SIZES = (37, 0, 300, 45, 9, 131, 1, 77, 66, 91, 12, 234)
LIST_CASES = {
    # case: (first probe of every query, n_probes, scan)
    "long_list": (2, None, None),
    "empty_list": (1, None, None),
    "ends_at_n": (len(LIST_SIZES) - 1, None, None),
    "unaligned_starts": (None, None, None),
    "traced_n_probes": (None, 2, None),
    "traced_scan": (2, None, 37),
}


def _list_case(metric, first, b=11, P=4, d=16, seed=0):
    rng = np.random.default_rng(seed)
    sizes = np.asarray(LIST_SIZES, np.int32)
    n = int(sizes.sum())
    starts = np.concatenate([[0], np.cumsum(sizes)[:-1]]).astype(np.int32)
    X = rng.standard_normal((n, d)).astype(np.float32)
    Q = rng.standard_normal((b, d)).astype(np.float32)
    if metric == "angular":
        X /= np.linalg.norm(X, axis=1, keepdims=True)
        Q /= np.linalg.norm(Q, axis=1, keepdims=True)
    probes = np.stack([rng.permutation(len(sizes))[:P] for _ in range(b)])
    if first is not None:
        probes[:, 0] = first
        probes[:, 1:] = np.where(probes[:, 1:] == first,
                                 (first + 1) % len(sizes), probes[:, 1:])
    xsq = jnp.sum(jnp.asarray(X) ** 2, axis=1) \
        if metric == "euclidean" else None
    ids = rng.permutation(n).astype(np.int32)
    return (jnp.asarray(Q), jnp.asarray(X), starts[probes], sizes[probes],
            jnp.asarray(ids), xsq, int(sizes.max()))


@pytest.mark.parametrize("case", sorted(LIST_CASES))
@pytest.mark.parametrize("metric", ["euclidean", "angular"])
def test_list_path_matches_row_path(metric, case):
    """rerank_lists over the probed runs == the row kernel and the oracle
    over IVF's window (cand = start + arange(window), masked past each
    list, past n_probes and past the scan budget): ids bit for bit."""
    first, n_probes, scan = LIST_CASES[case]
    Q, X, st, sz, ids, xsq, M = _list_case(metric, first)
    n = X.shape[0]
    b, P = st.shape
    if case == "ends_at_n":
        assert st[0, 0] + sz[0, 0] == n
    if case == "unaligned_starts":
        assert np.any(st % 8 != 0) and np.all(sz <= M)
    offs = np.arange(M)
    valid = offs < sz[..., None]
    if n_probes is not None:
        valid = valid & (np.arange(P) < n_probes)[None, :, None]
    if scan is not None:
        valid = valid & (offs < scan)
    cand = jnp.asarray(np.minimum(st[..., None] + offs, n - 1).reshape(b, -1))
    kw = dict(k=10, metric=metric, xsq=xsq, row_ids=ids,
              valid=jnp.asarray(valid.reshape(b, -1)))
    rd, ri = rerank_topk_ref(Q, X, cand, **kw)
    _, ki = rerank_topk(Q, X, cand, use_kernel=True, block=128, **kw)
    traced = (lambda v: None if v is None else jnp.int32(v))
    ld, li = rerank_lists(Q, X, jnp.asarray(st), jnp.asarray(sz), k=10,
                          metric=metric, ids=ids, xsq=xsq,
                          n_probes=traced(n_probes), scan=traced(scan),
                          window=M, block=128)
    np.testing.assert_array_equal(np.asarray(ri), np.asarray(li))
    np.testing.assert_array_equal(np.asarray(ki), np.asarray(li))
    _assert_dists(metric, rd, ld)
    if case == "empty_list":            # the empty probe adds no candidate
        np.testing.assert_array_equal(np.asarray(li) >= 0,
                                      np.isfinite(np.asarray(ld)))


@pytest.fixture
def path_counter():
    PATH_COUNTS.clear()
    yield PATH_COUNTS
    PATH_COUNTS.clear()


PATH_CASES = {
    # name: (algo, fixture, build params, query params, path taken)
    "ivf_sift1m_flags": ("IVF", "small_dataset",
                         {"n_clusters": 20, "rerank_kernel": True},
                         {"n_probes": 8, "max_probes": 8}, "lists"),
    "ivf_quantized": ("IVF", "small_dataset",
                      {"n_clusters": 20, "rerank_kernel": True,
                       "quantize": {"pq": {"m": 4}}},
                      {"n_probes": 8, "max_probes": 8}, "rows"),
    "lsh": ("HyperplaneLSH", "small_angular",
            {"n_tables": 4, "n_bits": 8, "cap": 32, "rerank_kernel": True},
            {"n_probes": 3}, "rows"),
}


@pytest.mark.parametrize("name", sorted(PATH_CASES))
def test_engine_rerank_path(name, request, path_counter):
    """The path is chosen by the candidates' structure: an Engine over
    the benchmark's IVF build flags (chipbench sift1m-ivf1000) traces the
    list path; quantized IVF's ADC survivors and LSH's buckets are
    arbitrary row ids and keep the row path."""
    from repro.serve import Engine

    algo, fixture, build, query, path = PATH_CASES[name]
    ds = request.getfixturevalue(fixture)
    eng = Engine.build(algo, ds.train, metric=ds.metric, build_params=build,
                       query_params=query, k=10, batch_size=16)
    path_counter.clear()
    _, ids = eng.search(ds.test[:20])
    assert np.asarray(ids).shape == (20, 10)
    assert path_counter[path] >= 1
    assert dict(path_counter) == {path: path_counter[path]}


# ------------------------------------------------- traced knobs x kernel
@pytest.fixture
def trace_counter():
    functional.TRACE_COUNTS.clear()
    yield functional.TRACE_COUNTS
    functional.TRACE_COUNTS.clear()


def test_kernel_path_single_trace_knob_sweep(small_dataset, trace_counter):
    """The one-trace-per-sweep guarantee (tests/test_sweep.py) survives the
    kernel path: the traced n_probes/scan validity masks flow into the
    kernel as inputs, so sweeping them re-uses ONE trace, with parity to
    the static XLA fold path at every value."""
    spec = get_functional("IVF")
    kern = spec.build(small_dataset.train, metric="euclidean",
                      n_clusters=20, rerank_kernel=True, rerank_block=128)
    fold = spec.build(small_dataset.train, metric="euclidean",
                      n_clusters=20, rerank_block=128)
    Q = small_dataset.test[:8]
    jq = spec.jit_search(traced=("n_probes", "scan"))
    trace_counter.clear()
    for n_probes, scan in [(1, 8), (4, 32), (12, 8), (20, 32)]:
        _, ids = jq(kern, Q, k=10, n_probes=n_probes, scan=scan,
                    max_probes=20, max_scan=32)
        _, want = spec.search(fold, Q, k=10, n_probes=n_probes, scan=scan)
        w = np.asarray(want).shape[1]    # static path may be < k wide;
        np.testing.assert_array_equal(   # traced tail is (+inf,-1) padding
            np.asarray(ids)[:, :w], np.asarray(want),
            err_msg=f"kernel traced ({n_probes},{scan}) != static fold")
        assert np.all(np.asarray(ids)[:, w:] == -1)
    assert trace_counter["IVF"] == 1, (
        f"kernel path retraced: {trace_counter['IVF']} traces")


def test_kernel_path_search_sweep_single_trace(small_dataset, trace_counter):
    """search_sweep (vmap over the knob grid) composes with the kernel
    path too — one trace for the whole grid, rows == the static path."""
    spec = get_functional("IVF")
    kern = spec.build(small_dataset.train, metric="euclidean",
                      n_clusters=20, rerank_kernel=True, rerank_block=128)
    Q = small_dataset.test[:4]
    values = (1, 4, 12)
    trace_counter.clear()
    _, ids = search_sweep(kern, Q, k=10, knob_grid={"n_probes": values})
    assert trace_counter["IVF"] == 1
    for i, v in enumerate(values):
        _, want = spec.search(kern, Q, k=10, n_probes=v)
        np.testing.assert_array_equal(np.asarray(ids)[i], np.asarray(want))
