"""Hamming-space algorithms (paper §4 Q4 and Figure 9).

  * ``BruteForceHamming``  — XOR + popcount over packed uint32 codes
                             (exact; uses the Pallas popcount kernel in
                             batch mode when enabled).
  * ``BitsamplingAnnoy``   — the paper's Hamming-aware Annoy variant:
                             tree nodes split on a *single sampled bit*
                             (Bitsampling LSH) instead of hyperplanes, with
                             popcount rerank.
  * ``MultiIndexHashing``  — Norouzi et al.'s MIH: codes are split into m
                             contiguous chunks; a query probes, per chunk,
                             all buckets within chunk-radius r.  With
                             r >= ceil((t+1)/m)-1 for threshold t this is
                             the exact algorithm; we expose r as the query
                             parameter (r large enough => exact, smaller =>
                             approximate), matching the paper's observation
                             that MIH parameters strongly affect QPS.

All three share the dense sorted-bucket machinery from the LSH module and
the functional (build -> IndexState, pure search) core.  Points are packed
uint32 words; bits = 32 * words.
"""

from __future__ import annotations

import itertools

import numpy as np

import jax
import jax.numpy as jnp

from repro.ann.functional import (FunctionalSpec, IndexState,
                                  prepare_queries, register_functional)
from repro.ann.lsh import bucket_lookup, sorted_buckets
from repro.ann.rpforest import forest_window, mask_dead_trees
from repro.ann.topk import chunked_topk, topk_smallest
from repro.core.interface import FunctionalANN
from repro.core.registry import register
from repro.kernels.rerank_topk import rerank_topk


def _popcount_matrix(Q, X):
    x = jax.lax.bitwise_xor(Q[:, None, :].astype(jnp.uint32),
                            X[None, :, :].astype(jnp.uint32))
    return jnp.sum(jax.lax.population_count(x), axis=-1).astype(jnp.int32)


def _hamming_rerank(state: IndexState, Q, cand, k: int):
    """Popcount rerank of a [b, C] candidate-id window through the shared
    streaming fold (:func:`repro.kernels.rerank_topk.rerank_topk`, XOR +
    popcount mode): identical to the one-shot ``topk_unique`` while peak
    memory stays O(b * (block + k)).  The ``rerank_kernel`` build flag
    swaps in the fused Pallas kernel (packed words DMA'd into VMEM
    scratch); ``rerank_block`` overrides the autotuned block."""
    return rerank_topk(
        Q, state["X"], cand, k=k, metric="hamming",
        block=state.static.get("rerank_block"),
        use_kernel=bool(state.static.get("rerank_kernel", False)))


# ------------------------------------------------------- brute force popcount
def bruteforce_build(X: np.ndarray, *, metric: str = "hamming",
                     backend: str = "jnp", streaming: bool = False,
                     corpus_block: int = 65536,
                     query_block: int = 4096) -> IndexState:
    X = np.asarray(X, np.uint32)
    arrays = {"X": jnp.asarray(X)}
    if backend == "pallas":
        from repro.kernels.hamming import word_major

        arrays["XT"] = word_major(arrays["X"])    # the kernel's layout
    return IndexState("BruteForceHamming", metric, arrays, {
        "n": int(X.shape[0]), "backend": backend,
        "streaming": bool(streaming), "corpus_block": int(corpus_block),
        "query_block": int(query_block),
    })


def bruteforce_search(state: IndexState, Q, *, k: int):
    Q = prepare_queries(Q, "hamming")
    k = min(k, state.stat("n"))
    if state.stat("backend") == "pallas":
        from repro.kernels.hamming import ops as hops

        return hops.hamming_topk(Q, state["XT"], k=k)
    d = _popcount_matrix(Q, state["X"])
    return topk_smallest(d.astype(jnp.float32), k)


register_functional(FunctionalSpec(
    name="BruteForceHamming", build=bruteforce_build,
    search=bruteforce_search, supported_metrics=("hamming",),
))


@register("BruteForceHamming")
class BruteForceHamming(FunctionalANN):
    supported_metrics = ("hamming",)
    batch_block = 2048

    def __init__(self, metric: str, backend: str = "jnp",
                 streaming: bool = False, corpus_block: int = 65536,
                 query_block: int = 4096):
        super().__init__(metric, build_params=dict(
            backend=backend, streaming=bool(streaming),
            corpus_block=int(corpus_block), query_block=int(query_block)))
        self.backend = backend
        self.streaming = bool(streaming)
        self.corpus_block = int(corpus_block)
        self.query_block = int(query_block)
        suffix = ",streaming" if streaming else ""
        self.name = f"BruteForceHamming(backend={backend}{suffix})"
        self._dist_comps = 0

    def _sync_state(self):
        self._n = self._state.stat("n")

    def query(self, q, k):
        out = super().query(q, k)
        self._dist_comps += self._n
        return out

    def _batch_streaming(self, Qj, k):
        """Query-blocked corpus scan: per query block, stream corpus chunks
        through the fused Hamming top-k kernel and merge into a running
        (dist, id) accumulator — O(qblock * k) state, corpus never gathered
        whole."""
        X = self._state["X"]
        if self.backend == "pallas":
            from repro.kernels.hamming import ops as hops

            XT = self._state["XT"]

            def corpus_chunk(Qb):
                def chunk(s, size):
                    v, i = hops.hamming_topk(Qb, XT[:, s:s + size],
                                             k=min(k, size))
                    return v.astype(jnp.float32), i + s
                return chunk
        else:
            def corpus_chunk(Qb):
                def chunk(s, size):
                    d = _popcount_matrix(Qb, X[s:s + size])
                    ids = s + jnp.arange(size, dtype=jnp.int32)[None, :]
                    return (d.astype(jnp.float32),
                            jnp.broadcast_to(ids, d.shape))
                return chunk
        outs = []
        for qs in range(0, Qj.shape[0], self.query_block):
            Qb = Qj[qs:qs + self.query_block]
            _, ids = chunked_topk(self._n, k, self.corpus_block,
                                  corpus_chunk(Qb))
            outs.append(ids)
        return jnp.concatenate(outs, axis=0)

    def batch_query(self, Q, k):
        k = min(k, self._n)
        if self.streaming:
            Qj = jnp.asarray(np.asarray(Q, np.uint32))
            self._batch_results = jax.block_until_ready(
                self._batch_streaming(Qj, k))
            self._dist_comps += self._n * Q.shape[0]
        else:
            super().batch_query(Q, k)
            self._dist_comps += self._n * Q.shape[0]

    def get_additional(self):
        return {"dist_comps": self._dist_comps}


# ------------------------------------------------------- bitsampling forest
def bitsampling_build(X: np.ndarray, *, metric: str = "hamming",
                      n_trees: int = 10, leaf_size: int = 32, seed: int = 0,
                      streaming: bool = False, rerank_block=None,
                      rerank_kernel: bool = False) -> IndexState:
    """Annoy-style forest with single-bit splits (host build)."""
    X = np.asarray(X, np.uint32)
    n, w = X.shape
    bits = w * 32
    n_trees, leaf_size = int(n_trees), int(leaf_size)
    rng = np.random.default_rng(int(seed))
    max_depth = int(np.ceil(np.log2(
        max(2.0, n / max(1, leaf_size))))) + 6

    # Build: split on a random bit with the most even split among a few
    # tries (data-independent bitsampling, data-guided balance).
    trees_bits, trees_children, trees_leaves, roots = [], [], [], []
    host_bit = lambda pts, b: (pts[:, b // 32] >> (b % 32)) & 1  # noqa: E731

    for _ in range(n_trees):
        node_bits: list[int] = []
        children: list[list[int]] = []
        leaves: list[np.ndarray] = []

        def rec(ids: np.ndarray, depth: int) -> int:
            if len(ids) <= leaf_size or depth >= max_depth:
                leaves.append(ids)
                return -len(leaves)
            best_b, best_bal = None, -1.0
            for b in rng.integers(0, bits, size=4):
                side = host_bit(X[ids], int(b)).astype(bool)
                frac = side.mean()
                bal = min(frac, 1 - frac)
                if bal > best_bal:
                    best_bal, best_b = bal, int(b)
            side = host_bit(X[ids], best_b).astype(bool)
            if side.all() or (~side).all():
                side = rng.random(len(ids)) < 0.5
            node = len(node_bits)
            node_bits.append(best_b)
            children.append([0, 0])
            left = rec(ids[~side], depth + 1)
            right = rec(ids[side], depth + 1)
            children[node] = [left, right]
            return node

        roots.append(rec(np.arange(n), 0))
        trees_bits.append(node_bits)
        trees_children.append(children)
        trees_leaves.append(leaves)

    T = n_trees
    max_nodes = max(max(len(b), 1) for b in trees_bits)
    max_leaves = max(len(lv) for lv in trees_leaves)
    bits_arr = np.zeros((T, max_nodes), np.int32)
    child_arr = np.zeros((T, max_nodes, 2), np.int32)
    leaf_arr = np.full((T, max_leaves, leaf_size), -1, np.int32)
    for t in range(T):
        for i, (b, ch) in enumerate(zip(trees_bits[t], trees_children[t])):
            bits_arr[t, i], child_arr[t, i] = b, ch
        for li, ids in enumerate(trees_leaves[t]):
            leaf_arr[t, li, :len(ids)] = ids[:leaf_size]
    return IndexState("BitsamplingAnnoy", metric, {
        "X": jnp.asarray(X),
        "bits": jnp.asarray(bits_arr),
        "children": jnp.asarray(child_arr),
        "leaves": jnp.asarray(leaf_arr),
        "roots": jnp.asarray(np.asarray(roots, np.int32)),
    }, {"n": n, "w": w, "n_trees": T, "leaf_size": leaf_size,
        "depth": max_depth, "streaming": bool(streaming),
        "rerank_kernel": bool(rerank_kernel),
        "rerank_block": None if rerank_block is None else int(rerank_block)})


def _bitsampling_descend(state: IndexState, Q, cur):
    tree_ids = jnp.arange(cur.shape[1])[None, :]
    others = []
    for _ in range(state.stat("depth")):
        is_leaf = cur < 0
        node = jnp.maximum(cur, 0)
        b = state["bits"][tree_ids, node]                  # [bq, T]
        wsel = jnp.take_along_axis(
            Q.astype(jnp.uint32), (b // 32).astype(jnp.int32), axis=1)
        bit = (wsel >> (b % 32).astype(jnp.uint32)) & 1
        side = bit.astype(jnp.int32)
        nxt = state["children"][tree_ids, node, side]
        other = state["children"][tree_ids, node, 1 - side]
        others.append(jnp.where(is_leaf, cur, other))
        cur = jnp.where(is_leaf, cur, nxt)
    return cur, others


def bitsampling_search(state: IndexState, Q, *, k: int, probe: int = 1,
                       trees=None, max_probe=None, max_trees=None):
    """With ``max_probe`` (static) all cap leaves are descended and the
    candidates of alternates past the traced ``probe`` are masked to -1 —
    one trace serves every probe count up to the cap.  ``trees`` /
    ``max_trees`` is the same treatment along the tree axis (``None`` =
    all built trees): static it slices the forest, traced it masks dead
    trees' candidates — exact parity because the popcount rerank selects
    via ``topk_unique`` (canonical on the (id, dist) set)."""
    Q = prepare_queries(Q, "hamming")
    bq = Q.shape[0]
    T, trees = forest_window(state.stat("n_trees"), trees, max_trees)
    P = max(1, int(probe)) if max_probe is None else max(1, int(max_probe))
    start = jnp.broadcast_to(state["roots"][None, :T], (bq, T))
    leaf, others = _bitsampling_descend(state, Q, start)
    leaves = [leaf]
    # probe deepest not-taken branches (bit splits have no margins)
    for p in range(min(P - 1, len(others))):
        alt, _ = _bitsampling_descend(state, Q, others[-(p + 1)])
        leaves.append(alt)
    tree_ids = jnp.arange(T)[None, :]
    cands = []
    for j, lf in enumerate(leaves):
        lidx = jnp.maximum(-lf - 1, 0)
        pts = state["leaves"][tree_ids, lidx]
        pts = jnp.where((lf < 0)[..., None], pts, -1)
        pts = mask_dead_trees(pts, trees)               # traced trees knob
        if max_probe is not None and j > 0:
            # alternate j exists in the static path iff probe > j
            pts = jnp.where(jnp.asarray(probe) > j, pts, -1)
        cands.append(pts.reshape(bq, -1))
    cand = jnp.concatenate(cands, axis=1)
    return _hamming_rerank(state, Q, cand, k)


register_functional(FunctionalSpec(
    name="BitsamplingAnnoy", build=bitsampling_build,
    search=bitsampling_search,
    query_params=("probe", "trees", "max_probe", "max_trees"),
    query_defaults=(1, None, None, None),
    supported_metrics=("hamming",),
    traced_knobs=(("probe", "max_probe"), ("trees", "max_trees")),
))


@register("BitsamplingAnnoy")
class BitsamplingAnnoy(FunctionalANN):
    """Annoy with bit-sampling splits (paper Q4's 'A (Ham.)' variant)."""

    supported_metrics = ("hamming",)
    batch_block = 2048

    def __init__(self, metric: str, n_trees: int = 10, leaf_size: int = 32,
                 seed: int = 0, streaming: bool = False,
                 rerank_block=None, rerank_kernel: bool = False):
        super().__init__(metric, build_params=dict(
            n_trees=int(n_trees), leaf_size=int(leaf_size), seed=int(seed),
            streaming=bool(streaming), rerank_block=rerank_block,
            rerank_kernel=bool(rerank_kernel)))
        self.n_trees = int(n_trees)
        self.leaf_size = int(leaf_size)
        self.seed = int(seed)
        self.streaming = bool(streaming)
        self.rerank_block = rerank_block
        self.probe = 1
        self.name = f"BitsamplingAnnoy(T={n_trees},leaf={leaf_size})"
        self._dist_comps = 0

    def set_query_arguments(self, probe: int, trees=None) -> None:
        self.probe = max(1, int(probe))
        self._qparams["probe"] = self.probe
        self._qparams["trees"] = None if trees is None \
            else max(1, min(int(trees), self.n_trees))

    def query(self, q, k):
        out = super().query(q, k)
        self._dist_comps += self.n_trees * self.probe * self.leaf_size
        return out

    def batch_query(self, Q, k):
        super().batch_query(Q, k)
        self._dist_comps += Q.shape[0] * self.n_trees * self.probe * self.leaf_size

    def get_additional(self):
        return {"dist_comps": self._dist_comps}


# ------------------------------------------------------- multi-index hashing
def mih_build(X: np.ndarray, *, metric: str = "hamming",
              n_chunks: int = 16, cap: int = 128, seed: int = 0,
              streaming: bool = False, rerank_block=None,
              rerank_kernel: bool = False) -> IndexState:
    X = np.asarray(X, np.uint32)
    n, w = X.shape
    bits = w * 32
    m = int(n_chunks)
    chunk_bits = bits // m
    if chunk_bits > 30:
        raise ValueError("chunk too wide for int32 keys; use more chunks")
    # chunk substrings as int32 keys, one "table" per chunk
    keys = np.zeros((m, n), np.int32)
    unpacked = np.unpackbits(
        X.view(np.uint8), bitorder="little").reshape(n, bits)
    bit_weights = 2 ** np.arange(chunk_bits, dtype=np.int32)
    for c in range(m):
        seg = unpacked[:, c * chunk_bits:(c + 1) * chunk_bits]
        keys[c] = seg.astype(np.int64) @ bit_weights
    tkeys, tids = sorted_buckets(keys)
    return IndexState("MultiIndexHashing", metric, {
        "X": jnp.asarray(X), "keys": tkeys, "ids": tids,
        "bit_weights": jnp.asarray(bit_weights),
    }, {"n": n, "w": w, "n_chunks": m, "chunk_bits": chunk_bits,
        "cap": int(cap), "streaming": bool(streaming),
        "rerank_kernel": bool(rerank_kernel),
        "rerank_block": None if rerank_block is None else int(rerank_block)})


def _mih_query_chunks(state: IndexState, Q):
    """Q [b, w] uint32 -> chunk keys [b, m] int32 + bits [b, bits]."""
    bq = Q.shape[0]
    w = state.stat("w")
    chunk_bits = state.stat("chunk_bits")
    bits_total = w * 32
    words = Q.astype(jnp.uint32)
    shifts = jnp.arange(32, dtype=jnp.uint32)
    bits = ((words[:, :, None] >> shifts[None, None, :]) & 1)
    bits = bits.reshape(bq, bits_total).astype(jnp.int32)
    bw = state["bit_weights"]
    keys = [
        jnp.sum(bits[:, c * chunk_bits:(c + 1) * chunk_bits]
                * bw[None, :], axis=1)
        for c in range(state.stat("n_chunks"))
    ]
    return jnp.stack(keys, axis=1), bits


def mih_search(state: IndexState, Q, *, k: int, radius: int = 0,
               max_radius=None):
    """With ``max_radius`` (static) the probe-key tensor is enumerated at
    the cap and columns whose flip count exceeds the traced ``radius`` get
    key -1 (chunk keys are non-negative bit sums, so the lookup matches
    nothing) — one trace serves every radius up to the cap."""
    Q = prepare_queries(Q, "hamming")
    bq = Q.shape[0]
    m = state.stat("n_chunks")
    chunk_bits = state.stat("chunk_bits")
    R = int(radius) if max_radius is None else int(max_radius)
    base, bits = _mih_query_chunks(state, Q)               # [b, m]
    # probe keys: all chunk codes within hamming radius <= R
    flips: list[tuple[int, ...]] = [()]
    for r in range(1, R + 1):
        flips += list(itertools.combinations(range(chunk_bits), r))
    probe_keys = []
    bw = state["bit_weights"]
    for f in flips:
        delta = jnp.zeros((bq, m), jnp.int32)
        for bitpos in f:
            for c in range(m):
                qb = bits[:, c * chunk_bits + bitpos]
                delta = delta.at[:, c].add(
                    jnp.where(qb > 0, -bw[bitpos], bw[bitpos]))
        probe_keys.append(base + delta)
    qkeys = jnp.stack(probe_keys, axis=-1)                 # [b, m, P]
    if max_radius is not None:
        flip_r = jnp.asarray([len(f) for f in flips])      # [P]
        live = flip_r <= jnp.maximum(radius, 0)
        qkeys = jnp.where(live[None, None, :], qkeys, -1)
    cand = bucket_lookup(state["keys"], state["ids"], qkeys,
                         state.stat("cap"))
    return _hamming_rerank(state, Q, cand, k)


register_functional(FunctionalSpec(
    name="MultiIndexHashing", build=mih_build, search=mih_search,
    query_params=("radius", "max_radius"), query_defaults=(0, None),
    supported_metrics=("hamming",),
    traced_knobs=(("radius", "max_radius"),),
))


@register("MultiIndexHashing")
class MultiIndexHashing(FunctionalANN):
    supported_metrics = ("hamming",)
    batch_block = 1024

    def __init__(self, metric: str, n_chunks: int = 16, cap: int = 128,
                 seed: int = 0, streaming: bool = False,
                 rerank_block=None, rerank_kernel: bool = False):
        super().__init__(metric, build_params=dict(
            n_chunks=int(n_chunks), cap=int(cap), seed=int(seed),
            streaming=bool(streaming), rerank_block=rerank_block,
            rerank_kernel=bool(rerank_kernel)))
        self.n_chunks = int(n_chunks)
        self.cap = int(cap)
        self.streaming = bool(streaming)
        self.rerank_block = rerank_block
        self.radius = 0
        self.name = f"MIH(m={n_chunks},cap={cap})"
        self._dist_comps = 0

    def set_query_arguments(self, radius: int) -> None:
        self.radius = int(radius)
        self._qparams["radius"] = self.radius

    def query(self, q, k):
        out = super().query(q, k)
        self._dist_comps += self.n_chunks * self.cap
        return out

    def batch_query(self, Q, k):
        super().batch_query(Q, k)
        self._dist_comps += Q.shape[0] * self.n_chunks * self.cap

    def get_additional(self):
        return {"dist_comps": self._dist_comps}
