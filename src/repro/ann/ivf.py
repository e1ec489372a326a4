"""IVF — inverted file index with a k-means coarse quantizer (the FAISS-IVF
analogue from the paper's Table 2, "other: inverted file").

TPU adaptation (DESIGN.md §2.5): inverted lists are stored *cluster-major*
(corpus sorted by assigned centroid, plus offsets ``starts`` / ``sizes``),
so every probed list is one contiguous run of corpus rows — turning the
CPU's pointer-chasing list scan into slice reads + a running top-k that
lower cleanly onto TPU.

Functional core: ``build(X, n_clusters=...) -> IndexState`` (host k-means,
device arrays), ``search(state, Q, k, n_probes, max_probes)`` pure.  The
query-time knob ``n_probes`` is *traced-or-static*:

  * static (default): ``max_probes=None`` pins the candidate window to
    ``n_probes`` lists — one trace per probe count (legacy behaviour);
  * traced: pass a static ``max_probes`` cap and ``n_probes`` may be a
    runtime value (python int or scalar array) — probes beyond
    ``n_probes`` are masked out, so ONE trace serves every query-args
    group up to the cap.  This is what lets the serving engine sweep the
    recall/QPS knob without recompilation.

Rerank, two paths chosen by the candidates' structure:

  * runs (the plain fp32 search under the ``rerank_kernel`` build flag):
    :func:`repro.kernels.rerank_topk.rerank_lists` takes each probed
    list's start and size and the traced ``n_probes`` / ``scan``, and its
    kernel copies the lists as chunked slices straight into VMEM; chunks
    past a list's end or the scan budget, and probes past ``n_probes``,
    are never read;
  * row ids (the fold without the flag, ``live=`` / ``id_map=``, and the
    quantized builds' ADC survivors): a fixed ``max_probes x max_list``
    window of row ids with a validity mask goes through the shared
    streaming fold (:func:`repro.kernels.rerank_topk.rerank_topk`) —
    candidate blocks folded into a running unique-by-id (dist, id) top-k,
    peak memory O(b * (block + k)) plus one [b, block, d] gathered chunk —
    or, under ``rerank_kernel``, its Pallas kernel, one row DMA per slot.

Both return the same ids bit for bit over the same window.
``rerank_block`` overrides the autotuned block (the list kernel's chunk).
``streaming`` survives as an accepted no-op (the fold subsumes it).
"""

from __future__ import annotations

from typing import Optional

import numpy as np

import jax
import jax.numpy as jnp

from repro.ann import distances as D
from repro.ann.functional import (FunctionalSpec, IndexState, prepare_points,
                                  prepare_queries, register_functional)
from repro.ann.kmeans import kmeans
from repro.core.interface import FunctionalANN
from repro.core.registry import register
from repro.kernels.rerank_topk import rerank_lists, rerank_topk


# --------------------------------------------------------------- functional
def build(X: np.ndarray, *, metric: str = "euclidean",
          n_clusters: int = 100, n_iters: int = 10, seed: int = 0,
          streaming: bool = False, rerank_block=None,
          rerank_kernel: bool = False, quantize=None,
          keep_fp32: bool = True, adc_block=None) -> IndexState:
    """Host k-means + cluster-major corpus layout -> device IndexState.

    ``quantize`` adds the compressed-domain scan stage (README
    "Compressed-domain search"): each inverted list stores its members'
    packed :mod:`repro.quant` codes (cluster-major, like the corpus), the
    probed window is scored by ADC lookups — ``m`` code bytes per
    candidate instead of a ``4d``-byte fp32 row — and only the ``n_cand``
    ADC survivors go through the exact fp32 rerank.  ``keep_fp32=False``
    drops the fp32 corpus (and its norms table): the ADC ordering, exact
    over the dequantized corpus, is then the answer.
    """
    X = prepare_points(X, metric)
    n, d = X.shape
    C = min(int(n_clusters), n)
    centers, assign = kmeans(X, C, n_iters=int(n_iters), seed=int(seed))
    order = np.argsort(assign, kind="stable")
    sizes = np.bincount(assign, minlength=C)
    starts = np.zeros(C + 1, np.int64)
    np.cumsum(sizes, out=starts[1:])
    arrays = {
        "centers": jnp.asarray(centers),
        "X": jnp.asarray(X[order]),
        "ids": jnp.asarray(order.astype(np.int32)),
        "starts": jnp.asarray(starts[:-1].astype(np.int32)),
        "sizes": jnp.asarray(sizes.astype(np.int32)),
    }
    if metric == "euclidean":
        arrays["xsq"] = jnp.sum(arrays["X"] ** 2, axis=1)
    static = {
        "n": n, "d": d, "n_clusters": C, "pad": int(sizes.max()),
        "streaming": bool(streaming), "rerank_kernel": bool(rerank_kernel),
        "rerank_block": None if rerank_block is None else int(rerank_block),
        "quant": None,
    }
    if quantize is not None:
        from repro import quant

        qarrays, qstatic = quant.train_codec(X, quantize, metric=metric)
        # codes follow the cluster-major corpus order, so the probed
        # window's row indices address codes and fp32 rows identically
        arrays["codes"] = jnp.asarray(np.asarray(qarrays["codes"])[order])
        arrays["codebooks"] = qarrays["codebooks"]
        if not keep_fp32:
            arrays.pop("X")
            arrays.pop("xsq", None)
        static.update({
            "quant": qstatic, "keep_fp32": bool(keep_fp32),
            "adc_block": None if adc_block is None else int(adc_block),
        })
    return IndexState("IVF", metric, arrays, static)


def search(state: IndexState, Q, *, k: int, n_probes=1, scan=None,
           n_cand=None, max_probes: Optional[int] = None,
           max_scan: Optional[int] = None,
           max_cand: Optional[int] = None, live=None, id_map=None):
    """Q [b, d] -> (dists [b, kk], ids [b, kk]).  Fully jittable.

    ``live`` ([n] bool, indexed by corpus row) folds tombstones into the
    rerank's validity mask — dead rows can never surface, even on ties;
    ``id_map`` ([n] int32) relabels corpus rows with external ids, and the
    rerank's canonical unique select then orders by those external ids
    (the :mod:`repro.mutate` bitwise-oracle contract).

    Three traced-capable query knobs:

    ``n_probes`` / ``max_probes``   how many inverted lists to probe.  The
        static cap sizes the probed-list window; ``n_probes`` may then be
        traced (see module docstring).  With ``max_probes=None``,
        ``n_probes`` must be a concrete int and is used as the window.
    ``scan`` / ``max_scan``   per-list scan budget: only the first ``scan``
        entries of each probed list are reranked (``None`` = whole list).
        Statically it narrows the gather window; under a static
        ``max_scan`` cap it is a traced runtime value masked in-kernel.
    ``n_cand`` / ``max_cand``   rerank depth, quantized builds only: how
        many ADC-scan survivors go through the exact fp32 rerank
        (``None`` = every probed candidate).  Statically it sizes the ADC
        top-C window; under a static ``max_cand`` cap it is a traced mask
        over the canonically-sorted ADC prefix — bit-identical to the
        static window (the ``topk_unique`` contract).

    Both rerank paths (module docstring) select canonically on the
    (id, dist) set exactly like ``topk_unique`` — so traced-mode masking
    (which shifts candidate positions) is bit-identical to the static path
    regardless of distance ties.
    """
    C = state.stat("n_clusters")
    n = state.stat("n")
    pad = state.stat("pad")
    quant = state.static.get("quant")
    if quant is not None and (live is not None or id_map is not None):
        raise ValueError(
            "live=/id_map= need the plain fp32 rerank path (the ADC scan "
            "has no tombstone mask input)")
    if quant is None and (n_cand is not None or max_cand is not None):
        raise ValueError(
            "n_cand/max_cand are the compressed-domain rerank knobs; "
            "build with quantize= to use them")
    if max_probes is None:
        P = min(int(n_probes), C)
    else:
        P = min(int(max_probes), C)
    if max_scan is None:
        M = pad if scan is None else max(1, min(int(scan), pad))
        scan = None                     # window == budget: no mask needed
    else:
        M = max(1, min(int(max_scan), pad))
    Q = prepare_queries(Q, state.metric)
    # 1. coarse quantizer: the P nearest centroids, probes past n_probes
    #    masked (traced knob) so one trace serves every probe count <= P
    cd = D.sq_l2_matrix(Q, state["centers"])             # [b, C]
    _, probes = jax.lax.top_k(-cd, P)                    # [b, P]
    starts = state["starts"][probes]                     # [b, P]
    sizes = state["sizes"][probes]                       # [b, P]
    if quant is None and live is None and id_map is None \
            and state.static.get("rerank_kernel", False):
        # 2a. each probed list is one run of the cluster-major corpus: the
        #     kernel reads the runs as slices, probes past n_probes and
        #     rows past the scan budget never leave HBM
        return rerank_lists(
            Q, state["X"], starts, sizes, k=k, metric=state.metric,
            ids=state["ids"], xsq=state.arrays.get("xsq"),
            n_probes=n_probes, scan=scan, window=M,
            block=state.static.get("rerank_block"))
    # 2b. padded window of each probed list, entries past the traced scan
    #     budget masked (same treatment as the probe mask)
    probe_live = jnp.arange(P, dtype=jnp.int32) < n_probes       # [P]
    offs = jnp.arange(M, dtype=jnp.int32)                # [M]
    cand = starts[..., None] + offs[None, None, :]       # [b, P, M]
    valid = offs[None, None, :] < sizes[..., None]
    valid = valid & probe_live[None, :, None]
    if scan is not None:
        valid = valid & (offs[None, None, :] < jnp.maximum(scan, 1))
    cand = jnp.minimum(cand, n - 1).reshape(Q.shape[0], -1)
    valid = valid.reshape(Q.shape[0], -1)                # [b, P*M]
    if quant is not None:
        return _rerank_quantized(state, Q, cand, valid, k=k,
                                 n_cand=n_cand, max_cand=max_cand)
    # tombstones: `live` is indexed by corpus row, the gather window by
    # cluster-major position — translate through the ids permutation
    if live is not None:
        valid = valid & live[state["ids"]][cand]
    rids = state["ids"] if id_map is None \
        else id_map.astype(jnp.int32)[state["ids"]]
    # 3. exact distances on the candidate set: the shared streaming fold
    #    (optionally the fused Pallas kernel over the row ids), probe/scan
    #    validity masks flowing in as the fold's mask input
    return rerank_topk(
        Q, state["X"], cand, k=k, metric=state.metric,
        xsq=state.arrays.get("xsq"), row_ids=rids, valid=valid,
        block=state.static.get("rerank_block"),
        use_kernel=bool(state.static.get("rerank_kernel", False)))


def _rerank_quantized(state: IndexState, Q, cand, valid, *, k: int,
                      n_cand, max_cand):
    """Compressed-domain stage 3: ADC-score the probed window (m code
    bytes per candidate), keep the n_cand best, exact-rerank those."""
    from repro.kernels.adc_scan import adc_window_topk
    from repro.quant import build_luts

    Cw = cand.shape[1]
    if max_cand is None:
        W = Cw if n_cand is None else max(1, min(int(n_cand), Cw))
        n_cand = None                   # window == budget: no mask needed
    else:
        W = max(1, min(int(max_cand), Cw))
    luts = build_luts(state["codebooks"], Q, state.metric)
    adc_d, rows = adc_window_topk(
        state["codes"], luts, cand, k=W, valid=valid,
        block=state.static.get("adc_block"))
    live = None
    if n_cand is not None:
        live = (jnp.arange(W, dtype=jnp.int32) < n_cand)[None, :]
    if state.stat("keep_fp32"):
        return rerank_topk(
            Q, state["X"], rows, k=k, metric=state.metric,
            xsq=state.arrays.get("xsq"), row_ids=state["ids"], valid=live,
            block=state.static.get("rerank_block"),
            use_kernel=bool(state.static.get("rerank_kernel", False)))
    # no fp32 corpus retained: ADC ordering is the answer; map the
    # cluster-major rows back to corpus ids
    bad = rows < 0
    if live is not None:
        bad = bad | ~live
    adc_d = jnp.where(bad, jnp.inf, adc_d)
    ids = jnp.where(bad, -1, state["ids"][jnp.maximum(rows, 0)])
    kk = min(int(k), W)
    return adc_d[:, :kk], ids[:, :kk]


SPEC = register_functional(FunctionalSpec(
    name="IVF", build=build, search=search,
    query_params=("n_probes", "scan", "n_cand",
                  "max_probes", "max_scan", "max_cand"),
    query_defaults=(1, None, None, None, None, None),
    static_query_params=("n_probes", "scan", "n_cand",
                         "max_probes", "max_scan", "max_cand"),
    traced_knobs=(("n_probes", "max_probes"), ("scan", "max_scan"),
                  ("n_cand", "max_cand")),
))


# ------------------------------------------------------------ legacy class
@register("IVF")
class IVF(FunctionalANN):
    supported_metrics = ("euclidean", "angular")

    def __init__(self, metric: str, n_clusters: int = 100, n_iters: int = 10,
                 seed: int = 0, streaming: bool = False,
                 rerank_block=None, rerank_kernel: bool = False,
                 quantize=None, keep_fp32: bool = True):
        super().__init__(metric, build_params=dict(
            n_clusters=int(n_clusters), n_iters=int(n_iters), seed=int(seed),
            streaming=bool(streaming), rerank_block=rerank_block,
            rerank_kernel=bool(rerank_kernel), quantize=quantize,
            keep_fp32=bool(keep_fp32)))
        self.n_clusters = int(n_clusters)
        self.n_iters = int(n_iters)
        self.seed = int(seed)
        self.streaming = bool(streaming)      # accepted no-op (the shared
        self.rerank_block = rerank_block      # fold always streams)
        self.n_probes = 1
        self.name = f"IVF(C={n_clusters})"
        self._dist_comps = 0

    def _sync_state(self):
        st = self._state
        self._n = st.stat("n")
        self._d = st.stat("d")
        self._pad = st.stat("pad")
        self._sizes_np = np.asarray(st["sizes"])
        self._centers = st["centers"]

    def set_query_arguments(self, n_probes: int, scan=None,
                            n_cand=None) -> None:
        self.n_probes = int(n_probes)
        self._qparams["n_probes"] = min(self.n_probes, self.n_clusters)
        self._qparams["scan"] = None if scan is None else int(scan)
        if n_cand is not None:
            self._qparams["n_cand"] = int(n_cand)

    def _effective_scan(self) -> int:
        """Per-list window actually gathered: the scan budget when set
        (clamped to the pad), else the full list pad."""
        scan = self._qparams.get("scan")
        if scan is None:
            return self._pad
        return max(1, min(int(scan), self._pad))

    def _batch_block_size(self, k: int) -> int:
        # block queries so [b, P*M, d] stays bounded — M is the EFFECTIVE
        # scan window, not the full list pad (a tight scan budget shrinks
        # the gather, so bigger query blocks fit the same memory)
        nprobe = self._qparams["n_probes"]
        M = self._effective_scan()
        return max(1, 64_000_000 // max(nprobe * M * self._d, 1))

    def query(self, q: np.ndarray, k: int) -> np.ndarray:
        out = super().query(q, k)
        self._count_probes(np.asarray(q)[None, :])
        return out

    def batch_query(self, Q: np.ndarray, k: int) -> None:
        super().batch_query(Q, k)
        self._count_probes(Q)

    def _count_probes(self, Q):
        # distance computations = centroid scan + probed list sizes
        # (clamp to the BUILT cluster count C = min(n_clusters, n), like
        # the search path does; a per-list scan budget caps every probed
        # list at `scan` entries, so the count must clamp too — the
        # unclamped sum overcounts work the masked gather never does)
        nprobe = min(self._qparams["n_probes"], int(self._centers.shape[0]))
        cd = D.sq_l2_matrix(prepare_queries(Q, self.metric), self._centers)
        _, probes = jax.lax.top_k(-cd, nprobe)
        sizes = self._sizes_np[np.asarray(probes)]
        scan = self._qparams.get("scan")
        if scan is not None:
            sizes = np.minimum(sizes, max(1, int(scan)))
        self._dist_comps += int(sizes.sum()) \
            + Q.shape[0] * self._centers.shape[0]

    def get_additional(self):
        return {"dist_comps": self._dist_comps,
                "max_list_size": self._pad,
                "n_lists": int(self._centers.shape[0])}
