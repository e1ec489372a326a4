"""Distributed ANN serving on the generic sharded layer
(:mod:`repro.dist.shard_state`): any built ``IndexState`` partitioned over
a device mesh, per-shard *streaming* local top-k (O(b*(block+k)) memory —
never the [b, ns] matrix), and the compressed hierarchical top-k merge
(:func:`repro.dist.collectives.tree_merge_topk`) instead of a flat f32
``all_gather``.

Two shard plans are registered here:

* **row plan** (BruteForce — plain, quantized, hamming): corpus rows are
  dealt round the shards; the local pass is a blockwise
  :mod:`repro.ann.distances` scan folded through ``chunked_topk`` (or the
  fused ``distance_topk`` kernel with ``use_kernel=True``, or the ADC scan
  + ``rerank_topk`` two-stage for quantized builds).
* **inverted-list plan** (IVF, quantized IVF): the coarse quantizer is
  replicated, whole inverted lists are greedy-balanced across shards
  (biggest cluster to lightest shard); each shard reranks only the probed
  lists it owns with the shared ``rerank_topk`` fold — the traced
  ``n_probes`` knob rides through ``shard_map`` as a replicated scalar.

Exactness invariant: each global id lives on exactly one shard and each
shard's local top-m retains every global top-k element it owns, so

    topk_k( tree_merge( union_s topk_m(shard_s) ) ) == topk_k(corpus)

with ids exact under the merge tree's wire-precision tie budget (see
``tree_merge_topk``; the u16 hamming codec is unconditionally exact).

States carry the mesh *recipe* (axis names + shape) in their static dict,
so they remain pure pytrees and checkpoints stay mesh-portable —
``search`` reconstructs (and caches) the shard_map'd function from the
recipe, ``repro.dist.shard_state.reshard`` moves a state to a different
shard count, and ``ensure_servable`` adapts restored checkpoints to the
local device count.
"""

from __future__ import annotations

from typing import Optional, Sequence

import numpy as np

import jax
import jax.numpy as jnp
from jax.sharding import Mesh

from repro.ann import distances as D
from repro.ann.functional import (FunctionalSpec, IndexState,
                                  register_functional)
from repro.ann.topk import chunked_topk
from repro.core.interface import FunctionalANN
from repro.core.registry import register
from repro.dist import shard_state as SS
from repro.kernels.rerank_topk import rerank_topk

# static keys added by the sharding layer, stripped again on unshard
_SHARD_STATIC = ("L", "n_shards", "wire_codec", "fan_in", "carry",
                 "shard_arrays", "inner_algo", "shard_axes", "mesh_shape")


def _inner_static(state: IndexState) -> dict:
    return {k: v for k, v in state.static.items() if k not in _SHARD_STATIC}


# ----------------------------------------------------------------- row plan
def _row_shard(inner: IndexState, S: int):
    """Deal corpus rows round ``S`` shards: [n, ...] -> [S, L, ...] with
    id -1 / +inf-norm sentinels on the pad rows."""
    n = inner.stat("n")
    L = max(1, -(-n // S))
    ids = np.full(S * L, -1, np.int32)
    ids[:n] = np.arange(n, dtype=np.int32)
    sh = {"ids": ids.reshape(S, L)}
    rep = {}
    for nm in ("X", "codes"):
        if nm in inner.arrays:
            a = np.asarray(inner[nm])
            ap = np.zeros((S * L,) + a.shape[1:], a.dtype)
            ap[:n] = a
            sh[nm] = ap.reshape((S, L) + a.shape[1:])
    if "xsq" in inner.arrays:
        xsq = np.full(S * L, np.inf, np.float32)
        xsq[:n] = np.asarray(inner["xsq"], np.float32)
        sh["xsq"] = xsq.reshape(S, L)
    if "codebooks" in inner.arrays:
        rep["codebooks"] = inner["codebooks"]
    static = dict(inner.static)
    static["L"] = L
    return sh, rep, static


def _row_unshard(state: IndexState) -> IndexState:
    n = state.stat("n")
    ids = np.asarray(state["ids"]).reshape(-1)
    sel = ids >= 0
    arrays = {}
    for nm in ("X", "codes"):
        if nm in state.arrays:
            flat = np.asarray(state[nm])
            flat = flat.reshape((-1,) + flat.shape[2:])
            out = np.zeros((n,) + flat.shape[1:], flat.dtype)
            out[ids[sel]] = flat[sel]
            arrays[nm] = jnp.asarray(out)
    if "xsq" in state.arrays:
        flat = np.asarray(state["xsq"]).reshape(-1)
        out = np.zeros(n, np.float32)
        out[ids[sel]] = flat[sel]
        arrays["xsq"] = jnp.asarray(out)
    if "codebooks" in state.arrays:
        arrays["codebooks"] = state["codebooks"]
    return IndexState(state.stat("inner_algo"), state.metric, arrays,
                      _inner_static(state))


def _row_local_plain(q, loc, env, metric: str, m: int):
    """Blockwise streaming scan of this shard's rows: one [b, block]
    distance tile at a time through the shared metric kernels, folded
    into a running top-m — never the full [b, L] matrix."""
    x, ids = loc["X"], loc["ids"]
    L = ids.shape[0]
    if env.get("use_kernel") and metric in ("euclidean", "angular"):
        from repro.kernels.distance_topk import stream_topk
        return stream_topk(q, x, k=min(m, L), metric=metric,
                           row_ids=ids, valid=ids >= 0)
    block = min(int(env.get("corpus_block") or 2048), L)

    def chunk(start, size):
        xt = x[start:start + size]
        it = ids[start:start + size]
        if metric == "euclidean":
            d = D.sq_l2_matrix(q, xt, loc["xsq"][start:start + size])
        elif metric == "angular":
            d = D.angular_matrix(q, xt, normalized=True)
        else:
            d = D.hamming_matrix(q, xt)
        d = jnp.where(it[None, :] >= 0, d, jnp.inf)
        return d, jnp.broadcast_to(it[None, :], d.shape)

    return chunked_topk(L, min(m, L), block, chunk)


def _row_local_quant(q, loc, rep, env, metric: str, m: int):
    """Compressed-domain local pass: ADC scan over this shard's packed
    codes, then (keep_fp32) the exact rerank fold over the survivors."""
    from repro.kernels.adc_scan import adc_scan

    ids = loc["ids"]
    L = ids.shape[0]
    n_cand = env.get("sharded_n_cand")
    C = L if n_cand is None else max(1, min(int(n_cand), L))
    adc_d, rows = adc_scan(
        loc["codes"], rep["luts"], k=C, block=env.get("adc_block"),
        use_kernel=bool(env.get("adc_kernel", False)))
    # the zero code rows padding this shard to L score like real vectors
    # under ADC; their global id is -1, which is the pad signal
    gl = ids[jnp.maximum(rows, 0)]
    ok = (rows >= 0) & (gl >= 0)
    if env.get("keep_fp32", True) and "X" in loc:
        return rerank_topk(
            q, loc["X"], rows, k=m, metric=metric, xsq=loc.get("xsq"),
            row_ids=ids, valid=ok, block=env.get("rerank_block"),
            use_kernel=bool(env.get("rerank_kernel", False)))
    return (jnp.where(ok, adc_d, jnp.inf), jnp.where(ok, gl, -1))


def _row_local(q, knobs, loc, rep, env, metric: str, m: int):
    if env.get("quant") is not None:
        return _row_local_quant(q, loc, rep, env, metric, m)
    return _row_local_plain(q, loc, env, metric, m)


def _row_prep(q, rep, env, metric: str):
    from repro.quant import build_luts
    return {"luts": build_luts(rep["codebooks"], q, metric)}


SS.register_shard_plan(SS.ShardPlan(
    inner_algo="BruteForce", sharded_algo="ShardedBruteForce",
    shard=_row_shard, unshard=_row_unshard, local_topk=_row_local,
    prep=_row_prep, prep_names=("luts",),
    prep_when=lambda env: env.get("quant") is not None,
))


# -------------------------------------------------------- inverted-list plan
def _ivf_shard(inner: IndexState, S: int):
    """Partition whole inverted lists across shards, biggest cluster to
    the currently-lightest shard; each shard stores its own cluster-major
    sub-corpus padded to the max shard load."""
    C = inner.stat("n_clusters")
    g_starts = np.asarray(inner["starts"])
    g_sizes = np.asarray(inner["sizes"])
    g_ids = np.asarray(inner["ids"])
    owner = np.zeros(C, np.int32)
    load = np.zeros(S, np.int64)
    for c in np.argsort(-g_sizes, kind="stable"):
        s = int(np.argmin(load))
        owner[c] = s
        load[s] += int(g_sizes[c])
    L = max(int(load.max()) if S else 0, 1)

    ids = np.full((S, L), -1, np.int32)
    starts = np.zeros((S, C), np.int32)
    sizes = np.zeros((S, C), np.int32)
    sh = {"ids": ids, "starts": starts, "sizes": sizes}
    srcs = {}
    for nm in ("X", "codes"):
        if nm in inner.arrays:
            srcs[nm] = np.asarray(inner[nm])
            sh[nm] = np.zeros((S, L) + srcs[nm].shape[1:], srcs[nm].dtype)
    if "xsq" in inner.arrays:
        srcs["xsq"] = np.asarray(inner["xsq"], np.float32)
        sh["xsq"] = np.full((S, L), np.inf, np.float32)
    cursor = np.zeros(S, np.int64)
    for c in range(C):
        s, sz, g0 = int(owner[c]), int(g_sizes[c]), int(g_starts[c])
        lo = int(cursor[s])
        starts[s, c] = lo
        sizes[s, c] = sz
        ids[s, lo:lo + sz] = g_ids[g0:g0 + sz]
        for nm, src in srcs.items():
            sh[nm][s, lo:lo + sz] = src[g0:g0 + sz]
        cursor[s] += sz

    rep = {"centers": inner["centers"]}
    if "codebooks" in inner.arrays:
        rep["codebooks"] = inner["codebooks"]
    static = dict(inner.static)
    static["L"] = L
    return sh, rep, static


def _ivf_unshard(state: IndexState) -> IndexState:
    C = state.stat("n_clusters")
    s_ids = np.asarray(state["ids"])
    s_starts = np.asarray(state["starts"])
    s_sizes = np.asarray(state["sizes"])
    n = int(s_sizes.max(axis=0).sum())
    arrays = {"centers": state["centers"]}
    srcs = {"ids": s_ids}
    outs = {"ids": np.zeros(n, np.int32)}
    for nm in ("X", "codes"):
        if nm in state.arrays:
            srcs[nm] = np.asarray(state[nm])
            outs[nm] = np.zeros((n,) + srcs[nm].shape[2:], srcs[nm].dtype)
    if "xsq" in state.arrays:
        srcs["xsq"] = np.asarray(state["xsq"])
        outs["xsq"] = np.zeros(n, np.float32)
    g_starts = np.zeros(C, np.int32)
    g_sizes = np.zeros(C, np.int32)
    cursor = 0
    for c in range(C):
        s = int(np.argmax(s_sizes[:, c]))
        sz = int(s_sizes[s, c])
        lo = int(s_starts[s, c])
        g_starts[c], g_sizes[c] = cursor, sz
        for nm, out in outs.items():
            out[cursor:cursor + sz] = srcs[nm][s, lo:lo + sz]
        cursor += sz
    arrays.update({nm: jnp.asarray(a) for nm, a in outs.items()})
    arrays["starts"] = jnp.asarray(g_starts)
    arrays["sizes"] = jnp.asarray(g_sizes)
    if "codebooks" in state.arrays:
        arrays["codebooks"] = state["codebooks"]
    return IndexState(state.stat("inner_algo"), state.metric, arrays,
                      _inner_static(state))


def _ivf_local(q, knobs, loc, rep, env, metric: str, m: int):
    """One shard's IVF pass: the replicated coarse quantizer picks the
    same top-P lists everywhere (bit-identical to single-device IVF);
    this shard reranks only the probed lists it owns."""
    P = int(env["probe_cap"])
    M = int(env["pad"])                       # max inverted-list length
    ids = loc["ids"]
    L = ids.shape[0]
    cd = D.sq_l2_matrix(q, rep["centers"])               # [b, C]
    _, probes = jax.lax.top_k(-cd, P)                    # [b, P]
    probe_live = jnp.arange(P, dtype=jnp.int32) \
        < jnp.clip(knobs["n_probes"], 1, P)
    starts = loc["starts"][probes]                       # [b, P]
    sizes = loc["sizes"][probes]                         # [b, P]
    offs = jnp.arange(M, dtype=jnp.int32)
    cand = starts[..., None] + offs[None, None, :]       # [b, P, M]
    valid = offs[None, None, :] < sizes[..., None]
    valid = valid & probe_live[None, :, None]
    cand = jnp.minimum(cand, L - 1).reshape(q.shape[0], -1)
    valid = valid.reshape(q.shape[0], -1)                # [b, P*M]
    if env.get("quant") is not None:
        return _ivf_local_quant(q, loc, rep, env, metric, m, cand, valid)
    return rerank_topk(
        q, loc["X"], cand, k=m, metric=metric, xsq=loc.get("xsq"),
        row_ids=ids, valid=valid, block=env.get("rerank_block"),
        use_kernel=bool(env.get("rerank_kernel", False)))


def _ivf_local_quant(q, loc, rep, env, metric, m, cand, valid):
    """Compressed-domain list pass, mirroring single-device IVF's
    ``_rerank_quantized``: ADC-score the probed window, keep the best,
    exact-rerank when the fp32 rows were retained."""
    from repro.kernels.adc_scan import adc_window_topk

    Cw = cand.shape[1]
    n_cand = env.get("sharded_n_cand")
    W = Cw if n_cand is None else max(1, min(int(n_cand), Cw))
    adc_d, rows = adc_window_topk(loc["codes"], rep["luts"], cand, k=W,
                                  valid=valid, block=env.get("adc_block"))
    if env.get("keep_fp32", True) and "X" in loc:
        return rerank_topk(
            q, loc["X"], rows, k=m, metric=metric, xsq=loc.get("xsq"),
            row_ids=loc["ids"], valid=None,
            block=env.get("rerank_block"),
            use_kernel=bool(env.get("rerank_kernel", False)))
    gl = loc["ids"][jnp.maximum(rows, 0)]
    ok = (rows >= 0) & (gl >= 0)
    return (jnp.where(ok, adc_d, jnp.inf), jnp.where(ok, gl, -1))


def _ivf_prep(q, rep, env, metric: str):
    from repro.quant import build_luts
    return {"luts": build_luts(rep["codebooks"], q, metric)}


SS.register_shard_plan(SS.ShardPlan(
    inner_algo="IVF", sharded_algo="ShardedIVF",
    shard=_ivf_shard, unshard=_ivf_unshard, local_topk=_ivf_local,
    prep=_ivf_prep, prep_names=("luts",), knob_names=("n_probes",),
    prep_when=lambda env: env.get("quant") is not None,
))


# ------------------------------------------------- sharded brute force
def bruteforce_build(X: np.ndarray, *, metric: str = "euclidean",
                     mesh: Optional[Mesh] = None,
                     shard_axes: Optional[Sequence[str]] = None,
                     n_shards: Optional[int] = None,
                     corpus_block: Optional[int] = 2048,
                     wire_codec: Optional[str] = None, fan_in: int = 2,
                     carry: Optional[int] = None, quantize=None,
                     keep_fp32: bool = True) -> IndexState:
    """Build the single-device BruteForce state, then shard its rows."""
    from repro.ann import bruteforce

    inner = bruteforce.build(
        np.asarray(X), metric=metric, quantize=quantize,
        keep_fp32=keep_fp32,
        corpus_block=int(corpus_block) if corpus_block else 65536)
    if mesh is not None and shard_axes is None:
        shard_axes = mesh.axis_names
    return SS.shard_index(inner, mesh=mesh, shard_axes=shard_axes,
                          n_shards=n_shards, wire_codec=wire_codec,
                          fan_in=fan_in, carry=carry)


def bruteforce_search(state: IndexState, Q, *, k: int,
                      mesh: Optional[Mesh] = None, n_cand=None,
                      use_kernel: bool = False, exact_vals: bool = True,
                      shard_ok=None):
    """Exact sharded top-k: streaming per-shard scan + compressed merge
    tree, rebuilt (and cached) from the state's mesh recipe unless
    ``mesh`` is given.  ``n_cand`` narrows the quantized builds' local
    rerank window; ``use_kernel`` routes the fp32 local scan through the
    fused ``distance_topk`` Pallas kernel; ``exact_vals=False`` drops the
    full-precision root tiebreak (minimum wire bytes, wire-precision
    distances out).  ``shard_ok`` is the degraded-mode keep-mask
    (see :func:`repro.dist.shard_state.sharded_search`)."""
    k = min(int(k), state.stat("n"))
    env_extra = {"use_kernel": bool(use_kernel)}
    if n_cand is not None:
        env_extra["sharded_n_cand"] = int(n_cand)
    return SS.sharded_search(state, Q, k=k, mesh=mesh,
                             env_extra=env_extra, exact_vals=exact_vals,
                             shard_ok=shard_ok)


register_functional(FunctionalSpec(
    name="ShardedBruteForce", build=bruteforce_build,
    search=bruteforce_search, query_params=(),
    static_query_params=("mesh",),
    supported_metrics=("euclidean", "angular", "hamming"),
))


@register("ShardedBruteForce")
class ShardedBruteForce(FunctionalANN):
    """Exact brute force over a sharded corpus.  On a 1-device host this
    degenerates to BruteForce; on a mesh it is the multi-pod serving path
    (dry-run: launch/bench_ann.py)."""

    supported_metrics = ("euclidean", "angular", "hamming")

    def __init__(self, metric: str, mesh: Optional[Mesh] = None,
                 shard_axes: Optional[Sequence[str]] = None,
                 corpus_block: Optional[int] = None,
                 n_shards: Optional[int] = None,
                 wire_codec: Optional[str] = None, fan_in: int = 2):
        super().__init__(metric)
        if mesh is None and n_shards is None:
            mesh, shard_axes = SS.default_mesh()
        elif mesh is None:
            mesh, shard_axes = SS.flat_mesh(int(n_shards))
        self.mesh = mesh
        self.shard_axes = tuple(shard_axes or mesh.axis_names)
        self.corpus_block = corpus_block
        self._build_params = dict(
            mesh=mesh, shard_axes=self.shard_axes,
            corpus_block=corpus_block or 2048,
            wire_codec=wire_codec, fan_in=int(fan_in))
        self._qparams = {"mesh": mesh}
        suffix = ",streaming" if corpus_block else ""
        self.name = (f"ShardedBruteForce(axes={','.join(self.shard_axes)}"
                     f"{suffix})")
        self._dist_comps = 0

    def _sync_state(self):
        self._n = self._state.stat("n")

    def _n_shards(self) -> int:
        return int(np.prod([self.mesh.shape[a] for a in self.shard_axes]))

    def query(self, q: np.ndarray, k: int) -> np.ndarray:
        out = super().query(q, k)
        self._dist_comps += self._n
        return out

    def batch_query(self, Q: np.ndarray, k: int) -> None:
        super().batch_query(Q, k)
        self._dist_comps += self._n * Q.shape[0]

    def get_additional(self):
        return {"dist_comps": self._dist_comps,
                "n_shards": self._n_shards()}


# --------------------------------------------------------------- sharded IVF
def ivf_build(X: np.ndarray, *, metric: str = "euclidean",
              n_clusters: int = 100, mesh: Optional[Mesh] = None,
              shard_axes: Optional[Sequence[str]] = None,
              n_shards: Optional[int] = None, n_iters: int = 10,
              seed: int = 0, wire_codec: Optional[str] = None,
              fan_in: int = 2, carry: Optional[int] = None, quantize=None,
              keep_fp32: bool = True) -> IndexState:
    """Single-device IVF build (host k-means, cluster-major layout), then
    whole inverted lists greedy-balanced across the mesh."""
    from repro.ann import ivf

    inner = ivf.build(np.asarray(X), metric=metric,
                      n_clusters=int(n_clusters), n_iters=int(n_iters),
                      seed=int(seed), quantize=quantize,
                      keep_fp32=keep_fp32)
    if mesh is not None and shard_axes is None:
        shard_axes = mesh.axis_names
    return SS.shard_index(inner, mesh=mesh, shard_axes=shard_axes,
                          n_shards=n_shards, wire_codec=wire_codec,
                          fan_in=fan_in, carry=carry)


def ivf_search(state: IndexState, Q, *, k: int, n_probes=1,
               max_probes: Optional[int] = None,
               mesh: Optional[Mesh] = None, n_cand=None,
               exact_vals: bool = True, shard_ok=None):
    """``max_probes`` (static) sizes the probed window; ``n_probes`` may
    then be a traced runtime value (same contract as single-device IVF —
    it crosses into ``shard_map`` as a replicated scalar, so one trace
    serves every probe count <= the cap).  ``shard_ok`` is the
    degraded-mode keep-mask
    (see :func:`repro.dist.shard_state.sharded_search`)."""
    C = state.stat("n_clusters")
    k = min(int(k), state.stat("n"))
    if max_probes is None:
        cap = max(1, min(int(n_probes), C))
        n_probes = cap
    else:
        cap = max(1, min(int(max_probes), C))
    env_extra = {"probe_cap": cap}
    if n_cand is not None:
        env_extra["sharded_n_cand"] = int(n_cand)
    return SS.sharded_search(state, Q, k=k, mesh=mesh, knobs=(n_probes,),
                             env_extra=env_extra, exact_vals=exact_vals,
                             shard_ok=shard_ok)


register_functional(FunctionalSpec(
    name="ShardedIVF", build=ivf_build, search=ivf_search,
    query_params=("n_probes", "max_probes"), query_defaults=(1, None),
    static_query_params=("n_probes", "max_probes", "mesh"),
    traced_knobs=(("n_probes", "max_probes"),),
))


@register("ShardedIVF")
class ShardedIVF(FunctionalANN):
    """Distributed IVF: whole inverted lists partitioned across the mesh.

    fit(): k-means on the host driver (identical centers to single-device
    IVF at the same seed); clusters are assigned to shards greedy-balanced
    by descending size; each shard stores its own cluster-major sub-corpus
    (padded to the max shard load).
    query(): replicated coarse quantizer -> top-nprobe lists; every shard
    reranks the probed lists IT OWNS (unowned lists have size 0 locally)
    and the compressed hierarchical merge combines shard results.
    """

    supported_metrics = ("euclidean", "angular")
    batch_block = 2048

    def __init__(self, metric: str, n_clusters: int = 100,
                 mesh: Optional[Mesh] = None,
                 shard_axes: Optional[Sequence[str]] = None,
                 n_iters: int = 10, seed: int = 0,
                 n_shards: Optional[int] = None,
                 wire_codec: Optional[str] = None, fan_in: int = 2):
        super().__init__(metric)
        if mesh is None and n_shards is None:
            mesh, shard_axes = SS.default_mesh()
        elif mesh is None:
            mesh, shard_axes = SS.flat_mesh(int(n_shards))
        self.mesh = mesh
        self.shard_axes = tuple(shard_axes or mesh.axis_names)
        self.n_clusters = int(n_clusters)
        self.n_iters = int(n_iters)
        self.seed = int(seed)
        self.n_probes = 1
        self._build_params = dict(
            n_clusters=self.n_clusters, mesh=mesh,
            shard_axes=self.shard_axes, n_iters=self.n_iters,
            seed=self.seed, wire_codec=wire_codec, fan_in=int(fan_in))
        self._qparams = {"n_probes": 1, "mesh": mesh}
        self.name = f"ShardedIVF(C={n_clusters})"
        self._dist_comps = 0

    def _sync_state(self):
        self._n = self._state.stat("n")
        self._pad = self._state.stat("pad")

    def _n_shards(self) -> int:
        return int(np.prod([self.mesh.shape[a] for a in self.shard_axes]))

    def set_query_arguments(self, n_probes: int) -> None:
        self.n_probes = max(1, int(n_probes))
        self._qparams["n_probes"] = self.n_probes

    def query(self, q: np.ndarray, k: int) -> np.ndarray:
        out = super().query(q, k)
        nprobe = min(self.n_probes, int(self._state["centers"].shape[0]))
        self._dist_comps += (int(self._state["centers"].shape[0])
                             + nprobe * self._pad)
        return out

    def batch_query(self, Q: np.ndarray, k: int) -> None:
        super().batch_query(Q, k)
        nprobe = min(self.n_probes, int(self._state["centers"].shape[0]))
        self._dist_comps += Q.shape[0] * (
            int(self._state["centers"].shape[0]) + nprobe * self._pad)

    def get_additional(self):
        return {"dist_comps": self._dist_comps,
                "n_shards": self._n_shards(), "max_list": self._pad}


# ------------------------------------------------- legacy raw-array entry
def make_sharded_topk(mesh: Mesh, shard_axes: Sequence[str], k: int,
                      metric: str, corpus_block: Optional[int] = None,
                      wire_codec: Optional[str] = None, fan_in: int = 2):
    """Raw-array sharded top-k (``launch/bench_ann.py`` dry-runs): a jitted
    ``shard_map`` mapping replicated queries + row-sharded ``(x, ids,
    xsq)`` to the replicated exact global top-k.

    Rebuilt on the new layer: the blockwise streaming local scan
    (``corpus_block`` rows per tile, running top-k accumulator — never a
    local [nq, n/chips] matrix) feeds the compressed hierarchical merge
    tree (:func:`repro.dist.collectives.tree_merge_topk`, full-precision
    root tiebreak) instead of the old flat f32 ``all_gather``.
    """
    from jax import shard_map
    from jax.sharding import PartitionSpec as P

    from repro.dist import wire
    from repro.dist.collectives import tree_merge_topk

    axes = tuple(shard_axes)
    codec = wire_codec or wire.default_codec(metric)
    axis_sizes = tuple(int(mesh.shape[a]) for a in axes)
    env = {"corpus_block": corpus_block}

    def fn(q, x, ids, xsq):
        loc = {"X": x, "ids": ids, "xsq": xsq}
        vals, out_ids = _row_local_plain(q, loc, env, metric, int(k))
        return tree_merge_topk(vals, out_ids, axes=axes,
                               axis_sizes=axis_sizes, k=int(k),
                               codec=codec, fan_in=int(fan_in),
                               exact_vals=True)

    in_specs = (P(), P(axes), P(axes), P(axes))
    return jax.jit(shard_map(fn, mesh=mesh, in_specs=in_specs,
                             out_specs=(P(), P()), check_vma=False))
