"""Generic sharded serving layer: shard any registered ``IndexState``.

A :class:`ShardPlan` teaches this module how one single-device algorithm's
state partitions across devices (``shard``/``unshard``) and how one shard
answers a query locally (``local_topk``).  Everything else — mesh plumbing,
``shard_map`` construction, the compressed hierarchical top-k merge
(:func:`repro.dist.collectives.tree_merge_topk`), compiled-function
caching, resharding, and checkpoint-portability checks — is shared here,
so adding a sharded algorithm is just a plan registration
(:mod:`repro.ann.sharded` registers the row plan for BruteForce — plain,
quantized, and hamming — and the inverted-list plan for IVF).

States produced by :func:`shard_index` are ordinary pytree ``IndexState``s:
the device arrays carry a leading ``[n_shards, ...]`` dim laid out over the
mesh recipe recorded in ``static`` (``shard_axes`` + ``mesh_shape``), so
checkpoints stay mesh-portable — :func:`resolve_mesh` rebuilds the mesh on
load, :func:`reshard` moves a state to a different shard count, and
:func:`ensure_servable` auto-reshards on hosts with fewer devices.
"""

from __future__ import annotations

import dataclasses
import functools
from typing import Callable, Optional, Sequence

import numpy as np

import jax
import jax.numpy as jnp
from jax import shard_map
from jax.sharding import Mesh, PartitionSpec as P

from repro.dist import wire
from repro.dist.collectives import tree_merge_topk
from repro.dist.sharding import mesh_axes_size, rows_sharding


class ShardingError(ValueError):
    """A state's mesh recipe cannot be realised on this host."""


# ------------------------------------------------------------ mesh plumbing
@functools.lru_cache(maxsize=8)
def mesh_for(shape: tuple, axes: tuple) -> Mesh:
    return jax.make_mesh(shape, axes)


def default_mesh():
    """All visible devices on one flat 'data' axis."""
    return mesh_for((jax.device_count(),), ("data",)), ("data",)


def flat_mesh(n_shards: int):
    """``n_shards`` devices on one flat 'data' axis (errors if the host
    has fewer devices — simulate with ``--xla_force_host_platform_device_count``)."""
    if n_shards > jax.device_count():
        raise ShardingError(
            f"n_shards={n_shards} needs {n_shards} devices but only "
            f"{jax.device_count()} are visible (set XLA_FLAGS="
            f"--xla_force_host_platform_device_count={n_shards} to simulate)")
    return mesh_for((int(n_shards),), ("data",)), ("data",)


def mesh_recipe(mesh: Mesh, axes: tuple) -> dict:
    return {"shard_axes": tuple(axes),
            "mesh_shape": tuple(int(mesh.shape[a]) for a in axes)}


def resolve_mesh(state, mesh: Optional[Mesh] = None):
    """(mesh, axes) for a sharded state — from the caller's mesh or the
    state's recorded recipe; raises :class:`ShardingError` with the fix
    when the recipe needs more devices than this host has."""
    axes = tuple(state.stat("shard_axes"))
    if mesh is not None:
        return mesh, axes
    shape = tuple(state.stat("mesh_shape"))
    need = int(np.prod(shape))
    have = jax.device_count()
    if need > have:
        raise ShardingError(
            f"index was sharded for mesh shape {shape} over axes {axes} "
            f"({need} devices) but only {have} JAX device(s) are visible; "
            f"reshard it first — repro.dist.shard_state.reshard(state, "
            f"n_shards={have}) — or restore through ensure_servable()")
    return mesh_for(shape, axes), axes


# Bounded FIFO cache of compiled shard_map functions, shared across states
# on the same mesh but bounded so long sweeps cannot pin compiled programs
# (and their meshes) for the process lifetime.
_SHARDED_FNS: dict = {}
_SHARDED_FNS_MAX = 64


def cached_fn(key, builder):
    fn = _SHARDED_FNS.get(key)
    if fn is None:
        if len(_SHARDED_FNS) >= _SHARDED_FNS_MAX:
            _SHARDED_FNS.pop(next(iter(_SHARDED_FNS)))
        fn = _SHARDED_FNS[key] = builder()
    return fn


# ------------------------------------------------------------ plan registry
@dataclasses.dataclass(frozen=True)
class ShardPlan:
    """How one algorithm's IndexState shards and answers locally.

    ``shard(inner, n_shards) -> (shard_arrays, rep_arrays, static)``:
    partition a single-device state; every array in ``shard_arrays`` gains
    a leading ``[n_shards, ...]`` dim, ``rep_arrays`` are replicated.

    ``unshard(state) -> IndexState``: exact inverse (drives ``reshard``).

    ``local_topk(q, knobs, loc, rep, env, metric, m) -> (vals, ids)``:
    one shard's [b, m] best (f32 distance, *global* id) candidates; runs
    inside ``shard_map`` with ``loc`` = this shard's arrays (leading dim
    stripped), ``rep`` = replicated arrays (+ ``prep`` outputs), ``knobs``
    = traced runtime scalars, ``env`` = the state's static dict plus the
    caller's per-call statics.  Invalid slots must come back (+inf, -1).

    ``prep(q, rep, env, metric) -> dict``: optional per-query replicated
    arrays computed once outside shard_map (e.g. ADC LUTs), delivered to
    ``local_topk`` under ``prep_names``.  ``prep_when(env)`` gates it —
    when it returns False the prep stage (and its rep slots) vanish from
    the compiled fn (e.g. LUTs only exist for quantized builds).
    """
    inner_algo: str
    sharded_algo: str
    shard: Callable
    unshard: Callable
    local_topk: Callable
    prep: Optional[Callable] = None
    prep_when: Optional[Callable] = None
    prep_names: tuple = ()
    knob_names: tuple = ()


SHARD_PLANS: dict = {}
_BY_SHARDED: dict = {}


def register_shard_plan(plan: ShardPlan) -> ShardPlan:
    SHARD_PLANS[plan.inner_algo] = plan
    _BY_SHARDED[plan.sharded_algo] = plan
    return plan


def sharded_algos() -> tuple:
    """Registered sharded algorithm names (e.g. for launcher validation)."""
    return tuple(sorted(_BY_SHARDED))


def plan_for(state) -> ShardPlan:
    plan = _BY_SHARDED.get(state.algo)
    if plan is None:
        raise ShardingError(f"no shard plan registered for sharded state "
                            f"{state.algo!r} (known: {sorted(_BY_SHARDED)})")
    return plan


# ------------------------------------------------------------- build / serve
def shard_index(inner, *, mesh: Optional[Mesh] = None,
                shard_axes: Optional[Sequence[str]] = None,
                n_shards: Optional[int] = None,
                wire_codec: Optional[str] = None, fan_in: int = 2,
                carry: Optional[int] = None):
    """Shard a built single-device ``IndexState`` across a mesh.

    ``wire_codec`` picks the merge-tree distance codec (default:
    :func:`repro.dist.wire.default_codec` — u16 for hamming, bf16 else);
    ``carry`` is the per-fold tie budget (default 2k at query time).
    """
    from repro.ann.functional import IndexState

    plan = SHARD_PLANS.get(inner.algo)
    if plan is None:
        raise ShardingError(f"no shard plan registered for {inner.algo!r} "
                            f"(known: {sorted(SHARD_PLANS)})")
    if mesh is None:
        mesh, shard_axes = (flat_mesh(int(n_shards)) if n_shards
                            else default_mesh())
    axes = tuple(shard_axes or mesh.axis_names)
    S = mesh_axes_size(mesh, axes)
    codec = wire.check_codec(wire_codec or wire.default_codec(inner.metric))
    shard_arrays, rep_arrays, static = plan.shard(inner, S)
    spec = rows_sharding(mesh, axes)
    arrays = {nm: jax.device_put(np.asarray(a), spec)
              for nm, a in shard_arrays.items()}
    arrays.update({nm: jnp.asarray(a) for nm, a in rep_arrays.items()})
    static = dict(static)
    static.update(mesh_recipe(mesh, axes))
    static.update({
        "n_shards": S, "wire_codec": codec, "fan_in": int(fan_in),
        "carry": None if carry is None else int(carry),
        "shard_arrays": tuple(sorted(shard_arrays)),
        "inner_algo": inner.algo,
    })
    return IndexState(plan.sharded_algo, inner.metric, arrays, static)


def shard_coverage(state, keep) -> float:
    """Fraction of the index's live rows owned by the surviving shards.

    ``keep`` is a ``[n_shards]`` bool mask.  Both registered plans keep
    the global-id map in the ``ids`` shard array (``[S, L]`` with ``-1``
    padding), so per-shard live-row counts fall out of ``ids >= 0`` —
    this is the ``coverage`` a degraded response reports."""
    ids = np.asarray(jax.device_get(state["ids"]))
    live = (ids.reshape(ids.shape[0], -1) >= 0).sum(axis=1)
    total = int(live.sum())
    if total == 0:
        return 1.0
    return float(live[np.asarray(keep, bool).reshape(-1)].sum()) / total


def sharded_search(state, Q, *, k: int, mesh: Optional[Mesh] = None,
                   knobs: Sequence = (), env_extra: Optional[dict] = None,
                   cache_extra: tuple = (), exact_vals: bool = True,
                   shard_ok=None):
    """Replicated exact top-k over a sharded state: per-shard
    ``plan.local_topk`` + the compressed butterfly merge, compiled once
    per (mesh, k, statics) and cached.  ``knobs`` are the plan's traced
    runtime scalars (order = ``plan.knob_names``); ``env_extra`` overlays
    per-call statics onto the state's static dict (include anything
    shape-affecting in ``cache_extra`` too — it keys the compiled fn).

    ``exact_vals`` (default on) is the full-precision root tiebreak: the
    returned distances are the owners' exact f32 values and the final
    k-selection happens in f32, so results are order-identical to the
    single-device index.  Turning it off saves the root psum's ~carry * 8
    wire bytes and returns wire-precision distances (ids still exact up
    to the carry tie budget).

    ``shard_ok`` is an optional ``[n_shards]`` bool keep-mask: a masked
    shard's local results are forced to the merge tree's ``(+inf, -1)``
    sentinel channel, so the merge stays *exact over the surviving
    shards* — the degraded-mode mechanism (results equal a single-device
    search over only the survivors' rows).  The mask is an ordinary
    traced array input of the one cached program: masked and unmasked
    calls share the trace, and the all-True default is the identity."""
    from repro.ann.functional import _freeze, prepare_queries

    plan = plan_for(state)
    mesh, axes = resolve_mesh(state, mesh)
    sizes = tuple(int(mesh.shape[a]) for a in axes)
    S = int(np.prod(sizes))
    k = int(k)

    # ---- fault-injection hook (repro.serve.faults; no-op unless a plan
    # is installed).  Under an outer jit — the Engine's fixed-shape
    # serving trace — Q/shard_ok are tracers and the hook is skipped
    # here: the Engine calls it host-side per micro-batch and threads
    # the mask in as the traced ``shard_ok`` argument instead.
    tracing = isinstance(Q, jax.core.Tracer) \
        or isinstance(shard_ok, jax.core.Tracer)
    if not tracing:
        from repro.serve import faults as _faults

        mask = _faults.shard_events(S)     # may raise ShardFault / sleep
        if shard_ok is not None:
            sk = np.asarray(shard_ok, bool).reshape(-1)
            if sk.shape[0] != S:
                raise ShardingError(
                    f"shard_ok has {sk.shape[0]} entries for {S} shards")
            mask = sk if mask is None else (mask & sk)
        if mask is not None and not mask.all():
            _faults.note_degraded(
                shard_coverage(state, mask),
                tuple(int(s) for s in np.flatnonzero(~mask)))
        ok_arg = np.ones(S, bool) if mask is None else mask
    else:
        ok_arg = shard_ok if shard_ok is not None else np.ones(S, bool)
    carry_s = state.static.get("carry")
    carry = 2 * k if carry_s is None else max(k, int(carry_s))
    codec = state.stat("wire_codec")
    fan_in = state.stat("fan_in")
    env = dict(state.static)
    env.update(env_extra or {})
    metric = state.metric
    shard_names = tuple(state.stat("shard_arrays"))
    rep_names = tuple(sorted(set(state.arrays) - set(shard_names)))
    algo = state.algo
    key = (algo, mesh, axes, k, metric, codec, fan_in, carry,
           bool(exact_vals), shard_names, rep_names, _freeze(env),
           tuple(cache_extra))

    prep_on = plan.prep is not None and (
        plan.prep_when is None or plan.prep_when(env))
    prep_names = plan.prep_names if prep_on else ()

    def build():
        def local(q, kv, ok_t, rep_t, shard_t):
            loc = {nm: a[0] for nm, a in zip(shard_names, shard_t)}
            rep = dict(zip(rep_names + prep_names, rep_t))
            kn = dict(zip(plan.knob_names, kv))
            vals, ids = plan.local_topk(q, kn, loc, rep, env, metric, carry)
            # a dead shard presents every candidate as the merge tree's
            # (+inf, -1) sentinel — exactly a shard with zero valid rows,
            # so the fold stays exact over the survivors
            alive = ok_t[0]
            vals = jnp.where(alive, vals, jnp.inf)
            ids = jnp.where(alive, ids, -1)
            return tree_merge_topk(
                vals, ids, axes=axes, axis_sizes=sizes, k=k,
                codec=codec, carry=carry, fan_in=fan_in,
                exact_vals=bool(exact_vals))

        n_rep = len(rep_names) + len(prep_names)
        shm = shard_map(
            local, mesh=mesh,
            in_specs=(P(), (P(),) * len(plan.knob_names), P(axes),
                      (P(),) * n_rep, (P(axes),) * len(shard_names)),
            out_specs=(P(), P()), check_vma=False)

        def outer(q, kv, ok, rep_t, shard_t):
            if prep_names:
                extra = plan.prep(q, dict(zip(rep_names, rep_t)), env,
                                  metric)
                rep_t = rep_t + tuple(extra[nm] for nm in prep_names)
            return shm(q, kv, ok, rep_t, shard_t)

        return jax.jit(outer)

    fn = cached_fn(key, build)
    Qp = prepare_queries(Q, metric)
    kv = tuple(jnp.asarray(v, jnp.int32) for v in knobs)
    return fn(Qp, kv, jnp.asarray(ok_arg),
              tuple(state[nm] for nm in rep_names),
              tuple(state[nm] for nm in shard_names))


# --------------------------------------------------------------- resharding
def reshard(state, *, mesh: Optional[Mesh] = None,
            shard_axes: Optional[Sequence[str]] = None,
            n_shards: Optional[int] = None):
    """Move a sharded state to a different mesh / shard count by exact
    unshard -> reshard round-trip (same ids, same wire settings)."""
    plan = plan_for(state)
    return shard_index(
        plan.unshard(state), mesh=mesh, shard_axes=shard_axes,
        n_shards=n_shards, wire_codec=state.stat("wire_codec"),
        fan_in=state.stat("fan_in"), carry=state.static.get("carry"))


def ensure_servable(state):
    """Make a (possibly foreign) checkpointed state servable here: states
    whose mesh recipe fits the visible devices pass through untouched;
    oversized recipes are resharded onto all local devices."""
    if state.algo not in _BY_SHARDED:
        return state
    try:
        resolve_mesh(state, None)
        return state
    except ShardingError:
        return reshard(state, n_shards=jax.device_count())
