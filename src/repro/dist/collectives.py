"""Explicit collectives.

``sharded_embed_lookup`` is the recsys/LM embedding hot path: tables are
row-sharded over the 'model' axis, each shard answers with a masked local
gather, and a psum combines the one non-zero contribution per token.  This
keeps the full table from ever being replicated — the lookup moves
O(tokens * d) bytes instead of O(vocab * d).

``tree_merge_topk`` is the sharded-ANN merge hot path: each device's local
top-m (distance, global id) candidates are folded into the replicated
global top-k by a log-depth butterfly over every mesh axis, with distances
travelling in a compressed wire format (:mod:`repro.dist.wire`) — per-device
wire bytes drop from the flat all_gather's O(devices * k * 8) to
O(log(devices) * m * (4 + 1..2)).
"""

from __future__ import annotations

from typing import Optional, Sequence

import jax
import jax.numpy as jnp
from jax import shard_map
from jax.sharding import Mesh, PartitionSpec as P

from repro.dist import wire

_EMBED_AXIS = "model"


def sharded_embed_lookup(emb, tokens, mesh: Optional[Mesh] = None,
                         axis: str = _EMBED_AXIS):
    """emb [V, d] row-sharded over ``axis``; tokens int[...] -> [..., d].

    Falls back to a plain gather when there is no mesh, the axis is absent,
    or the vocab does not divide evenly across the axis.
    """
    if mesh is None or axis not in mesh.axis_names:
        return emb[tokens]
    n_shards = mesh.shape[axis]
    V = emb.shape[0]
    if n_shards <= 1 or V % n_shards != 0:
        return emb[tokens]

    def local(e, t):
        # e [V/s, d] local rows; t replicated global token ids
        per = e.shape[0]
        shard = jax.lax.axis_index(axis)
        rel = t.astype(jnp.int32) - shard * per
        ok = (rel >= 0) & (rel < per)
        safe = jnp.where(ok, rel, 0)
        out = jnp.where(ok[..., None], e[safe], 0).astype(e.dtype)
        return jax.lax.psum(out, axis)

    fn = shard_map(local, mesh=mesh, in_specs=(P(axis, None), P()),
                   out_specs=P(), check_vma=False)
    return fn(emb, tokens)


# ------------------------------------------------- hierarchical top-k merge
def _butterfly_perm(S: int, stride: int, f: int, t: int):
    """ppermute pairs for butterfly round digit-shift ``t``: device p
    receives from the device whose base-``f`` digit at ``stride`` is
    ``(digit(p) + t) mod f``."""
    perm = []
    for p in range(S):
        d = (p // stride) % f
        base = p - d * stride
        perm.append((base + ((d + t) % f) * stride, p))
    return perm


def _axis_schedule(S: int, fan_in: int):
    """(rounds) for one mesh axis: a list of (stride, f) butterfly rounds.
    Power-of-fan_in sizes get the full log-depth ladder; anything else
    falls back to a single fan_in=S exchange round (still compressed)."""
    f = max(2, int(fan_in))
    rounds, s = [], 1
    n = S
    while n % f == 0:
        rounds.append((s, f))
        s *= f
        n //= f
    if n != 1:                      # ragged axis: one flat exchange round
        return [(1, S)]
    return rounds


def tree_merge_topk(vals, ids, *, axes: Sequence[str],
                    axis_sizes: Sequence[int], k: int,
                    codec: str = "f32", carry: Optional[int] = None,
                    fan_in: int = 2, exact_vals: bool = False):
    """Global top-k merge inside ``shard_map``: fold every device's local
    candidates into the replicated exact top-k.

    ``vals [b, m]`` f32 distances / ``ids [b, m]`` int32 *global* ids of
    the local candidates (id -1 = invalid).  Each global id must live on
    exactly one device, so every copy of an id that spreads through the
    tree carries the same wire value.

    The fold is a butterfly: per mesh axis (innermost last), ``log_f(S)``
    rounds of ``f - 1`` ``ppermute`` exchanges of ``carry`` compressed
    entries, each concatenated and re-folded with
    ``merge_topk_unique_rounds``.  All devices finish with the *identical*
    top-k (the fold is a selection under the (value, id) total order, so
    it is independent of arrival order), which is what lets the butterfly
    skip a broadcast leg entirely.

    Exactness: distances are snapped to wire precision *before* the first
    fold (every codec's encode/decode is monotone and idempotent), so the
    tree computes the exact top-``carry`` of the union under the wire
    total order.  A true top-k id can only be lost if more than
    ``carry - k`` smaller-id candidates share its exact wire bucket —
    ``carry`` (default 2k) is the tie budget.  The u16 codec (hamming's
    integer distances) is unconditionally exact.  Returned values are wire
    precision; ``exact_vals=True`` adds a full-precision root tiebreak —
    one psum re-scores the carried candidate set from the owners' f32
    values before the final k-selection (costs ~carry * 8 extra bytes per
    axis, so the compressed byte win is for ids-only callers).
    """
    from repro.kernels.rerank_topk import (     # deferred: import cycle
        merge_topk_unique_rounds)

    wire.check_codec(codec)
    m = vals.shape[1]
    carry = max(int(k), 2 * int(k) if carry is None else int(carry))
    vals = jnp.where(ids >= 0, vals.astype(jnp.float32), jnp.inf)
    ids = jnp.where(ids >= 0, ids.astype(jnp.int32), -1)
    if m > carry:
        vals, ids = merge_topk_unique_rounds(vals, ids, carry)
    elif m < carry:
        vals = jnp.pad(vals, ((0, 0), (0, carry - m)),
                       constant_values=jnp.inf)
        ids = jnp.pad(ids, ((0, 0), (0, carry - m)), constant_values=-1)

    live_axes = [(ax, int(s)) for ax, s in zip(axes, axis_sizes) if s > 1]
    if not live_axes:                  # single shard: nothing to exchange
        return merge_topk_unique_rounds(vals, ids, int(k))
    lo = hi = None
    if wire.needs_scale(codec):
        finite = jnp.isfinite(vals)
        lo = jnp.min(jnp.where(finite, vals, jnp.inf), 1, keepdims=True)
        hi = jnp.max(jnp.where(finite, vals, -jnp.inf), 1, keepdims=True)
        for ax, _ in live_axes:
            lo = jax.lax.pmin(lo, ax)
            hi = jax.lax.pmax(hi, ax)
    own_vals, own_ids = vals, ids          # f32, for the exact_vals root
    # snap local values into wire precision so every fold compares in the
    # same (idempotent) domain regardless of merge grouping
    vals = wire.snap(vals, codec, lo, hi, ids)

    for ax, S in reversed(live_axes):
        for stride, f in _axis_schedule(S, fan_in):
            w = wire.encode(vals, codec, lo, hi)
            parts_v, parts_i = [vals], [ids]
            for t in range(1, f):
                perm = _butterfly_perm(S, stride, f, t)
                wt = jax.lax.ppermute(w, ax, perm)
                it = jax.lax.ppermute(ids, ax, perm)
                parts_v.append(wire.decode(wt, codec, lo, hi, it))
                parts_i.append(it)
            vals, ids = merge_topk_unique_rounds(
                jnp.concatenate(parts_v, axis=1),
                jnp.concatenate(parts_i, axis=1), carry)

    if exact_vals:
        # full-precision root tiebreak: each owner contributes its f32
        # value for any carried id it holds; one psum replicates them
        match = (ids[:, :, None] == own_ids[:, None, :]) \
            & (own_ids[:, None, :] >= 0)
        safe = jnp.where(jnp.isfinite(own_vals), own_vals, 0.0)
        contrib = jnp.sum(jnp.where(match, safe[:, None, :], 0.0), axis=2)
        count = jnp.sum(match, axis=2).astype(jnp.float32)
        stacked = jnp.stack([contrib, count], axis=-1)
        for ax, _ in live_axes:
            stacked = jax.lax.psum(stacked, ax)
        vals = jnp.where(stacked[..., 1] > 0, stacked[..., 0], jnp.inf)
        ids = jnp.where(stacked[..., 1] > 0, ids, -1)
    return merge_topk_unique_rounds(vals, ids, int(k))
