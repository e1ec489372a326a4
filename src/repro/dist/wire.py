"""Wire codecs for the distributed top-k merge tree.

The sharded search merge (``repro.dist.collectives.tree_merge_topk``)
exchanges per-shard candidate sets — (distance, global id) pairs — across
mesh axes.  The flat baseline moves both halves of every pair as 8 bytes
(f32 dist + int32 id); this module shrinks the *distance* half, which is
only ever used for ordering, to 1–2 bytes:

    f32    4 B  identity (the uncompressed reference wire format)
    bf16   2 B  truncated f32 — scale-free and *monotone* (d1 <= d2 implies
                bf16(d1) <= bf16(d2)), so quantized-domain merge order can
                only differ from exact order inside a bf16 tie bucket
    u16    2 B  lossless for integer-valued distances < 65535 — the hamming
                codec (popcount distances are small ints), exact always
    int8   1 B  affine over a shared per-query [lo, hi] range (a 2-float
                collective pre-pass), 254 levels + an overflow/invalid
                sentinel; the aggressive wire-bytes option

Ids always travel as int32 (the exactness contract is on ids).  Every codec
is monotone, so comparing *decoded* values is equivalent to comparing wire
values — the merge folds decode immediately after receipt and fold in f32
with id tiebreak, which is what keeps the fold bit-deterministic across
devices regardless of merge grouping.

Invalid entries are signalled by ``id == -1``; ``decode`` forces their
value to +inf so they can never win a fold.
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp

#: distance bytes per wire entry, by codec (ids add ID_BYTES each).
CODEC_DIST_BYTES = {"f32": 4, "bf16": 2, "u16": 2, "int8": 1}
ID_BYTES = 4
WIRE_CODECS = tuple(CODEC_DIST_BYTES)

_U16_INF = 65535
_I8_LEVELS = 254          # 0..253 payload, 254 = overflow, 255 unused
_I8_INF = 255


def default_codec(metric: str) -> str:
    """bf16 for float metrics; u16 (lossless integer) for hamming."""
    return "u16" if metric == "hamming" else "bf16"


def check_codec(codec: str) -> str:
    if codec not in CODEC_DIST_BYTES:
        raise ValueError(
            f"unknown wire codec {codec!r}; known: {sorted(CODEC_DIST_BYTES)}")
    return codec


def needs_scale(codec: str) -> bool:
    return codec == "int8"


def encode(d, codec: str, lo=None, hi=None):
    """f32 distances -> wire array (same shape, codec dtype).

    ``lo``/``hi`` are the shared affine range for int8 — [b, 1] (or scalar)
    f32 arrays that MUST be identical on every participating device (use a
    pmin/pmax pre-pass); other codecs ignore them.
    """
    d = d.astype(jnp.float32)
    if codec == "f32":
        return d
    if codec == "bf16":
        return d.astype(jnp.bfloat16)
    if codec == "u16":
        w = jnp.clip(d, 0.0, float(_U16_INF - 1))
        w = jnp.where(jnp.isfinite(d), w, float(_U16_INF))
        return w.astype(jnp.uint16)
    # int8: affine onto 0..253; anything past hi (or non-finite) -> sentinel
    span = jnp.maximum(hi - lo, 1e-30)
    q = jnp.round((d - lo) / span * (_I8_LEVELS - 1))
    q = jnp.clip(q, 0, _I8_LEVELS - 1)
    q = jnp.where(jnp.isfinite(d) & (d <= hi), q, float(_I8_INF))
    return q.astype(jnp.uint8)


def decode(w, codec: str, lo=None, hi=None, ids=None):
    """Wire array -> f32 values; entries with ``ids < 0`` (or the codec's
    overflow sentinel) decode to +inf."""
    if codec == "f32":
        out = w.astype(jnp.float32)
    elif codec == "bf16":
        out = w.astype(jnp.float32)
    elif codec == "u16":
        out = jnp.where(w == _U16_INF, jnp.inf, w.astype(jnp.float32))
    else:
        span = jnp.maximum(hi - lo, 1e-30)
        val = lo + w.astype(jnp.float32) * (span / (_I8_LEVELS - 1))
        out = jnp.where(w == _I8_INF, jnp.inf, val)
    if ids is not None:
        out = jnp.where(ids < 0, jnp.inf, out)
    return out


def snap(d, codec: str, lo=None, hi=None, ids=None):
    """f32 distances rounded to wire precision and kept f32: the values of
    ``decode(encode(d))``, which every device must fold its own candidates
    with, bit for bit what its peers decode from the wire.

    bf16 rounds to nearest-even on the f32 bit pattern with integer ops.
    A TPU build keeps floats inside a fusion at f32 where XLA allows
    excess precision, so a f32 -> bf16 -> f32 convert pair there can leave
    a device's own values unrounded while its peers receive them rounded;
    the butterfly partners then fold different candidate sets."""
    if codec != "bf16":
        return decode(encode(d, codec, lo, hi), codec, lo, hi, ids)
    u = jax.lax.bitcast_convert_type(d.astype(jnp.float32), jnp.uint32)
    u = (u + 0x7FFF + ((u >> 16) & 1)) & jnp.uint32(0xFFFF0000)
    out = jax.lax.bitcast_convert_type(u, jnp.float32)
    if ids is not None:
        out = jnp.where(ids < 0, jnp.inf, out)
    return out


# ------------------------------------------------------------- byte models
def entry_bytes(codec: str) -> int:
    """Wire bytes for one (id, dist) candidate entry."""
    return ID_BYTES + CODEC_DIST_BYTES[check_codec(codec)]


def flat_gather_wire_bytes(n_shards: int, k: int) -> int:
    """Per-device candidate-buffer bytes per query for the flat f32
    ``all_gather`` merge: every shard's k (f32, int32) pairs land on every
    device."""
    return n_shards * k * (4 + ID_BYTES)


def merge_wire_bytes(n_shards: int, k: int, *, codec: str = "bf16",
                     fan_in: int = 2, carry: int | None = None) -> int:
    """Per-device candidate-buffer bytes per query for the hierarchical
    merge tree: ``log_fan_in(n_shards)`` butterfly rounds, each moving
    ``fan_in - 1`` windows of ``carry`` compressed entries (+ the int8
    codec's 2-float shared-range pre-pass)."""
    if n_shards <= 1:
        return 0
    carry = k if carry is None else int(carry)
    rounds = max(1, math.ceil(math.log(n_shards, max(2, fan_in))))
    total = rounds * (fan_in - 1) * carry * entry_bytes(codec)
    if needs_scale(codec):
        total += 8                       # per-query lo/hi f32 pre-pass
    return total
