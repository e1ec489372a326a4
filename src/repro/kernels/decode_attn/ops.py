"""Public wrapper: multi-kv-head GQA decode attention.

``decode_attention(q [B,H,dh], k/v [B,S,KV,dh], lengths)`` vmaps the
per-kv-head kernel over KV heads with the H = KV * G query heads regrouped.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.kernels import interpret_mode
from repro.kernels.decode_attn.decode_attn import decode_attention_pallas


def decode_attention(q, k, v, lengths=None, *, bs: int = 512,
                     interpret: bool | None = None):
    interpret = interpret_mode() if interpret is None else interpret
    B, H, dh = q.shape
    S, KV = k.shape[1], k.shape[2]
    assert H % KV == 0
    G = H // KV
    if lengths is None:
        lengths = jnp.full((B,), S, jnp.int32)
    bs = min(bs, S)
    pad = (-S) % bs
    if pad:
        k = jnp.pad(k, ((0, 0), (0, pad), (0, 0), (0, 0)))
        v = jnp.pad(v, ((0, 0), (0, pad), (0, 0), (0, 0)))
    qg = q.reshape(B, KV, G, dh)

    def per_kv(qh, kh, vh):
        return decode_attention_pallas(qh, kh, vh, lengths, bs=bs,
                                       interpret=interpret)

    out = jax.vmap(per_kv, in_axes=(1, 2, 2), out_axes=1)(qg, k, v)
    return out.reshape(B, H, dh)
