"""Single-token decode attention with online softmax (flash-decode style).

The LM serving hot-spot for the decode_32k / long_500k shapes: one new query
token attends over a long KV cache.  The kernel scans KV blocks, keeping a
running (max, denominator, weighted-sum) triple in VMEM scratch — the
numerically stable online softmax — so the [S] score vector never
materialises in HBM.  GQA is handled by folding the q-heads-per-kv-head
group into the tile's sublane dimension.

Shapes (one kv head per grid row):
    q       [B, G, dh]      G = q heads per kv head
    k, v    [B, S, dh]
    out     [B, G, dh]

Grid: (B, S/bs) — batch parallel, sequence sequential.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


_NEG_INF = -1e30


def _decode_attn_kernel(q_ref, k_ref, v_ref, len_ref, o_ref,
                        m_ref, l_ref, acc_ref, *, bs: int, scale: float):
    j = pl.program_id(1)

    @pl.when(j == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, _NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    q = q_ref[0].astype(jnp.float32) * scale          # [G, dh]
    k = k_ref[0].astype(jnp.float32)                  # [bs, dh]
    v = v_ref[0].astype(jnp.float32)                  # [bs, dh]
    s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                            preferred_element_type=jnp.float32)  # [G, bs]
    # mask beyond the valid cache length
    pos = j * bs + jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
    s = jnp.where(pos < len_ref[0], s, _NEG_INF)

    m_prev = m_ref[...]                               # [G, 1]
    m_new = jnp.maximum(m_prev, jnp.max(s, axis=1, keepdims=True))
    alpha = jnp.exp(m_prev - m_new)
    p = jnp.exp(s - m_new)                            # [G, bs]
    l_new = l_ref[...] * alpha + jnp.sum(p, axis=1, keepdims=True)
    acc_ref[...] = acc_ref[...] * alpha + jax.lax.dot_general(
        p, v, (((1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32)           # [G, dh]
    m_ref[...] = m_new
    l_ref[...] = l_new

    @pl.when(j == pl.num_programs(1) - 1)
    def _finish():
        o_ref[0] = (acc_ref[...] / jnp.maximum(l_ref[...], 1e-30)
                    ).astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("bs", "interpret"))
def decode_attention_pallas(
    q: jnp.ndarray,            # [B, G, dh]
    k: jnp.ndarray,            # [B, S, dh]
    v: jnp.ndarray,            # [B, S, dh]
    lengths: jnp.ndarray,      # [B] valid cache lengths
    *,
    bs: int = 512,
    interpret: bool,
) -> jnp.ndarray:
    B, G, dh = q.shape
    S = k.shape[1]
    assert S % bs == 0
    scale = 1.0 / (dh ** 0.5)
    grid = (B, S // bs)
    kernel = functools.partial(_decode_attn_kernel, bs=bs, scale=scale)
    return pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, G, dh), lambda b, j: (b, 0, 0)),
            pl.BlockSpec((1, bs, dh), lambda b, j: (b, j, 0)),
            pl.BlockSpec((1, bs, dh), lambda b, j: (b, j, 0)),
            pl.BlockSpec((1,), lambda b, j: (b,),
                         memory_space=pltpu.SMEM),
        ],
        out_specs=pl.BlockSpec((1, G, dh), lambda b, j: (b, 0, 0)),
        out_shape=jax.ShapeDtypeStruct((B, G, dh), q.dtype),
        scratch_shapes=[
            pltpu.VMEM((G, 1), jnp.float32),
            pltpu.VMEM((G, 1), jnp.float32),
            pltpu.VMEM((G, dh), jnp.float32),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary"),
        ),
        interpret=interpret,
    )(q, k, v, lengths)
