"""ADC scan kernel: packed codes streamed through VMEM, LUT distances on
the MXU, running top-C candidate fold in VMEM scratch.

Per grid step a [bn, m] uint8 code block and the query tile's resident
lookup tables (built ONCE per batch) meet in VMEM.  TPUs have no fast
dynamic vector gather, so the per-candidate table lookup
``sum_j LUT[q, j, code[i, j]]`` is reformulated as a matmul the MXU can
chew: one-hot(code block) contracted against the LUT tile,

    d[q, i] = sum_{j, c} LUT[q, j, c] * onehot(codes[i, j])[c]

chunked over the K axis so the one-hot tile stays inside a VMEM budget.
The MXU contracts over one dimension, so the (subspace, code) pair is
folded into one axis of width ``m * kc``: the wrapper lays the LUTs out as
``[K / kc, b, m * kc]`` (column ``j * kc + c`` of chunk ``ci`` holds
``LUT[q, j, ci * kc + c]``), and the kernel widens each code to the same
columns with a 0/1 repeat matmul, ``codes @ E`` with
``E[j, t] = (t // kc == j)``.  The one-hot entries are exactly 0/1, so
each distance is a sum of the SAME m table entries the gather formulation
reads — this is a lookup evaluated as arithmetic, not an approximation.

Each block's (dist, row) pairs fold into a running per-query top-C
accumulator via the shared ``merge_topk_unique_rounds`` (bit-identical to
the canonical ``topk_unique`` select — the contract the traced ``n_cand``
mask parity rests on); the output is written once per query tile on the
last code step.  Peak memory is O(bq * (bn + C)) accumulator state plus
the one-hot chunk — the [b, n] distance matrix never exists.

Grid: (b/bq, n/bn), code axis sequential ("arbitrary"), query axis
parallel.  Rows past the true corpus length (shape padding) are masked to
(+inf, -1) in-kernel via a row iota against the static ``n``.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.select import NEG_ONE, merge_topk_unique_rounds

_ONEHOT_BUDGET = 2 << 20    # [bn, m * kc] one-hot chunk VMEM bytes


def _pick_kc(bn: int, m: int, K: int,
             budget: int = _ONEHOT_BUDGET) -> int:
    kc = K
    while kc > 8 and 4 * bn * m * kc > budget:
        kc //= 2
    return kc


def _adc_kernel(codes_ref, luts_ref, vals_out, idx_out, vals_ref, idx_ref,
                *, k: int, bq: int, bn: int, m: int, kc: int, n_chunks: int,
                n: int, n_steps: int):
    j = pl.program_id(1)                       # code-block step

    @pl.when(j == 0)
    def _init_state():
        vals_ref[...] = jnp.full_like(vals_ref, jnp.inf)
        idx_ref[...] = jnp.full_like(idx_ref, NEG_ONE)

    width = m * kc
    shift = kc.bit_length() - 1                # kc is a power of two
    col = jax.lax.broadcasted_iota(jnp.int32, (m, width), 1)
    sub = jax.lax.broadcasted_iota(jnp.int32, (m, width), 0)
    rep = (jax.lax.shift_right_logical(col, shift) == sub).astype(jnp.float32)
    # [bn, m * kc]: code of subspace t // kc at every column t (exact: the
    # codes are integers below 256 and rep is 0/1)
    codes = jax.lax.dot_general(
        codes_ref[...].astype(jnp.int32).astype(jnp.float32), rep,
        (((1,), (0,)), ((), ())), precision=jax.lax.Precision.HIGHEST,
        preferred_element_type=jnp.float32)
    slot = jax.lax.bitwise_and(
        jax.lax.broadcasted_iota(jnp.int32, (1, width), 1),
        kc - 1).astype(jnp.float32)            # [1, m * kc]: t % kc
    d = jnp.zeros((bq, bn), jnp.float32)
    # K-chunked one-hot matmul: static python unroll (K/kc steps, so the
    # LUT chunk offsets stay compile-time constants)
    for ci in range(n_chunks):
        sel = (codes == slot + float(ci * kc)).astype(jnp.float32)
        d = d + jax.lax.dot_general(
            luts_ref[ci], sel, (((1,), (1,)), ((), ())),
            precision=jax.lax.Precision.HIGHEST,
            preferred_element_type=jnp.float32)

    rows = j * bn + jax.lax.broadcasted_iota(jnp.int32, (bq, bn), 1)
    live = rows < n                            # shape-padding mask
    cand_d = jnp.concatenate(
        [vals_ref[...], jnp.where(live, d, jnp.inf)], axis=1)
    cand_i = jnp.concatenate(
        [idx_ref[...], jnp.where(live, rows, NEG_ONE)], axis=1)
    out_d, out_i = merge_topk_unique_rounds(cand_d, cand_i, k)
    vals_ref[...] = out_d
    idx_ref[...] = out_i

    @pl.when(j == n_steps - 1)
    def _flush():
        vals_out[...] = vals_ref[...]
        idx_out[...] = idx_ref[...]


@functools.partial(
    jax.jit, static_argnames=("k", "bq", "bn", "kc", "n", "interpret"))
def adc_scan_pallas(
    codes: jnp.ndarray,            # [n_pad, m] uint8 packed code table
    luts: jnp.ndarray,             # [b_pad, m, K] f32 per-query LUTs
    *,
    k: int,
    n: int,                        # true corpus length (pre-padding)
    bq: int = 8,
    bn: int = 256,
    kc: int = 128,
    interpret: bool,
):
    n_pad, m = codes.shape
    b_pad, _, K = luts.shape
    assert b_pad % bq == 0 and n_pad % bn == 0, (b_pad, n_pad, bq, bn)
    assert K % kc == 0 and kc & (kc - 1) == 0, (K, kc)
    n_steps = n_pad // bn
    n_chunks = K // kc
    # [K/kc, b, m*kc]: chunk ci, column j*kc + c holds LUT[:, j, ci*kc + c]
    luts = luts.reshape(b_pad, m, n_chunks, kc).transpose(2, 0, 1, 3) \
        .reshape(n_chunks, b_pad, m * kc)
    kernel = functools.partial(_adc_kernel, k=k, bq=bq, bn=bn, m=m, kc=kc,
                               n_chunks=n_chunks, n=n, n_steps=n_steps)
    vals, idx = pl.pallas_call(
        kernel,
        grid=(b_pad // bq, n_steps),
        in_specs=[
            pl.BlockSpec((bn, m), lambda i, j: (j, 0)),
            pl.BlockSpec((n_chunks, bq, m * kc), lambda i, j: (0, i, 0)),
        ],
        out_specs=[
            pl.BlockSpec((bq, k), lambda i, j: (i, 0)),
            pl.BlockSpec((bq, k), lambda i, j: (i, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((b_pad, k), jnp.float32),
            jax.ShapeDtypeStruct((b_pad, k), jnp.int32),
        ],
        scratch_shapes=[
            pltpu.VMEM((bq, k), jnp.float32),    # running top-C dists
            pltpu.VMEM((bq, k), jnp.int32),      # running top-C rows
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary"),
        ),
        interpret=interpret,
    )(codes, luts)
    return vals, idx


def _ceil_to(x: int, mult: int) -> int:
    return -(-x // mult) * mult


def adc_scan_kernel_path(codes, luts, *, k: int, block, interpret: bool):
    """Pad shapes to kernel tiles and run the Pallas scan (the
    ``use_kernel=True`` route of :func:`ops.adc_scan`)."""
    n, m = codes.shape
    b = luts.shape[0]
    bq = 8
    bn = max(8, min(int(block), 1024)) if block else 256
    bn = min(bn, _ceil_to(n, 8))
    kc = _pick_kc(bn, m, luts.shape[2])
    n_pad = _ceil_to(n, bn)
    b_pad = _ceil_to(b, bq)
    codes_p = jnp.pad(jnp.asarray(codes), ((0, n_pad - n), (0, 0)))
    luts_p = jnp.pad(jnp.asarray(luts, jnp.float32),
                     ((0, b_pad - b), (0, 0), (0, 0)))
    vals, idx = adc_scan_pallas(codes_p, luts_p, k=k, n=n, bq=bq, bn=bn,
                                kc=kc, interpret=interpret)
    return vals[:b], idx[:b]
