"""Public wrappers for the ADC (asymmetric-distance) code scan.

``adc_scan``        full-corpus compressed scan: per-query LUTs
                    (:func:`repro.quant.build_luts`, built ONCE per batch)
                    against the packed ``[n, m]`` code table, streamed in
                    blocks through the canonical unique top-k fold — the
                    compressed analogue of ``distance_topk``.  Two device
                    paths with identical select semantics:

                    * **XLA gather-fold** (default) — each code block
                      indexes the flattened ``[b, m*K]`` LUTs
                      (``jnp.take``), per-subspace contributions sum to the
                      decoded distance, blocks fold through
                      ``chunked_topk(unique=True)``; peak memory is
                      O(b * (block * m + C)) instead of the [b, n]
                      distance matrix.
                    * **Pallas kernel** (``use_kernel=True``) — codes
                      stream through VMEM in blocks, distances form as
                      one-hot(code) x LUT chunk matmuls on the MXU, and a
                      running top-C accumulator
                      (``merge_topk_unique_rounds``) folds in-kernel;
                      compiled on a TPU, interpreted on a CPU backend,
                      gated against the XLA fold.

``adc_window_topk`` the candidate-window variant for list-organised
                    indexes (IVF): gathers each candidate's ``m``-byte code
                    (instead of its ``4d``-byte fp32 row) and folds the
                    same way, with the probe/scan validity masks flowing in
                    exactly like ``rerank_topk``'s.

Both return what ``ref.adc_scan_ref`` returns: rows sorted canonically by
(dist, id) ascending with (+inf, -1) padding — the ``topk_unique``
contract, so a traced ``n_cand`` mask over the top-``max_cand`` prefix is
bit-identical to the static ``n_cand`` window (the PR 3-5 parity
invariant).  Ids are bit-identical across ref / fold / kernel; float
distances agree only to the ulp (blocking reassociates the subspace sum).
"""

from __future__ import annotations

from typing import Optional

import jax.numpy as jnp

from repro.kernels import interpret_mode
from repro.kernels.adc_scan.adc_scan import adc_scan_kernel_path

_FOLD_BUDGET = 32 << 20     # XLA fold: per-block gathered LUT working set


def pick_adc_block(b: int, n: int, m: int, k: int, *,
                   budget: int = _FOLD_BUDGET) -> int:
    """Largest power-of-two code-block (256..8192) whose per-fold working
    set — the [b, block, m] gathered LUT entries plus [b, block + 3k]
    merge state — fits ``budget``; small corpora collapse to one-shot."""
    block = 8192

    def working_set(blk: int) -> int:
        return 4 * max(1, b) * (blk * (m + 2) + 3 * k)

    while block > 256 and block >= 2 * max(1, n):
        block //= 2
    while block > 256 and working_set(block) > budget:
        block //= 2
    return block


def _lut_flat(luts):
    """[b, m, K] -> ([b, m*K], per-subspace index offsets [m])."""
    b, m, K = luts.shape
    offs = jnp.arange(m, dtype=jnp.int32) * K
    return luts.reshape(b, m * K), offs


def adc_scan(codes, luts, *, k: int, block: Optional[int] = None,
             use_kernel: bool = False, interpret: Optional[bool] = None):
    """(adc_dists [b, kk], rows [b, kk]) of the kk = min(k, n) best rows.

    ``codes [n, m]`` uint8 packed code table; ``luts [b, m, K]`` float32
    per-query tables.  ``block`` overrides the autotuned code-block;
    ``use_kernel`` routes through the Pallas kernel (the ``adc_kernel``
    build flag).
    """
    from repro.ann.topk import chunked_topk   # deferred: import cycle

    n, m = codes.shape
    b = luts.shape[0]
    kk = min(int(k), n)
    if use_kernel and n > 0 and b > 0:
        interpret = interpret_mode() if interpret is None else interpret
        return adc_scan_kernel_path(codes, luts, k=kk, block=block,
                                    interpret=interpret)
    flat, offs = _lut_flat(luts)
    blk = block if block else pick_adc_block(b, n, m, kk)
    codes = jnp.asarray(codes, jnp.int32)

    def chunk(s, size):
        idx = (codes[s:s + size] + offs[None, :]).reshape(-1)   # [size*m]
        d = jnp.take(flat, idx, axis=1).reshape(b, size, m).sum(-1)
        rows = jnp.broadcast_to(
            jnp.arange(s, s + size, dtype=jnp.int32), d.shape)
        return d, rows

    return chunked_topk(n, kk, blk, chunk, unique=True)


def adc_window_topk(codes, luts, cand, *, k: int, valid=None,
                    block: Optional[int] = None):
    """ADC top-k over a [b, C] candidate window (IVF's probed lists).

    ``cand`` holds row indices into ``codes`` (-1 = masked); ``valid`` is
    the optional extra [b, C] mask the traced probe/scan windows flow
    through, exactly like ``rerank_topk``.  Returns (adc_dists [b, kk],
    rows [b, kk]) with rows from ``cand`` (-1 where masked/padded),
    kk = min(k, C).  Gathers ``m`` code bytes per candidate — the whole
    point of scanning compressed-domain first.
    """
    from repro.ann.topk import chunked_topk   # deferred: import cycle

    cand = jnp.asarray(cand, jnp.int32)
    b, C = cand.shape
    kk = min(int(k), C)
    if C == 0:
        return (jnp.full((b, 0), jnp.inf, jnp.float32),
                jnp.full((b, 0), -1, jnp.int32))
    bad = cand < 0
    if valid is not None:
        bad = bad | ~valid
    flat, offs = _lut_flat(luts)
    m = codes.shape[1]
    codes = jnp.asarray(codes, jnp.int32)
    blk = block if block else pick_adc_block(b, C, m, kk)

    def chunk(s, size):
        cnd = cand[:, s:s + size]
        bd = bad[:, s:s + size]
        cd = codes[jnp.maximum(cnd, 0)]                       # [b, c, m]
        idx = (cd + offs[None, None, :]).reshape(b, -1)
        d = jnp.take_along_axis(flat, idx, axis=1) \
            .reshape(b, size, m).sum(-1)
        d = d + jnp.where(bd, jnp.inf, 0.0).astype(jnp.float32)
        return d, jnp.where(bd, -1, cnd)

    return chunked_topk(C, kk, blk, chunk, unique=True)
