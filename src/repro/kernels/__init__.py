"""Pallas TPU kernels for the framework's compute hot-spots.

Each kernel lives in its own subpackage with three files:

    <name>.py   pl.pallas_call + explicit BlockSpec VMEM tiling (TPU target)
    ops.py      jit'd public wrapper (shape padding, dtype plumbing,
                interpret mode on a CPU backend, see ``interpret_mode``)
    ref.py      pure-jnp oracle the tests assert against

Kernels:
    distance/      tiled L2/IP/cosine distance matrix (MXU matmul + epilogue)
    distance_topk/ streaming fused distance + top-k: VMEM-scratch top-k
                   accumulators, d-tiling, and query-block streaming so
                   nq and n are both unbounded by HBM (O(nq*k) output)
                   (supersedes the retired topk_scan kernel)
    rerank_topk/   fused candidate rerank: row gather driven by per-tile
                   SMEM id blocks into VMEM scratch + distance + running
                   unique-by-id top-k, so the [b, C, d] gathered candidate
                   tensor never exists in HBM (every algorithm's
                   verification hot path); IVF's probed lists, runs of
                   its cluster-major corpus, are copied as slices instead
    adc_scan/      compressed-domain ADC scan: per-query LUTs resident in
                   VMEM, packed uint8 codes streamed in blocks, distances
                   as one-hot x LUT matmuls on the MXU, running top-C fold
                   (the scan stage of the repro.quant two-stage design)
    hamming/       XOR + popcount distances over packed uint32 codes
    embedbag/      embedding-bag gather-reduce (recsys hot path)
    decode_attn/   single-token decode attention with online softmax

``select.py`` holds the in-kernel top-k selects the scan kernels share.
"""


def interpret_mode(backend: str | None = None) -> bool:
    """Whether a Pallas kernel runs in interpret mode: only on a ``cpu``
    backend, where there is no TPU to compile for.

    Decided when the kernel is called, never at import: ``backend``
    defaults to ``jax.default_backend()``.  On a TPU the kernel is
    compiled by Mosaic or the call raises; nothing falls back to the
    interpreter.
    """
    if backend is None:
        import jax

        backend = jax.default_backend()
    return backend == "cpu"
