from repro.kernels.hamming.ops import hamming_topk, word_major
from repro.kernels.hamming.ref import hamming_topk_ref

__all__ = ["hamming_topk", "hamming_topk_ref", "word_major"]
