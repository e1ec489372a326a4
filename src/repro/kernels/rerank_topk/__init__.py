from repro.kernels.rerank_topk.ops import (PATH_COUNTS,  # noqa: F401
                                           pick_rerank_block, rerank_lists,
                                           rerank_topk)
from repro.kernels.rerank_topk.ref import rerank_topk_ref  # noqa: F401
from repro.kernels.rerank_topk.rerank_topk import (  # noqa: F401
    merge_topk_unique_rounds, rerank_lists_pallas, rerank_topk_pallas)
