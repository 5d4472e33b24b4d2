"""sym.contrib — contrib op namespace for symbols.

Reference: python/mxnet/symbol/contrib.py.  The op set mirrors
nd.contrib (ndarray/contrib.py); symbolic control flow (foreach /
while_loop / cond) builds the corresponding graph nodes when the
executor traces the graph — on this framework symbols execute by
tracing into XLA, so the nd implementations are reused at bind time.
"""

from __future__ import annotations

from ..ops import registry as _reg
from .register import populate as _populate

_CONTRIB_OPS = [
    "box_nms", "box_iou", "MultiBoxPrior", "MultiBoxTarget",
    "MultiBoxDetection", "ROIAlign", "BilinearResize2D",
    "AdaptiveAvgPooling2D", "boolean_mask", "quadratic",
    "arange_like", "getnnz", "index_copy", "index_add",
    "adamw_update", "_contrib_flash_attention", "_contrib_div_sqrt_dim",
    "_contrib_interleaved_matmul_selfatt_qk",
    "_contrib_interleaved_matmul_selfatt_valatt",
    "_contrib_rms_norm", "_contrib_rope", "_contrib_gated_silu",
    "_contrib_mla_qkv", "_contrib_mla_out",
    "_contrib_gqa_qkv", "_contrib_gqa_out", "_contrib_head_gate",
    "_contrib_gated_short_conv",
    "_contrib_moe_route", "_contrib_moe_experts",
    "_contrib_linear_cross_entropy",
    "_contrib_hc_coefficients", "_contrib_hc_pre_mix", "_contrib_hc_post_mix",
]

_populate(globals(), names=[n for n in _CONTRIB_OPS if n in _reg.list_ops()])
