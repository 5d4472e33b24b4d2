"""Layer ``lm_head``: ``lm_head.products_per_step``, the product
instructions a step under the scope ``lm_head`` (the head fused with its
loss, forward and backward): the first chip's executed instructions whose
``op_name`` lies under the scope, as ``lm_head.ms_per_step`` matches them
(``harness/scope_time.py``), and whose operations in the optimized HLO are
above 0 (a fusion counts once), over the traced tail's steps.  Four a
chunk while the backward pass formed a chunk's logits again, three since
the forward pass forms the gradient (PR 38).  None without a device trace
or where no instruction carries the scope; 0 where it holds no product."""

from benchmark.harness import program_spans, scope_time, trace


def read(obs):
    recorded = program_spans._on_a_chip(obs)
    if not recorded:
        return None
    under, modules = scope_time._under(("lm_head",)), obs.get("modules") or []
    seen = products = 0
    for op in trace.leaf_ops(recorded):
        flops, op_name = trace._cost(op, modules)
        if under.search(op_name):
            seen += 1
            products += flops > 0
    return products / obs["tail"]["steps"] if seen else None
