"""Layer ``kernels``: ``kernels.ffn_roofline``, the share of their roofline
that the gated-SiLU feed-forward's instructions reach (the scope
``ffn.gated``, as ``ffn.ms_per_step`` reads it): sum of bounds over sum of
measured times on the first chip in the traced tail.  An instruction's
bound is the larger of its operations (from the optimized HLO) over the
bfloat16 peak and its least HBM bytes over the peak bandwidth
(``harness/hlo_cost.py``, ``peaks.json``); waits for transfers count in
the time and have no bound.  The bytes are not cut to what the measured
time could move, as ``trace.roofline`` cuts them: a byte count too high
reads above 100 %.  A product whose fusion forms an operand again on every
pass over its result takes longer for the same operations and reads
lower.  None where the program has no such scope."""

from benchmark.harness import hlo_cost, program_spans, scope_time, trace

SCOPE = "ffn.gated"


def read(obs):
    recorded = program_spans._on_a_chip(obs)
    if not recorded:
        return None
    modules, under = obs.get("modules") or [], scope_time._under((SCOPE,))
    flops_peak = obs["peaks"]["bf16_flops_per_s"]
    bw_peak = obs["peaks"]["hbm_bytes_per_s"]
    time_s = bound_s = 0.0
    for op in trace.leaf_ops(recorded):
        flops, op_name = trace._cost(op, modules)
        if not under.search(op_name):
            continue
        time_s += (op.end - op.start) / 1e9
        if not op.opcode.endswith(("-start", "-done")):
            bound_s += max(flops / flops_peak,
                           hlo_cost.min_hbm_bytes(op.text) / bw_peak)
    return 100.0 * bound_s / time_s if time_s > 0 else None
