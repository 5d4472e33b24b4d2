"""Operations and least bytes of the flash-attention kernels
(``mxnet_tpu/ops/attention.py``: one forward and two backward Pallas
kernels, custom calls in the step program), beside ``hlo_cost.py``, which
gives a custom call no operations.

A kernel is recognised by its instruction: a ``custom-call`` to
``tpu_custom_call`` whose operands are ``q (bh, s, d)``, ``k (bh, s, d)``,
``v (bh, s, dv)`` and, in the backward kernels, ``do``, ``lse``, ``delta``.
Operations are what causal attention requires, half the square of the
sequence (the blocks a kernel computes above the diagonal and masks are its
waste, not its work), per pair of positions and head:

    forward              scores 2 d, values 2 dv
    backward, dq         scores 2 d, dp 2 dv, dq 2 d
    backward, dk and dv  scores 2 d, dv 2 dv, dp 2 dv, dk 2 d

Least bytes: every operand read once and every result written once
(``hlo_cost.min_hbm_bytes`` on the instruction's own text).
"""

from . import hlo_cost

KERNEL_TARGET = "tpu_custom_call"


def kernel_flops(instruction_text):
    """Required operations of one attention kernel instruction, or None
    where the instruction is not one of the three."""
    _, opcode, (result, operands, _) = hlo_cost.split_instruction(
        instruction_text)
    if opcode != "custom-call" or KERNEL_TARGET not in instruction_text:
        return None
    ins = [dims for _, dims, _ in hlo_cost.shape_dims(operands)]
    outs = [dims for _, dims, _ in hlo_cost.shape_dims(result)]
    if len(ins) not in (3, 6) or any(len(d) != 3 for d in ins[:3]):
        return None
    (bh, sq, d), (_, sk, _), (_, _, dv) = ins[:3]
    pairs = bh * sq * sk / 2.0
    if len(ins) == 3:
        per_pair = 2 * d + 2 * dv
    elif len(outs) == 1:
        per_pair = 4 * d + 2 * dv
    else:
        per_pair = 4 * d + 4 * dv
    return pairs * per_pair


def kernels(recorded, modules, scope):
    """[(Op, required operations)] of the first chip's attention kernels in
    the traced window whose ``op_name`` lies under the named scope."""
    from . import trace

    found = []
    for op in trace.leaf_ops(recorded):
        if op.opcode != "custom-call":
            continue
        op_name = trace._cost(op, modules)[1]
        flops = kernel_flops(op.text)
        if flops is not None and scope in op_name:
            found.append((op, flops))
    return found


def roofline(recorded, modules, peaks, scope):
    """Sum of bounds over sum of measured times of the attention kernels
    under ``scope``: {"share", "bound_s", "time_s", "kernels"} or None."""
    flops_peak, bw_peak = peaks["bf16_flops_per_s"], peaks["hbm_bytes_per_s"]
    time_s = bound_s = 0.0
    found = kernels(recorded, modules, scope)
    for op, flops in found:
        time_s += (op.end - op.start) / 1e9
        bound_s += max(flops / flops_peak,
                       hlo_cost.min_hbm_bytes(op.text) / bw_peak)
    if time_s <= 0:
        return None
    return {"share": bound_s / time_s, "bound_s": bound_s, "time_s": time_s,
            "kernels": len(found)}
