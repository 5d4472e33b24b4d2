"""Operations and least bytes of the flash-attention kernels under
grouped-query heads, beside ``attention_cost.py`` (which reads both from
the instruction's operands and knows equal heads only).

Here both come from the shapes of the work, not from what the instruction
was handed: ``rows`` sequences of ``seq`` positions, ``heads`` query heads
and ``kv_heads`` key / value heads of ``d``.  Operations are what causal
attention requires, half the square of the sequence per query head, per
pair of positions:

    forward              scores 2 d, values 2 d
    backward, dq         scores 2 d, dp 2 d, dq 2 d
    backward, dk and dv  scores 2 d, dv 2 d, dp 2 d, dk 2 d

Least bytes: ``q``, ``o``, ``do``, ``dq`` once per query head, ``k``, ``v``,
``dk``, ``dv`` once per key head, ``lse`` and ``delta`` (float32) once per
query head.  A kernel that is fed the key heads repeated moves more than
that and so reads a lower share of its roofline, not a higher one.

A kernel is recognised by its instruction, as ``attention_cost.py`` does
it: a ``custom-call`` to ``tpu_custom_call`` with three operands (forward)
or six (backward: one result for dq, two for dk and dv), whose first
operand is ``q (rows * heads, seq, d)``.
"""

from . import hlo_cost
from .attention_cost import KERNEL_TARGET

PER_PAIR = {"forward": 4, "dq": 6, "dkv": 8}        # x d operations
# (arrays the size of q, arrays the size of k, float32 rows) read + written
ARRAYS = {"forward": (2, 2, 1), "dq": (3, 2, 2), "dkv": (2, 4, 2)}


def work(arch, rows, seq):
    """The shapes of the work from a configuration's ``architecture``."""
    heads = int(arch["num_attention_heads"])
    return {"rows": int(rows), "seq": int(seq), "heads": heads,
            "kv_heads": int(arch["num_key_value_heads"]),
            "d": int(arch["hidden_size"]) // heads}


def kernel_kind(instruction_text, shapes):
    """("forward" | "dq" | "dkv", bytes of an element of q) of one
    attention kernel instruction over ``shapes``, or None."""
    _, opcode, (result, operands, _) = hlo_cost.split_instruction(
        instruction_text)
    if opcode != "custom-call" or KERNEL_TARGET not in instruction_text:
        return None
    ins = hlo_cost.shape_dims(operands)
    outs = hlo_cost.shape_dims(result)
    if len(ins) not in (3, 6):
        return None
    dtype, dims, _ = ins[0]
    if dims != [shapes["rows"] * shapes["heads"], shapes["seq"], shapes["d"]]:
        return None
    kind = "forward" if len(ins) == 3 else "dq" if len(outs) == 1 else "dkv"
    return kind, hlo_cost.DTYPE_BYTES[dtype]


def kernel_cost(kind, itemsize, shapes):
    """(required operations, least bytes) of one kernel."""
    positions = shapes["rows"] * shapes["seq"]
    flops = positions * shapes["heads"] * shapes["seq"] / 2.0 \
        * PER_PAIR[kind] * shapes["d"]
    as_q, as_k, rows = ARRAYS[kind]
    least = positions * shapes["d"] * itemsize * (
        as_q * shapes["heads"] + as_k * shapes["kv_heads"]) \
        + rows * positions * shapes["heads"] * 4
    return flops, least


def roofline(recorded, modules, peaks, scope, shapes):
    """Sum of bounds over sum of measured times of the attention kernels
    under ``scope``: {"share", "bound_s", "time_s", "kernels"} or None."""
    from . import trace

    flops_peak, bw_peak = peaks["bf16_flops_per_s"], peaks["hbm_bytes_per_s"]
    time_s = bound_s = 0.0
    found = 0
    for op in trace.leaf_ops(recorded):
        if op.opcode != "custom-call" \
                or scope not in trace._cost(op, modules)[1]:
            continue
        kind = kernel_kind(op.text, shapes)
        if kind is None:
            continue
        flops, least = kernel_cost(kind[0], kind[1], shapes)
        time_s += (op.end - op.start) / 1e9
        bound_s += max(flops / flops_peak, least / bw_peak)
        found += 1
    if time_s <= 0:
        return None
    return {"share": bound_s / time_s, "bound_s": bound_s, "time_s": time_s,
            "kernels": found}
