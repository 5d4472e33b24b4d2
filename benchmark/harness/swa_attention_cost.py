"""Operations and least bytes of the flash-attention kernels under a
sliding window, beside ``gqa_attention_cost.py`` (grouped-query heads, the
causal half of the square), whose recognition of a kernel and whose count
of a pair's products and of the arrays moved it uses.

Both come from the shapes of the work, not from what the kernel ran:
``rows`` sequences of ``seq`` positions, ``heads`` query heads and
``kv_heads`` key / value heads of ``d``, a query seeing the ``window`` keys
up to itself.  A query head attends ``S W - W (W - 1) / 2`` pairs of
positions a row (the band: ``W`` keys a query, fewer for the first ``W - 1``
queries), and every pair costs what it costs in ``gqa_attention_cost``
(forward 4 d, dq 6 d, dk and dv 8 d operations).  Least bytes are the causal
kernels': ``q``, ``o``, ``do``, ``dq`` once per query head, ``k``, ``v``,
``dk``, ``dv`` once per key head, ``lse`` and ``delta`` once per query head:
a window changes which pairs are computed, not which arrays are touched.  A
kernel that runs blocks outside the band takes longer for the same bound
and so reads a lower share of its roofline, never a higher one.
"""

from . import gqa_attention_cost
from .gqa_attention_cost import kernel_kind  # noqa: F401  (the same call)


def work(arch, rows, seq, window=None):
    """The shapes of the work from a configuration's ``architecture`` with
    a ``head_dim`` of its own (not hidden / heads); ``window``: the band's
    width, None for a layer over the whole row."""
    shapes = {"rows": int(rows), "seq": int(seq),
              "heads": int(arch["num_attention_heads"]),
              "kv_heads": int(arch["num_key_value_heads"]),
              "d": int(arch["head_dim"])}
    if window is not None:
        shapes["window"] = min(int(window), int(seq))
    return shapes


def band_pairs(seq, window):
    """Pairs of positions one query head attends in one row."""
    return seq * window - window * (window - 1) / 2.0


def kernel_cost(kind, itemsize, shapes):
    """(required operations, least bytes) of one kernel over the band."""
    _, least = gqa_attention_cost.kernel_cost(kind, itemsize, shapes)
    flops = shapes["rows"] * shapes["heads"] \
        * band_pairs(shapes["seq"], shapes["window"]) \
        * gqa_attention_cost.PER_PAIR[kind] * shapes["d"]
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
