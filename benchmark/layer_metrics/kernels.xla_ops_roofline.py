"""Layer ``kernels``: the share of their roofline that the step program's
XLA-generated instructions reach: sum of bounds over sum of measured times
on the first chip in the traced tail.  An instruction's bound is the larger
of its operations over the bfloat16 peak and its least HBM bytes over the
peak bandwidth (``harness/hlo_cost.py``, ``peaks.json``); waits for
prefetches count in the time and have no bound."""

from benchmark.harness import trace


def read(obs):
    if not obs["trace"]:
        return None
    found = trace.roofline(obs["trace"], obs["modules"], obs["peaks"])
    return 100.0 * found["share"] if found else None
