"""Layer ``kernels``: which bound dominates: the share of the summed
roofline bound that comes from instructions bound by HBM bandwidth (the
rest is bound by the bfloat16 peak)."""

from benchmark.harness import trace


def read(obs):
    if not obs["trace"]:
        return None
    found = trace.roofline(obs["trace"], obs["modules"], obs["peaks"])
    if not found or found["bound_s"] <= 0:
        return None
    return 100.0 * found["bytes_bound_s"] / found["bound_s"]
