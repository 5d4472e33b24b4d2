"""Layer ``kernels``: the share of their roofline that the flash-attention
kernels under the scope ``gqa.attention`` reach (forward and both backward
kernels; the forward's results are kept across the recomputation): sum of
bounds over sum of measured times on the first chip.  A kernel's bound is
the larger of its required operations over the bfloat16 peak and its least
bytes over the peak bandwidth, both counted from the shapes of the work
(the causal half of the square per query head; ``k``, ``v``, ``dk``, ``dv``
once per key head): ``harness/gqa_attention_cost.py``, ``peaks.json``."""

from benchmark.harness import gqa_attention_cost


def read(obs):
    recorded = obs.get("trace")
    if not recorded or not recorded.devices:
        return None
    cell = obs["cell"]
    shapes = gqa_attention_cost.work(
        cell.config["architecture"],
        int(cell.traffic["global_batch"]) // obs["chips"],
        cell.config["input"]["shape"][0])
    found = gqa_attention_cost.roofline(
        recorded, obs.get("modules") or [], obs["peaks"], "gqa.attention",
        shapes)
    return 100.0 * found["share"] if found else None
