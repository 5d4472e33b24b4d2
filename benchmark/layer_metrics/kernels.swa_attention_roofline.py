"""Layer ``kernels``: the share of their roofline that the flash-attention
kernels under the scope ``swa.attention`` reach (the window layers' forward
and both backward kernels; the forward's results are kept across the
recomputation): sum of bounds over sum of measured times on the first chip.
A kernel's bound is the larger of its required operations over the bfloat16
peak and its least bytes over the peak bandwidth, both counted from the
shapes of the work: the band's pairs only, ``S W - W (W - 1) / 2`` a query
head; ``k``, ``v``, ``dk``, ``dv`` once per key head
(``harness/swa_attention_cost.py``, ``peaks.json``).  A kernel that runs
blocks behind the window reads lower."""

from benchmark.harness import swa_attention_cost


def read(obs):
    recorded = obs.get("trace")
    if not recorded or not recorded.devices:
        return None
    cell = obs["cell"]
    arch = cell.config["architecture"]
    shapes = swa_attention_cost.work(
        arch, int(cell.traffic["global_batch"]) // obs["chips"],
        cell.config["input"]["shape"][0], arch["sliding_window"])
    found = swa_attention_cost.roofline(
        recorded, obs.get("modules") or [], obs["peaks"], "swa.attention",
        shapes)
    return 100.0 * found["share"] if found else None
