"""Layer ``kernels``: ``kernels.gqa_attention_roofline`` for the cells of
``laguna_s_2_1_ep32``: the flash kernels under ``gqa.attention`` (the full
layers), bounds from ``harness/gqa_attention_cost.py`` over the causal
half of the square, with the query heads of a full layer
(``num_attention_heads_per_layer``, not ``num_attention_heads``) and the
configuration's own ``head_dim``."""

from benchmark.harness import gqa_attention_cost, swa_attention_cost


def read(obs):
    recorded = obs.get("trace")
    if not recorded or not recorded.devices:
        return None
    cell = obs["cell"]
    arch = cell.config["architecture"]
    shapes = swa_attention_cost.work(
        arch, int(cell.traffic["global_batch"]) // obs["chips"],
        cell.config["input"]["shape"][0])
    shapes["heads"] = int(arch["num_attention_heads_per_layer"][
        list(arch["layer_types"]).index("full_attention")])
    found = gqa_attention_cost.roofline(
        recorded, obs.get("modules") or [], obs["peaks"], "gqa.attention",
        shapes)
    return 100.0 * found["share"] if found else None
