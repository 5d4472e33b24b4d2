"""Layer ``kernels``: ``kernels.swa_attention_roofline`` for the cells of
``laguna_s_2_1_ep32``: the flash kernels under ``swa.attention`` (the
window layers' forward and both backward kernels), bounds from
``harness/swa_attention_cost.py`` over the band's pairs, with the query
heads of a window layer (``num_attention_heads_per_layer``, not
``num_attention_heads``) and the configuration's window.  A kernel that
computes pairs outside the band reads lower."""

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
    shapes["heads"] = int(arch["num_attention_heads_per_layer"][
        list(arch["layer_types"]).index("sliding_attention")])
    found = swa_attention_cost.roofline(
        recorded, obs.get("modules") or [], obs["peaks"], "swa.attention",
        shapes)
    return 100.0 * found["share"] if found else None
