"""Layer ``kernels``: ``kernels.gqa_attention_roofline`` for the cells of
``mellum2_12b_ep4``: the flash kernels under ``gqa.attention`` (the layers
over the whole row), bounds from ``harness/gqa_attention_cost.py`` over the
shapes of the work, the head size the configuration's own ``head_dim`` (a
metric's ``workloads`` list is an entry of its own, which a PR that adds a
cell may not edit; a ``benchmark`` PR folds the two)."""

from benchmark.harness import gqa_attention_cost, swa_attention_cost


def read(obs):
    recorded = obs.get("trace")
    if not recorded or not recorded.devices:
        return None
    cell = obs["cell"]
    shapes = swa_attention_cost.work(
        cell.config["architecture"],
        int(cell.traffic["global_batch"]) // obs["chips"],
        cell.config["input"]["shape"][0])
    found = gqa_attention_cost.roofline(
        recorded, obs.get("modules") or [], obs["peaks"], "gqa.attention",
        shapes)
    return 100.0 * found["share"] if found else None
