"""Layer ``moe``: ``moe.max_expert_load_ratio`` for the cells of
``mellum2_12b_ep4``: the same counter (``RoutedExperts.max_load``, the
worst routed layer of the traced tail's last step), read the same way; here
the routers have no bias and are levelled by the balancing loss alone.  A
``benchmark`` PR folds the doubles."""


def read(obs):
    counters = (obs.get("tail") or {}).get("counters") or {}
    loads = [v for name, v in counters.items() if name.endswith("max_load")]
    return max(loads) if loads else None
