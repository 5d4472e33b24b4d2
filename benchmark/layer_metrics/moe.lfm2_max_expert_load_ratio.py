"""Layer ``moe``: ``moe.max_expert_load_ratio`` for the cells of
``lfm2_8b_a1b_ep4``: the same counter (``RoutedExperts.max_load``, the
worst routed layer of the traced tail's last step), read the same way; a
``benchmark`` PR folds the two."""


def read(obs):
    counters = (obs.get("tail") or {}).get("counters") or {}
    loads = [v for name, v in counters.items() if name.endswith("max_load")]
    return max(loads) if loads else None
