"""Layer ``moe``: ``moe.max_expert_load_ratio`` for the cells of
``laguna_s_2_1_ep32``: the same counter (``RoutedExperts.max_load``, the
largest held expert's pairs over the held experts' mean, worst routed layer
of the traced tail's last step), read the same way; here the routers'
selection bias is trained by its balancing rule."""


def read(obs):
    counters = (obs.get("tail") or {}).get("counters") or {}
    loads = [v for name, v in counters.items() if name.endswith("max_load")]
    return max(loads) if loads else None
