"""Layer ``moe``: the largest held expert's routed pairs over the mean of
the held experts', the worst of the routed layers in the last step of the
traced tail: the program's own device counter (``RoutedExperts.max_load``),
read from the step's state after the group's fetch.  1 is a perfect
balance; the cost of the grouped product follows the rows, so an imbalance
costs padding only."""


def read(obs):
    counters = (obs.get("tail") or {}).get("counters") or {}
    loads = [v for name, v in counters.items() if name.endswith("max_load")]
    return max(loads) if loads else None
