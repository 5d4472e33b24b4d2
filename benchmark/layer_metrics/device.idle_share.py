"""Layer ``device``: 1 - busy / traced window, averaged over the chips
(device trace).  The ``breakdown`` names the gaps by the host span that
covered them."""

from benchmark.harness import trace


def read(obs):
    if not obs["trace"]:
        return None
    busy_s, window_s = trace.busy_and_window_s(obs["trace"])
    if busy_s <= 0 or window_s <= 0:
        return None
    return 100.0 * (1.0 - busy_s / window_s)
