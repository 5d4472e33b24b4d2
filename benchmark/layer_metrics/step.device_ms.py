"""Layer ``step``: time per step in which an instruction ran on the chip:
the union of the device-op intervals of the traced tail, averaged over the
chips, over its steps (device trace)."""

from benchmark.harness import trace


def read(obs):
    if not obs["trace"] or not obs["tail"]:
        return None
    busy_s, _ = trace.busy_and_window_s(obs["trace"])
    return busy_s * 1e3 / obs["tail"]["steps"] if busy_s > 0 else None
