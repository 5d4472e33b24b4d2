"""Layer ``kernels``: the share of their roofline that the flash-attention
kernels under the scope ``mla.attention`` reach (forward, recomputed
forward and both backward kernels): sum of bounds over sum of measured
times on the first chip.  A kernel's bound is the larger of its required
operations (causal half; 192 for the scores, 128 for the values) over the
bfloat16 peak and its least bytes over the peak bandwidth
(``harness/attention_cost.py``, ``peaks.json``)."""

from benchmark.harness import attention_cost


def read(obs):
    recorded = obs.get("trace")
    if not recorded or not recorded.devices:
        return None
    found = attention_cost.roofline(recorded, obs.get("modules") or [],
                                    obs["peaks"], "mla.attention")
    return 100.0 * found["share"] if found else None
