"""Layer ``kernels``: ``kernels.hc_roofline``, the hyper-connections' share
of their roofline: the least bytes any implementation of the coefficients
and the two mixes must move in a step (``harness/hc_cost.py``, from the
configuration's shapes: every stream read and written once an operation,
the recomputation not counted) over the peak HBM bandwidth
(``peaks.json``), divided by the device time of a step under the scopes
``hc.coef`` and ``hc.mix`` (as ``hc.ms_per_step`` reads it).  None where
the program has no such scope."""

from benchmark.harness import hc_cost, program_spans, scope_time


def read(obs):
    recorded = program_spans._on_a_chip(obs)
    if not recorded:
        return None
    seconds = scope_time.scope_s(recorded, obs.get("modules") or [],
                                 hc_cost.SCOPES)
    if seconds <= 0:
        return None
    cell = obs["cell"]
    least = hc_cost.least_bytes(
        cell.config["architecture"], int(cell.traffic["global_batch"]),
        int(cell.config["input"]["shape"][0]),
        hc_cost.itemsize(cell.traffic["compute_dtype"]))
    return 100.0 * least / obs["peaks"]["hbm_bytes_per_s"] \
        / (seconds / obs["tail"]["steps"])
