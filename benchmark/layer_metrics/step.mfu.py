"""Layer ``step``: model FLOP/s utilization: the operations the forward and
backward passes of one sample require (the configuration's flops function,
recomputation not counted) times the window's samples per second, over the
chips' bfloat16 peak.  Arithmetic on the end-to-end number, named for what
it is: not a kernel's roofline share, and blind to idle time's cause."""


def read(obs):
    config = obs["cell"].config
    flops = getattr(obs["reference"], config["flops_function"])(
        config["architecture"], config["input"]["shape"])
    rate = obs["values"]["train_samples_per_s"]
    return 100.0 * flops * rate / (
        obs["chips"] * obs["peaks"]["bf16_flops_per_s"])
