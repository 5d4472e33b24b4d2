"""What the training entries share: building the configuration's Gluon model
from the seed, the check batch, and reading the program's parameters and its
dropout draws for the reference."""

import importlib

import numpy as np


def build_net(config, seed):
    """The configuration's model, its weights drawn from ``seed`` by the
    program's own initializer (at the first forward: shapes are deferred)."""
    import mxnet_tpu as mx

    mx.random.seed(seed)
    np.random.seed(seed)
    module, _, name = config["factory"].rpartition(".")
    net = getattr(importlib.import_module(module), name)(
        **config["factory_kwargs"])
    net.initialize()
    return net


def check_batch(config, seed):
    """The seeded sample the system and the reference both compute on."""
    rng = np.random.RandomState(seed)
    n = int(config["check_batch"])
    x = rng.rand(n, *config["input"]["shape"]).astype(np.float32)
    y = rng.randint(0, config["architecture"]["classes"], (n,)) \
        .astype(np.int32)
    return x, y


def predict_logits(net, x):
    """Inference logits from the program's forward on ``x``.

    First one eager forward of a single zero image: the program resolves
    its deferred shapes and draws the weights only by running (one small
    program per operator signature; a full-size probe because Inception's
    fixed pool refuses a smaller one).  Then the whole forward staged as
    one program at highest matmul precision, through ``staged_call``, the
    idiom the program's own whole-step tracers use."""
    import jax

    import mxnet_tpu as mx
    from mxnet_tpu.gluon.block import staged_call
    from mxnet_tpu.ndarray import NDArray

    net(mx.nd.zeros((1,) + tuple(x.shape[1:]))).wait_to_read()
    params = list(net.collect_params().values())

    def forward(values, batch):
        override = {p: NDArray(v) for p, v in zip(params, values)}
        out, _ = staged_call(net, override, None, (NDArray(batch),),
                             train=False)
        return out._data

    with jax.default_matmul_precision("highest"):
        return np.asarray(jax.jit(forward)(
            [p.data().data_jax for p in params], x))


def named_params(net):
    """[(name without the model's prefix, value)] in the program's order."""
    cut = len(net.prefix)
    return [(name[cut:], p.data().data_jax)
            for name, p in net.collect_params().items()]


def trainable_names(net):
    cut = len(net.prefix)
    return [name[cut:] for name, p in net.collect_params().items()
            if p.grad_req != "null"]


def replay_dropout(net, shapes):
    """The scaled masks the next training step's dropout layers will draw,
    by running the model's own dropout layers on ones under the key that
    step will get, then putting the program's key chain back.  Relies on the
    dropout layers being the step's only random draws, in call order."""
    import mxnet_tpu as mx
    from mxnet_tpu import autograd
    from mxnet_tpu import random as mxrandom

    if not shapes:
        return []
    layers = []
    net.apply(lambda b: layers.append(b)
              if type(b).__name__ == "Dropout" else None)
    if len(layers) != len(shapes):
        raise ValueError("the reference expects %d dropout layers, the "
                         "model has %d" % (len(shapes), len(layers)))
    state = mxrandom.get_state()
    key = mxrandom.next_key()
    with mxrandom.TraceRNG(key), autograd.train_mode():
        masks = [np.asarray(layer(mx.nd.ones(tuple(shape))).data_jax)
                 for layer, shape in zip(layers, shapes)]
    mxrandom.set_state(state)
    return masks


def program_counters():
    """The program's own jit-cache misses and compiles so far."""
    from mxnet_tpu import runtime_stats

    probe = runtime_stats.health_probe()
    return {"jit_cache_misses": probe["jit_cache_misses"],
            "compiles": probe["compiles"]}
