"""Entry ``gluon_next_token_train_step``: a decoder language model with one
stream and one loss term (``gluon.nn.DecoderLM`` and its kin: the hidden
states go to ``NextTokenLoss``, which applies the model's head, tied to the
embedding or not, fused with the loss), trained by
``parallel.gluon_step.GluonTrainStep`` with ``optimizer=Adam``, the whole
step one donated XLA program over a ``{"dp": n}`` mesh.  A sample is one
row of ``seq_len`` token ids.  Beside ``gluon_lm_train_step.py`` (two
streams, two heads' terms), whose measured loop, check batch and counters
it uses: that file's docstring has the check batch's rule and why a
gradient is read from Adam's first moment.

Traffic parameters: ``mesh``, ``global_batch``, ``compute_dtype``,
``steps_per_fetch``, ``traced_groups``, ``warmup_groups``.

A model whose routers train their selection bias by the balancing rule
(``RoutedExperts(bias_update_rate=)``: part of the step program) starts the
window from that rule's own steady state: the warm-up runs
``warmup_groups`` groups of the timed step, nothing else, so the bias as
the seed drew it has moved by the rate a step until every expert's load
swings about the mean.  Random routers are not balanced (the experts'
popularity spreads by 11 %), a training run's are; the pairs on the held
experts, and with them the step time, otherwise follow the seed (PERF.md,
Findings PR 32).  What a step does to that state is compared like a
gradient: ``after_step.<router_bias>`` (the bias's move in units of the
rate) and ``after_step.<held_pairs>`` of ``check_gradients``.

The timed step's own shape is compared twice, in float32 at highest
precision on seeded rows of ``global_batch x seq_len`` tokens, in one
program.  The layers before the first router (embedding, the leading dense
blocks, the final norm, the head fused with the loss over its chunks):
their stream and the gradients named ``dense_prefix.<parameter>``.  And the
first attention layer, which those layers need not hold, fed the dense
blocks' stream as a constant: its output ``gqa_timed.out`` and the
gradients ``gqa_timed.<parameter>`` of half the output's mean square, the
flash kernels at the blocks the timed step runs them at.
"""

import gc
import importlib

import numpy as np

import gluon_lm_train_step as two_streams
import gluon_model
from benchmark.harness.train_window import TrainSession


def build(ctx):
    return Session(ctx)


def build_net(config, seed):
    """The configuration's model (the architecture's sizes and what
    ``factory_kwargs`` adds to them) with its weights drawn from ``seed``
    by the program's own initializers, on the host."""
    import mxnet_tpu as mx

    mx.random.seed(seed)
    np.random.seed(seed)
    module, _, name = config["factory"].rpartition(".")
    net = getattr(importlib.import_module(module), name)(
        **dict(config["architecture"], **config["factory_kwargs"]))
    net.initialize(ctx=mx.cpu())
    return net


class Session(two_streams.Session):
    def __init__(self, ctx):
        from mxnet_tpu import optimizer
        from mxnet_tpu.gluon.nn import NextTokenLoss
        from mxnet_tpu.parallel.gluon_step import GluonTrainStep
        from mxnet_tpu.parallel.mesh import create_mesh

        self.ctx = ctx
        cfg, traffic = ctx.config, ctx.traffic
        self.batch = int(traffic["global_batch"])
        self.steps_per_fetch = int(traffic["steps_per_fetch"])
        self.traced_groups = int(traffic["traced_groups"])
        self.train = train = cfg["training"]
        mesh = create_mesh(dict(traffic["mesh"]), devices=ctx.devices)
        self.net = build_net(cfg, ctx.seed)
        self.params = two_streams.named_params(self.net)
        ctx.say("model on the host: %.1f M parameters in %d arrays"
                % (sum(v.size for _, v in self.params) / 1e6,
                   len(self.params)))

        def make_step(dtype):
            adam = optimizer.Adam(
                learning_rate=train["lr"], beta1=train["beta1"],
                beta2=train["beta2"], epsilon=train["epsilon"],
                wd=train["wd"])
            return GluonTrainStep(self.net, NextTokenLoss(self.net.head),
                                  mesh=mesh, compute_dtype=dtype,
                                  optimizer=adam)

        self._make_step = make_step
        self.step = None

    # ------------------------------------------------------------- check
    def _staged(self, fn, params, tokens, train=True):
        """``fn(NDArray of tokens)`` staged over ``params``' values -> a
        function of (values, tokens) for ``jax.jit``, and the values on the
        chip."""
        import jax

        from mxnet_tpu.gluon.block import staged_call
        from mxnet_tpu.ndarray import NDArray

        def run(values, ids):
            override = {p: NDArray(v) for p, v in zip(params, values)}
            out, _ = staged_call(fn, override, None, (NDArray(ids),),
                                 train=train)
            return jax.tree.map(lambda a: a._data, out,
                                is_leaf=lambda a: isinstance(a, NDArray))

        device = self.ctx.devices[0]
        values = [jax.device_put(p.data().data_jax, device) for p in params]
        return run, values, jax.device_put(tokens, device)

    def _logits(self, tokens):
        """Inference logits from the program's forward, staged as one
        program at highest matmul precision; the weights are on the chip
        only while it runs."""
        import jax

        net = self.net
        run, values, ids = self._staged(
            lambda t: net.head(net(t)),
            list(net.collect_params().values()), tokens, train=False)
        with jax.default_matmul_precision("highest"):
            return np.asarray(jax.jit(run)(values, ids))

    def _timed_shape(self, tokens):
        """-> {``dense_prefix.hidden``, ``dense_prefix.<name>``,
        ``gqa_timed.out``, ``gqa_timed.<name>``} of ``check_gradients``
        (module docstring): the program's blocks and its loss block staged
        as one program, float32 at highest matmul precision."""
        import jax

        import mxnet_tpu as mx
        from mxnet_tpu.gluon.nn import NextTokenLoss

        net, arch = self.net, self.ctx.config["architecture"]
        dense = net.blocks[:arch["num_dense_layers"]]
        attends = net.blocks[list(arch["layer_types"]).index(
            "full_attention")]
        parts = {"dense_prefix.": [net.embed] + dense + [net.norm, net.head],
                 "gqa_timed.": [attends.ln1, attends.mixer]}
        params, owner = [], {}
        for part, blocks in parts.items():
            for blk in blocks:
                for p in blk.collect_params().values():
                    if p not in owner:      # a tied head's weight once
                        owner[p] = part
                        params.append(p)
        loss = NextTokenLoss(net.head)

        def both(ids):
            h = net.embed(ids)
            for blk in dense:
                h = blk(h)
            hidden = net.norm(h)
            out = attends.mixer(attends.ln1(mx.nd.stop_gradient(h)))
            value = mx.nd.mean(loss(hidden, ids)) \
                + 0.5 * mx.nd.mean(mx.nd.square(out))
            return value, (hidden, out)

        run, values, ids = self._staged(both, params, tokens)
        with jax.default_matmul_precision("highest"):
            (_, (hidden, out)), grads = jax.jit(jax.value_and_grad(
                run, has_aux=True))(values, ids)
        cut = len(net.prefix)
        named = {owner[p] + p.name[cut:]: g for p, g in zip(params, grads)}
        named.update({"dense_prefix.hidden": hidden, "gqa_timed.out": out})
        return {n: np.asarray(named[n])
                for n in self.ctx.config["check_gradients"] if n in named}

    def system_outputs(self, reference):
        """Logits, loss and the named gradients from the program, on the
        check batch, with what the reference needs to compute the same."""
        import jax

        cfg, train = self.ctx.config, self.train
        rng = np.random.RandomState(self.ctx.seed % (2 ** 32))
        tokens = two_streams.choose_check_batch(cfg, rng, reference,
                                                self.params, self.ctx.say)
        timed_shape = rng.randint(
            0, cfg["architecture"]["vocab_size"],
            (self.batch, int(cfg["input"]["shape"][0]))).astype(np.int32)
        logits = self._logits(tokens)
        gradients = self._timed_shape(timed_shape)
        gc.collect()
        host = dict(self.params)
        with jax.default_matmul_precision("highest"):
            step = self._make_step(None)
            names = gluon_model.trainable_names(self.net)
            loss = float(np.asarray(step(tokens, tokens)))
            first_moment = dict(zip(names, step.opt_state[0::2]))
            cut = len(self.net.prefix)
            state = {"after_step." + p.name[cut:]: np.asarray(v)
                     for p, v in zip(step.aux, step.aux_vals)}
            for n in cfg["check_gradients"]:
                if n in first_moment:
                    gradients[n] = np.asarray(first_moment[n]) \
                        / (1.0 - train["beta1"]) - train["wd"] * host[n]
                elif n.endswith("router_bias") and n in state:
                    gradients[n] = (state[n] - host[n.partition(".")[2]]) \
                        / cfg["architecture"]["bias_update_rate"]
                elif n in state:
                    gradients[n] = state[n]
        del step, first_moment      # the twin's state leaves the chip
        gc.collect()
        return {"params": self.params, "x": tokens, "y": timed_shape,
                "dropout_masks": [], "logits": logits, "loss": loss,
                "gradients": gradients}

    # ------------------------------------------------------------ window
    def warm_up(self):
        """The timed step compiled or loaded and run once, then
        ``warmup_groups`` groups of it (module docstring)."""
        super().warm_up()
        for _ in range(int(self.ctx.traffic["warmup_groups"])):
            for _ in range(self.steps_per_fetch):
                handle = self.dispatch()
            self.fetch(handle)
        counters = self.read_counters()
        self.ctx.say("after the warm-up: largest held expert over the mean "
                     "%s" % [round(v, 3) for n, v in sorted(counters.items())
                             if n.endswith("max_load")])

    def _describe(self, window):
        """Beside the two-stream entry's lines, every group's time: a window
        that loses a second or more (PERF.md section 7) shows where."""
        window = super()._describe(window)
        n = self.steps_per_fetch
        self.ctx.say("groups, s (dispatches + fetch): %s" % [
            round(sum(window["dispatch_s"][i * n:(i + 1) * n]) + waited, 3)
            for i, waited in enumerate(window["fetch_s"])])
        return window
