"""Entry ``gluon_lm_train_step``: a decoder language model trained by
``parallel.gluon_step.GluonTrainStep`` with ``optimizer=Adam``, the whole
step (forward, the loss of both terms, backward, Adam) one donated XLA
program over a ``{"dp": n}`` mesh.  A sample is one row of ``seq_len``
token ids.

Traffic parameters: ``mesh``, ``global_batch``, ``compute_dtype``,
``steps_per_fetch``, ``traced_groups``.

The model's parameters are drawn on the host (``net.initialize(ctx=cpu)``):
the chip holds the step's state and nothing beside it.

The system's side of the check is the cell's own path in float32 at highest
matmul precision: the logits of both heads from the model's forward, and
one more ``GluonTrainStep`` step with the same Adam on the check batch.
After one step from zero state Adam's first moment is ``(1 - beta1) (g + wd
W0)``, so ``g = m / (1 - beta1) - wd W0``: the gradient of the program's own
backward pass, through its own update rule.

The check batch.  Routing is discontinuous: where a token's last selected
and first rejected selection scores lie closer than float32 rounding moves
them, system and reference may pick different experts, and that token's
output differs by O(10 %).  The reference reports every token's margin
(``routing_margins``); the check batch is the first ``check_batch`` of
``check_candidates`` seeded rows whose every margin, in the evaluation on
the program's weights and in the one on the weights ``check.py`` moves by
one ulp, exceeds ``tolerances.routing_margin``.  Rows are independent (no
batch statistics), routes are not forced and no output is masked.

Those rows are short (``check_seq_len``: a row of the timed length is
rejected almost surely), so the timed step's own shape is compared where
no route can flip: the layers before the first router (embedding, the
leading dense blocks with their attention kernels at the timed blocks,
the final norm, the head fused with the loss of both terms over its
chunks) run on seeded rows of ``global_batch x seq_len`` tokens, again in
float32 at highest precision, and their stream and the gradients named
``dense_prefix.<parameter>`` in ``check_gradients`` are compared like the
others.  Those rows travel to the reference as the system's ``y``: the
labels of both batches are the rows' own next tokens.
"""

import gc

import numpy as np

import gluon_model
from benchmark.harness import check
from benchmark.harness.train_window import TrainSession


def build(ctx):
    return Session(ctx)


def build_net(config, seed):
    """The configuration's model (the architecture's sizes and what
    ``factory_kwargs`` adds to them) with its weights drawn from ``seed``
    by the program's own initializers, on the host."""
    import importlib

    import mxnet_tpu as mx

    mx.random.seed(seed)
    np.random.seed(seed)
    module, _, name = config["factory"].rpartition(".")
    sizes = dict(config["architecture"], **config["factory_kwargs"])
    del sizes["mtp_loss_weight"]    # the loss's
    net = getattr(importlib.import_module(module), name)(**sizes)
    net.initialize(ctx=mx.cpu())
    return net


def named_params(net):
    """``gluon_model.named_params`` with the values as host arrays."""
    return [(name, np.asarray(value))
            for name, value in gluon_model.named_params(net)]


def moved_as_the_check_moves(params):
    """The weights as ``check.against_reference`` will move them: the same
    generator, the same order."""
    rng = np.random.RandomState(0)
    return [(n, check.one_ulp(v, rng)) for n, v in params]


def choose_check_batch(config, rng, reference, params, say):
    """-> (check_batch, check_seq_len) token ids: the first seeded candidate
    rows free of routing margins under ``tolerances.routing_margin``."""
    arch, tol = config["architecture"], config["tolerances"]
    rows, seq = int(config["check_batch"]), int(config["check_seq_len"])
    eps = float(tol["routing_margin"])
    candidates = rng.randint(0, arch["vocab_size"],
                             (int(config["check_candidates"]), seq)) \
        .astype(np.int32)
    smallest = np.minimum(
        reference.routing_margins(arch, params, candidates).min(axis=1),
        reference.routing_margins(arch, moved_as_the_check_moves(params),
                                  candidates).min(axis=1))
    free = np.flatnonzero(smallest > eps)
    say("check batch: margin %.3g; %d of %d candidate rows rejected (%.0f %%)"
        % (eps, len(candidates) - len(free), len(candidates),
           100.0 * (1 - len(free) / len(candidates))))
    if len(free) < rows:    # never seen; say so and take the widest
        say("check batch: fewer than %d rows free, taking the rows with the "
            "widest smallest margins" % rows)
        free = np.argsort(-smallest, kind="stable")
    return candidates[free[:rows]]


class Session(TrainSession):
    def __init__(self, ctx):
        from mxnet_tpu import optimizer
        from mxnet_tpu.gluon.nn import MultiTokenLoss
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
        self.params = named_params(self.net)
        ctx.say("model on the host: %.1f M parameters"
                % (sum(v.size for _, v in self.params) / 1e6))

        def make_step(dtype):
            adam = optimizer.Adam(
                learning_rate=train["lr"], beta1=train["beta1"],
                beta2=train["beta2"], epsilon=train["epsilon"],
                wd=train["wd"])
            loss = MultiTokenLoss(self.net.head, train["mtp_loss_weight"])
            return GluonTrainStep(self.net, loss, mesh=mesh,
                                  compute_dtype=dtype, optimizer=adam)

        self._make_step = make_step
        self.step = None

    # ------------------------------------------------------------- check
    def _logits(self, tokens):
        """Both heads' inference logits from the program's forward, staged
        as one program at highest matmul precision; the weights are on the
        chip only while it runs."""
        import jax

        import mxnet_tpu as mx
        from mxnet_tpu.gluon.block import staged_call
        from mxnet_tpu.ndarray import NDArray

        net = self.net
        params = list(net.collect_params().values())

        def both_heads(ids):
            main, mtp = net(ids)
            return mx.nd.stack(net.head(main), net.head(mtp))

        def forward(values, ids):
            override = {p: NDArray(v) for p, v in zip(params, values)}
            out, _ = staged_call(both_heads, override, None, (NDArray(ids),),
                                 train=False)
            return out._data

        device = self.ctx.devices[0]
        values = [jax.device_put(p.data().data_jax, device) for p in params]
        with jax.default_matmul_precision("highest"):
            return np.asarray(jax.jit(forward)(
                values, jax.device_put(tokens, device)))

    def _dense_prefix(self, tokens):
        """-> {``dense_prefix.hidden``: the stream of the layers before the
        first router on ``tokens``, ``dense_prefix.<name>``: the gradient
        of the loss of both terms read from that stream}: the program's
        blocks and its loss block staged as one program, float32 at
        highest matmul precision."""
        import jax

        import mxnet_tpu as mx
        from mxnet_tpu.gluon.block import staged_call
        from mxnet_tpu.gluon.nn import MultiTokenLoss
        from mxnet_tpu.ndarray import NDArray

        net = self.net
        dense = net.blocks[:self.ctx.config["architecture"][
            "first_k_dense_replace"]]
        params = [p for block in [net.embed] + dense + [net.norm, net.head]
                  for p in block.collect_params().values()]
        loss = MultiTokenLoss(net.head, self.train["mtp_loss_weight"])

        def prefix(ids):
            h = net.embed(ids)
            for block in dense:
                h = block(h)
            h = net.norm(h)
            return mx.nd.mean(loss((h, h), ids)), h

        def value(values, ids):
            override = {p: NDArray(v) for p, v in zip(params, values)}
            (out, hidden), _ = staged_call(prefix, override, None,
                                           (NDArray(ids),))
            return out._data, hidden._data

        device = self.ctx.devices[0]
        values = [jax.device_put(p.data().data_jax, device) for p in params]
        with jax.default_matmul_precision("highest"):
            (_, hidden), grads = jax.jit(jax.value_and_grad(
                value, has_aux=True))(values, jax.device_put(tokens, device))
        cut = len(net.prefix)
        named = {"dense_prefix." + p.name[cut:]: g
                 for p, g in zip(params, grads)}
        named["dense_prefix.hidden"] = hidden
        return {n: np.asarray(named[n])
                for n in self.ctx.config["check_gradients"] if n in named}

    def system_outputs(self, reference):
        """Logits, loss and the named gradients from the program, on the
        check batch, with what the reference needs to compute the same."""
        import jax

        cfg, train = self.ctx.config, self.train
        rng = np.random.RandomState(self.ctx.seed % (2 ** 32))
        tokens = choose_check_batch(cfg, rng, reference, self.params,
                                    self.ctx.say)
        timed_shape = rng.randint(
            0, cfg["architecture"]["vocab_size"],
            (self.batch, int(cfg["input"]["shape"][0]))).astype(np.int32)
        logits = self._logits(tokens)
        gradients = self._dense_prefix(timed_shape)
        gc.collect()
        host = dict(self.params)
        with jax.default_matmul_precision("highest"):
            step = self._make_step(None)
            names = gluon_model.trainable_names(self.net)
            loss = float(np.asarray(step(tokens, tokens)))
            first_moment = dict(zip(names, step.opt_state[0::2]))
            for n in cfg["check_gradients"]:
                if n in first_moment:
                    gradients[n] = np.asarray(first_moment[n]) \
                        / (1.0 - train["beta1"]) - train["wd"] * host[n]
        del step, first_moment      # the twin's state leaves the chip
        gc.collect()
        return {"params": self.params, "x": tokens, "y": timed_shape,
                "dropout_masks": [], "logits": logits, "loss": loss,
                "gradients": gradients}

    # ------------------------------------------------------------ window
    def warm_up(self):
        import jax

        seq = int(self.ctx.config["input"]["shape"][0])
        vocab = self.ctx.config["architecture"]["vocab_size"]
        self.step = self._make_step(self.ctx.traffic["compute_dtype"])
        self.x = jax.jit(
            lambda key: jax.random.randint(key, (self.batch, seq), 0, vocab,
                                           np.int32),
            out_shardings=self.step.batch_sharding)(
            jax.random.PRNGKey(self.ctx.seed % (2 ** 32)))
        first = self.fetch(self.dispatch())     # compiles or loads
        self.ctx.say("first step: loss %.4f" % first)
        for _ in range(self.steps_per_fetch):
            handle = self.dispatch()
        self.fetch(handle)

    def dispatch(self):
        return self.step(self.x, self.x)

    def fetch(self, handle):
        return float(np.asarray(handle))

    def read_counters(self):
        """The routed layers' device counters of the last step, read from
        the step's state after a group's fetch: {name: value}."""
        cut = len(self.net.prefix)
        return {p.name[cut:]: float(np.asarray(v)[0])
                for p, v in zip(self.step.aux, self.step.aux_vals)
                if p.name.endswith(("held_pairs", "max_load"))}

    def _describe(self, window):
        window = super()._describe(window)
        window["counters"] = counters = self.read_counters()
        self.ctx.say("routed layers, last step: pairs on held experts %s; "
                     "largest held expert over the mean %s" % tuple(
                         [round(v, 3) for n, v in sorted(counters.items())
                          if n.endswith(kind)]
                         for kind in ("held_pairs", "max_load")))
        return window

    program_counters = staticmethod(gluon_model.program_counters)
