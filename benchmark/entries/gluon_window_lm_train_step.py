"""Entry ``gluon_window_lm_train_step``: ``gluon_next_token_train_step`` for a
decoder whose every layer is routed and whose attention layers are of two
types, some with a sliding window (``gluon.nn.LayerTypesMoELM`` built from
``layer_types`` with ``"sliding_attention"``).  The model's blocks hand
their routers' balancing terms to ``NextTokenLoss`` as a value of the step
program, so the fetched loss carries them.  It differs from the entry it
subclasses in what it compares at the timed shape and in what the warm-up
prints.

Traffic parameters: those of ``gluon_next_token_train_step``.

The timed step's own shape is compared three times, in float32 at highest
precision on seeded rows of ``global_batch x seq_len`` tokens, in one
program.  What lies before the first router, here the embedding alone, then
the final norm and the head fused with the loss over its chunks:
``dense_prefix.hidden`` and the gradients ``dense_prefix.<parameter>``.
And the first layer of each attention type alone, fed the embedding's
stream as a constant: the output ``swa_timed.out`` / ``gqa_timed.out`` and
the gradients ``swa_timed.<parameter>`` / ``gqa_timed.<parameter>`` of half
the output's mean square, the flash kernels at the blocks the timed step
runs them at: a window layer's over its band, a full layer's with its
scaled rotary tables past the original length.

The routers have no selection bias: what levels their loads is the
balancing loss, through Adam, during the warm-up's ``warmup_groups`` groups
of the timed step.  The warm-up prints every routed layer's busiest held
expert over the mean and the pairs on the held experts after every group,
so a run's lines show the loads' course.  A router that the balancing term
alone trains (``RoutedExperts(router_trained_by="balance")``) takes no
weight decay, so its compared gradient is Adam's first moment alone, ``g =
m / (1 - beta1)``: ``wd W0`` is some thousand times such a gradient, and
subtracting what was never added would leave float32's rounding of it.
"""

import gc

import numpy as np

import gluon_model

import gluon_next_token_train_step as one_stream


def build(ctx):
    return Session(ctx)


# the layers compared alone at the timed shape: the first of each type
TIMED = {"swa_timed.": "sliding_attention", "gqa_timed.": "full_attention"}


class Session(one_stream.Session):
    def _timed_shape(self, tokens):
        """-> {``dense_prefix.*``, ``swa_timed.*``, ``gqa_timed.*``} of
        ``check_gradients`` (module docstring): the program's blocks and its
        loss block staged as one program, float32 at highest matmul
        precision."""
        import jax

        import mxnet_tpu as mx
        from mxnet_tpu.gluon.nn import NextTokenLoss

        net = self.net
        kinds = list(self.ctx.config["architecture"]["layer_types"])
        alone = {part: net.blocks[kinds.index(kind)]
                 for part, kind in TIMED.items()}
        parts = {"dense_prefix.": [net.embed, net.norm, net.head]}
        parts.update({part: [blk.ln1, blk.mixer]
                      for part, blk in alone.items()})
        params, owner = [], {}
        for part, blocks in parts.items():
            for blk in blocks:
                for p in blk.collect_params().values():
                    owner[p] = part
                    params.append(p)
        loss = NextTokenLoss(net.head)

        def all_of_them(ids):
            h = net.embed(ids)
            hidden = net.norm(h)
            value = mx.nd.mean(loss(hidden, ids))
            outs = {}
            for part, blk in alone.items():
                outs[part] = blk.mixer(blk.ln1(mx.nd.stop_gradient(h)))
                value = value + 0.5 * mx.nd.mean(mx.nd.square(outs[part]))
            return value, (hidden, outs)

        run, values, ids = self._staged(all_of_them, params, tokens)
        with jax.default_matmul_precision("highest"):
            (_, (hidden, outs)), grads = jax.jit(jax.value_and_grad(
                run, has_aux=True))(values, ids)
        cut = len(net.prefix)
        named = {owner[p] + p.name[cut:]: g for p, g in zip(params, grads)}
        named["dense_prefix.hidden"] = hidden
        named.update({part + "out": out for part, out in outs.items()})
        return {n: np.asarray(named[n])
                for n in self.ctx.config["check_gradients"] if n in named}

    def system_outputs(self, reference):
        """The entry's, with the gradient of every parameter that takes no
        decay read again from the check step's first moment (module
        docstring)."""
        make, made = self._make_step, []
        self._make_step = lambda dtype: made.append(make(dtype)) or made[-1]
        try:
            out = super().system_outputs(reference)
        finally:
            self._make_step = make
        step = made.pop()
        names = gluon_model.trainable_names(self.net)
        moments = dict(zip(names, step.opt_state[0::2]))
        for n, p in zip(names, step.trainable):
            if n in out["gradients"] and p.wd_mult == 0:
                out["gradients"][n] = np.asarray(moments[n]) \
                    / (1.0 - self.train["beta1"])
        del step, moments       # the twin's state leaves the chip
        gc.collect()
        return out

    def warm_up(self):
        """The timed step compiled or loaded and run once, a group, then
        ``warmup_groups`` groups of it, the loads printed after each."""
        super(one_stream.Session, self).warm_up()
        for group in range(int(self.ctx.traffic["warmup_groups"])):
            for _ in range(self.steps_per_fetch):
                handle = self.dispatch()
            value = self.fetch(handle)
            counters = sorted(self.read_counters().items())
            self.ctx.say(
                "warm-up group %d: loss %.4f; largest held expert over the "
                "mean %s; pairs on held experts %s; balancing term %s"
                % ((group + 1, value) + tuple(
                    [round(v, 4) for n, v in counters if n.endswith(kind)]
                    for kind in ("max_load", "held_pairs", "balance_term"))))

    def read_counters(self):
        """The routed layers' device counters of the last step: the two of
        the entry it subclasses and every router's balancing term."""
        cut = len(self.net.prefix)
        return {p.name[cut:]: float(np.asarray(v)[0])
                for p, v in zip(self.step.aux, self.step.aux_vals)}
