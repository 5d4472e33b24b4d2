"""Entry ``gluon_gated_window_lm_train_step``: ``gluon_window_lm_train_step``
for a decoder with a leading dense layer, window and full attention layers
of their own head counts with a per-head output gate, and routers whose
selection bias the balancing rule trains (``gluon.nn.LayerTypesMoELM`` with
``mlp_layer_types``, ``num_attention_heads_per_layer``, ``gating_types``
and ``bias_update_rate``).  It differs from the entry it subclasses in what
it compares at the timed shape.

Traffic parameters: those of ``gluon_next_token_train_step``.

The timed step's own shape is compared twice, in float32 at highest
precision on seeded rows of ``global_batch x seq_len`` tokens, in one
program.  What lies before the first router, here the embedding and the
leading dense layers whole (attention, gate and all), then the final norm
and the head fused with the loss over its chunks: ``dense_prefix.hidden``
and the gradients ``dense_prefix.<parameter>``.  And the first window
layer's attention alone, fed that stream as a constant: its output
``swa_timed.out`` and the gradients ``swa_timed.<parameter>`` of half the
output's mean square, the flash kernels at the blocks the timed step runs
them at, over the window's band.

The warm-up prints the routed layers' loads after every group, as the
entry it subclasses does; here what levels them is the selection bias's
balancing rule (``gluon_next_token_train_step``'s docstring), and what a
step does to that state is compared like a gradient (``after_step.*``).
"""

import numpy as np

import gluon_window_lm_train_step as window_lm


def build(ctx):
    return Session(ctx)


class Session(window_lm.Session):
    def _timed_shape(self, tokens):
        """-> {``dense_prefix.*``, ``swa_timed.*``} of ``check_gradients``
        (module docstring): the program's blocks and its loss block staged
        as one program, float32 at highest matmul precision."""
        import jax

        import mxnet_tpu as mx
        from mxnet_tpu.gluon.nn import NextTokenLoss

        net, arch = self.net, self.ctx.config["architecture"]
        dense = net.blocks[:list(arch["mlp_layer_types"]).index("sparse")]
        alone = net.blocks[list(arch["layer_types"]).index(
            "sliding_attention")]
        parts = {"dense_prefix.": [net.embed] + dense + [net.norm, net.head],
                 "swa_timed.": [alone.ln1, alone.mixer]}
        params, owner = [], {}
        for part, blocks in parts.items():
            for blk in blocks:
                for p in blk.collect_params().values():
                    owner[p] = part
                    params.append(p)
        loss = NextTokenLoss(net.head)

        def both(ids):
            h = net.embed(ids)
            for blk in dense:
                h = blk(h)
            hidden = net.norm(h)
            out = alone.mixer(alone.ln1(mx.nd.stop_gradient(h)))
            value = mx.nd.mean(loss(hidden, ids)) \
                + 0.5 * mx.nd.mean(mx.nd.square(out))
            return value, (hidden, out)

        run, values, ids = self._staged(both, params, tokens)
        with jax.default_matmul_precision("highest"):
            (_, (hidden, out)), grads = jax.jit(jax.value_and_grad(
                run, has_aux=True))(values, ids)
        cut = len(net.prefix)
        named = {owner[p] + p.name[cut:]: g for p, g in zip(params, grads)}
        named.update({"dense_prefix.hidden": hidden, "swa_timed.out": out})
        return {n: np.asarray(named[n])
                for n in self.ctx.config["check_gradients"] if n in named}
