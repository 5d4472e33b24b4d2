"""Entry ``gluon_hc_lm_train_step``: ``gluon_next_token_train_step`` for a
decoder whose residual stream is widened to ``hc_mult`` streams mixed by
hyper-connections (``gluon.nn.MLAMoELM`` with ``hc_mult`` > 1 and no
multi-token prediction module): the embedding is copied into every stream,
every sublayer reads and writes the streams through its hyper-connection,
and the streams are summed before the final norm.  It differs from the
entry it subclasses in what it compares at the timed shape.

Traffic parameters: those of ``gluon_next_token_train_step``.

The timed step's own shape is compared once, in float32 at highest
precision on seeded rows of ``global_batch x seq_len`` tokens, in one
program: what lies before the first router, here the embedding, its
expansion into the streams, the leading dense layers whole (latent
attention with its yarn tables, the flash kernels at the blocks the timed
step runs them at, the dense feed-forward and both hyper-connections of
each layer), the collapse of the streams, the final norm and the head
fused with the loss over its chunks: ``dense_prefix.hidden`` and the
gradients ``dense_prefix.<parameter>``.

What a step does to the routers' selection bias and counters is compared
like a gradient (``after_step.*``, ``gluon_next_token_train_step``'s
docstring).

The host's memory: the reference is told the names the comparison reads
(``COMPARED``) and returns their gradients alone, and the entry prints the
process's peak resident memory after its set-up's stages.
"""

import resource

import numpy as np

import gluon_next_token_train_step as one_stream


def build(ctx):
    return Session(ctx)


def peak_host_gb():
    """The process's peak resident memory so far, GB (``ru_maxrss``, KiB
    on Linux)."""
    return resource.getrusage(resource.RUSAGE_SELF).ru_maxrss * 1024 / 1e9


class Session(one_stream.Session):
    def system_outputs(self, reference):
        reference.COMPARED = frozenset(self.ctx.config["check_gradients"])
        self.ctx.say("peak host memory before the check: %.2f GB"
                     % peak_host_gb())
        out = super().system_outputs(reference)
        self.ctx.say("peak host memory after the system's side of the "
                     "check: %.2f GB" % peak_host_gb())
        return out

    def warm_up(self):
        super().warm_up()
        self.ctx.say("peak host memory after the warm-up: %.2f GB"
                     % peak_host_gb())

    def _timed_shape(self, tokens):
        """-> {``dense_prefix.*``} of ``check_gradients`` (module
        docstring): the program's blocks and its loss block staged as one
        program, float32 at highest matmul precision."""
        import jax

        import mxnet_tpu as mx
        from mxnet_tpu.gluon.nn import NextTokenLoss

        net = self.net
        dense = net.blocks[:self.ctx.config["architecture"][
            "first_k_dense_replace"]]
        params = [p for blk in [net.embed] + dense + [net.norm, net.head]
                  for p in blk.collect_params().values()]
        loss = NextTokenLoss(net.head)

        def prefix(ids):
            x = net.expand(mx.nd, net.embed(ids))
            for blk in dense:
                x = blk(x)
            hidden = net.norm(net.collapse(mx.nd, x))
            return mx.nd.mean(loss(hidden, ids)), hidden

        run, values, ids = self._staged(prefix, params, tokens)
        with jax.default_matmul_precision("highest"):
            (_, hidden), grads = jax.jit(jax.value_and_grad(
                run, has_aux=True))(values, ids)
        cut = len(net.prefix)
        named = {"dense_prefix." + p.name[cut:]: g
                 for p, g in zip(params, grads)}
        named["dense_prefix.hidden"] = hidden
        return {n: np.asarray(named[n])
                for n in self.ctx.config["check_gradients"] if n in named}
