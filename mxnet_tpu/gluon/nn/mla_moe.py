"""Decoder language model with latent attention and a mixture of experts
(DeepSeek-V3, arXiv:2412.19437; the block of JoyAI-LLM-Flash and its
kin): Gluon HybridBlocks over the operators of ``ops/llm.py`` and the
Pallas flash attention of ``ops/attention.py``.  Docs: docs/LLM_OPS.md.

- :class:`RMSNorm`, :class:`GatedFFN`: the norm and the gated-SiLU
  feed-forward, no biases.
- :class:`MLAttention`: multi-head latent attention in its training form
  (no weight absorption): low-rank query and key/value projections with a
  norm on each latent, rotary embedding on a part of each head that all
  heads share on the key side, scores over ``nope + rope`` and values of
  another head size.
- :class:`RoutedExperts`: top-k routing over sigmoid scores with a
  selection bias or over softmax scores without one, the held experts'
  grouped feed-forward and, unless told not to, one shared expert; a
  softmax router may hand its balancing term to the loss.  The
  layer is told which experts it holds (``held_experts=(first, count)``):
  the router keeps the model's width, selection and normalisation run over
  all experts, and the layer computes its own experts' part of the sum:
  one chip's share of an expert-parallel layer, without the exchange.
- :class:`HyperConnection`: the coefficients of one sublayer's
  manifold-constrained hyper-connection (mHC, DeepSeek-AI 2025, on
  Hyper-Connections, arXiv:2409.19606) over a residual stream widened to
  n streams: ``h_pre``, ``h_post`` and the Sinkhorn-normalised mixing
  matrix ``H_res``.
- :class:`DecoderBlock`: ``h += mixer(norm(h)); h += ffn(norm(h))`` over
  the token mixer and the feed-forward it is given, its forward recomputed
  in the backward pass while a step is staged.  The one block class of
  every decoder model here (``gluon/nn/hybrid_lm.py`` has the other
  mixers).  **A side term for the loss**: while training, a feed-forward
  may return ``(y, term)``, ``term`` a scalar that belongs in the loss
  (a router's balancing term, already weighted); the block then returns
  ``(h, term)`` through its recomputation, the model sums the blocks'
  terms and returns ``(hidden, side)``, and the loss block adds ``side``
  to every row's loss: a value of the program from the block to the loss,
  with its gradient.  With ``hc_mult`` n > 1 the block carries n streams
  and wraps each sublayer in a :class:`HyperConnection`.
- :class:`DecoderLM`: embedding, blocks, final norm and a head that may be
  tied to the embedding (with n streams: the embedding copied into each,
  their sum normed); it returns the normed hidden states (with the
  blocks' side term where they give one), ``net.head`` makes logits of
  them, and :class:`NextTokenLoss` fuses the head with the loss over token
  chunks.
- :class:`MLAMoELM`: a :class:`DecoderLM` of latent-attention blocks with
  one multi-token prediction module that shares embedding and head.  It
  returns the two streams' normed hidden states;
  :class:`MultiTokenLoss` fuses the head with the loss of both terms.
  Without the module (``num_nextn_predict_layers`` 0) it returns one, as
  :class:`DecoderLM` does; only then may its residual be widened.

Named scopes (``xray.scope``): ``mla.proj``, ``mla.attention``,
``moe.route``, ``moe.dispatch``, ``moe.experts``, ``moe.combine``,
``moe.shared``, ``moe.aux``, ``mtp``, ``lm_head``, and ``ffn.gated``
around every :class:`GatedFFN` (``ops/llm.py::gated_silu``), dense or a
shared expert (then inside ``moe.shared``); ``hc.coef`` and ``hc.mix``
around a hyper-connection's coefficients and its two mixes.  Counters: every
:class:`RoutedExperts` keeps ``held_pairs`` (pairs routed to its held
experts in the last step) and ``max_load`` (the largest held expert's
pairs over their mean), and one with a balancing loss ``balance_term``
(its router's last term, unweighted: 1 when the experts are chosen and
scored alike), as parameters that take no gradient, updated by the step
like batch-norm statistics, so they leave the step program with its
state.
"""

from __future__ import annotations

import math

from ... import initializer as _init
from ... import xray as _xray
from ...ops.llm import rotary_frequencies
from ..block import Block, HybridBlock, recomputed, update_aux_state
from .basic_layers import Dense, Embedding

__all__ = ["RMSNorm", "GatedFFN", "MLAttention", "RoutedExperts",
           "HyperConnection", "DecoderBlock", "DecoderLM", "MLAMoELM",
           "NextTokenLoss", "MultiTokenLoss"]

# at a quarter of the scores' spread (0.05) the bias alone made the busiest
# expert 4-7 times the mean at random weights (PERF.md, PR 28)
ROUTER_BIAS_STD = 0.01


class RMSNorm(HybridBlock):
    """``x * rsqrt(mean(x^2) + eps) * weight`` over the last axis."""

    def __init__(self, units, epsilon=1e-6, **kwargs):
        super().__init__(**kwargs)
        self._epsilon = epsilon
        with self.name_scope():
            self.weight = self.params.get("weight", shape=(units,),
                                          init="ones")

    def hybrid_forward(self, F, x, weight):
        return F.contrib.rms_norm(x, weight, eps=self._epsilon)


class GatedFFN(HybridBlock):
    """``W_down(silu(W_gate x) * W_up x)``, no biases."""

    def __init__(self, units, hidden_size, weight_std=0.02, **kwargs):
        super().__init__(**kwargs)
        init = _init.Normal(weight_std)
        with self.name_scope():
            self.gate_weight = self.params.get(
                "gate_weight", shape=(hidden_size, units), init=init)
            self.up_weight = self.params.get(
                "up_weight", shape=(hidden_size, units), init=init)
            self.down_weight = self.params.get(
                "down_weight", shape=(units, hidden_size), init=init)

    def hybrid_forward(self, F, x, gate_weight, up_weight, down_weight):
        return F.contrib.gated_silu(x, gate_weight, up_weight, down_weight)


def deepseek_yarn(dim, rope_theta, rope_scaling):
    """A DeepSeek ``config.json``'s ``rope_scaling`` (``type`` ``"yarn"``,
    ``factor``, ``original_max_position_embeddings``, ``beta_fast``,
    ``beta_slow``, ``mscale``, ``mscale_all_dim``) for ``dim`` rotary lanes
    -> ``(inv_freq, amplitude, softmax_factor)``: yarn's frequencies
    (``ops.llm.rotary_frequencies``), the tables' amplitude ``m(mscale) /
    m(mscale_all_dim)`` and what the softmax scale is multiplied by,
    ``m(mscale_all_dim)^2`` where ``mscale_all_dim`` is given, with ``m(a)
    = 0.1 a ln(factor) + 1`` (1 for a factor of 1 or less): DeepSeek-V3's
    ``yarn_get_mscale``."""
    how = dict(rope_scaling)
    kind = how.get("type", how.get("rope_type"))
    if kind != "yarn":
        raise ValueError("deepseek_yarn: rope_scaling type %r is not 'yarn'"
                         % (kind,))
    factor = float(how["factor"])

    def m(a):
        return 0.1 * a * math.log(factor) + 1.0 if factor > 1 else 1.0

    every = how.get("mscale_all_dim", 0)
    inv_freq, amplitude = rotary_frequencies(
        dim, rope_theta=rope_theta, rope_type="yarn", factor=factor,
        original_max_position_embeddings=how[
            "original_max_position_embeddings"],
        beta_fast=how.get("beta_fast", 32), beta_slow=how.get("beta_slow", 1),
        attention_factor=m(how.get("mscale", 1)) / m(every))
    return inv_freq, amplitude, m(every) ** 2 if every else 1.0


class MLAttention(HybridBlock):
    """Multi-head latent attention, causal, in its training form:
    ``mla_qkv`` (``ops/llm.py``), the flash kernels, ``mla_out``.  The
    parameters have the names and shapes of the published checkpoints;
    the heads and the nope / rotary / value parts are split on the
    weights inside the operator, which hands ``q``, ``k`` and ``v`` to
    the kernel as ``(B, heads, S, head)``, the layout it reads."""

    def __init__(self, units, num_heads, q_lora_rank, kv_lora_rank,
                 qk_nope_head_dim, qk_rope_head_dim, v_head_dim,
                 rope_theta=10000.0, epsilon=1e-6, weight_std=0.02,
                 rope_scaling=None, **kwargs):
        super().__init__(**kwargs)
        self._heads = num_heads
        self._theta, self._epsilon = rope_theta, epsilon
        qk = qk_nope_head_dim + qk_rope_head_dim
        self._sm_scale = qk ** -0.5
        self._rotary = {}
        if rope_scaling:
            inv_freq, amplitude, softmax = deepseek_yarn(
                qk_rope_head_dim, rope_theta, rope_scaling)
            self._rotary = {"inv_freq": inv_freq, "amplitude": amplitude}
            self._sm_scale *= softmax
        init = _init.Normal(weight_std)
        shapes = {
            "qa_weight": (q_lora_rank, units),
            "qb_weight": (num_heads * qk, q_lora_rank),
            "kva_weight": (kv_lora_rank + qk_rope_head_dim, units),
            "kvb_weight": (num_heads * (qk_nope_head_dim + v_head_dim),
                           kv_lora_rank),
            "o_weight": (units, num_heads * v_head_dim),
        }
        with self.name_scope():
            for name, shape in shapes.items():
                setattr(self, name, self.params.get(name, shape=shape,
                                                    init=init))
            self.qnorm_weight = self.params.get(
                "qnorm_weight", shape=(q_lora_rank,), init="ones")
            self.kvnorm_weight = self.params.get(
                "kvnorm_weight", shape=(kv_lora_rank,), init="ones")

    def hybrid_forward(self, F, x, qa_weight, qb_weight, kva_weight,
                       kvb_weight, o_weight, qnorm_weight, kvnorm_weight):
        q, k, v = F.contrib.mla_qkv(
            x, qa_weight, qb_weight, kva_weight, kvb_weight, qnorm_weight,
            kvnorm_weight, num_heads=self._heads, theta=self._theta,
            eps=self._epsilon, **self._rotary)
        with _xray.scope("mla.attention"):
            o = F.contrib.flash_attention(q, k, v, causal=True,
                                          sm_scale=self._sm_scale)
        return F.contrib.mla_out(o, o_weight)


class RoutedExperts(HybridBlock):
    """Routed experts, with one shared expert unless ``shared`` is false;
    see the module docstring.  ``shared``: true, a shared expert as wide as
    a routed one (``hidden_size``); a number, one of that width.

    ``num_experts``: the router's width (the model's experts);
    ``held_experts`` ``(first, count)``: the consecutive expert ids held
    here, all of them by default.  ``scoring``: ``"sigmoid"`` scores with a
    selection bias, or ``"softmax"`` scores over all the experts and no
    bias (the layer then has no ``router_bias`` parameter).
    ``balance_loss_weight``: when not zero, a training forward returns
    ``(y, balance_loss_weight x the router's balancing term)``
    (``ops/llm.py::moe_route(balance=True)``: ``experts x sum_e f_e P_e``
    over this call's tokens), which :class:`DecoderBlock` carries to the
    loss, and keeps the term in ``balance_term``.
    ``router_trained_by``: ``"loss"``, the routing weights carry the
    loss's gradient to the router; or ``"balance"``, they reach the
    experts' sum as constants and ``router_weight`` takes no weight decay
    (``wd_mult`` 0), so the balancing term alone moves the router.  That
    is what one chip's share of an expert-parallel layer computes without
    the exchange: the loss's gradient on a routing weight is the stream's
    gradient times that expert's output, which for an expert held
    elsewhere is not here, and the held experts' part alone pulls every
    token towards them.
    ``router_bias`` takes no gradient (the
    training loop that balances the load owns it); it is drawn N(0,
    ``ROUTER_BIAS_STD``), non-zero so that it takes part in the selection,
    small against the scores' spread (~0.2) so that it does not decide
    it.  ``route_epsilon``: what ``moe_route`` adds to the selected scores'
    sum.  ``bias_update_rate``: when not zero, a training step ends with the
    balancing rule of DeepSeek-V3 section 2.1.2 (auxiliary-loss-free): the
    bias of an expert that got more than the mean of the batch's choices
    goes down by the rate, of one that got less up; the new bias leaves the
    step with its state, like a batch norm's running statistics."""

    def __init__(self, units, hidden_size, num_experts, experts_per_token,
                 held_experts=None, routed_scaling_factor=1.0,
                 weight_std=0.02, shared=True, route_epsilon=1e-20,
                 bias_update_rate=0.0, scoring="sigmoid",
                 balance_loss_weight=0.0, router_trained_by="loss",
                 **kwargs):
        super().__init__(**kwargs)
        first, held = held_experts or (0, num_experts)
        if first < 0 or held < 1 or first + held > num_experts:
            raise ValueError("held_experts %r do not lie in 0..%d"
                             % ((first, held), num_experts))
        if scoring != "sigmoid" and bias_update_rate:
            raise ValueError("a %s router has no selection bias to update"
                             % scoring)
        if router_trained_by not in ("loss", "balance") or (
                router_trained_by == "balance" and not balance_loss_weight):
            raise ValueError("router_trained_by %r: 'loss', or 'balance' "
                             "with a balance_loss_weight"
                             % (router_trained_by,))
        self._scoring, self._balance = scoring, balance_loss_weight
        self._by_balance = router_trained_by == "balance"
        self._first, self._held = int(first), int(held)
        self._k, self._scale = experts_per_token, routed_scaling_factor
        self._route_epsilon = route_epsilon
        self._experts, self._bias_update_rate = num_experts, bias_update_rate
        init = _init.Normal(weight_std)
        with self.name_scope():
            self.router_weight = self.params.get(
                "router_weight", shape=(num_experts, units), init=init,
                wd_mult=0.0 if self._by_balance else 1.0)
            if scoring == "sigmoid":
                self.router_bias = self.params.get(
                    "router_bias", shape=(num_experts,), grad_req="null",
                    init=_init.Normal(ROUTER_BIAS_STD))
            if balance_loss_weight:
                self.balance_term = self.params.get(
                    "balance_term", shape=(1,), grad_req="null",
                    init="zeros")
            self.experts_gate_weight = self.params.get(
                "experts_gate_weight", shape=(held, units, hidden_size),
                init=init)
            self.experts_up_weight = self.params.get(
                "experts_up_weight", shape=(held, units, hidden_size),
                init=init)
            self.experts_down_weight = self.params.get(
                "experts_down_weight", shape=(held, hidden_size, units),
                init=init)
            self.held_pairs = self.params.get(
                "held_pairs", shape=(1,), grad_req="null", init="zeros")
            self.max_load = self.params.get(
                "max_load", shape=(1,), grad_req="null", init="zeros")
            self.shared = GatedFFN(
                units, hidden_size if shared is True else int(shared),
                weight_std=weight_std, prefix="shared_") if shared else None

    def hybrid_forward(self, F, x, router_weight, experts_gate_weight,
                       experts_up_weight, experts_down_weight, held_pairs,
                       max_load, router_bias=None, balance_term=None):
        from ... import autograd

        rows = F.reshape(x, shape=(-1, x.shape[-1]))
        to_the_loss = bool(self._balance) and autograd.is_training()
        how = dict(k=self._k, scale=self._scale, eps=self._route_epsilon)
        if self._scoring != "sigmoid":
            how["scoring"] = self._scoring
        if to_the_loss:
            ids, weights, term = F.contrib.moe_route(
                rows, router_weight, router_bias, balance=True, **how)
        else:
            ids, weights = F.contrib.moe_route(rows, router_weight,
                                               router_bias, **how)
        if self._by_balance:
            weights = F.stop_gradient(weights)
        y, pairs, load = F.contrib.moe_experts(
            rows, ids, weights, experts_gate_weight, experts_up_weight,
            experts_down_weight, first_expert=self._first)
        if autograd.is_training():
            update_aux_state(self.held_pairs, F.reshape(pairs, shape=(1,)))
            update_aux_state(self.max_load, F.reshape(load, shape=(1,)))
            if self._bias_update_rate:
                with _xray.scope("moe.route"):
                    chosen = F.sum(F.one_hot(F.reshape(ids, shape=(-1,)),
                                             depth=self._experts), axis=0)
                    update_aux_state(
                        self.router_bias,
                        router_bias - self._bias_update_rate * F.sign(
                            chosen - F.mean(chosen, keepdims=True)))
        if self.shared is not None:
            with _xray.scope("moe.shared"):
                y = y + self.shared(rows)
        y = F.reshape(y, shape=x.shape)
        if not to_the_loss:
            return y
        update_aux_state(self.balance_term, F.reshape(term, shape=(1,)))
        return y, self._balance * term


# the hyper-connections' scalars alpha_pre, alpha_post, alpha_res as drawn
# (mHC's small start: the coefficients begin near their biases)
HC_ALPHA = 0.01
# what the biases of the hyper-connections are drawn about, and how widely:
# h_pre's about logit(1 / n) (u starts near the streams' mean), h_post's
# about 0 (2 sigmoid: each stream takes the sublayer's result once), the
# mixing matrix's logits about HC_DIAGONAL on the diagonal and 0 off it, so
# that Sinkhorn's H_res starts near the identity but not at it
HC_DIAGONAL = 2.0
HC_BIAS_STD = 0.3


class HyperConnectionBias(_init.Initializer):
    """The bias ``[pre (n) | post (n) | res (n x n)]`` of a
    :class:`HyperConnection`: ``logit(1 / n)``, 0 and ``diagonal`` times
    the identity, each plus N(0, ``sigma``) (``HC_DIAGONAL``,
    ``HC_BIAS_STD``)."""

    def __init__(self, streams, diagonal=HC_DIAGONAL, sigma=HC_BIAS_STD):
        super().__init__(streams=streams, diagonal=diagonal, sigma=sigma)
        self._n, self._diagonal, self._sigma = streams, diagonal, sigma

    def _init_weight(self, _, arr):
        import numpy as np

        from ...ndarray import array
        from ...ndarray import random as ndr

        n = self._n
        centre = np.concatenate([
            np.full(n, -math.log(n - 1.0)), np.zeros(n),
            self._diagonal * np.eye(n).reshape(-1)]).astype(np.float32)
        arr[:] = array(centre) + ndr.normal(0.0, self._sigma,
                                            shape=arr.shape)


class HyperConnection(HybridBlock):
    """The coefficients of one sublayer's manifold-constrained
    hyper-connection over ``streams`` streams of ``units``
    (``ops/llm.py::hc_coefficients``): ``phi (streams x units, 2 streams +
    streams^2)`` drawn N(0, ``weight_std``), ``bias`` by
    :class:`HyperConnectionBias`, ``alpha`` (pre, post, res) ``HC_ALPHA``.
    Called on the widened stream ``(B, S, streams x units)`` it returns
    ``(h_pre, h_post, H_res)``, the token axes last."""

    def __init__(self, units, streams, sinkhorn_iters=20, epsilon=1e-6,
                 clamp=(-30.0, 30.0), weight_std=0.02, **kwargs):
        super().__init__(**kwargs)
        self._how = dict(streams=streams, iters=sinkhorn_iters, eps=epsilon,
                         clamp_min=clamp[0], clamp_max=clamp[1])
        width = 2 * streams + streams * streams
        with self.name_scope():
            self.phi = self.params.get(
                "phi", shape=(streams * units, width),
                init=_init.Normal(weight_std))
            self.bias = self.params.get(
                "bias", shape=(width,), init=HyperConnectionBias(streams))
            self.alpha = self.params.get(
                "alpha", shape=(3,), init=_init.Constant(HC_ALPHA))

    def hybrid_forward(self, F, x, phi, bias, alpha):
        return F.contrib.hc_coefficients(x, phi, bias, alpha, **self._how)


class DecoderBlock(HybridBlock):
    """One decoder block: ``h += mixer(norm(h)); h += ffn(norm(h))``.
    ``mixer`` and ``ffn`` are called without arguments inside the block's
    name scope and return the token mixer (:class:`MLAttention`, or the
    grouped-query attention and the short convolution of
    ``gluon/nn/hybrid_lm.py``) and the feed-forward (:class:`GatedFFN`,
    :class:`RoutedExperts`).  While a step is staged only the
    block's input and its attention kernel's results are kept for the
    backward pass, which runs the rest of the forward again
    (``gluon.block.recomputed``): a block's activations are thirty times
    its input, and a model of this kind fills a chip with its state.

    ``hc_mult`` n > 1: the block carries a residual stream widened to n
    streams, ``(B, S, n x units)``, and wraps each sublayer ``f`` in a
    hyper-connection (:class:`HyperConnection`, ``hc_attn`` and ``hc_ffn``;
    ``hc``: its keyword arguments): ``u = sum_i h_pre[i] X_i``, ``y =
    f(norm(u))``, ``X'_i = sum_j H_res[i, j] X_j + h_post[i] y``
    (``ops/llm.py``'s ``hc_pre_mix`` / ``hc_post_mix``), recomputed like
    the rest."""

    def __init__(self, units, mixer, ffn, epsilon=1e-6, hc_mult=1, hc=None,
                 **kwargs):
        super().__init__(**kwargs)
        self._streams = int(hc_mult)
        with self.name_scope():
            if self._streams > 1:
                self.hc_attn = HyperConnection(units, self._streams,
                                               prefix="hc_attn_",
                                               **(hc or {}))
            self.ln1 = RMSNorm(units, epsilon, prefix="ln1_")
            self.mixer = mixer()
            if self._streams > 1:
                self.hc_ffn = HyperConnection(units, self._streams,
                                              prefix="hc_ffn_", **(hc or {}))
            self.ln2 = RMSNorm(units, epsilon, prefix="ln2_")
            self.ffn = ffn()

    def _body(self, h):
        h = h + self.mixer(self.ln1(h))
        y = self.ffn(self.ln2(h))
        if isinstance(y, tuple):        # with a side term for the loss
            return h + y[0], y[1]
        return h + y

    def _widened_body(self, F, x):
        h_pre, h_post, h_res = self.hc_attn(x)
        y = self.mixer(self.ln1(F.contrib.hc_pre_mix(x, h_pre)))
        x = F.contrib.hc_post_mix(x, h_res, h_post, y)
        h_pre, h_post, h_res = self.hc_ffn(x)
        y = self.ffn(self.ln2(F.contrib.hc_pre_mix(x, h_pre)))
        side = None
        if isinstance(y, tuple):        # with a side term for the loss
            y, side = y
        x = F.contrib.hc_post_mix(x, h_res, h_post, y)
        return x if side is None else (x, side)

    def hybrid_forward(self, F, h):
        if self._streams > 1:
            return recomputed(lambda x: self._widened_body(F, x), h)
        return recomputed(self._body, h)


def feed_forward(units, dense_size=None, moe=None, weight_std=0.02):
    """What :class:`DecoderBlock` takes as ``ffn``: the dense feed-forward
    of ``dense_size``, or the routed one (``moe``: keyword arguments of
    :class:`RoutedExperts`)."""
    if moe is None:
        return lambda: GatedFFN(units, dense_size, weight_std=weight_std,
                                prefix="ffn_")
    return lambda: RoutedExperts(units, weight_std=weight_std,
                                 prefix="moe_", **moe)


# the parameters that write to the residual stream, by the end of their names
TO_THE_STREAM = ("o_weight", "out_weight", "down_weight")


class DecoderLM(HybridBlock):
    """Decoder-only language model: embedding, :class:`DecoderBlock`s, final
    norm, head.

    Input ``(batch, seq)`` integer token ids.  Result: the normed hidden
    states ``(batch, seq, units)``; ``head(hidden)[i]`` are the logits for
    token ``i + 1`` (:class:`NextTokenLoss` applies the head fused with the
    loss).  Where blocks give a side term for the loss (training only: the
    module docstring), the result is ``(hidden, the sum of the terms)``.  ``blocks``: one ``(mixer, ffn)`` pair of :class:`DecoderBlock`
    arguments per layer.  ``tie_embedding``: the head reads the embedding's
    weight, one parameter with two uses.

    Weights are drawn N(0, ``weight_std``), the projections that write to
    the residual stream (``TO_THE_STREAM``) N(0, ``weight_std / sqrt(2 x
    stream_blocks)``) as in GPT-2 and Megatron-LM: unscaled, attention's
    running mean of the values dominates the stream and the tokens of a
    row route alike.

    ``hc_mult`` n > 1: the residual stream is widened to n streams
    (:class:`DecoderBlock`'s ``hc_mult``, ``hc`` its hyper-connections'
    keyword arguments): the embedding is copied into every stream
    (``expand``) and the streams are summed before the final norm
    (``collapse``), Hyper-Connections' ends."""

    def __init__(self, vocab_size, hidden_size, blocks, epsilon=1e-6,
                 weight_std=0.02, tie_embedding=False, hc_mult=1, hc=None,
                 **kwargs):
        super().__init__(**kwargs)
        self._weight_std = weight_std
        self._streams = int(hc_mult)
        init = _init.Normal(weight_std)
        with self.name_scope():
            self.embed = Embedding(vocab_size, hidden_size, prefix="embed_",
                                   weight_initializer=init)
            self.blocks = []
            for i, (mixer, ffn) in enumerate(blocks):
                blk = DecoderBlock(hidden_size, mixer, ffn, epsilon=epsilon,
                                   hc_mult=hc_mult, hc=dict(
                                       hc or {}, weight_std=weight_std),
                                   prefix="l%d_" % i)
                self.register_child(blk, "l%d" % i)
                self.blocks.append(blk)
            self.norm = RMSNorm(hidden_size, epsilon, prefix="norm_")
            self.head = Dense(vocab_size, use_bias=False, flatten=False,
                              in_units=hidden_size, prefix="head_",
                              weight_initializer=init,
                              params=self.embed.params if tie_embedding
                              else None)
        self.scale_stream_writers(len(self.blocks))

    def scale_stream_writers(self, stream_blocks):
        to_the_stream = _init.Normal(
            self._weight_std / (2 * stream_blocks) ** 0.5)
        for name, param in self.collect_params().items():
            if name.endswith(TO_THE_STREAM):
                param.init = to_the_stream

    def expand(self, F, h):
        """The embedding's ``(B, S, C)`` copied into every stream: ``(B, S,
        n x C)``; ``h`` itself with one stream."""
        if self._streams == 1:
            return h
        return F.concat(*([h] * self._streams), dim=-1)

    def collapse(self, F, x):
        """The streams' sum, ``(B, S, C)``; ``x`` itself with one stream."""
        if self._streams == 1:
            return x
        c = x.shape[-1] // self._streams
        return F.add_n(*[F.slice_axis(x, axis=-1, begin=i * c,
                                      end=(i + 1) * c)
                         for i in range(self._streams)])

    def hybrid_forward(self, F, tokens):
        h, side = self.expand(F, self.embed(tokens)), None
        for blk in self.blocks:
            h = blk(h)
            if isinstance(h, tuple):
                h, term = h
                side = term if side is None else side + term
        hidden = self.norm(self.collapse(F, h))
        return hidden if side is None else (hidden, side)


class MLAMoELM(DecoderLM):
    """:class:`DecoderLM` of latent-attention blocks with one multi-token
    prediction module (DeepSeek-V3 section 2.2).

    Result: the normed hidden
    states ``(main, mtp)``, each ``(batch, seq, units)``: ``head(main)[i]``
    are the logits for token ``i + 1``, ``head(mtp)[i]`` for token ``i +
    2``.  The MTP module: ``u_i = W_eh [norm(embed(t_{i+1})); norm(h_i)]``
    (embedding first, as the released weights have it), one routed block,
    its own final norm; embedding and head are the main model's.  It runs
    on all positions; its last input wraps to the row's first token, which
    causal attention keeps from every other position and the loss leaves
    out.

    The keyword arguments carry the names of the model's ``config.json``.
    ``held_experts`` ``(first, count)`` gives this chip's share of every
    routed layer.  ``rope_scaling``: the config's (DeepSeek's yarn, with
    ``mscale`` and ``mscale_all_dim``: :func:`deepseek_yarn`).
    ``bias_update_rate``: :class:`RoutedExperts`' (the selection bias
    trained by its balancing rule).  ``num_nextn_predict_layers`` 0: no
    MTP module, and the model returns the one normed stream
    (:class:`NextTokenLoss`'s input), as :class:`DecoderLM` does.
    ``hc_mult`` n > 1, ``hc_sinkhorn_iters``, ``hc_eps``,
    ``mhc_h_res_clamp_min`` / ``_max``: a residual stream of n streams
    with a hyper-connection around every sublayer (:class:`DecoderLM`'s
    ``hc_mult``); it takes no MTP module, since where that module would
    read the widened stream is not given."""

    def __init__(self, vocab_size, hidden_size, num_hidden_layers,
                 first_k_dense_replace, intermediate_size,
                 moe_intermediate_size, router_outputs, num_experts_per_tok,
                 num_attention_heads, q_lora_rank, kv_lora_rank,
                 qk_nope_head_dim, qk_rope_head_dim, v_head_dim,
                 held_experts=None, routed_scaling_factor=1.0,
                 rope_theta=10000.0, rms_norm_eps=1e-6, weight_std=0.02,
                 rope_scaling=None, bias_update_rate=0.0,
                 num_nextn_predict_layers=1, hc_mult=1, hc_sinkhorn_iters=20,
                 hc_eps=1e-6, mhc_h_res_clamp_min=-30.0,
                 mhc_h_res_clamp_max=30.0, **kwargs):
        if num_nextn_predict_layers not in (0, 1):
            raise ValueError("MLAMoELM: %r multi-token prediction modules; "
                             "0 or 1" % (num_nextn_predict_layers,))
        if hc_mult > 1 and num_nextn_predict_layers:
            raise ValueError(
                "MLAMoELM: hc_mult %d with a multi-token prediction module: "
                "where that module reads the widened stream is not given"
                % hc_mult)
        self._mtp = bool(num_nextn_predict_layers)

        def attention():
            return MLAttention(
                hidden_size, num_heads=num_attention_heads,
                q_lora_rank=q_lora_rank, kv_lora_rank=kv_lora_rank,
                qk_nope_head_dim=qk_nope_head_dim,
                qk_rope_head_dim=qk_rope_head_dim, v_head_dim=v_head_dim,
                rope_theta=rope_theta, epsilon=rms_norm_eps,
                weight_std=weight_std, rope_scaling=rope_scaling,
                prefix="attn_")

        routed = feed_forward(hidden_size, weight_std=weight_std, moe=dict(
            hidden_size=moe_intermediate_size, num_experts=router_outputs,
            experts_per_token=num_experts_per_tok,
            held_experts=held_experts and tuple(held_experts),
            routed_scaling_factor=routed_scaling_factor,
            bias_update_rate=bias_update_rate))
        dense = feed_forward(hidden_size, intermediate_size,
                             weight_std=weight_std)
        super().__init__(
            vocab_size, hidden_size,
            [(attention, dense if i < first_k_dense_replace else routed)
             for i in range(num_hidden_layers)],
            epsilon=rms_norm_eps, weight_std=weight_std, hc_mult=hc_mult,
            hc=dict(sinkhorn_iters=hc_sinkhorn_iters, epsilon=hc_eps,
                    clamp=(mhc_h_res_clamp_min, mhc_h_res_clamp_max)),
            **kwargs)
        if not self._mtp:
            return
        with self.name_scope():
            self.mtp_enorm = RMSNorm(hidden_size, rms_norm_eps,
                                     prefix="mtp_enorm_")
            self.mtp_hnorm = RMSNorm(hidden_size, rms_norm_eps,
                                     prefix="mtp_hnorm_")
            self.mtp_proj = Dense(hidden_size, use_bias=False, flatten=False,
                                  in_units=2 * hidden_size,
                                  prefix="mtp_proj_",
                                  weight_initializer=_init.Normal(weight_std))
            self.mtp_blk = DecoderBlock(hidden_size, attention, routed,
                                        epsilon=rms_norm_eps,
                                        prefix="mtp_blk_")
            self.mtp_norm = RMSNorm(hidden_size, rms_norm_eps,
                                    prefix="mtp_norm_")
        self.scale_stream_writers(num_hidden_layers + 1)    # the MTP's too

    def hybrid_forward(self, F, tokens):
        if not self._mtp:
            return super().hybrid_forward(F, tokens)
        h = self.embed(tokens)
        for blk in self.blocks:
            h = blk(h)
        with _xray.scope("mtp"):
            shifted = F.concat(
                F.slice_axis(tokens, axis=1, begin=1, end=None),
                F.slice_axis(tokens, axis=1, begin=0, end=1), dim=1)
            u = self.mtp_proj(F.concat(self.mtp_enorm(self.embed(shifted)),
                                       self.mtp_hnorm(h), dim=-1))
            u = self.mtp_norm(self.mtp_blk(u))
        return self.norm(h), u


def _head_loss(F, head, hidden, tokens, ahead, scale=1.0):
    """``scale * CE(head(hidden_i), t_{i + ahead})``, a mean over the valid
    positions of a row, the head's product fused with the loss over chunks
    of tokens (``ops/llm.py::linear_cross_entropy``); one value per row."""
    batch, seq = tokens.shape
    # token i + ahead labels position i; the row's last ``ahead``
    # positions have no label
    labels = F.concat(
        F.slice_axis(tokens, axis=1, begin=ahead, end=None),
        F.full((batch, ahead), -1, dtype=tokens.dtype), dim=1)
    rows = F.contrib.linear_cross_entropy(
        F.reshape(hidden, shape=(-1, hidden.shape[-1])), head.weight.data(),
        F.reshape(labels, shape=(-1,)))
    return F.sum(F.reshape(rows, shape=(batch, seq)), axis=1) \
        * (scale / (seq - ahead))


class NextTokenLoss(Block):
    """``CE(head(hidden_i), t_{i+1})``, a mean over a row's valid
    positions; one value per row.

    ``head``: the model's output projection (``DecoderLM.head``; with a
    tied head its weight is the embedding's), applied here, fused with the
    loss over chunks of tokens so that the float32 logits never stand
    whole.  The labels are the input's own rows of token ids.  A model that
    returns ``(hidden, side)`` has its blocks' side term added to every
    row's value (so to the mean over rows, once)."""

    def __init__(self, head, **kwargs):
        super().__init__(**kwargs)
        self.__dict__["_head"] = head       # the model's block, not a child

    def forward(self, hidden, tokens):
        from ... import ndarray as F

        side = None
        if isinstance(hidden, tuple):
            hidden, side = hidden
        with _xray.scope("lm_head"):
            rows = _head_loss(F, self._head, hidden, tokens, 1)
        return rows if side is None else rows + side


class MultiTokenLoss(Block):
    """``CE(head(main_i), t_{i+1}) + weight * CE(head(mtp_i), t_{i+2})``,
    each a mean over its valid positions of a row; one value per row.

    ``head``: the model's output projection (``MLAMoELM.head``), applied
    here, fused with the loss over chunks of tokens, so that the float32
    logits of neither head stand whole.  The labels are the input's own
    rows of token ids."""

    def __init__(self, head, weight=0.3, **kwargs):
        super().__init__(**kwargs)
        self.__dict__["_head"] = head       # the model's block, not a child
        self._weight = weight

    def forward(self, streams, tokens):
        from ... import ndarray as F

        main, mtp = streams
        with _xray.scope("lm_head"):
            return _head_loss(F, self._head, main, tokens, 1) \
                + _head_loss(F, self._head, mtp, tokens, 2, self._weight)
