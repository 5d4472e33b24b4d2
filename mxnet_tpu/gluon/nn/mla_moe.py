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
  with its gradient.
- :class:`DecoderLM`: embedding, blocks, final norm and a head that may be
  tied to the embedding; it returns the normed hidden states (with the
  blocks' side term where they give one), ``net.head`` makes logits of
  them, and :class:`NextTokenLoss` fuses the head with the loss over token
  chunks.
- :class:`MLAMoELM`: a :class:`DecoderLM` of latent-attention blocks with
  one multi-token prediction module that shares embedding and head.  It
  returns the two streams' normed hidden states;
  :class:`MultiTokenLoss` fuses the head with the loss of both terms.

Named scopes (``xray.scope``): ``mla.proj``, ``mla.attention``,
``moe.route``, ``moe.dispatch``, ``moe.experts``, ``moe.combine``,
``moe.shared``, ``moe.aux``, ``mtp``, ``lm_head``, and ``ffn.gated``
around every :class:`GatedFFN` (``ops/llm.py::gated_silu``), dense or a
shared expert (then inside ``moe.shared``).  Counters: every
:class:`RoutedExperts` keeps ``held_pairs`` (pairs routed to its held
experts in the last step) and ``max_load`` (the largest held expert's
pairs over their mean), and one with a balancing loss ``balance_term``
(its router's last term, unweighted: 1 when the experts are chosen and
scored alike), as parameters that take no gradient, updated by the step
like batch-norm statistics, so they leave the step program with its
state.
"""

from __future__ import annotations

from ... import initializer as _init
from ... import xray as _xray
from ..block import Block, HybridBlock, recomputed, update_aux_state
from .basic_layers import Dense, Embedding

__all__ = ["RMSNorm", "GatedFFN", "MLAttention", "RoutedExperts",
           "DecoderBlock", "DecoderLM", "MLAMoELM", "NextTokenLoss",
           "MultiTokenLoss"]

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
                 **kwargs):
        super().__init__(**kwargs)
        self._heads = num_heads
        self._theta, self._epsilon = rope_theta, epsilon
        qk = qk_nope_head_dim + qk_rope_head_dim
        self._sm_scale = qk ** -0.5
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
            eps=self._epsilon)
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
    its input, and a model of this kind fills a chip with its state."""

    def __init__(self, units, mixer, ffn, epsilon=1e-6, **kwargs):
        super().__init__(**kwargs)
        with self.name_scope():
            self.ln1 = RMSNorm(units, epsilon, prefix="ln1_")
            self.mixer = mixer()
            self.ln2 = RMSNorm(units, epsilon, prefix="ln2_")
            self.ffn = ffn()

    def _body(self, h):
        h = h + self.mixer(self.ln1(h))
        y = self.ffn(self.ln2(h))
        if isinstance(y, tuple):        # with a side term for the loss
            return h + y[0], y[1]
        return h + y

    def hybrid_forward(self, F, h):
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
    row route alike."""

    def __init__(self, vocab_size, hidden_size, blocks, epsilon=1e-6,
                 weight_std=0.02, tie_embedding=False, **kwargs):
        super().__init__(**kwargs)
        self._weight_std = weight_std
        init = _init.Normal(weight_std)
        with self.name_scope():
            self.embed = Embedding(vocab_size, hidden_size, prefix="embed_",
                                   weight_initializer=init)
            self.blocks = []
            for i, (mixer, ffn) in enumerate(blocks):
                blk = DecoderBlock(hidden_size, mixer, ffn, epsilon=epsilon,
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

    def hybrid_forward(self, F, tokens):
        h, side = self.embed(tokens), None
        for blk in self.blocks:
            h = blk(h)
            if isinstance(h, tuple):
                h, term = h
                side = term if side is None else side + term
        hidden = self.norm(h)
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
    routed layer."""

    def __init__(self, vocab_size, hidden_size, num_hidden_layers,
                 first_k_dense_replace, intermediate_size,
                 moe_intermediate_size, router_outputs, num_experts_per_tok,
                 num_attention_heads, q_lora_rank, kv_lora_rank,
                 qk_nope_head_dim, qk_rope_head_dim, v_head_dim,
                 held_experts=None, routed_scaling_factor=1.0,
                 rope_theta=10000.0, rms_norm_eps=1e-6, weight_std=0.02,
                 **kwargs):
        def attention():
            return MLAttention(
                hidden_size, num_heads=num_attention_heads,
                q_lora_rank=q_lora_rank, kv_lora_rank=kv_lora_rank,
                qk_nope_head_dim=qk_nope_head_dim,
                qk_rope_head_dim=qk_rope_head_dim, v_head_dim=v_head_dim,
                rope_theta=rope_theta, epsilon=rms_norm_eps,
                weight_std=weight_std, prefix="attn_")

        routed = feed_forward(hidden_size, weight_std=weight_std, moe=dict(
            hidden_size=moe_intermediate_size, num_experts=router_outputs,
            experts_per_token=num_experts_per_tok,
            held_experts=held_experts and tuple(held_experts),
            routed_scaling_factor=routed_scaling_factor))
        dense = feed_forward(hidden_size, intermediate_size,
                             weight_std=weight_std)
        super().__init__(
            vocab_size, hidden_size,
            [(attention, dense if i < first_k_dense_replace else routed)
             for i in range(num_hidden_layers)],
            epsilon=rms_norm_eps, weight_std=weight_std, **kwargs)
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
