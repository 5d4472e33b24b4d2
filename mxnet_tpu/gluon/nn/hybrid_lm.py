"""Decoder language model whose layers mix tokens as a list of layer types
says: by a gated short convolution, by grouped-query attention over the
whole row, or by the same attention inside a sliding window, with dense and
routed feed-forwards (the blocks of LFM2-style hybrids, of Qwen3-MoE-style
models with windowed layers, and of Laguna-style models with gated
attention and a shared expert): the token mixers, and the model built of
``gluon/nn/mla_moe.py``'s :class:`DecoderBlock`s.  Docs: docs/LLM_OPS.md.

- :class:`GQAttention`: causal grouped-query attention with an RMS norm
  on every head of ``q`` and ``k`` before the rotation (by halves):
  ``gqa_qkv`` (``ops/llm.py``), the flash kernels reading each key head
  from where it lies, ``gqa_out``.  ``window``: a query sees that many
  keys, itself the last (the kernels skip what lies behind).  ``rotary``:
  the layer's rotary scaling (a ``config.json``'s rope parameters, yarn's
  among them, and ``partial_rotary_factor``: only that share of a head's
  first lanes is rotated), handed to the operator as frequencies and an
  amplitude.  ``gate="per_head"``: every head's result times a sigmoid of
  the layer's input, one scalar a head and position (``head_gate``).
- :class:`ShortConv`: ``[b; c; x] = W_in h``; a depthwise causal
  convolution of ``b * x`` over ``kernel_size`` positions; ``W_out (c *
  conv)``.
- :class:`LayerTypesMoELM`: a :class:`DecoderLM` whose layer ``i`` mixes
  by ``layer_types[i]`` (``"conv"``, ``"full_attention"``,
  ``"sliding_attention"``) with its own count of query heads and gate,
  its feed-forward dense or routed, the routed ones with a shared expert
  where the model has one.  ``ConvAttentionMoELM`` is its name from before
  it knew windows.

Named scopes (``xray.scope``): ``gqa.proj``, ``gqa.gate`` (the per-head
gate's product, sigmoid and multiply), ``gqa.attention`` (a layer over the
whole row), ``swa.attention`` (a layer with a window), ``shortconv.proj``,
``shortconv.conv``, and the ``moe.*`` and ``lm_head`` of ``mla_moe.py``.
"""

from __future__ import annotations

from ... import initializer as _init
from ... import xray as _xray
from ...ops.llm import rotary_frequencies
from ..block import HybridBlock
from .mla_moe import DecoderLM, feed_forward

__all__ = ["GQAttention", "ShortConv", "LayerTypesMoELM",
           "ConvAttentionMoELM"]


class GQAttention(HybridBlock):
    """Grouped-query attention, causal: ``num_heads`` query heads and
    ``num_kv_heads`` key / value heads of ``head_dim``; query head ``h``
    reads key head ``h // (num_heads // num_kv_heads)``.  The parameters
    have the shapes of the published checkpoints' ``q_proj`` .. ``out_proj``
    and ``q_layernorm`` / ``k_layernorm``.  ``window``: query ``i`` sees the
    keys ``i - window < j <= i`` (sliding-window attention).  ``rotary``: a
    dict of :func:`~mxnet_tpu.ops.llm.rotary_frequencies`' arguments under
    their ``config.json`` names (``rope_type``, ``rope_theta``, ``factor``,
    ...) in place of ``rope_theta``; its ``partial_rotary_factor`` rotates
    that share of a head's lanes, the first ones, by frequencies over that
    many lanes (``gqa_qkv``'s ``rotary_dim``).  ``gate="per_head"``: a
    ``gate_weight (num_heads, units)`` and every head's result times
    ``sigmoid(x W_g^T)`` before the output projection (``head_gate``)."""

    def __init__(self, units, num_heads, num_kv_heads, head_dim=None,
                 rope_theta=10000.0, epsilon=1e-6, weight_std=0.02,
                 window=None, rotary=None, gate=None, **kwargs):
        super().__init__(**kwargs)
        head_dim = head_dim or units // num_heads
        if gate not in (None, "per_head"):
            raise ValueError("GQAttention: gate %r is not 'per_head'"
                             % (gate,))
        self._epsilon, self._window, self._gate = epsilon, window, gate
        self._rotary = {"theta": rope_theta}
        if rotary is not None:
            lanes = int(head_dim * rotary.get("partial_rotary_factor", 1))
            inv_freq, amplitude = rotary_frequencies(lanes, **rotary)
            self._rotary = {"inv_freq": inv_freq, "amplitude": amplitude}
            if lanes < head_dim:
                self._rotary["rotary_dim"] = lanes
        self._sm_scale = head_dim ** -0.5
        init = _init.Normal(weight_std)
        shapes = {
            "q_weight": (num_heads * head_dim, units),
            "k_weight": (num_kv_heads * head_dim, units),
            "v_weight": (num_kv_heads * head_dim, units),
            "o_weight": (units, num_heads * head_dim),
        }
        if gate:
            shapes["gate_weight"] = (num_heads, units)
        with self.name_scope():
            for name, shape in shapes.items():
                setattr(self, name, self.params.get(name, shape=shape,
                                                    init=init))
            self.qnorm_weight = self.params.get(
                "qnorm_weight", shape=(head_dim,), init="ones")
            self.knorm_weight = self.params.get(
                "knorm_weight", shape=(head_dim,), init="ones")

    def hybrid_forward(self, F, x, q_weight, k_weight, v_weight, o_weight,
                       qnorm_weight, knorm_weight, gate_weight=None):
        q, k, v = F.contrib.gqa_qkv(
            x, q_weight, k_weight, v_weight, qnorm_weight, knorm_weight,
            eps=self._epsilon, **self._rotary)
        with _xray.scope("swa.attention" if self._window
                         else "gqa.attention"):
            o = F.contrib.flash_attention(q, k, v, causal=True,
                                          sm_scale=self._sm_scale,
                                          window=self._window)
        if self._gate:
            o = F.contrib.head_gate(o, x, gate_weight)
        return F.contrib.gqa_out(o, o_weight)


class ShortConv(HybridBlock):
    """Gated short convolution: ``W_out (c * conv(b * x))`` with ``[b; c;
    x] = W_in h`` and a depthwise causal kernel of ``kernel_size`` taps
    (``ops/llm.py::gated_short_conv``); no biases.  A row is one document:
    nothing crosses rows."""

    def __init__(self, units, kernel_size=3, weight_std=0.02, **kwargs):
        super().__init__(**kwargs)
        self._units = units
        init = _init.Normal(weight_std)
        with self.name_scope():
            self.in_weight = self.params.get(
                "in_weight", shape=(3 * units, units), init=init)
            self.conv_weight = self.params.get(
                "conv_weight", shape=(units, kernel_size), init=init)
            self.out_weight = self.params.get(
                "out_weight", shape=(units, units), init=init)

    def hybrid_forward(self, F, x, in_weight, conv_weight, out_weight):
        with _xray.scope("shortconv.proj"):
            bcx = F.FullyConnected(x, in_weight, None, no_bias=True,
                                   num_hidden=3 * self._units, flatten=False)
        y = F.contrib.gated_short_conv(bcx, conv_weight)
        with _xray.scope("shortconv.proj"):
            return F.FullyConnected(y, out_weight, None, no_bias=True,
                                    num_hidden=self._units, flatten=False)


class LayerTypesMoELM(DecoderLM):
    """:class:`DecoderLM` whose layer ``i`` mixes tokens by
    ``layer_types[i]``: ``"conv"`` a :class:`ShortConv`,
    ``"full_attention"`` a :class:`GQAttention`, ``"sliding_attention"``
    one with ``sliding_window``.  Its feed-forward is dense
    (``intermediate_size``) where ``mlp_layer_types[i]`` is ``"dense"``
    (without that list: for ``i < num_dense_layers``), else
    :class:`RoutedExperts`, with one shared expert of
    ``shared_expert_intermediate_size`` where that is given.  The head is
    tied to the embedding unless ``tie_embedding`` is false.

    The keyword arguments carry the names of the model's ``config.json``;
    ``router_outputs`` is its ``num_experts`` (the router's width), and
    ``held_experts`` ``(first, count)`` gives this chip's share of every
    routed layer.  ``num_attention_heads_per_layer``: layer ``i``'s query
    heads (``num_attention_heads`` where it is not given);
    ``gating_types``: layer ``i``'s attention gate (``"per_head"``, or
    None).  ``rope_parameters``: per layer type, the rotary scaling
    of its attention layers (:class:`GQAttention`'s ``rotary``, with its
    ``partial_rotary_factor``); ``rope_theta`` serves the types it does not
    name.  ``scoring_func`` and
    ``router_aux_loss_coef`` are :class:`RoutedExperts`' ``scoring`` and
    ``balance_loss_weight`` (a softmax router trains under the auxiliary
    balancing loss), ``router_trained_by`` its option of that name;
    ``bias_update_rate`` is :class:`RoutedExperts`' (the
    model's ``use_expert_bias``: a training loop keeps a sigmoid router's
    loads level through the selection bias)."""

    def __init__(self, vocab_size, hidden_size, layer_types,
                 moe_intermediate_size, router_outputs, num_experts_per_tok,
                 num_attention_heads, num_key_value_heads,
                 num_dense_layers=0, intermediate_size=None, head_dim=None,
                 sliding_window=None, rope_parameters=None, conv_L_cache=3,
                 held_experts=None, routed_scaling_factor=1.0,
                 route_epsilon=1e-6, bias_update_rate=0.0,
                 scoring_func="sigmoid", router_aux_loss_coef=0.0,
                 router_trained_by="loss",
                 rope_theta=10000.0, norm_eps=1e-5, weight_std=0.02,
                 tie_embedding=True, mlp_layer_types=None,
                 num_attention_heads_per_layer=None, gating_types=None,
                 shared_expert_intermediate_size=None, **kwargs):
        layers = len(layer_types)
        heads = num_attention_heads_per_layer or [num_attention_heads] * layers
        gates = gating_types or [None] * layers
        dense_at = [kind == "dense" for kind in mlp_layer_types] \
            if mlp_layer_types else [i < num_dense_layers
                                     for i in range(layers)]
        if not len(heads) == len(gates) == len(dense_at) == layers:
            raise ValueError("LayerTypesMoELM: the per-layer lists do not "
                             "have the %d layers of layer_types" % layers)

        def mixer(i, kind):
            if kind == "conv":
                return lambda: ShortConv(hidden_size, conv_L_cache,
                                         weight_std=weight_std,
                                         prefix="conv_")
            how = {"window": sliding_window} \
                if kind == "sliding_attention" else {}
            if gates[i]:
                how["gate"] = gates[i]
            return lambda: GQAttention(
                hidden_size, heads[i], num_key_value_heads,
                head_dim=head_dim, rope_theta=rope_theta, epsilon=norm_eps,
                weight_std=weight_std, prefix="attn_",
                rotary=(rope_parameters or {}).get(kind), **how)

        routed = feed_forward(hidden_size, weight_std=weight_std, moe=dict(
            hidden_size=moe_intermediate_size, num_experts=router_outputs,
            experts_per_token=num_experts_per_tok,
            held_experts=held_experts and tuple(held_experts),
            routed_scaling_factor=routed_scaling_factor,
            shared=shared_expert_intermediate_size or False,
            route_epsilon=route_epsilon,
            bias_update_rate=bias_update_rate, scoring=scoring_func,
            balance_loss_weight=router_aux_loss_coef,
            router_trained_by=router_trained_by))
        dense = feed_forward(hidden_size, intermediate_size,
                             weight_std=weight_std)
        super().__init__(
            vocab_size, hidden_size,
            [(mixer(i, kind), dense if dense_at[i] else routed)
             for i, kind in enumerate(layer_types)],
            epsilon=norm_eps, weight_std=weight_std,
            tie_embedding=tie_embedding, **kwargs)


# the name an accepted configuration file of the benchmark builds it by
ConvAttentionMoELM = LayerTypesMoELM
