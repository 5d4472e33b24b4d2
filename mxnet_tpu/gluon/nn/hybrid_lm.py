"""Decoder language model whose layers mix tokens by a gated short
convolution or by grouped-query attention, as a list of layer types says,
with dense and routed feed-forwards (the block of LFM2-style hybrid
models): the token mixers, and the model built of
``gluon/nn/mla_moe.py``'s :class:`DecoderBlock`s.  Docs: docs/LLM_OPS.md.

- :class:`GQAttention`: causal grouped-query attention with an RMS norm
  on every head of ``q`` and ``k`` before the rotation (by halves):
  ``gqa_qkv`` (``ops/llm.py``), the flash kernels reading each key head
  from where it lies, ``gqa_out``.
- :class:`ShortConv`: ``[b; c; x] = W_in h``; a depthwise causal
  convolution of ``b * x`` over ``kernel_size`` positions; ``W_out (c *
  conv)``.
- :class:`ConvAttentionMoELM`: a :class:`DecoderLM` whose layer ``i`` mixes
  by ``layer_types[i]``, the first ``num_dense_layers`` with a dense
  feed-forward and the rest with routed experts and no shared one; the
  head tied to the embedding.

Named scopes (``xray.scope``): ``gqa.proj``, ``gqa.attention``,
``shortconv.proj``, ``shortconv.conv``, and the ``moe.*`` and ``lm_head``
of ``mla_moe.py``.
"""

from __future__ import annotations

from ... import initializer as _init
from ... import xray as _xray
from ..block import HybridBlock
from .mla_moe import DecoderLM, feed_forward

__all__ = ["GQAttention", "ShortConv", "ConvAttentionMoELM"]


class GQAttention(HybridBlock):
    """Grouped-query attention, causal: ``num_heads`` query heads and
    ``num_kv_heads`` key / value heads of ``head_dim``; query head ``h``
    reads key head ``h // (num_heads // num_kv_heads)``.  The parameters
    have the shapes of the published checkpoints' ``q_proj`` .. ``out_proj``
    and ``q_layernorm`` / ``k_layernorm``."""

    def __init__(self, units, num_heads, num_kv_heads, head_dim=None,
                 rope_theta=10000.0, epsilon=1e-6, weight_std=0.02,
                 **kwargs):
        super().__init__(**kwargs)
        head_dim = head_dim or units // num_heads
        self._theta, self._epsilon = rope_theta, epsilon
        self._sm_scale = head_dim ** -0.5
        init = _init.Normal(weight_std)
        shapes = {
            "q_weight": (num_heads * head_dim, units),
            "k_weight": (num_kv_heads * head_dim, units),
            "v_weight": (num_kv_heads * head_dim, units),
            "o_weight": (units, num_heads * head_dim),
        }
        with self.name_scope():
            for name, shape in shapes.items():
                setattr(self, name, self.params.get(name, shape=shape,
                                                    init=init))
            self.qnorm_weight = self.params.get(
                "qnorm_weight", shape=(head_dim,), init="ones")
            self.knorm_weight = self.params.get(
                "knorm_weight", shape=(head_dim,), init="ones")

    def hybrid_forward(self, F, x, q_weight, k_weight, v_weight, o_weight,
                       qnorm_weight, knorm_weight):
        q, k, v = F.contrib.gqa_qkv(
            x, q_weight, k_weight, v_weight, qnorm_weight, knorm_weight,
            theta=self._theta, eps=self._epsilon)
        with _xray.scope("gqa.attention"):
            o = F.contrib.flash_attention(q, k, v, causal=True,
                                          sm_scale=self._sm_scale)
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


class ConvAttentionMoELM(DecoderLM):
    """:class:`DecoderLM` of short-convolution and grouped-query attention
    blocks.  Layer ``i`` mixes tokens by :class:`GQAttention` where
    ``layer_types[i] == "full_attention"``, by :class:`ShortConv` where it
    is ``"conv"``; its feed-forward is dense (``intermediate_size``) for ``i
    < num_dense_layers``, else :class:`RoutedExperts` without a shared
    expert.  The head is tied to the embedding unless ``tie_embedding`` is
    false.

    The keyword arguments carry the names of the model's ``config.json``;
    ``router_outputs`` is its ``num_experts`` (the router's width), and
    ``held_experts`` ``(first, count)`` gives this chip's share of every
    routed layer, ``bias_update_rate`` is :class:`RoutedExperts`' (the
    model's ``use_expert_bias``: a training loop keeps the experts' loads
    level through the selection bias)."""

    def __init__(self, vocab_size, hidden_size, layer_types, num_dense_layers,
                 intermediate_size, moe_intermediate_size, router_outputs,
                 num_experts_per_tok, num_attention_heads,
                 num_key_value_heads, conv_L_cache=3, held_experts=None,
                 routed_scaling_factor=1.0, route_epsilon=1e-6,
                 bias_update_rate=0.0, rope_theta=10000.0, norm_eps=1e-5,
                 weight_std=0.02, tie_embedding=True, **kwargs):
        mixers = {
            "full_attention": lambda: GQAttention(
                hidden_size, num_attention_heads, num_key_value_heads,
                rope_theta=rope_theta, epsilon=norm_eps,
                weight_std=weight_std, prefix="attn_"),
            "conv": lambda: ShortConv(hidden_size, conv_L_cache,
                                      weight_std=weight_std, prefix="conv_"),
        }
        routed = feed_forward(hidden_size, weight_std=weight_std, moe=dict(
            hidden_size=moe_intermediate_size, num_experts=router_outputs,
            experts_per_token=num_experts_per_tok,
            held_experts=held_experts and tuple(held_experts),
            routed_scaling_factor=routed_scaling_factor, shared=False,
            route_epsilon=route_epsilon,
            bias_update_rate=bias_update_rate))
        dense = feed_forward(hidden_size, intermediate_size,
                             weight_std=weight_std)
        super().__init__(
            vocab_size, hidden_size,
            [(mixers[kind], dense if i < num_dense_layers else routed)
             for i, kind in enumerate(layer_types)],
            epsilon=norm_eps, weight_std=weight_std,
            tie_embedding=tie_embedding, **kwargs)
