"""JoyAI-LLM-Flash (``model_type`` ``joyai_llm_flash``, 48B-A2.7B; the
DeepSeek-V3 block, arXiv:2412.19437), plain reference: forward, the loss
of both of its terms and the gradients, in float32 ``jax.numpy``.

``arch`` (sizes under the names of the model's ``config.json``):
``vocab_size``, ``hidden_size``, ``num_hidden_layers``,
``first_k_dense_replace``, ``intermediate_size``,
``moe_intermediate_size``, ``router_outputs`` (the published count of
routed experts: the router's width), ``held_experts`` ``[first, count]``
(the consecutive expert ids this chip holds; all of them for the uncut
layer), ``num_experts_per_tok``, ``routed_scaling_factor``,
``num_attention_heads``, ``q_lora_rank``, ``kv_lora_rank``,
``qk_nope_head_dim``, ``qk_rope_head_dim``, ``v_head_dim``, ``rope_theta``,
``rms_norm_eps``, ``mtp_loss_weight``.  One shared expert, one multi-token
prediction module, no biases, ``n_group`` = ``topk_group`` = 1.

Parameters are looked up by name (a dense weight is ``(out, in)``; the
held experts' weights are stacked ``(held, in, out)``):

    embed_weight, head_weight, norm_weight
    l<i>_ln1_weight, l<i>_ln2_weight
    l<i>_attn_{qa,qb,kva,kvb,o}_weight, l<i>_attn_{qnorm,kvnorm}_weight
    l<i>_ffn_{gate,up,down}_weight                       (dense block)
    l<i>_moe_router_weight, l<i>_moe_router_bias         (bias: not trained)
    l<i>_moe_experts_{gate,up,down}_weight, l<i>_moe_shared_{gate,up,down}_weight
    mtp_enorm_weight, mtp_hnorm_weight, mtp_proj_weight, mtp_norm_weight
    mtp_blk_...                                          (one routed block)

Equations.  Block: ``h += MLA(RMSNorm(h))``; ``h += FFN(RMSNorm(h))``.  MLA
in its training form (no weight absorption): ``c_q = RMSNorm(x W_qa)``,
``q = c_q W_qb`` -> heads x (nope + rope); ``[c_kv; k_r] = x W_kva``,
``c_kv <- RMSNorm(c_kv)``, ``[k_nope; v] = c_kv W_kvb``; rotary embedding
on each head's ``q_rope`` and on the one ``k_r`` all heads share; scores
over ``[nope; rope]`` scaled by ``(nope + rope)^-1/2``, causal softmax,
``o = P v`` -> ``W_o``.  MoE: ``s = sigmoid(x W_g)``; the
``num_experts_per_tok`` largest of ``s + b`` are selected; weights ``s_k /
(sum of the selected s + 1e-20) * routed_scaling_factor`` (the bias
selects, it does not weigh); ``y = sum_{k: e_k held} g_k E_{e_k}(x) +
E_shared(x)``: selection and normalisation run over all experts, what the
absent ones would add is left out.  MTP: ``u_i = W_eh [RMSNorm(Emb(t_{i+1}))
; RMSNorm(h_i)]``, one routed block, its own final norm, the shared head.
Loss: ``CE(head(RMSNorm(h_i)), t_{i+1}) + mtp_loss_weight *
CE(head_mtp_i, t_{i+2})``, each a mean over its valid positions.

Departures from the sources, noted: the rotary embedding rotates the
adjacent pairs ``(2i, 2i+1)`` in place (``rope_interleave``); the released
code moves the pairs to the two halves first, the same permutation on q
and k, so every score is the same.  ``W_eh`` takes the embedding first, as
the released weights' layout has it; the paper writes the hidden state
first.  The MTP module runs on all ``S`` positions, its last input the
embedding of the row's first token (a roll): causal attention keeps that
position from every other, and the loss leaves it out.

``check.py`` hands ``x`` over as float32, moved by one ulp: ``rint`` gives
the ids back (ids < 2^24 survive).

Two batches are compared (``outputs``).  ``x``: short rows through the whole
model, chosen free of routing margins (``routing_margins``).  ``y``: rows of
the timed step's own shape through the layers that come before the first
router (``dense_prefix``: embedding, the leading dense blocks, the final
norm, the head and the loss), where no route can flip and so any row
serves at any length.  The labels of both are the rows' own next tokens.
"""

import math

import jax
import jax.numpy as jnp
import numpy as np


def rms_norm(x, weight, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True)
                             + eps) * weight


def rope(x, theta):
    """Rotate the adjacent pairs ``(2i, 2i+1)`` of the last axis of
    ``x`` (..., S, d) by ``position * theta^(-2i/d)``."""
    seq, d = x.shape[-2], x.shape[-1]
    inv = theta ** (-np.arange(0, d, 2, dtype=np.float64) / d)
    angle = np.arange(seq, dtype=np.float64)[:, None] * inv[None, :]
    cos = jnp.asarray(np.cos(angle), jnp.float32)
    sin = jnp.asarray(np.sin(angle), jnp.float32)
    even, odd = x[..., 0::2], x[..., 1::2]
    out = jnp.stack([even * cos - odd * sin, even * sin + odd * cos],
                    axis=-1)
    return out.reshape(x.shape)


def gated_silu(x, gate, up, down):
    """``W_down(silu(W_gate x) * W_up x)``, weights ``(out, in)``."""
    return (jax.nn.silu(x @ gate.T) * (x @ up.T)) @ down.T


def mla(p, pre, x, arch):
    heads = arch["num_attention_heads"]
    nope, rot = arch["qk_nope_head_dim"], arch["qk_rope_head_dim"]
    vd, rank = arch["v_head_dim"], arch["kv_lora_rank"]
    eps = arch["rms_norm_eps"]
    b, s, _ = x.shape
    c_q = rms_norm(x @ p[pre + "qa_weight"].T, p[pre + "qnorm_weight"], eps)
    q = (c_q @ p[pre + "qb_weight"].T).reshape(b, s, heads, nope + rot)
    q = q.transpose(0, 2, 1, 3)                         # (b, heads, s, .)
    kva = x @ p[pre + "kva_weight"].T
    c_kv = rms_norm(kva[..., :rank], p[pre + "kvnorm_weight"], eps)
    k_r = rope(kva[..., rank:], arch["rope_theta"])     # (b, s, rot)
    kv = (c_kv @ p[pre + "kvb_weight"].T).reshape(b, s, heads, nope + vd)
    kv = kv.transpose(0, 2, 1, 3)
    k_nope, v = kv[..., :nope], kv[..., nope:]
    q = jnp.concatenate([q[..., :nope],
                         rope(q[..., nope:], arch["rope_theta"])], axis=-1)
    k = jnp.concatenate([k_nope, jnp.broadcast_to(
        k_r[:, None], (b, heads, s, rot))], axis=-1)
    scores = jnp.einsum("bhqd,bhkd->bhqk", q, k) / math.sqrt(nope + rot)
    causal = jnp.arange(s)[:, None] >= jnp.arange(s)[None, :]
    scores = jnp.where(causal, scores, -jnp.inf)
    o = jnp.einsum("bhqk,bhkd->bhqd", jax.nn.softmax(scores, axis=-1), v)
    o = o.transpose(0, 2, 1, 3).reshape(b, s, heads * vd)
    return o @ p[pre + "o_weight"].T


def route(p, pre, x, arch):
    """-> (expert ids (..., k), weights (..., k), margin (...)): the
    selection over all of the router's outputs; ``margin`` is the distance
    between the last selected and the first rejected selection score."""
    k = arch["num_experts_per_tok"]
    s = jax.nn.sigmoid(x @ p[pre + "router_weight"].T)
    chosen, ids = jax.lax.top_k(s + p[pre + "router_bias"], k + 1)
    margin = chosen[..., k - 1] - chosen[..., k]
    ids = ids[..., :k]
    picked = jnp.take_along_axis(s, ids, axis=-1)
    weights = picked / (jnp.sum(picked, axis=-1, keepdims=True) + 1e-20) \
        * arch["routed_scaling_factor"]
    return ids, weights, margin


def moe(p, pre, x, arch, margins):
    """The held experts' part of the routed sum, and the shared expert."""
    ids, weights, margin = route(p, pre, x, arch)
    margins.append(margin)
    first, held = arch["held_experts"]
    y = gated_silu(x, p[pre + "shared_gate_weight"],
                   p[pre + "shared_up_weight"], p[pre + "shared_down_weight"])
    for j in range(held):   # a dense mask over the tokens, expert by expert
        w = jnp.sum(jnp.where(ids == first + j, weights, 0.0), axis=-1)
        y = y + w[..., None] * (
            (jax.nn.silu(x @ p[pre + "experts_gate_weight"][j])
             * (x @ p[pre + "experts_up_weight"][j]))
            @ p[pre + "experts_down_weight"][j])
    return y


def block(p, pre, h, arch, dense, margins):
    eps = arch["rms_norm_eps"]
    h = h + mla(p, pre + "attn_", rms_norm(h, p[pre + "ln1_weight"], eps),
                arch)
    x = rms_norm(h, p[pre + "ln2_weight"], eps)
    if dense:
        return h + gated_silu(x, p[pre + "ffn_gate_weight"],
                              p[pre + "ffn_up_weight"],
                              p[pre + "ffn_down_weight"])
    return h + moe(p, pre + "moe_", x, arch, margins)


def hidden_states(p, tokens, arch):
    """-> (main stream, MTP stream), each (b, s, hidden) after its final
    norm, and the routing margins [(b, s)] of every routed block."""
    eps = arch["rms_norm_eps"]
    margins = []
    h = p["embed_weight"][tokens]
    for i in range(arch["num_hidden_layers"]):
        h = block(p, "l%d_" % i, h, arch,
                  i < arch["first_k_dense_replace"], margins)
    e = p["embed_weight"][jnp.roll(tokens, -1, axis=1)]
    u = jnp.concatenate([rms_norm(e, p["mtp_enorm_weight"], eps),
                         rms_norm(h, p["mtp_hnorm_weight"], eps)], axis=-1)
    u = block(p, "mtp_blk_", u @ p["mtp_proj_weight"].T, arch, False, margins)
    return (rms_norm(h, p["norm_weight"], eps),
            rms_norm(u, p["mtp_norm_weight"], eps), margins)


def _ids(x):
    return jnp.rint(x).astype(jnp.int32)


def forward(p, x, arch, train=False, dropout_masks=()):
    """Logits of both heads, (2, b, s, vocab): the main stream's for token
    ``i + 1`` and the MTP module's for token ``i + 2``.  ``p``: {name:
    value}.  Nothing differs between training and inference."""
    main, mtp, _ = hidden_states(p, _ids(x), arch)
    return jnp.stack([main @ p["head_weight"].T, mtp @ p["head_weight"].T])


def cross_entropy(logits, labels, valid):
    """Mean over the ``valid`` leading positions of each row."""
    logp = jax.nn.log_softmax(logits[:, :valid], axis=-1)
    picked = jnp.take_along_axis(logp, labels[:, :valid, None], axis=-1)
    return -jnp.mean(picked)


def loss(p, x, tokens, arch):
    """``tokens``: the labels' source, the rows of ``x`` themselves."""
    logits = forward(p, x, arch)
    s = tokens.shape[1]
    return cross_entropy(logits[0], jnp.roll(tokens, -1, axis=1), s - 1) \
        + arch["mtp_loss_weight"] * cross_entropy(
            logits[1], jnp.roll(tokens, -2, axis=1), s - 2)


def dense_prefix(p, tokens, arch):
    """The layers before the first router: embedding, the
    ``first_k_dense_replace`` dense blocks, the final norm -> (b, s,
    hidden)."""
    h = p["embed_weight"][tokens]
    for i in range(arch["first_k_dense_replace"]):
        h = block(p, "l%d_" % i, h, arch, True, None)
    return rms_norm(h, p["norm_weight"], arch["rms_norm_eps"])


def in_dense_prefix(name, arch):
    return name.startswith(("embed_", "norm_", "head_") + tuple(
        "l%d_" % i for i in range(arch["first_k_dense_replace"])))


def prefix_loss(p, tokens, arch):
    """Both terms of the loss read from the dense prefix's one stream ->
    (loss, the stream)."""
    hidden = dense_prefix(p, tokens, arch)
    logits = hidden @ p["head_weight"].T
    s = tokens.shape[1]
    value = cross_entropy(logits, jnp.roll(tokens, -1, axis=1), s - 1) \
        + arch["mtp_loss_weight"] * cross_entropy(
            logits, jnp.roll(tokens, -2, axis=1), s - 2)
    return value, hidden


def dropout_shapes(arch, batch):
    return []


def _cpu():
    try:
        return jax.devices("cpu")[0]
    except RuntimeError:        # jax was started without its CPU backend
        return jax.devices()[0]


def _put(named_params, where):
    return {n: jax.device_put(np.asarray(v, np.float32), where)
            for n, v in named_params}


def not_trained(name):
    return name.endswith(("router_bias", "held_pairs", "max_load"))


def outputs(arch, variants, y, dropout_masks=()):
    """[(logits of both heads, training loss, {name: gradient})] for each
    ``(named_params, x)`` of ``variants``, on the host's CPU device.  ``y``
    (rows, seq) token ids: the rows of the dense prefix's comparison; the
    gradients of ``prefix_loss`` on them are ``dense_prefix.<name>`` and its
    stream is ``dense_prefix.hidden``, beside the whole model's gradients
    on ``x``.  Row by row: the loss is a mean over rows, and one row's
    scores of 4,096 x 4,096 x 32 heads are what the host holds at ease."""
    where = _cpu()

    @jax.jit
    def both(p, x, tokens):
        trained = {n: v for n, v in p.items() if not not_trained(n)}
        rest = {n: v for n, v in p.items() if not_trained(n)}
        value, grads = jax.value_and_grad(
            lambda t: loss(dict(t, **rest), x, tokens, arch))(trained)
        return forward(p, x, arch), value, grads

    one_row = jax.jit(jax.value_and_grad(
        lambda p, row: prefix_loss(p, row, arch), has_aux=True))

    rows = jax.device_put(np.asarray(y, np.int32), where)
    out = []
    with jax.default_matmul_precision("highest"):
        for named_params, x in variants:
            p = _put(named_params, where)
            x = jax.device_put(np.asarray(x, np.float32), where)
            logits, value, grads = both(p, x, _ids(x))
            prefix = {n: v for n, v in p.items()
                      if in_dense_prefix(n, arch)}
            streams, total = [], None
            for row in rows:
                (_, hidden), g = one_row(prefix, row[None])
                streams.append(hidden)
                total = g if total is None else jax.tree_util.tree_map(
                    jnp.add, total, g)
            grads = dict(grads, **{"dense_prefix." + n: v / len(rows)
                                   for n, v in total.items()})
            grads["dense_prefix.hidden"] = jnp.concatenate(streams)
            out.append((logits, value, grads))
    return out


def routing_margins(arch, named_params, x):
    """(rows, routed blocks x positions) float32: for every token of every
    routed block (the MTP module's last), how far the last expert selected
    lies above the first one rejected.  Rows are independent (no batch
    statistics), so a row's margins do not depend on the rows beside it."""
    where = _cpu()

    @jax.jit
    def run(p, tokens):
        return jnp.concatenate(hidden_states(p, _ids(tokens), arch)[2],
                               axis=1)

    with jax.default_matmul_precision("highest"):
        return np.asarray(run(_put(named_params, where), jax.device_put(
            np.asarray(x, np.float32), where)))


# ------------------------------------------------------------- operations


def forward_flops_per_token(arch, seq, pairs=None, square_share=0.5):
    """2 x the multiply-adds of one token's forward pass on this chip, from
    the same walk over the layers as ``hidden_states``.  ``pairs``: the
    (token, held expert) products a token costs; by default what the router
    sends here on average, ``num_experts_per_tok`` x held / router outputs
    (this file's dense mask computes every held expert on every token:
    ``pairs`` = held).  ``square_share``: the part of the sequence's square
    that attention computes: half under the causal mask (this file computes
    it whole: 1)."""
    hid, heads = arch["hidden_size"], arch["num_attention_heads"]
    qk = arch["qk_nope_head_dim"] + arch["qk_rope_head_dim"]
    vd, nope = arch["v_head_dim"], arch["qk_nope_head_dim"]
    attn = hid * arch["q_lora_rank"] + arch["q_lora_rank"] * heads * qk \
        + hid * (arch["kv_lora_rank"] + arch["qk_rope_head_dim"]) \
        + arch["kv_lora_rank"] * heads * (nope + vd) + heads * vd * hid \
        + heads * (qk + vd) * seq * square_share
    expert = 3 * hid * arch["moe_intermediate_size"]
    if pairs is None:
        pairs = arch["num_experts_per_tok"] * arch["held_experts"][1] \
            / float(arch["router_outputs"])
    routed = hid * arch["router_outputs"] + expert * (1 + pairs)
    macs = 0.0
    for i in range(arch["num_hidden_layers"]):
        dense = i < arch["first_k_dense_replace"]
        macs += attn + (3 * hid * arch["intermediate_size"] if dense
                        else routed)
    macs += 2 * hid * hid + attn + routed       # the MTP module
    macs += 2 * hid * arch["vocab_size"]        # both heads
    return 2.0 * macs


def flops_per_sample(arch, input_shape):
    """Operations one training row requires of this chip: forward x 3
    (one product for the input gradient and one for the weight gradient of
    every matrix product), recomputation not counted."""
    seq = int(input_shape[0])
    return 3.0 * forward_flops_per_token(arch, seq) * seq
