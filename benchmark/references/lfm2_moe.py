"""LFM2-8B-A1B (``model_type`` ``lfm2_moe``, LiquidAI, 8.3B-A1.5B), plain
reference: forward, loss and gradients in float32 ``jax.numpy``, written
from the equations of the published ``modeling_lfm2_moe.py``.

``arch`` (sizes under the names of the model's ``config.json``):
``vocab_size``, ``hidden_size``, ``layer_types`` (``"conv"`` |
``"full_attention"`` per layer), ``num_dense_layers``, ``intermediate_size``,
``moe_intermediate_size``, ``router_outputs`` (the published ``num_experts``:
the router's width), ``held_experts`` ``[first, count]`` (the consecutive
expert ids this chip holds; all of them for the uncut layer),
``num_experts_per_tok``, ``routed_scaling_factor``, ``route_epsilon``,
``bias_update_rate``, ``num_attention_heads``, ``num_key_value_heads``,
``conv_L_cache``, ``rope_theta``, ``norm_eps``.  No bias anywhere, no shared
expert, the head tied to the embedding.

Parameters are looked up by name (a dense weight is ``(out, in)``; the held
experts' weights are stacked ``(held, in, out)``):

    embed_weight (also the head), norm_weight
    l<i>_ln1_weight, l<i>_ln2_weight
    l<i>_conv_{in,conv,out}_weight           in (3 h, h), conv (h, L), out (h, h)
    l<i>_attn_{q,k,v,o}_weight, l<i>_attn_{qnorm,knorm}_weight
    l<i>_ffn_{gate,up,down}_weight                       (dense layer)
    l<i>_moe_router_weight, l<i>_moe_router_bias   (bias: no gradient, below)
    l<i>_moe_experts_{gate,up,down}_weight

Equations.  Block ``i``: ``h += Op_i(RMSNorm(h))``; ``h += FFN_i(RMSNorm(h))``;
``RMSNorm(x) = x rsqrt(mean(x^2) + eps) w``; after the last block one more
RMSNorm, then the head.  Short convolution (``L`` taps): ``[B; C; x~] = x
W_in``; ``u = B * x~``; ``c_t = sum_j k_j * u_{t-(L-1)+j}`` with ``u`` zero
before the row's first token, written here as ``L`` shifted products; ``y =
(C * c) W_out``.  Attention: ``q = x W_q`` (heads x d), ``k = x W_k``, ``v =
x W_v`` (kv heads x d); every head of ``q`` and ``k`` RMS-normalised over its
``d`` values with one learned scale of ``d``; rotary embedding over all ``d``
dimensions, pairs ``(i, i + d/2)``; scores x ``d^-1/2``, causal softmax;
query head ``h`` reads key/value head ``h // group``, the key heads repeated
here and the whole square computed; ``o W_o``.  Experts: ``s = sigmoid(x
W_g)``; the ``num_experts_per_tok`` largest of ``s + b`` are selected;
weights ``s_k / (sum of the selected s + route_epsilon) *
routed_scaling_factor`` (the bias selects, it does not weigh); ``y = sum_{k:
e_k held} g_k E_{e_k}(x)``, a dense mask over the held experts: selection and
normalisation run over all experts, what the absent ones would add is left
out.  Loss: ``CE(head(RMSNorm(h_i)), t_{i+1})``, a mean over a row's valid
positions.  The selection bias takes no gradient; a training step moves it
by the balancing rule the model trains under (``use_expert_bias``;
auxiliary-loss-free, DeepSeek-V3 section 2.1.2): with ``c_e`` the times the
batch's tokens chose expert ``e`` (all of the router's outputs, held here or
not), ``b_e -= bias_update_rate * sign(c_e - mean(c))`` (``after_step``
gives the move in units of the rate, so this file never reads the rate).

Departures from the published code, noted.  It computes in the checkpoint's
bfloat16 and its RMSNorm casts to float32 and back; here everything is
float32.  Its convolution is an ``nn.Conv1d`` over ``(batch, channels,
seq)`` with padding ``L - 1`` cut back to ``seq``; the shifted products are
the same sums.  Its experts run a loop over the experts that were hit; the
dense mask gives the same sum.  Its ``expert_bias`` is a buffer that the
training loop updates by a rule the published code does not carry; the rule
above is the one of the paper it takes the bias from, counted over this
chip's tokens (a deployment sums the counts over the chips that share the
layer).  The head is tied to the
embedding as ``tie_embedding: true`` of LFM2's configs says (the catalog
row lacks the key).  Packed documents (the convolution and attention reset
at a boundary) are not modelled: a row is one document.

``check.py`` hands ``x`` over as float32, moved by one ulp: ``rint`` gives
the ids back (ids < 2^24 survive).

Three comparisons (``outputs``).  ``x``: short rows through the whole model,
chosen free of routing margins (``routing_margins``).  ``y``: rows of the
timed step's own shape, twice.  Through the layers before the first router
(``dense_prefix``: embedding, the leading dense blocks, the final norm, the
tied head and the loss), where no route can flip and so any row serves at
any length: ``dense_prefix.hidden`` and ``dense_prefix.<name>``.  And,
because those layers hold no attention, through the first attention layer
fed the dense blocks' stream (``timed_attention``): its output
``gqa_timed.out`` and the gradients ``gqa_timed.<name>`` of half its mean
square, computed over blocks of queries so that a row's 8,192 x 8,192 x 32
scores never stand whole.  The labels are the rows' own next tokens.  Beside
the gradients of ``x``, what the step leaves in the routed blocks' state
(``after_step.<name>``): every bias's move and the pairs on the held experts.
"""

import math

import jax
import jax.numpy as jnp
import numpy as np

QUERY_BLOCK = 512       # of ``timed_attention``'s scores


def rms_norm(x, weight, eps):
    f32 = jnp.float32
    x32 = x.astype(f32)
    return (x32 * jax.lax.rsqrt(jnp.mean(x32 * x32, axis=-1, keepdims=True)
                                + eps) * weight.astype(f32)).astype(x.dtype)


def rope(x, theta):
    """Rotate the pairs ``(i, i + d/2)`` of the last axis of ``x`` (..., S,
    d) by ``position * theta^(-2i/d)`` ("rotate half")."""
    seq, d = x.shape[-2], x.shape[-1]
    inv = theta ** (-np.arange(0, d, 2, dtype=np.float64) / d)
    angle = np.arange(seq, dtype=np.float64)[:, None] * inv[None, :]
    cos = jnp.asarray(np.cos(angle), x.dtype)
    sin = jnp.asarray(np.sin(angle), x.dtype)
    first, second = x[..., :d // 2], x[..., d // 2:]
    return jnp.concatenate([first * cos - second * sin,
                            second * cos + first * sin], axis=-1)


def gated_silu(x, gate, up, down):
    """``W_down(silu(W_gate x) * W_up x)``, weights ``(out, in)``."""
    return (jax.nn.silu(x @ gate.T) * (x @ up.T)) @ down.T


def short_conv(p, pre, x, arch):
    taps = arch["conv_L_cache"]
    seq = x.shape[1]
    gate_b, gate_c, xt = jnp.split(x @ p[pre + "in_weight"].T, 3, axis=-1)
    u = jnp.pad(gate_b * xt, ((0, 0), (taps - 1, 0), (0, 0)))
    kernel = p[pre + "conv_weight"]                     # (hidden, taps)
    conv = sum(kernel[:, j] * u[:, j:j + seq] for j in range(taps))
    return (gate_c * conv) @ p[pre + "out_weight"].T


def _qkv(p, pre, x, arch):
    """-> q (b, heads, s, d), k and v (b, heads, s, d) with the key heads
    repeated."""
    heads, kv = arch["num_attention_heads"], arch["num_key_value_heads"]
    d = arch["hidden_size"] // heads
    b, s, _ = x.shape
    eps, theta = arch["norm_eps"], arch["rope_theta"]

    def split(weight, n):
        return (x @ weight.T).reshape(b, s, n, d).transpose(0, 2, 1, 3)

    q = rope(rms_norm(split(p[pre + "q_weight"], heads),
                      p[pre + "qnorm_weight"], eps), theta)
    k = rope(rms_norm(split(p[pre + "k_weight"], kv),
                      p[pre + "knorm_weight"], eps), theta)
    v = split(p[pre + "v_weight"], kv)
    return q, jnp.repeat(k, heads // kv, axis=1), \
        jnp.repeat(v, heads // kv, axis=1)


def _attend(q, k, v, first):
    """Causal softmax attention of the queries at positions ``first ..``
    over all keys: (b, heads, queries, d)."""
    d = q.shape[-1]
    scores = jnp.einsum("bhqd,bhkd->bhqk", q, k) / math.sqrt(d)
    seen = (first + jnp.arange(q.shape[2]))[:, None] \
        >= jnp.arange(k.shape[2])[None, :]
    scores = jnp.where(seen, scores, -jnp.inf)
    probs = jax.nn.softmax(scores.astype(jnp.float32), axis=-1)
    return jnp.einsum("bhqk,bhkd->bhqd", probs.astype(v.dtype), v)


def _heads_out(p, pre, o):
    b, heads, s, d = o.shape
    return o.transpose(0, 2, 1, 3).reshape(b, s, heads * d) \
        @ p[pre + "o_weight"].T


def attention(p, pre, x, arch):
    q, k, v = _qkv(p, pre, x, arch)
    return _heads_out(p, pre, _attend(q, k, v, 0))


def attention_in_blocks(p, pre, x, arch, block):
    """``attention`` with the scores of ``block`` queries at a time."""
    q, k, v = _qkv(p, pre, x, arch)
    b, heads, s, d = q.shape
    starts = jnp.arange(0, s, block)

    @jax.checkpoint
    def one(start):
        rows = jax.lax.dynamic_slice_in_dim(q, start, block, axis=2)
        return _attend(rows, k, v, start)

    o = jax.lax.map(one, starts)            # (blocks, b, heads, block, d)
    o = o.transpose(1, 2, 0, 3, 4).reshape(b, heads, s, d)
    return _heads_out(p, pre, o)


def route(p, pre, x, arch):
    """-> (expert ids (..., k), weights (..., k), margin (...)): the
    selection over all of the router's outputs; ``margin`` is the distance
    between the last selected and the first rejected selection score."""
    k = arch["num_experts_per_tok"]
    s = jax.nn.sigmoid((x @ p[pre + "router_weight"].T).astype(jnp.float32))
    chosen, ids = jax.lax.top_k(s + p[pre + "router_bias"], k + 1)
    margin = chosen[..., k - 1] - chosen[..., k]
    ids = ids[..., :k]
    picked = jnp.take_along_axis(s, ids, axis=-1)
    weights = picked / (jnp.sum(picked, axis=-1, keepdims=True)
                        + arch["route_epsilon"]) \
        * arch["routed_scaling_factor"]
    return ids, weights.astype(x.dtype), margin


def moe(p, pre, x, arch, routes):
    """The held experts' part of the routed sum; the block's ``(ids,
    margin)`` joins ``routes``."""
    ids, weights, margin = route(p, pre, x, arch)
    routes.append((ids, margin))
    first, held = arch["held_experts"]
    y = jnp.zeros_like(x)
    for j in range(held):   # a dense mask over the tokens, expert by expert
        w = jnp.sum(jnp.where(ids == first + j, weights, 0.0), axis=-1)
        y = y + w[..., None] * (
            (jax.nn.silu(x @ p[pre + "experts_gate_weight"][j])
             * (x @ p[pre + "experts_up_weight"][j]))
            @ p[pre + "experts_down_weight"][j])
    return y


def mixer(p, pre, x, arch, kind):
    if kind == "full_attention":
        return attention(p, pre + "attn_", x, arch)
    return short_conv(p, pre + "conv_", x, arch)


def block(p, i, h, arch, routes):
    pre, eps = "l%d_" % i, arch["norm_eps"]
    h = h + mixer(p, pre, rms_norm(h, p[pre + "ln1_weight"], eps), arch,
                  arch["layer_types"][i])
    x = rms_norm(h, p[pre + "ln2_weight"], eps)
    if i < arch["num_dense_layers"]:
        return h + gated_silu(x, p[pre + "ffn_gate_weight"],
                              p[pre + "ffn_up_weight"],
                              p[pre + "ffn_down_weight"])
    return h + moe(p, pre + "moe_", x, arch, routes)


def hidden_states(p, tokens, arch, layers=None):
    """-> (the stream after ``layers`` blocks (all by default), not yet
    normed, (b, s, hidden); every routed block's ``(expert ids (b, s, k),
    routing margins (b, s))``)."""
    routes = []
    h = p["embed_weight"][tokens]
    n = len(arch["layer_types"]) if layers is None else layers
    for i in range(n):
        h = block(p, i, h, arch, routes)
    return h, routes


def after_step(p, tokens, arch):
    """What a training step on ``tokens`` leaves in the routed blocks'
    state beside their weights -> {``l<i>_moe_router_bias``: (experts,) the
    balancing rule's move of the bias in units of ``bias_update_rate``, -1
    for an expert that the batch's tokens chose more often than the mean,
    +1 for one chosen less, 0 at the mean; ``l<i>_moe_held_pairs``: (1,)
    the (token, expert) pairs that fell on the held experts}."""
    first, held = arch["held_experts"]
    routed = range(arch["num_dense_layers"], len(arch["layer_types"]))
    state = {}
    for i, (ids, _) in zip(routed, hidden_states(p, tokens, arch)[1]):
        chosen = jnp.sum(jax.nn.one_hot(ids.reshape(-1),
                                        arch["router_outputs"]), axis=0)
        state["l%d_moe_router_bias" % i] = -jnp.sign(
            chosen - jnp.mean(chosen))
        state["l%d_moe_held_pairs" % i] = jnp.sum(
            (ids >= first) & (ids < first + held)).astype(
                jnp.float32).reshape(1)
    return state


def _ids(x):
    return jnp.rint(x).astype(jnp.int32)


def forward(p, x, arch, train=False, dropout_masks=()):
    """Logits (b, s, vocab) for token ``i + 1``.  ``p``: {name: value}.
    Nothing differs between training and inference."""
    h, _ = hidden_states(p, _ids(x), arch)
    return rms_norm(h, p["norm_weight"], arch["norm_eps"]) \
        @ p["embed_weight"].T


def cross_entropy(logits, labels, valid):
    """Mean over the ``valid`` leading positions of each row."""
    logp = jax.nn.log_softmax(logits[:, :valid].astype(jnp.float32), axis=-1)
    picked = jnp.take_along_axis(logp, labels[:, :valid, None], axis=-1)
    return -jnp.mean(picked)


def loss(p, x, tokens, arch):
    """``tokens``: the labels' source, the rows of ``x`` themselves."""
    return cross_entropy(forward(p, x, arch), jnp.roll(tokens, -1, axis=1),
                         tokens.shape[1] - 1)


def in_dense_prefix(name, arch):
    return name.startswith(("embed_", "norm_") + tuple(
        "l%d_" % i for i in range(arch["num_dense_layers"])))


def prefix_loss(p, tokens, arch):
    """The loss read from the stream of the layers before the first router
    -> (loss, that stream after the final norm)."""
    h, _ = hidden_states(p, tokens, arch, arch["num_dense_layers"])
    hidden = rms_norm(h, p["norm_weight"], arch["norm_eps"])
    value = cross_entropy(hidden @ p["embed_weight"].T,
                          jnp.roll(tokens, -1, axis=1), tokens.shape[1] - 1)
    return value, hidden


def first_attention_layer(arch):
    return list(arch["layer_types"]).index("full_attention")


def timed_attention(p, tokens, arch, block=QUERY_BLOCK):
    """The first attention layer on the stream of the layers before the
    first router (that stream a constant) -> (half the mean square of its
    output, the output (b, s, hidden))."""
    layer = first_attention_layer(arch)
    pre = "l%d_" % layer
    h, _ = hidden_states(p, tokens, arch, arch["num_dense_layers"])
    x = rms_norm(jax.lax.stop_gradient(h), p[pre + "ln1_weight"],
                 arch["norm_eps"])
    out = attention_in_blocks(p, pre + "attn_", x, arch,
                              min(block, tokens.shape[1]))
    return 0.5 * jnp.mean(jnp.square(out.astype(jnp.float32))), out


def dropout_shapes(arch, batch):
    return []


def _cpu():
    try:
        return jax.devices("cpu")[0]
    except RuntimeError:        # jax was started without its CPU backend
        return jax.devices()[0]


def not_trained(name):
    return name.endswith(("router_bias", "held_pairs", "max_load"))


def _put(named_params, where, dtype):
    """The parameters on ``where``: float32 as handed over, then cast to
    ``dtype`` (the router's bias stays float32: it is added to float32
    scores)."""
    return {n: jax.device_put(np.asarray(v, np.float32), where).astype(
        jnp.float32 if not_trained(n) else dtype) for n, v in named_params}


def outputs(arch, variants, y, dropout_masks=(), dtype="float32"):
    """[(logits, training loss, {name: gradient})] for each ``(named_params,
    x)`` of ``variants``, on the host's CPU device.  ``y`` (rows, seq) token
    ids: the rows of the two comparisons at the timed shape (module
    docstring), computed row by row: the loss is a mean over rows.
    ``dtype``: float32, the reference; ``"bfloat16"`` computes the same in
    the nearest precision below it (what the tolerances must refuse)."""
    where = _cpu()
    dtype = jnp.dtype(dtype)
    attn = "l%d_attn_" % first_attention_layer(arch)

    @jax.jit
    def whole(p, x, tokens):
        trained = {n: v for n, v in p.items() if not not_trained(n)}
        rest = {n: v for n, v in p.items() if not_trained(n)}
        value, grads = jax.value_and_grad(
            lambda t: loss(dict(t, **rest), x, tokens, arch))(trained)
        grads.update({"after_step." + n: v
                      for n, v in after_step(p, tokens, arch).items()})
        return forward(p, x, arch), value, grads

    prefix_row = jax.jit(jax.value_and_grad(
        lambda p, row: prefix_loss(p, row, arch), has_aux=True))
    attention_row = jax.jit(jax.value_and_grad(
        lambda layer, rest, row: timed_attention(dict(layer, **rest), row,
                                                 arch), has_aux=True))

    def over_rows(one_row, rows):
        """-> (the rows' streams, the mean of their gradients)."""
        streams, total = [], None
        for row in rows:
            (_, stream), g = one_row(row[None])
            streams.append(stream)
            total = g if total is None else jax.tree_util.tree_map(
                jnp.add, total, g)
        return jnp.concatenate(streams), {n: v / len(rows)
                                          for n, v in total.items()}

    rows = jax.device_put(np.asarray(y, np.int32), where)
    out = []
    with jax.default_matmul_precision("highest"):
        for named_params, x in variants:
            p = _put(named_params, where, dtype)
            x = jax.device_put(np.asarray(x, np.float32), where)
            logits, value, grads = whole(p, x, _ids(x))
            grads = dict(grads)
            prefix = {n: v for n, v in p.items() if in_dense_prefix(n, arch)}
            layer = {n: v for n, v in p.items() if n.startswith(attn)}
            rest = dict(prefix, **{attn[:-5] + "ln1_weight":
                                   p[attn[:-5] + "ln1_weight"]})
            for name, stream, one_row in (
                    ("dense_prefix.", "hidden",
                     lambda row: prefix_row(prefix, row)),
                    ("gqa_timed.", "out",
                     lambda row: attention_row(layer, rest, row))):
                grads[name + stream], mean = over_rows(one_row, rows)
                grads.update({name + n: v for n, v in mean.items()})
            out.append((logits, value, grads))
    return out


def routing_margins(arch, named_params, x):
    """(rows, routed blocks x positions) float32: for every token of every
    routed block, how far the last expert selected lies above the first one
    rejected.  Rows are independent (no batch statistics), so a row's
    margins do not depend on the rows beside it."""
    where = _cpu()

    @jax.jit
    def run(p, tokens):
        return jnp.concatenate(
            [margin for _, margin in hidden_states(p, _ids(tokens),
                                                   arch)[1]], axis=1)

    with jax.default_matmul_precision("highest"):
        return np.asarray(run(
            _put(named_params, where, jnp.float32),
            jax.device_put(np.asarray(x, np.float32), where)))


# ------------------------------------------------------------- operations


def forward_flops_per_token(arch, seq, pairs=None, square_share=0.5):
    """2 x the multiply-adds of one token's forward pass on this chip, from
    the same walk over the layers as ``hidden_states``.  ``pairs``: the
    (token, held expert) products a token costs in a routed layer; by
    default what the router sends here on average, ``num_experts_per_tok``
    x held / router outputs (this file's dense mask computes every held
    expert on every token: ``pairs`` = held).  ``square_share``: the part
    of the sequence's square that attention computes: half under the
    causal mask (this file computes it whole: 1)."""
    hid, heads = arch["hidden_size"], arch["num_attention_heads"]
    d = hid // heads
    kv = arch["num_key_value_heads"] * d
    mixers = {
        "conv": 3 * hid * hid + hid * hid,
        "full_attention": 2 * hid * heads * d + 2 * hid * kv
        + heads * 2 * d * seq * square_share,
    }
    if pairs is None:
        pairs = arch["num_experts_per_tok"] * arch["held_experts"][1] \
            / float(arch["router_outputs"])
    routed = hid * arch["router_outputs"] \
        + 3 * hid * arch["moe_intermediate_size"] * pairs
    macs = 0.0
    for i, kind in enumerate(arch["layer_types"]):
        macs += mixers[kind] + (3 * hid * arch["intermediate_size"]
                                if i < arch["num_dense_layers"] else routed)
    macs += hid * arch["vocab_size"]        # the tied head
    return 2.0 * macs


def flops_per_sample(arch, input_shape):
    """Operations one training row requires of this chip: forward x 3
    (one product for the input gradient and one for the weight gradient of
    every matrix product), recomputation not counted."""
    seq = int(input_shape[0])
    return 3.0 * forward_flops_per_token(arch, seq) * seq
