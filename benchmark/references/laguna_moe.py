"""Laguna-S-2.1 (``model_type`` ``laguna``, poolside, 118B), plain
reference: forward, loss and gradients in float32 ``jax.numpy``, written
from the equations below (the published ``config.json``'s key
vocabulary is Qwen-MoE's with Laguna's own ``gating``, ``gating_types``
and ``num_attention_heads_per_layer``; where the catalog's config is
silent the configuration file's ``assumed`` says what was taken).  The
layers every language reference here has (RMS norm, the gated-SiLU
feed-forward, cross-entropy, the heads' output product, the sigmoid router
with its selection bias and the held experts' dense mask) are
``lfm2_moe.py``'s; yarn's frequencies, the window's mask and the rotation by
halves are ``mellum2_moe.py``'s; ``plain_layers.py`` has none of them.

``arch`` (sizes under the names of the model's ``config.json``):
``vocab_size``, ``hidden_size``, ``layer_types`` (``"full_attention"`` |
``"sliding_attention"`` per layer), ``mlp_layer_types`` (``"dense"`` |
``"sparse"`` per layer), ``num_attention_heads_per_layer``,
``gating_types`` (``"per_head"`` per layer), ``intermediate_size``,
``moe_intermediate_size``, ``shared_expert_intermediate_size``,
``router_outputs`` (the published ``num_experts``: the router's width),
``held_experts`` ``[first, count]`` (the consecutive expert ids this chip
holds; all of them for the uncut layer), ``num_experts_per_tok``,
``routed_scaling_factor``, ``route_epsilon``, ``bias_update_rate``,
``num_key_value_heads``, ``head_dim``, ``sliding_window``,
``rope_parameters`` (per layer type: ``rope_type``, ``rope_theta``,
``partial_rotary_factor`` and yarn's ``factor``,
``original_max_position_embeddings``, ``beta_fast``, ``beta_slow``,
``attention_factor``), ``norm_eps``.  No bias anywhere, the head untied.

Parameters are looked up by name (a dense weight is ``(out, in)``; the held
experts' weights are stacked ``(held, in, out)``):

    embed_weight, head_weight, norm_weight
    l<i>_ln1_weight, l<i>_ln2_weight
    l<i>_attn_{q,k,v,o,gate}_weight, l<i>_attn_{qnorm,knorm}_weight
    l<i>_ffn_{gate,up,down}_weight                      (a dense layer)
    l<i>_moe_router_weight, l<i>_moe_router_bias  (bias: no gradient)
    l<i>_moe_experts_{gate,up,down}_weight
    l<i>_moe_shared_{gate,up,down}_weight

Equations.  Block ``i``: ``h += Attn_i(RMSNorm(h))``; ``h +=
FFN_i(RMSNorm(h))``; after the last block one more RMSNorm, then the head.
Attention with ``H_i`` query heads (``num_attention_heads_per_layer[i]``):
``q = x W_q`` (``H_i`` x d), ``k = x W_k``, ``v = x W_v`` (kv heads x d);
every head of ``q`` and ``k`` RMS-normalised over its ``d`` values with one
learned scale of ``d``; the first ``r = d x partial_rotary_factor`` lanes
of a head rotated, pairs ``(i, i + r/2)``, by the layer type's frequencies
over ``r`` lanes and its amplitude (a window layer ``theta^(-2i/r)`` and 1,
here ``r = d``; a full layer yarn's blend over ``r = d / 2`` lanes, ``cos``
and ``sin`` times ``attention_factor``), the lanes from ``r`` on passed
through unscaled; scores x ``d^-1/2``; key ``j`` is visible to query ``i``
iff ``0 <= i - j`` in a full layer, ``0 <= i - j < sliding_window`` in a
window layer; softmax; query head ``h`` reads key / value head ``h //
(H_i / kv heads)``, the key heads repeated here; the per-head gate ``g =
sigmoid(x W_gate)`` (``W_gate (H_i, hidden)``), one scalar a head and
position, multiplies head ``h``'s result; ``o W_o``.  Feed-forward: a dense
layer's gated SiLU of ``intermediate_size``; a sparse layer's ``s =
sigmoid(x W_r)`` over all the router's outputs, the ``num_experts_per_tok``
largest of ``s + b`` selected, weights ``s_e / (sum of the selected s +
route_epsilon) x routed_scaling_factor``, ``y = FFN_shared(x) + sum_{e
selected and held} w_e FFN_e(x)``, a dense mask over the held experts:
selection and normalisation run over all experts, what the absent ones
would add is left out, the shared expert counted once.  Loss: the mean over
rows and valid positions of ``CE(head(RMSNorm(h_i)), t_{i+1})``.  The
selection bias takes no gradient; a training step moves it by the
balancing rule (DeepSeek-V3 section 2.1.2, as ``lfm2_moe.py``:
``after_step``).

Departures from the published description, noted.  The checkpoint computes
in bfloat16; here everything is float32.  The rule that trains the bias is
counted over this chip's tokens (a deployment sums the counts over the
chips that share the layer).  A published loop over the experts that were
hit gives the dense mask's sum.  Packed documents are not modelled: a row
is one document.

``check.py`` hands ``x`` over as float32, moved by one ulp: ``rint`` gives
the ids back (ids < 2^24 survive).

Comparisons (``outputs``).  ``x``: short rows through the whole model,
chosen free of routing margins (``routing_margins``).  ``y``: rows of the
timed step's own shape, compared twice from one stream in one program
(``timed_rows``, as the system's side does).  Through what lies before the
first router (``dense_prefix``: the embedding, layer 0 whole, the final
norm, the head and the loss over chunks of positions):
``dense_prefix.hidden`` and ``dense_prefix.<name>``.  And through the
first window layer's attention alone, fed that stream as a constant: its
output ``swa_timed.out`` and the gradients ``swa_timed.<name>`` of half
its mean square.  Attention at that length is computed over blocks of
queries (a window layer's over the keys of its band only), so that a row's
scores never stand whole.  Those rows
are computed where ``timed_device`` says: in a run on the chip, which
``run.py`` has finished measuring by then; the short rows stay on the
host's CPU (the whole model's weights and gradients in float32 would not
fit beside the system's state), one forward pass giving their logits, loss
and routes.  Beside the gradients of ``x``, what the step leaves in the
routed blocks' state (``after_step.<name>``): every bias's move and the
pairs on the held experts.
"""

import jax
import jax.numpy as jnp
import numpy as np

import lfm2_moe as lfm2
import mellum2_moe as mellum
from lfm2_moe import _cpu, _heads_out, _ids, _put, cross_entropy, \
    gated_silu, not_trained, rms_norm

QUERY_BLOCK = 256       # of the timed layers' scores
LOSS_CHUNK = 1024       # positions of the timed row's logits at a time


def rotary_dim(arch, kind):
    """The lanes of a head that a layer of type ``kind`` rotates."""
    share = arch["rope_parameters"][kind].get("partial_rotary_factor", 1)
    return int(arch["head_dim"] * share)


def rotary(arch, kind):
    """-> (the ``r / 2`` angles a position advances a pair by, the
    amplitude): ``mellum2_moe.rotary`` over the rotated lanes alone (yarn's
    ``dim`` is ``head_dim x partial_rotary_factor``)."""
    return mellum.rotary(dict(arch, head_dim=rotary_dim(arch, kind)), kind)


def rope(x, arch, kind):
    """The first ``rotary_dim`` lanes of ``x (..., S, d)`` rotated by
    halves, the others passed through."""
    lanes = rotary_dim(arch, kind)
    inv_freq, amplitude = rotary(arch, kind)
    return jnp.concatenate([mellum.rope(x[..., :lanes], inv_freq, amplitude),
                            x[..., lanes:]], axis=-1)


def window_of(arch, kind):
    return arch["sliding_window"] if kind == "sliding_attention" else None


def _qkv(p, pre, x, arch, i):
    """-> q (b, heads, s, d), k and v (b, heads, s, d) with the key heads
    repeated, for layer ``i``."""
    kind = arch["layer_types"][i]
    heads = arch["num_attention_heads_per_layer"][i]
    kv, d, eps = arch["num_key_value_heads"], arch["head_dim"], \
        arch["norm_eps"]
    b, s, _ = x.shape

    def split(weight, n):
        return (x @ weight.T).reshape(b, s, n, d).transpose(0, 2, 1, 3)

    q = rope(rms_norm(split(p[pre + "q_weight"], heads),
                      p[pre + "qnorm_weight"], eps), arch, kind)
    k = rope(rms_norm(split(p[pre + "k_weight"], kv),
                      p[pre + "knorm_weight"], eps), arch, kind)
    v = split(p[pre + "v_weight"], kv)
    return q, jnp.repeat(k, heads // kv, axis=1), \
        jnp.repeat(v, heads // kv, axis=1)


def head_gate(p, pre, x):
    """-> (b, heads, s, 1): ``sigmoid(x W_gate)``, one scalar a head and
    position."""
    g = jax.nn.sigmoid((x @ p[pre + "gate_weight"].T).astype(jnp.float32))
    return g.transpose(0, 2, 1)[..., None]


def _gated_out(p, pre, x, o):
    return _heads_out(p, pre, (o * head_gate(p, pre, x)).astype(o.dtype))


def attention(p, pre, x, arch, i):
    q, k, v = _qkv(p, pre, x, arch, i)
    o = mellum._attend(q, k, v, 0, 0, window_of(arch, arch["layer_types"][i]))
    return _gated_out(p, pre, x, o)


def attention_in_blocks(p, pre, x, arch, i, block):
    """``attention`` with the scores of ``block`` queries at a time; a
    window layer's against the ``block + window - 1`` keys its band can
    reach."""
    q, k, v = _qkv(p, pre, x, arch, i)
    b, heads, s, d = q.shape
    window = window_of(arch, arch["layer_types"][i])
    behind = 0 if window is None else min(window - 1, s)
    if window is not None:      # keys before position 0: padding
        k, v = (jnp.pad(a, ((0, 0), (0, 0), (behind, 0), (0, 0)))
                for a in (k, v))

    @jax.checkpoint
    def one(start):
        rows = jax.lax.dynamic_slice_in_dim(q, start, block, axis=2)
        if window is None:
            return mellum._attend(rows, k, v, start, 0, None)
        keys, values = (jax.lax.dynamic_slice_in_dim(
            a, start, block + behind, axis=2) for a in (k, v))
        return mellum._attend(rows, keys, values, start, start - behind,
                              window)

    o = jax.lax.map(one, jnp.arange(0, s, block))
    o = o.transpose(1, 2, 0, 3, 4).reshape(b, heads, s, d)
    return _gated_out(p, pre, x, o)


def moe(p, pre, x, arch, routes):
    """The held experts' part of the routed sum and the shared expert; the
    block's ``(ids, margin)`` joins ``routes``."""
    return lfm2.moe(p, pre, x, arch, routes) + gated_silu(
        x, p[pre + "shared_gate_weight"], p[pre + "shared_up_weight"],
        p[pre + "shared_down_weight"])


def block(p, i, h, arch, routes, block_queries=None):
    pre, eps = "l%d_" % i, arch["norm_eps"]
    x = rms_norm(h, p[pre + "ln1_weight"], eps)
    if block_queries is None:
        h = h + attention(p, pre + "attn_", x, arch, i)
    else:
        h = h + attention_in_blocks(p, pre + "attn_", x, arch, i,
                                    block_queries)
    x = rms_norm(h, p[pre + "ln2_weight"], eps)
    if arch["mlp_layer_types"][i] == "dense":
        return h + gated_silu(x, p[pre + "ffn_gate_weight"],
                              p[pre + "ffn_up_weight"],
                              p[pre + "ffn_down_weight"])
    return h + moe(p, pre + "moe_", x, arch, routes)


def hidden_states(p, tokens, arch):
    """-> (the stream after the blocks, not yet normed, (b, s, hidden);
    every sparse block's ``(expert ids (b, s, k), routing margins (b,
    s))``)."""
    routes = []
    h = p["embed_weight"][tokens]
    for i in range(len(arch["layer_types"])):
        h = block(p, i, h, arch, routes)
    return h, routes


def _head(p, h, arch):
    return rms_norm(h, p["norm_weight"], arch["norm_eps"]) \
        @ p["head_weight"].T


def forward(p, x, arch, train=False, dropout_masks=()):
    """Logits (b, s, vocab) for token ``i + 1``.  ``p``: {name: value}.
    Nothing differs between training and inference."""
    return _head(p, hidden_states(p, _ids(x), arch)[0], arch)


def sparse_layers(arch):
    return [i for i, kind in enumerate(arch["mlp_layer_types"])
            if kind == "sparse"]


def after_step(p, tokens, arch):
    """What a training step on ``tokens`` leaves in the routed blocks'
    state beside their weights -> {``l<i>_moe_router_bias``: (experts,) the
    balancing rule's move of the bias in units of ``bias_update_rate``, -1
    for an expert that the batch's tokens chose more often than the mean,
    +1 for one chosen less, 0 at the mean; ``l<i>_moe_held_pairs``: (1,)
    the (token, expert) pairs that fell on the held experts}."""
    return state_after(hidden_states(p, tokens, arch)[1], arch)


def state_after(routes, arch):
    """``after_step`` from the sparse blocks' ``(ids, margins)``."""
    first, held = arch["held_experts"]
    state = {}
    for i, (ids, _) in zip(sparse_layers(arch), routes):
        chosen = jnp.sum(jax.nn.one_hot(ids.reshape(-1),
                                        arch["router_outputs"]), axis=0)
        state["l%d_moe_router_bias" % i] = -jnp.sign(
            chosen - jnp.mean(chosen))
        state["l%d_moe_held_pairs" % i] = jnp.sum(
            (ids >= first) & (ids < first + held)).astype(
                jnp.float32).reshape(1)
    return state


def dense_prefix(arch):
    """The leading dense layers: what lies before the first router."""
    return range(sparse_layers(arch)[0])


def prefix_stream(p, tokens, arch, queries=QUERY_BLOCK):
    """The stream after the leading dense layers, not yet normed, their
    attention over blocks of ``queries``."""
    h = p["embed_weight"][tokens]
    for i in dense_prefix(arch):
        h = block(p, i, h, arch, [], min(queries, tokens.shape[1]))
    return h


def stream_loss(p, h, tokens, arch, chunk=LOSS_CHUNK):
    """The next-token loss read from the stream ``h`` of the layers before
    the first router -> (loss, that stream after the final norm); the
    logits ``chunk`` positions at a time, so that a row's never stand
    whole."""
    hidden = rms_norm(h, p["norm_weight"], arch["norm_eps"])
    rows, seq = tokens.shape
    if seq % chunk:
        chunk = seq
    labels = jnp.roll(tokens, -1, axis=1)

    @jax.checkpoint
    def one(start):
        h, wanted = (jax.lax.dynamic_slice_in_dim(a, start, chunk, axis=1)
                     for a in (hidden, labels))
        logp = jax.nn.log_softmax(
            (h @ p["head_weight"].T).astype(jnp.float32), axis=-1)
        picked = jnp.take_along_axis(logp, wanted[..., None], axis=-1)[..., 0]
        return jnp.sum(jnp.where(start + jnp.arange(chunk) < seq - 1,
                                 picked, 0.0))

    total = jnp.sum(jax.lax.map(one, jnp.arange(0, seq, chunk)))
    return -total / (rows * (seq - 1)), hidden


def timed_layer(arch):
    """The first window layer, compared alone at the timed shape."""
    return list(arch["layer_types"]).index("sliding_attention")


def window_attention(p, h, arch, queries=QUERY_BLOCK):
    """The first window layer's attention on the stream ``h`` -> (half the
    mean square of its output, the output (b, s, hidden))."""
    i = timed_layer(arch)
    pre = "l%d_" % i
    x = rms_norm(h, p[pre + "ln1_weight"], arch["norm_eps"])
    out = attention_in_blocks(p, pre + "attn_", x, arch, i,
                              min(queries, h.shape[1]))
    return 0.5 * jnp.mean(jnp.square(out.astype(jnp.float32))), out


def timed_rows(p, tokens, arch, queries=QUERY_BLOCK):
    """Both comparisons at the timed shape on one stream of the layers
    before the first router -> (the loss read from it plus half the mean
    square of the first window layer's attention on it, held constant;
    (the stream after the final norm, that attention's output)).  The two
    terms share no gradient: the attention sees the stream as a constant,
    and the loss no weight of the window layer."""
    h = prefix_stream(p, tokens, arch, queries)
    value, hidden = stream_loss(p, h, tokens, arch)
    half, out = window_attention(p, jax.lax.stop_gradient(h), arch, queries)
    return value + half, (hidden, out)


def dropout_shapes(arch, batch):
    return []


def timed_device():
    """Where the rows at the timed shape are computed: the run's first
    device, as in ``mellum2_moe.py`` (in a run of the cell the chip, which
    the window has finished with; in the tests the CPU)."""
    return jax.devices()[0]


def _in_prefix(name, arch):
    return name.startswith(("embed_", "norm_", "head_") + tuple(
        "l%d_" % i for i in dense_prefix(arch)))


def outputs(arch, variants, y, dropout_masks=(), dtype="float32"):
    """[(logits, training loss, {name: gradient})] for each ``(named_params,
    x)`` of ``variants``: the rows of ``x`` through the whole model on the
    host's CPU device.  ``y`` (rows, seq) token ids: the rows of the two
    comparisons at the timed shape (module docstring), computed row by row
    (each is a mean over rows) on ``timed_device()``.  ``dtype``: float32,
    the reference; ``"bfloat16"`` computes the same in the nearest
    precision below it (what the tolerances must refuse)."""
    where, at = _cpu(), timed_device()
    dtype = jnp.dtype(dtype)
    pre = "l%d_" % timed_layer(arch)

    @jax.jit
    def whole(p, tokens):
        """Logits, loss, gradients and ``after_step`` from one forward
        pass."""
        trained = {n: v for n, v in p.items() if not not_trained(n)}
        rest = {n: v for n, v in p.items() if not_trained(n)}

        def value(t):
            q = dict(t, **rest)
            h, routes = hidden_states(q, tokens, arch)
            logits = _head(q, h, arch)
            return cross_entropy(logits, jnp.roll(tokens, -1, axis=1),
                                 tokens.shape[1] - 1), (logits, routes)

        (value, (logits, routes)), grads = jax.value_and_grad(
            value, has_aux=True)(trained)
        grads.update({"after_step." + n: v
                      for n, v in state_after(routes, arch).items()})
        return logits, value, grads

    timed_row = jax.jit(jax.value_and_grad(
        lambda p, row: timed_rows(p, row, arch), has_aux=True))

    def compared_as(name):
        return "dense_prefix." + name if _in_prefix(name, arch) \
            else "swa_timed." + name

    def at_the_timed_shape(named_params, rows):
        """The rows one at a time, the gradients' mean over them."""
        p = _put([(n, v) for n, v in named_params
                  if _in_prefix(n, arch) or n.startswith(pre + "attn_")
                  or n == pre + "ln1_weight"], at, dtype)
        hidden, out, total = [], [], None
        for row in rows:
            (_, (h, o)), g = timed_row(p, row[None])
            hidden.append(h)
            out.append(o)
            total = g if total is None else jax.tree_util.tree_map(
                jnp.add, total, g)
        grads = {compared_as(n): v / len(rows) for n, v in total.items()
                 if n != pre + "ln1_weight"}
        grads.update({"dense_prefix.hidden": jnp.concatenate(hidden),
                      "swa_timed.out": jnp.concatenate(out)})
        return grads

    rows = jax.device_put(np.asarray(y, np.int32), at)
    out = []
    with jax.default_matmul_precision("highest"):
        for named_params, x in variants:
            timed = at_the_timed_shape(named_params, rows)
            p = _put(named_params, where, dtype)
            x = jax.device_put(np.asarray(x, np.float32), where)
            logits, value, grads = whole(p, _ids(x))
            # to the host: the device keeps one evaluation's results at a time
            out.append((logits, value, dict(grads, **jax.device_get(timed))))
    return out


def routing_margins(arch, named_params, x):
    """(rows, sparse blocks x positions) float32: for every token of every
    sparse block, how far the last expert selected lies above the first one
    rejected.  Rows are independent, so a row's margins do not depend on
    the rows beside it."""
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


band_pairs = mellum.band_pairs


def forward_flops_per_token(arch, seq, pairs=None, whole_square=False):
    """2 x the multiply-adds of one token's forward pass on this chip, from
    the same walk over the layers as ``hidden_states``.  ``pairs``: the
    (token, held expert) products a token costs in a sparse layer; by
    default what the router sends here on average, ``num_experts_per_tok``
    x held / router outputs (this file's dense mask computes every held
    expert on every token: ``pairs`` = held).  Attention is charged the
    pairs of its band (``band_pairs``); ``whole_square``: the sequence's
    whole square, which ``attention`` here computes and masks.  The shared
    expert and the gate count once a token."""
    hid, d = arch["hidden_size"], arch["head_dim"]
    kv = arch["num_key_value_heads"]
    if pairs is None:
        pairs = arch["num_experts_per_tok"] * arch["held_experts"][1] \
            / float(arch["router_outputs"])
    routed = hid * arch["router_outputs"] \
        + 3 * hid * arch["moe_intermediate_size"] * pairs \
        + 3 * hid * arch["shared_expert_intermediate_size"]
    macs = 0.0
    for i, kind in enumerate(arch["layer_types"]):
        heads = arch["num_attention_heads_per_layer"][i]
        seen = seq if whole_square else \
            band_pairs(seq, window_of(arch, kind)) / seq
        macs += 2 * hid * heads * d + 2 * hid * kv * d + hid * heads \
            + heads * 2 * d * seen
        macs += 3 * hid * arch["intermediate_size"] \
            if arch["mlp_layer_types"][i] == "dense" else routed
    macs += hid * arch["vocab_size"]        # the head
    return 2.0 * macs


def flops_per_sample(arch, input_shape):
    """Operations one training row requires of this chip: forward x 3
    (one product for the input gradient and one for the weight gradient of
    every matrix product), recomputation not counted."""
    seq = int(input_shape[0])
    return 3.0 * forward_flops_per_token(arch, seq) * seq
