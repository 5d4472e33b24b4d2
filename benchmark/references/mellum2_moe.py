"""Mellum2-12B-A2.5B-Instruct (``model_type`` ``mellum``, JetBrains, 12B
parameters, 2.5B active), plain reference: forward, loss with the routers'
balancing term, and gradients in float32 ``jax.numpy``, written from the
equations of ISSUE 34 (the published ``config.json``'s key vocabulary is
Qwen3-MoE's; where the catalog's config is silent the configuration file's
``assumed`` says what was taken).  The layers every language reference here
has (RMS norm, cross-entropy, the heads' output product) are
``lfm2_moe.py``'s; ``plain_layers.py`` has none of them.

``arch`` (sizes under the names of the model's ``config.json``):
``vocab_size``, ``hidden_size``, ``layer_types`` (``"sliding_attention"`` |
``"full_attention"`` per layer), ``moe_intermediate_size``,
``router_outputs`` (the published ``num_experts``: the router's width),
``held_experts`` ``[first, count]`` (the consecutive expert ids this chip
holds; all of them for the uncut layer), ``num_experts_per_tok``,
``num_attention_heads``, ``num_key_value_heads``, ``head_dim``,
``sliding_window``, ``rope_parameters`` (per layer type: ``rope_type``
``"default"`` | ``"yarn"``, ``rope_theta`` and yarn's ``factor``,
``original_max_position_embeddings``, ``beta_fast``, ``beta_slow``,
``attention_factor``), ``router_aux_loss_coef``, ``router_trained_by``
(``"loss"`` | ``"balance"``), ``route_epsilon``, ``norm_eps``.  No bias
anywhere, no shared expert, no dense layer, the head
untied.

Parameters are looked up by name (a dense weight is ``(out, in)``; the held
experts' weights are stacked ``(held, in, out)``):

    embed_weight, head_weight, norm_weight
    l<i>_ln1_weight, l<i>_ln2_weight
    l<i>_attn_{q,k,v,o}_weight, l<i>_attn_{qnorm,knorm}_weight
    l<i>_moe_router_weight
    l<i>_moe_experts_{gate,up,down}_weight

Equations.  Block ``i``: ``h += Attn_i(RMSNorm(h))``; ``h += MoE_i(RMSNorm(
h))``; after the last block one more RMSNorm, then the head.  Attention:
``q = x W_q`` (heads x d), ``k = x W_k``, ``v = x W_v`` (kv heads x d);
every head of ``q`` and ``k`` RMS-normalised over its ``d`` values with one
learned scale of ``d``; rotary embedding over all ``d`` dimensions, pairs
``(i, i + d/2)``, by the layer type's frequencies and amplitude
(``rotary``: a window layer ``theta^(-2i/d)`` and 1; a full layer yarn's
blend of ``theta^(-2i/d)`` and the same over ``factor``, ``cos`` and ``sin``
times ``attention_factor``); scores x ``d^-1/2``; key ``j`` is visible to
query ``i`` iff ``0 <= i - j`` in a full layer, ``0 <= i - j <
sliding_window`` in a window layer; softmax; query head ``h`` reads key /
value head ``h // group``, the key heads repeated here; ``o W_o``.  Experts:
``p = softmax(x W_g)`` over all the router's outputs in float32; the
``num_experts_per_tok`` largest are selected; weights ``p_e / (sum of the
selected p + route_epsilon)``; ``y = sum_{e selected and held} g_e E_e(x)``,
a dense mask over the held experts: selection and normalisation run over
all experts, what the absent ones would add is left out.  Loss: the mean
over rows and valid positions of ``CE(head(RMSNorm(h_i)), t_{i+1})`` plus
``router_aux_loss_coef x sum over the layers of E x sum_e f_e P_e``, ``f_e``
the share of the layer's ``tokens x k`` pairs that chose expert ``e`` (no
gradient), ``P_e`` the mean of ``p_e`` over the tokens, both over all ``E``
experts and all the batch's tokens.

Departures from the published description, noted.  The checkpoint computes
in bfloat16; here everything is float32.  ``described_as`` names an MTP
head that ``config`` has no key for: none is built.  The balancing term is
counted over this chip's tokens (a job's ``f`` and ``P`` sum over the chips
that share the layer: the exchange's, left out like the exchange).  With
``router_trained_by`` ``"balance"`` the routing weights are constants of
the loss (``stop_gradient``): the loss's gradient on a routing weight is
the stream's gradient times that expert's output, which a chip has for its
held experts alone; that part alone pulls every token towards the held
experts, so it is left out with the exchange it needs and the balancing
term alone reaches the router (whose rows the training rule then does not
decay: the configuration's ``assumed``).  A
published loop over the experts that were hit gives the dense mask's sum.
Packed documents are not modelled: a row is one document.

``check.py`` hands ``x`` over as float32, moved by one ulp: ``rint`` gives
the ids back (ids < 2^24 survive).

Comparisons (``outputs``).  ``x``: short rows through the whole model,
chosen free of routing margins (``routing_margins``).  ``y``: rows of the
timed step's own shape, three times.  Through what lies before the first
router, which here is the embedding alone, then the final norm, the head
and the loss (``dense_prefix.hidden``, ``dense_prefix.<name>``).  Through
the first window layer and through the first full layer, each alone on the
embedding's stream (a constant): its output ``swa_timed.out`` /
``gqa_timed.out`` and the gradients ``swa_timed.<name>`` / ``gqa_timed.
<name>`` of half the output's mean square, computed over blocks of queries
(a window layer's over the keys of its band only) so that a row's 16,384 x
16,384 x 32 scores never stand whole, as the loss is over chunks of
positions.  Those three are computed where ``timed_device`` says: in a run
on the chip, which ``run.py`` has finished measuring by then, so that a run
stays inside its time limit; the short rows through the whole model stay on
the host's CPU.  Beside the gradients of ``x``, what
the step leaves in the routed blocks' counters (``after_step.<name>``): the
pairs on the held experts and the balancing term.
"""

import math

import jax
import jax.numpy as jnp
import numpy as np

from lfm2_moe import _cpu, _heads_out, _ids, cross_entropy, rms_norm

QUERY_BLOCK = 256       # of the timed layers' scores
LOSS_CHUNK = 2048       # positions of the timed row's logits at a time


def rotary(arch, kind):
    """-> (the ``d / 2`` angles a position advances a pair by, what ``cos``
    and ``sin`` are multiplied by) of a layer of type ``kind``."""
    how = arch["rope_parameters"][kind]
    d, theta = arch["head_dim"], float(how["rope_theta"])
    pair = np.arange(d // 2, dtype=np.float64)
    extrapolated = theta ** (-2 * pair / d)
    if how["rope_type"] == "default":
        return extrapolated, 1.0
    interpolated = extrapolated / how["factor"]
    original = how["original_max_position_embeddings"]

    def turning(times):     # the pair that turns ``times`` over the original
        return d * math.log(original / (2 * math.pi * times)) \
            / (2 * math.log(theta))

    low = max(math.floor(turning(how["beta_fast"])), 0)
    high = min(math.ceil(turning(how["beta_slow"])), d - 1)
    ramp = np.clip((pair - low) / (high - low), 0, 1)
    return interpolated * ramp + extrapolated * (1 - ramp), \
        how["attention_factor"]


def rope(x, inv_freq, amplitude):
    """Rotate the pairs ``(i, i + d/2)`` of the last axis of ``x`` (..., S,
    d) by ``position * inv_freq_i`` ("rotate half"), times ``amplitude``."""
    seq, d = x.shape[-2], x.shape[-1]
    angle = np.arange(seq, dtype=np.float64)[:, None] * inv_freq[None, :]
    cos = jnp.asarray(np.cos(angle) * amplitude, x.dtype)
    sin = jnp.asarray(np.sin(angle) * amplitude, x.dtype)
    first, second = x[..., :d // 2], x[..., d // 2:]
    return jnp.concatenate([first * cos - second * sin,
                            second * cos + first * sin], axis=-1)


def _qkv(p, pre, x, arch, kind):
    """-> q (b, heads, s, d), k and v (b, heads, s, d) with the key heads
    repeated."""
    heads, kv = arch["num_attention_heads"], arch["num_key_value_heads"]
    d, eps = arch["head_dim"], arch["norm_eps"]
    b, s, _ = x.shape
    inv_freq, amplitude = rotary(arch, kind)

    def split(weight, n):
        return (x @ weight.T).reshape(b, s, n, d).transpose(0, 2, 1, 3)

    q = rope(rms_norm(split(p[pre + "q_weight"], heads),
                      p[pre + "qnorm_weight"], eps), inv_freq, amplitude)
    k = rope(rms_norm(split(p[pre + "k_weight"], kv),
                      p[pre + "knorm_weight"], eps), inv_freq, amplitude)
    v = split(p[pre + "v_weight"], kv)
    return q, jnp.repeat(k, heads // kv, axis=1), \
        jnp.repeat(v, heads // kv, axis=1)


def window_of(arch, kind):
    return arch["sliding_window"] if kind == "sliding_attention" else None


def _attend(q, k, v, first, first_key, window):
    """Softmax attention of the queries at positions ``first ..`` over the
    keys at positions ``first_key ..`` (a key before position 0 is
    padding): causal, and with ``window`` inside it: (b, heads, queries,
    d)."""
    d = q.shape[-1]
    scores = jnp.einsum("bhqd,bhkd->bhqk", q, k) / math.sqrt(d)
    ahead = (first + jnp.arange(q.shape[2]))[:, None] \
        - (first_key + jnp.arange(k.shape[2]))[None, :]
    seen = (ahead >= 0) & (first_key + jnp.arange(k.shape[2]) >= 0)[None, :]
    if window is not None:
        seen = seen & (ahead < window)
    scores = jnp.where(seen, scores, -jnp.inf)
    probs = jax.nn.softmax(scores.astype(jnp.float32), axis=-1)
    return jnp.einsum("bhqk,bhkd->bhqd", probs.astype(v.dtype), v)


def attention(p, pre, x, arch, kind):
    q, k, v = _qkv(p, pre, x, arch, kind)
    return _heads_out(p, pre, _attend(q, k, v, 0, 0, window_of(arch, kind)))


def attention_in_blocks(p, pre, x, arch, kind, block):
    """``attention`` with the scores of ``block`` queries at a time; a
    window layer's against the ``block + window - 1`` keys its band can
    reach."""
    q, k, v = _qkv(p, pre, x, arch, kind)
    b, heads, s, d = q.shape
    window = window_of(arch, kind)
    behind = 0 if window is None else min(window - 1, s)
    if window is not None:      # keys before position 0: padding
        k, v = (jnp.pad(a, ((0, 0), (0, 0), (behind, 0), (0, 0)))
                for a in (k, v))

    @jax.checkpoint
    def one(start):
        rows = jax.lax.dynamic_slice_in_dim(q, start, block, axis=2)
        if window is None:
            return _attend(rows, k, v, start, 0, None)
        keys, values = (jax.lax.dynamic_slice_in_dim(
            a, start, block + behind, axis=2) for a in (k, v))
        return _attend(rows, keys, values, start, start - behind, window)

    o = jax.lax.map(one, jnp.arange(0, s, block))
    o = o.transpose(1, 2, 0, 3, 4).reshape(b, heads, s, d)
    return _heads_out(p, pre, o)


def route(p, pre, x, arch):
    """-> (expert ids (..., k), weights (..., k), margin (...), the
    router's balancing term): the selection over all of the router's
    outputs; ``margin`` is the distance between the last selected and the
    first rejected probability."""
    k, experts = arch["num_experts_per_tok"], arch["router_outputs"]
    probs = jax.nn.softmax(
        (x @ p[pre + "router_weight"].T).astype(jnp.float32), axis=-1)
    chosen, ids = jax.lax.top_k(probs, k + 1)
    margin = chosen[..., k - 1] - chosen[..., k]
    ids, picked = ids[..., :k], chosen[..., :k]
    weights = picked / (jnp.sum(picked, axis=-1, keepdims=True)
                        + arch["route_epsilon"])
    if arch.get("router_trained_by", "loss") == "balance":
        weights = jax.lax.stop_gradient(weights)
    share = jax.lax.stop_gradient(jnp.mean(
        jax.nn.one_hot(ids.reshape(-1), experts, dtype=jnp.float32), axis=0))
    term = experts * jnp.sum(share * jnp.mean(probs.reshape(-1, experts),
                                              axis=0))
    return ids, weights.astype(x.dtype), margin, term


def moe(p, pre, x, arch, routes):
    """The held experts' part of the routed sum; the block's ``(ids,
    margin, balancing term)`` joins ``routes``."""
    ids, weights, margin, term = route(p, pre, x, arch)
    routes.append((ids, margin, term))
    first, held = arch["held_experts"]
    y = jnp.zeros_like(x)
    for j in range(held):   # a dense mask over the tokens, expert by expert
        w = jnp.sum(jnp.where(ids == first + j, weights, 0.0), axis=-1)
        y = y + w[..., None] * (
            (jax.nn.silu(x @ p[pre + "experts_gate_weight"][j])
             * (x @ p[pre + "experts_up_weight"][j]))
            @ p[pre + "experts_down_weight"][j])
    return y


def block(p, i, h, arch, routes):
    pre, eps = "l%d_" % i, arch["norm_eps"]
    h = h + attention(p, pre + "attn_", rms_norm(h, p[pre + "ln1_weight"],
                                                  eps), arch,
                      arch["layer_types"][i])
    return h + moe(p, pre + "moe_", rms_norm(h, p[pre + "ln2_weight"], eps),
                   arch, routes)


def hidden_states(p, tokens, arch):
    """-> (the stream after the blocks, not yet normed, (b, s, hidden);
    every block's ``(expert ids (b, s, k), routing margins (b, s), balancing
    term)``)."""
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


def balancing_loss(routes, arch):
    return arch["router_aux_loss_coef"] * sum(term for _, _, term in routes)


def loss(p, x, tokens, arch):
    """``tokens``: the labels' source, the rows of ``x`` themselves."""
    h, routes = hidden_states(p, _ids(x), arch)
    return cross_entropy(_head(p, h, arch), jnp.roll(tokens, -1, axis=1),
                         tokens.shape[1] - 1) + balancing_loss(routes, arch)


def after_step(p, tokens, arch):
    """What a training step on ``tokens`` leaves in the routed blocks'
    counters -> {``l<i>_moe_held_pairs``: (1,) the (token, expert) pairs
    that fell on the held experts, ``l<i>_moe_balance_term``: (1,) the
    router's balancing term, unweighted}."""
    first, held = arch["held_experts"]
    state = {}
    for i, (ids, _, term) in enumerate(hidden_states(p, tokens, arch)[1]):
        state["l%d_moe_held_pairs" % i] = jnp.sum(
            (ids >= first) & (ids < first + held)).astype(
                jnp.float32).reshape(1)
        state["l%d_moe_balance_term" % i] = term.reshape(1)
    return state


def prefix_loss(p, tokens, arch, chunk=LOSS_CHUNK):
    """The next-token loss read from the embedding's stream (there is no
    layer before the first router) -> (loss, that stream after the final
    norm).  ``cross_entropy`` over ``chunk`` positions at a time, so that a
    row's 16,384 x 24,576 logits never stand whole."""
    hidden = rms_norm(p["embed_weight"][tokens], p["norm_weight"],
                      arch["norm_eps"])
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


# the layers compared alone at the timed shape: the first of each type
TIMED = {"swa_timed.": "sliding_attention", "gqa_timed.": "full_attention"}


def timed_layer(arch, kind):
    return list(arch["layer_types"]).index(kind)


def timed_attention(p, tokens, arch, kind, block=QUERY_BLOCK):
    """The first attention layer of type ``kind`` on the embedding's stream
    (a constant) -> (half the mean square of its output, the output (b, s,
    hidden))."""
    pre = "l%d_" % timed_layer(arch, kind)
    x = rms_norm(jax.lax.stop_gradient(p["embed_weight"][tokens]),
                 p[pre + "ln1_weight"], arch["norm_eps"])
    out = attention_in_blocks(p, pre + "attn_", x, arch, kind,
                              min(block, tokens.shape[1]))
    return 0.5 * jnp.mean(jnp.square(out.astype(jnp.float32))), out


def dropout_shapes(arch, batch):
    return []


def not_trained(name):
    return name.endswith(("held_pairs", "max_load", "balance_term"))


def _put(named_params, where, dtype):
    """The parameters on ``where``: float32 as handed over, then cast to
    ``dtype`` (the counters stay float32)."""
    return {n: jax.device_put(np.asarray(v, np.float32), where).astype(
        jnp.float32 if not_trained(n) else dtype) for n, v in named_params}


def timed_device():
    """Where the rows at the timed shape are computed: the run's first
    device.  In a run of the cell that is the chip (``run.py`` calls the
    reference after the window and after it has read the device's memory,
    and a run has a time limit: one row of 16,384 tokens through the head
    and two attention layers, forward and backward in float32, is ~25 T
    operations an evaluation, two to three minutes of the host's CPU and
    seconds of the chip at highest matmul precision); in the tests it is
    the CPU."""
    return jax.devices()[0]


def timed_programs(arch):
    """-> (what lies before the first router, {layer type: that attention
    layer alone}): the value and gradient of one row of the timed shape,
    jitted; each runs where its arguments are."""
    prefix_row = jax.jit(jax.value_and_grad(
        lambda p, row: prefix_loss(p, row, arch), has_aux=True))
    attention_row = {kind: jax.jit(jax.value_and_grad(
        lambda layer, rest, row, kind=kind: timed_attention(
            dict(layer, **rest), row, arch, kind), has_aux=True))
        for kind in TIMED.values()}
    return prefix_row, attention_row


def timed_parameters(arch):
    """The names of the parameters that ``timed_programs`` read."""
    names = {"embed_weight", "norm_weight", "head_weight"}
    for kind in TIMED.values():
        pre = "l%d_" % timed_layer(arch, kind)
        names |= {pre + "ln1_weight"} | {
            pre + "attn_" + n + "_weight"
            for n in ("q", "k", "v", "o", "qnorm", "knorm")}
    return names


def outputs(arch, variants, y, dropout_masks=(), dtype="float32"):
    """[(logits, training loss, {name: gradient})] for each ``(named_params,
    x)`` of ``variants``: the rows of ``x`` through the whole model on the
    host's CPU device.  ``y`` (rows, seq) token ids: the rows of the
    comparisons at the timed shape (module docstring), computed row by row
    (each is a mean over rows) on ``timed_device()``, which works on them
    while the host computes the rest.  ``dtype``: float32, the reference;
    ``"bfloat16"`` computes the same in the nearest precision below it
    (what the tolerances must refuse)."""
    where, at = _cpu(), timed_device()
    dtype = jnp.dtype(dtype)

    @jax.jit
    def whole(p, x, tokens):
        trained = {n: v for n, v in p.items() if not not_trained(n)}
        value, grads = jax.value_and_grad(
            lambda t: loss(t, x, tokens, arch))(trained)
        grads.update({"after_step." + n: v
                      for n, v in after_step(p, tokens, arch).items()})
        return forward(p, x, arch), value, grads

    prefix_row, attention_row = timed_programs(arch)
    needed = timed_parameters(arch)

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

    def at_the_timed_shape(named_params, rows):
        p = _put([(n, v) for n, v in named_params if n in needed], at, dtype)
        compared = [("dense_prefix.", "hidden", lambda row: prefix_row(
            {n: p[n] for n in ("embed_weight", "norm_weight",
                               "head_weight")}, row))]
        for name, kind in TIMED.items():
            pre = "l%d_" % timed_layer(arch, kind)
            layer = {n: v for n, v in p.items()
                     if n.startswith(pre + "attn_")}
            rest = {n: p[n] for n in ("embed_weight", pre + "ln1_weight")}
            compared.append((name, "out", lambda row, kind=kind, layer=layer,
                             rest=rest: attention_row[kind](layer, rest,
                                                            row)))
        grads = {}
        for name, stream, one_row in compared:
            grads[name + stream], mean = over_rows(one_row, rows)
            grads.update({name + n: v for n, v in mean.items()})
        return grads

    rows = jax.device_put(np.asarray(y, np.int32), at)
    out = []
    with jax.default_matmul_precision("highest"):
        for named_params, x in variants:
            timed = at_the_timed_shape(named_params, rows)
            p = _put(named_params, where, dtype)
            x = jax.device_put(np.asarray(x, np.float32), where)
            logits, value, grads = whole(p, x, _ids(x))
            # to the host: the device keeps one evaluation's results at a time
            out.append((logits, value, dict(grads, **jax.device_get(timed))))
    return out


def routing_margins(arch, named_params, x):
    """(rows, routed blocks x positions) float32: for every token of every
    block, how far the last expert selected lies above the first one
    rejected.  A row's routes do not depend on the rows beside it (the
    balancing term does, and no route reads it)."""
    where = _cpu()

    @jax.jit
    def run(p, tokens):
        return jnp.concatenate(
            [margin for _, margin, _ in hidden_states(p, _ids(tokens),
                                                      arch)[1]], axis=1)

    with jax.default_matmul_precision("highest"):
        return np.asarray(run(
            _put(named_params, where, jnp.float32),
            jax.device_put(np.asarray(x, np.float32), where)))


# ------------------------------------------------------------- operations


def band_pairs(seq, window=None):
    """The (query, key) pairs a query head of one row attends: the causal
    half of the square, or with a window ``S W - W (W - 1) / 2``."""
    window = seq if window is None else min(window, seq)
    return seq * window - window * (window - 1) / 2.0


def forward_flops_per_token(arch, seq, pairs=None, whole_square=False):
    """2 x the multiply-adds of one token's forward pass on this chip, from
    the same walk over the layers as ``hidden_states``.  ``pairs``: the
    (token, held expert) products a token costs in a routed layer; by
    default what the router sends here on average, ``num_experts_per_tok``
    x held / router outputs (this file's dense mask computes every held
    expert on every token: ``pairs`` = held).  Attention is charged the
    pairs of its band (``band_pairs``); ``whole_square``: the sequence's
    whole square, which ``attention`` here computes and masks."""
    hid, heads, d = arch["hidden_size"], arch["num_attention_heads"], \
        arch["head_dim"]
    projections = 2 * hid * heads * d \
        + 2 * hid * arch["num_key_value_heads"] * d
    if pairs is None:
        pairs = arch["num_experts_per_tok"] * arch["held_experts"][1] \
            / float(arch["router_outputs"])
    routed = hid * arch["router_outputs"] \
        + 3 * hid * arch["moe_intermediate_size"] * pairs
    macs = 0.0
    for kind in arch["layer_types"]:
        seen = seq if whole_square else \
            band_pairs(seq, window_of(arch, kind)) / seq
        macs += projections + heads * 2 * d * seen + routed
    macs += hid * arch["vocab_size"]        # the head
    return 2.0 * macs


def flops_per_sample(arch, input_shape):
    """Operations one training row requires of this chip: forward x 3
    (one product for the input gradient and one for the weight gradient of
    every matrix product), recomputation not counted."""
    seq = int(input_shape[0])
    return 3.0 * forward_flops_per_token(arch, seq) * seq
