"""Xing4.0-29B-A4B (``model_type`` ``xing4_0``), plain reference: forward,
loss and gradients in float32 ``jax.numpy``, written from the equations
below: the DeepSeek-V3 block (arXiv:2412.19437: latent attention, sigmoid
router with a selection bias, one shared expert) with yarn rotary scaling,
inside a residual stream of ``hc_mult`` streams mixed by manifold-
constrained hyper-connections (mHC, DeepSeek-AI 2025, on Hyper-Connections,
Zhu et al., arXiv:2409.19606).  Nothing here is taken from the program:
every layer is written out in this file.

``arch`` (sizes under the names of the model's ``config.json``):
``vocab_size``, ``hidden_size``, ``num_hidden_layers``,
``first_k_dense_replace``, ``intermediate_size``, ``moe_intermediate_size``,
``router_outputs`` (the published ``n_routed_experts``: the router's
width), ``held_experts`` ``[first, count]`` (the consecutive expert ids this
chip holds; all of them for the uncut layer), ``num_experts_per_tok``,
``routed_scaling_factor``, ``bias_update_rate``, ``num_attention_heads``,
``q_lora_rank``, ``kv_lora_rank``, ``qk_nope_head_dim``,
``qk_rope_head_dim``, ``v_head_dim``, ``rope_theta``, ``rope_scaling``
(DeepSeek's yarn: ``factor``, ``original_max_position_embeddings``,
``beta_fast``, ``beta_slow``, ``mscale``, ``mscale_all_dim``),
``rms_norm_eps``, ``hc_mult``, ``hc_sinkhorn_iters``, ``hc_eps``,
``mhc_h_res_clamp_min``, ``mhc_h_res_clamp_max``.  No MTP module, no bias
anywhere, the head untied.

Parameters are looked up by name (a dense weight is ``(out, in)``; the held
experts' weights are stacked ``(held, in, out)``):

    embed_weight, head_weight, norm_weight
    l<i>_hc_attn_{phi,bias,alpha}, l<i>_hc_ffn_{phi,bias,alpha}
    l<i>_ln1_weight, l<i>_ln2_weight
    l<i>_attn_{qa,qb,kva,kvb,o}_weight, l<i>_attn_{qnorm,kvnorm}_weight
    l<i>_ffn_{gate,up,down}_weight                       (dense block)
    l<i>_moe_router_weight, l<i>_moe_router_bias         (bias: not trained)
    l<i>_moe_experts_{gate,up,down}_weight, l<i>_moe_shared_{gate,up,down}_weight

Equations.  A token's stream is ``X (n, C)``, ``n = hc_mult``.  Embedding:
``X_0,i = Emb(t)`` for every ``i``.  Each sublayer ``f`` (a block's
``MLA(RMSNorm_ln1(.))``, then its ``FFN(RMSNorm_ln2(.))``) with its own
``phi (n C, 2n + n^2)``, ``b (2n + n^2)``, ``alpha (3)``: ``x = vec(X)``,
``r = x / sqrt(mean(x^2) + hc_eps)`` over the ``n C`` lanes; ``[p | q | R]
= [alpha_0 (r phi)_pre | alpha_1 (r phi)_post | alpha_2 (r phi)_res] + b``;
``h_pre = sigmoid(p)``, ``h_post = 2 sigmoid(q)``; ``H_res =
Sinkhorn(exp(clamp(mat(R), min, max)))``, ``mat`` row-major, Sinkhorn
``hc_sinkhorn_iters`` times the rows divided by their sums plus ``hc_eps``,
then the columns; ``u = sum_i h_pre[i] X_i``, ``y = f(u)``, ``X'_i = sum_j
H_res[i, j] X_j + h_post[i] y``.  Output: ``RMSNorm_final(sum_i X_L,i)``,
then the head.  MLA in its training form (no weight absorption): ``c_q =
RMSNorm(x W_qa)``, ``q = c_q W_qb`` -> heads x (nope + rope); ``[c_kv; k_r]
= x W_kva``, ``c_kv <- RMSNorm(c_kv)``, ``[k_nope; v] = c_kv W_kvb``; yarn
rotation of each head's ``q_rope`` and of the one ``k_r`` all heads share,
adjacent pairs (``rope_interleave``), frequencies ``theta^(-2i/d)`` blended
towards ``/ factor`` over yarn's ramp between the pairs that turn
``beta_fast`` and ``beta_slow`` times over the original length, ``cos`` and
``sin`` times ``m(mscale) / m(mscale_all_dim)``; scores over ``[nope;
rope]`` times ``(nope + rope)^-1/2 m(mscale_all_dim)^2``, ``m(a) = 0.1 a
ln(factor) + 1``; causal softmax; ``o = P v`` -> ``W_o``.  Feed-forward: the
leading dense layers' gated SiLU of ``intermediate_size``; a routed layer's
``s = sigmoid(x W_r)`` over all the router's outputs, the
``num_experts_per_tok`` largest of ``s + b`` selected, weights ``s_e / (sum
of the selected s + 1e-20) x routed_scaling_factor``, ``y = FFN_shared(x) +
sum_{e selected and held} w_e FFN_e(x)``, a dense mask over the held
experts: selection and normalisation run over all experts, what the absent
ones would add is left out, the shared expert counted once.  Loss: the mean
over rows and valid positions of ``CE(logits_i, t_{i+1})``.  The selection
bias takes no gradient; a training step moves it by the balancing rule of
DeepSeek-V3 section 2.1.2 (``after_step``).

Departures from the published description, noted.  The checkpoint computes
in bfloat16; here everything is float32.  The rotation takes adjacent
pairs; the released code moves the pairs to the two halves first, the same
permutation on q and k, so every score is the same.  The rule that trains
the bias is counted over this chip's tokens.  A published loop over the
experts that were hit gives the dense mask's sum.  A row is one document.

``check.py`` hands ``x`` over as float32, moved by one ulp: ``rint`` gives
the ids back (ids < 2^24 survive).

Comparisons (``outputs``).  ``x``: short rows through the whole model,
chosen free of routing margins (``routing_margins``), on the host's CPU.
``y``: rows of the timed step's own shape through what lies before the
first router (``dense_prefix``: the embedding, the expansion, the dense
layer whole with both hyper-connections, the collapse, the final norm, the
head and the loss over chunks of positions), its attention over blocks of
queries, on ``timed_device()``: ``dense_prefix.hidden`` and
``dense_prefix.<name>``.
"""

import math
import resource

import jax
import jax.numpy as jnp
import numpy as np

QUERY_BLOCK = 256       # of the timed layer's scores
LOSS_CHUNK = 1024       # positions of the timed row's logits at a time
# the names of the gradients the comparison reads, set by the cell's entry;
# ``outputs`` returns those alone (None: every one).  The whole model's
# float32 gradients, twice, would be 6 GB of the host's memory beside the
# chip's runtime and the weights the check holds.
COMPARED = None


def rms_norm(x, weight, eps):
    f32 = jnp.float32
    x32 = x.astype(f32)
    return (x32 * jax.lax.rsqrt(jnp.mean(x32 * x32, axis=-1, keepdims=True)
                                + eps) * weight.astype(f32)).astype(x.dtype)


def gated_silu(x, gate, up, down):
    """``W_down(silu(W_gate x) * W_up x)``, weights ``(out, in)``."""
    return (jax.nn.silu(x @ gate.T) * (x @ up.T)) @ down.T


# ------------------------------------------------------------- yarn


def _mscale(arch, a):
    factor = float(arch["rope_scaling"]["factor"])
    return 0.1 * a * math.log(factor) + 1.0 if factor > 1 else 1.0


def rotary(arch):
    """-> (the ``rope / 2`` angles a position advances a pair by, the
    amplitude of ``cos`` and ``sin``): DeepSeek-V3's yarn."""
    how, d = arch["rope_scaling"], arch["qk_rope_head_dim"]
    theta = float(arch["rope_theta"])
    pair = np.arange(0, d, 2, dtype=np.float64)
    extra = 1.0 / theta ** (pair / d)
    inter = extra / float(how["factor"])
    original = how["original_max_position_embeddings"]

    def correction(turns):
        return d * math.log(original / (turns * 2 * math.pi)) \
            / (2 * math.log(theta))

    low = max(math.floor(correction(how["beta_fast"])), 0)
    high = min(math.ceil(correction(how["beta_slow"])), d - 1)
    if low == high:
        high += 0.001
    ramp = np.clip((np.arange(d // 2, dtype=np.float64) - low)
                   / (high - low), 0, 1)
    mask = 1.0 - ramp               # 1 where the pair keeps its frequency
    amplitude = _mscale(arch, how.get("mscale", 1)) \
        / _mscale(arch, how.get("mscale_all_dim", 0))
    return inter * (1 - mask) + extra * mask, amplitude


def softmax_scale(arch):
    qk = arch["qk_nope_head_dim"] + arch["qk_rope_head_dim"]
    every = arch["rope_scaling"].get("mscale_all_dim", 0)
    return qk ** -0.5 * (_mscale(arch, every) ** 2 if every else 1.0)


def rope(x, arch):
    """Rotate the adjacent pairs ``(2i, 2i+1)`` of the last axis of ``x``
    (..., S, d) by ``position * inv_freq_i``, times the amplitude."""
    inv_freq, amplitude = rotary(arch)
    angle = np.arange(x.shape[-2], dtype=np.float64)[:, None] \
        * inv_freq[None, :]
    cos = jnp.asarray(np.cos(angle) * amplitude, x.dtype)
    sin = jnp.asarray(np.sin(angle) * amplitude, x.dtype)
    even, odd = x[..., 0::2], x[..., 1::2]
    return jnp.stack([even * cos - odd * sin, even * sin + odd * cos],
                     axis=-1).reshape(x.shape)


# ------------------------------------------------------------- attention


def _qkv(p, pre, x, arch):
    """-> q, k (b, heads, s, nope + rope), v (b, heads, s, v)."""
    heads = arch["num_attention_heads"]
    nope, rot = arch["qk_nope_head_dim"], arch["qk_rope_head_dim"]
    vd, rank, eps = arch["v_head_dim"], arch["kv_lora_rank"], \
        arch["rms_norm_eps"]
    b, s, _ = x.shape
    c_q = rms_norm(x @ p[pre + "qa_weight"].T, p[pre + "qnorm_weight"], eps)
    q = (c_q @ p[pre + "qb_weight"].T).reshape(b, s, heads, nope + rot) \
        .transpose(0, 2, 1, 3)
    kva = x @ p[pre + "kva_weight"].T
    c_kv = rms_norm(kva[..., :rank], p[pre + "kvnorm_weight"], eps)
    k_r = rope(kva[..., rank:], arch)
    kv = (c_kv @ p[pre + "kvb_weight"].T).reshape(b, s, heads, nope + vd) \
        .transpose(0, 2, 1, 3)
    q = jnp.concatenate([q[..., :nope], rope(q[..., nope:], arch)], axis=-1)
    k = jnp.concatenate([kv[..., :nope], jnp.broadcast_to(
        k_r[:, None], (b, heads, s, rot))], axis=-1)
    return q, k, kv[..., nope:]


def _attend(q, k, v, first, scale):
    """Causal softmax attention of the queries at positions ``first ..``
    over all keys: (b, heads, queries, v)."""
    scores = jnp.einsum("bhqd,bhkd->bhqk", q, k) * scale
    seen = (first + jnp.arange(q.shape[2]))[:, None] \
        >= jnp.arange(k.shape[2])[None, :]
    scores = jnp.where(seen, scores, -jnp.inf)
    probs = jax.nn.softmax(scores.astype(jnp.float32), axis=-1)
    return jnp.einsum("bhqk,bhkd->bhqd", probs.astype(v.dtype), v)


def _heads_out(p, pre, o):
    b, heads, s, d = o.shape
    return o.transpose(0, 2, 1, 3).reshape(b, s, heads * d) \
        @ p[pre + "o_weight"].T


def mla(p, pre, x, arch, block=None):
    """Latent attention; with ``block``, the scores of that many queries at
    a time."""
    q, k, v = _qkv(p, pre, x, arch)
    scale = softmax_scale(arch)
    if block is None:
        return _heads_out(p, pre, _attend(q, k, v, 0, scale))
    b, heads, s, _ = q.shape

    @jax.checkpoint
    def one(start):
        rows = jax.lax.dynamic_slice_in_dim(q, start, block, axis=2)
        return _attend(rows, k, v, start, scale)

    o = jax.lax.map(one, jnp.arange(0, s, block))
    o = o.transpose(1, 2, 0, 3, 4).reshape(b, heads, s, v.shape[-1])
    return _heads_out(p, pre, o)


# ------------------------------------------------------------- experts


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
    weights = picked / (jnp.sum(picked, axis=-1, keepdims=True) + 1e-20) \
        * arch["routed_scaling_factor"]
    return ids, weights.astype(x.dtype), margin


def moe(p, pre, x, arch, routes):
    """The held experts' part of the routed sum and the shared expert; the
    block's ``(ids, margin)`` joins ``routes``."""
    ids, weights, margin = route(p, pre, x, arch)
    routes.append((ids, margin))
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


# ------------------------------------------------------ hyper-connections


def sinkhorn(m, arch):
    """``m (..., n, n)``: rows, then columns, divided by their sums plus
    ``hc_eps``, ``hc_sinkhorn_iters`` times."""
    eps = arch["hc_eps"]

    def one(_, m):
        m = m / (jnp.sum(m, axis=-1, keepdims=True) + eps)
        return m / (jnp.sum(m, axis=-2, keepdims=True) + eps)

    return jax.lax.fori_loop(0, arch["hc_sinkhorn_iters"], one, m)


def coefficients(p, pre, X, arch):
    """-> h_pre, h_post (b, s, n), H_res (b, s, n, n) of the
    hyper-connection ``pre`` on the stream ``X (b, s, n, C)``."""
    n, f32 = arch["hc_mult"], jnp.float32
    x = X.reshape(X.shape[:2] + (-1,)).astype(f32)
    r = x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True)
                          + arch["hc_eps"])
    rphi = r @ p[pre + "phi"].astype(f32)
    a, b = p[pre + "alpha"].astype(f32), p[pre + "bias"].astype(f32)
    z = jnp.concatenate([a[0] * rphi[..., :n], a[1] * rphi[..., n:2 * n],
                         a[2] * rphi[..., 2 * n:]], axis=-1) + b
    h_pre = jax.nn.sigmoid(z[..., :n])
    h_post = 2.0 * jax.nn.sigmoid(z[..., n:2 * n])
    res = jnp.exp(jnp.clip(z[..., 2 * n:], arch["mhc_h_res_clamp_min"],
                           arch["mhc_h_res_clamp_max"]))
    return h_pre, h_post, sinkhorn(res.reshape(X.shape[:2] + (n, n)), arch)


def connected(p, pre, X, arch, f):
    """``X' = H_res X + h_post f(u)``, ``u = h_pre . X``; ``f`` the
    sublayer (norm included)."""
    h_pre, h_post, h_res = coefficients(p, pre, X, arch)
    u = jnp.einsum("bsi,bsic->bsc", h_pre.astype(X.dtype), X)
    y = f(u)
    return jnp.einsum("bsij,bsjc->bsic", h_res.astype(X.dtype), X) \
        + h_post.astype(X.dtype)[..., None] * y[:, :, None, :]


def block(p, i, X, arch, routes, queries=None):
    pre, eps = "l%d_" % i, arch["rms_norm_eps"]
    X = connected(p, pre + "hc_attn_", X, arch, lambda u: mla(
        p, pre + "attn_", rms_norm(u, p[pre + "ln1_weight"], eps), arch,
        queries))

    def ffn(u):
        x = rms_norm(u, p[pre + "ln2_weight"], eps)
        if i < arch["first_k_dense_replace"]:
            return gated_silu(x, p[pre + "ffn_gate_weight"],
                              p[pre + "ffn_up_weight"],
                              p[pre + "ffn_down_weight"])
        return moe(p, pre + "moe_", x, arch, routes)

    return connected(p, pre + "hc_ffn_", X, arch, ffn)


def expand(e, arch):
    """``(b, s, C)`` -> ``(b, s, n, C)``: the embedding in every stream."""
    return jnp.broadcast_to(e[:, :, None], e.shape[:2] + (arch["hc_mult"],)
                            + e.shape[2:])


def hidden_states(p, tokens, arch, layers=None, queries=None):
    """-> (the streams' sum after ``layers`` blocks (all by default), not
    yet normed, (b, s, C); every routed block's ``(expert ids (b, s, k),
    routing margins (b, s))``)."""
    routes = []
    X = expand(p["embed_weight"][tokens], arch)
    n = arch["num_hidden_layers"] if layers is None else layers
    for i in range(n):
        X = block(p, i, X, arch, routes, queries)
    return jnp.sum(X, axis=2), routes


def _ids(x):
    return jnp.rint(x).astype(jnp.int32)


def _head(p, h, arch):
    return rms_norm(h, p["norm_weight"], arch["rms_norm_eps"]) \
        @ p["head_weight"].T


def forward(p, x, arch, train=False, dropout_masks=()):
    """Logits (b, s, vocab) for token ``i + 1``.  ``p``: {name: value}.
    Nothing differs between training and inference."""
    return _head(p, hidden_states(p, _ids(x), arch)[0], arch)


def cross_entropy(logits, labels, valid):
    """Mean over the ``valid`` leading positions of each row."""
    logp = jax.nn.log_softmax(logits[:, :valid].astype(jnp.float32), axis=-1)
    picked = jnp.take_along_axis(logp, labels[:, :valid, None], axis=-1)
    return -jnp.mean(picked)


def routed_layers(arch):
    return range(arch["first_k_dense_replace"], arch["num_hidden_layers"])


def state_after(routes, arch):
    """What a training step leaves in the routed blocks' state beside their
    weights, from their ``(ids, margins)`` -> {``l<i>_moe_router_bias``:
    (experts,) the balancing rule's move of the bias in units of
    ``bias_update_rate``, -1 for an expert the batch's tokens chose more
    often than the mean, +1 for one chosen less, 0 at the mean;
    ``l<i>_moe_held_pairs``: (1,) the (token, expert) pairs that fell on
    the held experts}."""
    first, held = arch["held_experts"]
    state = {}
    for i, (ids, _) in zip(routed_layers(arch), routes):
        chosen = jnp.sum(jax.nn.one_hot(ids.reshape(-1),
                                        arch["router_outputs"]), axis=0)
        state["l%d_moe_router_bias" % i] = -jnp.sign(
            chosen - jnp.mean(chosen))
        state["l%d_moe_held_pairs" % i] = jnp.sum(
            (ids >= first) & (ids < first + held)).astype(
                jnp.float32).reshape(1)
    return state


def after_step(p, tokens, arch):
    return state_after(hidden_states(p, tokens, arch)[1], arch)


# --------------------------------------------------- at the timed shape


def in_dense_prefix(name, arch):
    return name.startswith(("embed_", "norm_", "head_") + tuple(
        "l%d_" % i for i in range(arch["first_k_dense_replace"])))


def prefix_loss(p, tokens, arch, queries=QUERY_BLOCK, chunk=LOSS_CHUNK):
    """The next-token loss read from the stream of the layers before the
    first router -> (loss, that stream after the collapse and the final
    norm); attention over blocks of ``queries``, the logits ``chunk``
    positions at a time, so that a row's scores and logits never stand
    whole."""
    rows, seq = tokens.shape
    h, _ = hidden_states(p, tokens, arch, arch["first_k_dense_replace"],
                         min(queries, seq))
    hidden = rms_norm(h, p["norm_weight"], arch["rms_norm_eps"])
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


def dropout_shapes(arch, batch):
    return []


def _cpu():
    try:
        return jax.devices("cpu")[0]
    except RuntimeError:        # jax was started without its CPU backend
        return jax.devices()[0]


def timed_device():
    """Where the rows at the timed shape are computed: the run's first
    device (in a run of the cell the chip, which the window has finished
    with: the dense layer whole and the head at 1 x 4,096 in float32 at
    highest precision are some tens of T operations; in the tests the
    CPU)."""
    return jax.devices()[0]


def not_trained(name):
    return name.endswith(("router_bias", "held_pairs", "max_load"))


def _put(named_params, where, dtype):
    """The parameters on ``where``: float32 as handed over, then cast to
    ``dtype`` (the router's bias stays float32: it is added to float32
    scores)."""
    return {n: jax.device_put(np.asarray(v, np.float32), where).astype(
        jnp.float32 if not_trained(n) else dtype) for n, v in named_params}


def peak_host_gb():
    """The process's peak resident memory so far, GB (``ru_maxrss``, KiB
    on Linux)."""
    return resource.getrusage(resource.RUSAGE_SELF).ru_maxrss * 1024 / 1e9


def outputs(arch, variants, y, dropout_masks=(), dtype="float32"):
    """[(logits, training loss, {name: gradient})] for each ``(named_params,
    x)`` of ``variants``: the rows of ``x`` through the whole model on the
    host's CPU device.  ``y`` (rows, seq) token ids: the rows of the
    comparison at the timed shape (module docstring), computed row by row
    (the loss is a mean over rows) on ``timed_device()``.  ``dtype``:
    float32, the reference; ``"bfloat16"`` computes the same in the nearest
    precision below it (what the tolerances must refuse).  Prints the
    process's peak host memory at its end."""
    where, at = _cpu(), timed_device()
    dtype = jnp.dtype(dtype)

    def compared(grads, prefix=""):
        return {n: v for n, v in grads.items()
                if COMPARED is None or prefix + n in COMPARED}

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
        return logits, value, compared(grads)

    prefix_row = jax.jit(jax.value_and_grad(
        lambda p, row: prefix_loss(p, row, arch), has_aux=True))

    def at_the_timed_shape(named_params, rows):
        """The rows one at a time, the gradients' mean over them."""
        p = _put([(n, v) for n, v in named_params
                  if in_dense_prefix(n, arch)], at, dtype)
        hidden, total = [], None
        for row in rows:
            (_, h), g = prefix_row(p, row[None])
            hidden.append(h)
            total = g if total is None else jax.tree_util.tree_map(
                jnp.add, total, g)
        grads = {"dense_prefix." + n: v / len(rows)
                 for n, v in compared(total, "dense_prefix.").items()}
        grads["dense_prefix.hidden"] = jnp.concatenate(hidden)
        return grads

    rows = jax.device_put(np.asarray(y, np.int32), at)
    out = []
    with jax.default_matmul_precision("highest"):
        for named_params, x in variants:
            timed = jax.device_get(at_the_timed_shape(named_params, rows))
            p = _put(named_params, where, dtype)
            x = jax.device_put(np.asarray(x, np.float32), where)
            logits, value, grads = whole(p, _ids(x))
            out.append((logits, value, dict(grads, **timed)))
            del p, x, grads     # the next variant's weights take their place
    print("reference done: peak host memory of the run so far %.2f GB"
          % peak_host_gb(), flush=True)
    return out


def routing_margins(arch, named_params, x):
    """(rows, routed blocks x positions) float32: for every token of every
    routed block, how far the last expert selected lies above the first one
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


def hc_macs_per_token(arch):
    """Multiply-adds of one hyper-connection's products for one token: the
    projection ``r phi`` (``n C x (2n + n^2)``), the pre mix ``h_pre . X``
    (``n C``) and the post mix's ``H_res X`` (``n^2 C``); ``h_post y``, a
    scaling, is not counted."""
    n, c = arch["hc_mult"], arch["hidden_size"]
    return n * c * (2 * n + n * n) + n * c + n * n * c


def forward_flops_per_token(arch, seq, pairs=None, square_share=0.5):
    """2 x the multiply-adds of one token's forward pass on this chip, from
    the same walk over the layers as ``hidden_states``.  ``pairs``: the
    (token, held expert) products a token costs in a routed layer; by
    default what the router sends here on average, ``num_experts_per_tok``
    x held / router outputs (this file's dense mask computes every held
    expert on every token: ``pairs`` = held).  ``square_share``: the part
    of the sequence's square that attention computes: half under the
    causal mask (this file computes it whole: 1).  Every sublayer's
    hyper-connection counts ``hc_macs_per_token``; the shared expert
    counts once a token."""
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
        macs += attn + 2 * hc_macs_per_token(arch) \
            + (3 * hid * arch["intermediate_size"] if dense else routed)
    macs += hid * arch["vocab_size"]        # the head
    return 2.0 * macs


def flops_per_sample(arch, input_shape):
    """Operations one training row requires of this chip: forward x 3
    (one product for the input gradient and one for the weight gradient of
    every matrix product), recomputation not counted."""
    seq = int(input_shape[0])
    return 3.0 * forward_flops_per_token(arch, seq) * seq
