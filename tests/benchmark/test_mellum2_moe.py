"""The ``mellum2_12b_ep4`` configuration and its cell on the CPU: the file
keeps the published widths, the flops function counts what the reference's
own layer walk multiplies, the system agrees with the reference through the
cell's entry and ``check.against_reference`` at a tiny size with a window
that bites and rows past yarn's original length (and wrong computations,
and the reference in bfloat16, do not), the four shares of a routed layer
add up to the uncut layer, the new kernels' cost functions count the band's
pairs, the readers find their scopes on a hand-made trace, and one whole
run prints a result."""

import copy
import json
import os
import shutil
import sys
import types

import numpy as np
import pytest

REPO = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, REPO)

from benchmark import run  # noqa: E402
from benchmark.harness import (check, device, gqa_attention_cost,  # noqa: E402
                               manifest, swa_attention_cost, trace)

CELL = "mellum2_moe_train_seq16k"

_PERIOD = ["sliding_attention"] * 3 + ["full_attention"]
# the catalog row's ``config`` (its source: the cell's ``source`` URL)
PUBLISHED = {
    "attention_bias": False, "head_dim": 128, "hidden_act": "silu",
    "hidden_size": 2304, "intermediate_size": 7168,
    "layer_types": _PERIOD * 7, "mlp_layer_types": ["sparse"] * 28,
    "max_position_embeddings": 131072, "max_window_layers": 0,
    "model_type": "mellum", "moe_intermediate_size": 896,
    "norm_topk_prob": True, "num_attention_heads": 32, "num_experts": 64,
    "num_experts_per_tok": 8, "num_hidden_layers": 28,
    "num_key_value_heads": 4, "rms_norm_eps": 1e-06,
    "rope_parameters": {
        "full_attention": {
            "rope_type": "yarn", "rope_theta": 500000, "factor": 16,
            "original_max_position_embeddings": 8192, "beta_fast": 32,
            "beta_slow": 1, "attention_factor": 1.2772588722239782},
        "sliding_attention": {"rope_type": "default",
                              "rope_theta": 500000}},
    "sliding_window": 1024, "tie_word_embeddings": False,
    "vocab_size": 98304, "use_sliding_window": True}

TINY = dict(
    vocab_size=97, hidden_size=32,
    layer_types=["sliding_attention", "full_attention", "sliding_attention"],
    moe_intermediate_size=16, num_experts_per_tok=2, num_attention_heads=4,
    num_key_value_heads=2, head_dim=16, router_outputs=8,
    held_experts=[2, 4], sliding_window=8,
    rope_parameters={
        "full_attention": {
            "rope_type": "yarn", "rope_theta": 10000.0, "factor": 4,
            "original_max_position_embeddings": 32, "beta_fast": 2,
            "beta_slow": 0.25, "attention_factor": 0.1 * np.log(4.0) + 1.0},
        "sliding_attention": {"rope_type": "default",
                              "rope_theta": 10000.0}})


def tiny(config):
    """The cell's configuration at a size for the CPU: every key of the
    real file, the sizes replaced; rows of 64 with a window of 8, so the
    window bites and half the positions lie past yarn's original 32 (its
    ramp runs over the pairs 0 .. 3, from 0.81 .. 2.62 before rounding)."""
    config = copy.deepcopy(config)
    config["architecture"].update(TINY)
    config["factory_kwargs"].update(weight_std=0.3)
    config["input"]["shape"] = [64]
    real = {"l3_": "l1_", "l2_moe": "l2_moe", "l1_moe": "l1_moe"}
    names = []
    for n in config["check_gradients"]:
        for old, new in real.items():
            if old in n:
                n = n.replace(old, new)
                break
        names.append(n)
    config.update(check_seq_len=64, check_batch=2, check_candidates=24,
                  check_gradients=names)
    config["training"]["lr"] = 1e-3
    return config


@pytest.fixture(scope="module")
def cell():
    return manifest.Manifest(REPO).cell(CELL)


def test_the_file_keeps_every_published_number_but_the_reduced(cell):
    cfg, entry = cell.config, cell.manifest.named("configs",
                                                  cell.config_name)
    assert sorted(entry["reduced"]) == sorted(cfg["reduced"]) == [
        "layer_types", "mlp_layer_types", "num_experts",
        "num_hidden_layers", "vocab_size"]
    assert entry["source"] in cfg["source"]
    for key, value in PUBLISHED.items():
        if key not in cfg["reduced"]:
            assert cfg[key] == value, key
    assert cfg["published"] == {k: PUBLISHED[k] for k in cfg["reduced"]}
    # the share: 16 of 64 experts, a quarter of the vocabulary, published
    # layers 0-3: one whole period, every layer sparse
    assert cfg["num_experts"] * 4 == PUBLISHED["num_experts"]
    assert cfg["vocab_size"] * 4 == PUBLISHED["vocab_size"]
    assert cfg["layer_types"] == PUBLISHED["layer_types"][:4] == _PERIOD
    assert cfg["mlp_layer_types"] == ["sparse"] * 4
    assert cfg["num_hidden_layers"] == len(cfg["layer_types"]) == 4
    assert "4 chips share each layer" in cfg["deployment"]
    arch = cfg["architecture"]
    for key, value in arch.items():
        if key in cfg:
            assert value == cfg[key], key
    assert not set(cfg["factory_kwargs"]) & set(arch)
    assert arch["router_outputs"] == PUBLISHED["num_experts"]
    assert arch["held_experts"] == [0, cfg["num_experts"]]
    assert arch["norm_eps"] == cfg["rms_norm_eps"]
    assert arch["tie_embedding"] == cfg["tie_word_embeddings"]
    assert arch["scoring_func"] == "softmax"
    assert arch["router_aux_loss_coef"] == 0.001
    assert arch["router_trained_by"] == "balance"
    assert len(cfg["assumed"]) >= 10
    assert cfg["input"]["shape"] == [16384]
    assert cell.traffic["global_batch"] == 1 and cell.chips == 1
    assert cell.traffic["entry"] == "gluon_window_lm_train_step"
    assert 6 <= cell.traffic["warmup_groups"] <= 15     # ISSUE 34, 7 (b)
    reported = {m["name"] for m in cell.per_layer}
    assert {"attention.swa_gqa_ms_per_step", "kernels.swa_attention_roofline",
            "kernels.mellum_full_attention_roofline",
            "moe.mellum_routed_ms_per_step",
            "moe.mellum_max_expert_load_ratio", "lm_head.mellum_ms_per_step",
            "step.device_ms", "step.mfu", "device.idle_share"} <= reported
    assert not reported & {"kernels.gqa_attention_roofline",
                           "attention.gqa_ms_per_step",
                           "moe.lfm2_routed_ms_per_step"}


def _net(arch, **kwargs):
    from mxnet_tpu.gluon.nn import LayerTypesMoELM

    return LayerTypesMoELM(**dict(arch, **kwargs))


def _shapes(arch):
    """{parameter name: shape}, as the program names them."""
    net = _net(arch)
    cut = len(net.prefix)
    return {n[cut:]: p.shape for n, p in net.collect_params().items()}


def test_the_built_model_holds_the_parameters_the_file_states(cell):
    shapes = _shapes(cell.config["architecture"])
    assert shapes["head_weight"] == shapes["embed_weight"] == (24576, 2304)
    assert not any("router_bias" in n or "ffn_" in n for n in shapes)
    counters = ("held_pairs", "max_load", "balance_term")
    assert sum(n.endswith(counters) for n in shapes) == 3 * 4
    held = sum(int(np.prod(s)) for n, s in shapes.items()
               if not n.endswith(counters))
    attention = 2 * 2304 * 4096 + 2 * 2304 * 512 + 2 * 128
    layer = attention + 64 * 2304 + 16 * 3 * 2304 * 896 + 2 * 2304
    by_hand = 4 * layer + 2 * 24576 * 2304 + 2304
    assert held == by_hand
    assert "%.1f M" % (held / 1e6) == "595.2 M"
    assert "595.2 M" in cell.config["deployment"]


def _dot_macs(jaxpr):
    """Multiply-adds of every ``dot_general`` of a jaxpr, nested ones too."""
    macs = 0
    for eqn in jaxpr.eqns:
        if eqn.primitive.name == "dot_general":
            contract = eqn.params["dimension_numbers"][0][0]
            lhs = eqn.invars[0].aval.shape
            macs += int(np.prod(eqn.outvars[0].aval.shape)) * int(
                np.prod([lhs[i] for i in contract]))
        for value in eqn.params.values():
            inner = getattr(value, "jaxpr", None)
            if inner is not None:
                macs += _dot_macs(getattr(inner, "jaxpr", inner))
    return macs


def test_the_flops_function_is_the_references_own_layer_walk(cell):
    """The products the reference's forward makes at a tiny size, counted
    from its jaxpr, are what the formula gives for the same walk (the dense
    mask runs every held expert on every token, attention the whole
    square); the cell's number differs in those two terms only, and is the
    issue's hand count: the band's pairs in a window layer, the causal half
    in the full one."""
    import jax

    reference = cell.reference()
    arch = tiny(cell.config)["architecture"]
    rows, seq = 2, 16
    shapes = {n: jax.ShapeDtypeStruct(s, np.float32)
              for n, s in _shapes(arch).items()}
    jaxpr = jax.make_jaxpr(lambda p, x: reference.forward(p, x, arch))(
        shapes, jax.ShapeDtypeStruct((rows, seq), np.float32))
    walked = 2.0 * _dot_macs(jaxpr.jaxpr)
    formula = reference.forward_flops_per_token(
        arch, seq, pairs=arch["held_experts"][1], whole_square=True)
    assert walked == pytest.approx(formula * rows * seq, rel=1e-9)

    real = cell.config["architecture"]
    # by hand, multiply-adds a token: a layer's four projections, its
    # scores and values over the band, a router with two held pairs a
    # token, the head
    seq = 16384
    projections = 2 * 2304 * 4096 + 2 * 2304 * 512
    full = 32 * 256 * (seq + 1) / 2
    band = 32 * 256 * (1024 - 1024 * 1023 / 2 / seq)
    routed = 2304 * 64 + 3 * 2304 * 896 * (8 * 16 / 64)
    macs = 4 * (projections + routed) + 3 * band + full + 2304 * 24576
    per_token = reference.forward_flops_per_token(real, seq)
    assert per_token == pytest.approx(2.0 * macs, rel=1e-12)
    assert full == pytest.approx(67.1e6, rel=1e-3)     # the issue's numbers
    assert band == pytest.approx(8.13e6, rel=1e-3)
    assert per_token == pytest.approx(0.5663e9, rel=1e-3)
    assert reference.flops_per_sample(real, cell.config["input"]["shape"]) \
        == pytest.approx(3 * seq * per_token)
    assert reference.band_pairs(8, 3) == 3 * 8 - 3       # 1 + 2 + 6 x 3
    assert reference.band_pairs(8) == reference.band_pairs(8, 99) == 36


def test_the_kernels_cost_counts_the_bands_pairs(cell):
    """Operations: ``S W - W (W - 1) / 2`` pairs a query head; bytes: the
    causal kernels' (a window changes which pairs are computed, not which
    arrays are touched).  A window at least the sequence is the causal
    count; the full layers' shapes carry the configuration's own head
    size."""
    arch = cell.config["architecture"]
    shapes = swa_attention_cost.work(arch, 1, 16384, arch["sliding_window"])
    assert shapes == {"rows": 1, "seq": 16384, "heads": 32, "kv_heads": 4,
                      "d": 128, "window": 1024}
    pairs = 32 * (16384 * 1024 - 1024 * 1023 / 2)
    brute = sum(min(i + 1, 1024) for i in range(16384))
    assert pairs == 32 * brute
    q_bytes, k_bytes = 32 * 16384 * 128 * 2, 4 * 16384 * 128 * 2
    row_bytes = 32 * 16384 * 4
    want = {"forward": (pairs * 512, 2 * q_bytes + 2 * k_bytes + row_bytes),
            "dq": (pairs * 768, 3 * q_bytes + 2 * k_bytes + 2 * row_bytes),
            "dkv": (pairs * 1024, 2 * q_bytes + 4 * k_bytes + 2 * row_bytes)}
    for kind, cost in want.items():
        assert swa_attention_cost.kernel_cost(kind, 2, shapes) == cost
    full = swa_attention_cost.work(arch, 1, 16384)
    assert "window" not in full and full["d"] == 128
    whole = dict(full, window=16384)
    for kind in want:
        causal = gqa_attention_cost.kernel_cost(kind, 2, full)
        banded = swa_attention_cost.kernel_cost(kind, 2, whole)
        assert banded[1] == causal[1]
        assert banded[0] == pytest.approx(causal[0], rel=1e-4)
        # a window layer's kernels are asked for an eighth of the full one's
        assert want[kind][0] / causal[0] == pytest.approx(0.1211, rel=1e-3)


def _call(name, operands, results):
    return "%%%s = %s custom-call(%s), custom_call_target=" \
        "\"tpu_custom_call\"" % (name, results, ", ".join(
            "%s %%a%d" % (t, i) for i, t in enumerate(operands)))


def test_the_readers_find_their_scopes_on_a_hand_made_trace(cell):
    """One step of 1,000 us: a window layer's three kernels (10 us each)
    under ``swa.attention``, the full layer's (80 each) under
    ``gqa.attention``, a projection under ``gqa.proj``, the routed path's
    five scopes, the head.  The rooflines divide the bounds of the shapes
    of the work by these times; the scope metrics add their scopes up."""
    q, k, rows = "bf16[32,16384,128]", "bf16[4,16384,128]", \
        "f32[32,1,16384]"
    kernels = {"forward": ([q, k, k], "(%s, %s)" % (q, rows)),
               "dq": ([q, k, k, q, rows, rows], q),
               "dkv": ([q, k, k, q, rows, rows], "(%s, %s)" % (k, k))}
    ops, names, at = [], {}, 0

    def add(name, text, us, scope):
        nonlocal at
        ops.append(trace.Op(at, at + us * 1000, text))
        names[name] = (0, "jit(step)/jit(main)/%s/op" % scope)
        at += us * 1000

    for scope, us in (("swa.attention", 10), ("gqa.attention", 80)):
        for kind, (operands, results) in kernels.items():
            name = "%s.%s" % (kind, scope[:3])
            add(name, _call(name, operands, results), us, scope)
    for i, (scope, us) in enumerate([
            ("gqa.proj", 70), ("moe.route", 9), ("moe.dispatch", 6),
            ("moe.experts", 50), ("moe.combine", 11), ("moe.aux", 2),
            ("lm_head", 40)]):
        name = "fusion.%d" % i
        add(name, "%%%s = f32[256]{0} fusion(f32[256]{0} %%a), kind=kLoop, "
            "calls=%%fc%d" % (name, i), us, scope)
    recorded = trace.Trace({0: {"ops": ops, "async": [], "modules": []}},
                           [(trace.WINDOW_SPAN, 0, 1_000_000)])
    module = types.SimpleNamespace(instructions=names)
    obs = {"cell": cell, "trace": recorded, "modules": [module], "chips": 1,
           "peaks": device.load_peaks(REPO)["TPU v5 lite"],
           "tail": {"steps": 1, "counters": {
               "l0_moe_max_load": 1.02, "l3_moe_max_load": 1.07,
               "l3_moe_held_pairs": 32768.0, "l3_moe_balance_term": 1.01}}}

    def read(metric):
        return cell.reader(metric).read(obs)

    assert read("attention.swa_gqa_ms_per_step") == pytest.approx(
        (70 + 30 + 240) / 1e3)
    assert read("moe.mellum_routed_ms_per_step") == pytest.approx(78 / 1e3)
    assert read("lm_head.mellum_ms_per_step") == pytest.approx(40 / 1e3)
    assert read("moe.mellum_max_expert_load_ratio") == 1.07
    arch = cell.config["architecture"]
    peaks = obs["peaks"]
    for metric, scope, shapes, cost, us in (
            ("kernels.swa_attention_roofline", "swa.attention",
             swa_attention_cost.work(arch, 1, 16384, 1024),
             swa_attention_cost.kernel_cost, 30),
            ("kernels.mellum_full_attention_roofline", "gqa.attention",
             swa_attention_cost.work(arch, 1, 16384),
             gqa_attention_cost.kernel_cost, 240)):
        bound = sum(max(flops / peaks["bf16_flops_per_s"],
                        least / peaks["hbm_bytes_per_s"])
                    for flops, least in (cost(kind, 2, shapes)
                                         for kind in kernels))
        assert read(metric) == pytest.approx(100 * bound / (us * 1e-6))
    # no device trace (the parent, a CPU): nothing to read, nothing raised
    for empty in (dict(obs, trace=None), dict(obs, trace=trace.Trace({}, []))):
        for metric in ("attention.swa_gqa_ms_per_step",
                       "kernels.swa_attention_roofline",
                       "kernels.mellum_full_attention_roofline",
                       "moe.mellum_routed_ms_per_step",
                       "lm_head.mellum_ms_per_step"):
            assert cell.reader(metric).read(empty) is None
    assert cell.reader("moe.mellum_max_expert_load_ratio").read(
        dict(obs, tail=None)) is None


# ------------------------------------------------- system against reference


class Lines(list):
    def __call__(self, message):
        self.append(message)


@pytest.fixture(scope="module")
def session_and_system(cell):
    import jax

    small = copy.copy(cell)
    small.config = tiny(cell.config)
    reference = cell.reference()
    ctx = run.Context(small, seed=3400000123, devices=jax.devices()[:1])
    ctx.say = Lines()
    session = small.entry().build(ctx)
    return small, reference, session, session.system_outputs(reference)


def test_system_agrees_with_its_plain_reference(session_and_system):
    small, reference, session, system = session_and_system
    said = session.ctx.say
    assert any("candidate rows rejected" in line for line in said)
    assert system["x"].shape == (2, 64)
    assert system["logits"].shape == (2, 64, 97)
    # the timed step's shape: the traffic's one row of the input's length
    assert system["y"].shape == (1, 64)
    grads = system["gradients"]
    assert grads["dense_prefix.hidden"].shape == (1, 64, 32)
    assert grads["swa_timed.out"].shape == grads["gqa_timed.out"].shape \
        == (1, 64, 32)
    assert grads["swa_timed.l0_attn_k_weight"].shape == (32, 32)
    assert grads["gqa_timed.l1_attn_q_weight"].shape == (64, 32)
    assert grads["l1_moe_router_weight"].shape == (8, 32)
    lines = Lines()
    assert check.against_reference(reference, small.config, system, lines), \
        "\n".join(lines)
    # logits, loss, five gradients; the embedding's stream and two
    # gradients; each timed layer's output and three gradients; two routed
    # layers' held pairs and balancing terms after the step
    assert len(lines) == 22
    term = grads["after_step.l0_moe_balance_term"]
    assert term.shape == (1,) and 1.0 <= float(term[0]) < 2.0
    # the loss carries the balancing term: 0.001 x three layers' terms
    bare = float(np.asarray(reference.cross_entropy(
        reference.forward(dict(system["params"]), system["x"],
                          small.config["architecture"]),
        np.roll(system["x"], -1, axis=1), 63)))
    assert system["loss"] - bare == pytest.approx(0.003, rel=0.5)
    assert system["loss"] - bare > 0.003


def _a_window_one_key_wider(reference, config, monkeypatch):
    plain = reference.window_of
    monkeypatch.setattr(
        reference, "window_of",
        lambda arch, kind: plain(arch, kind) and plain(arch, kind) + 1)


def _no_window_at_all(reference, config, monkeypatch):
    monkeypatch.setattr(reference, "window_of", lambda arch, kind: None)


def _plain_rotary_in_the_full_layers(reference, config, monkeypatch):
    plain = reference.rotary
    monkeypatch.setattr(reference, "rotary",
                        lambda arch, kind: plain(arch, "sliding_attention"))


def _yarn_without_its_amplitude(reference, config, monkeypatch):
    plain = reference.rotary
    monkeypatch.setattr(reference, "rotary",
                        lambda arch, kind: (plain(arch, kind)[0], 1.0))


def _yarn_not_truncated(reference, config, monkeypatch):
    """``low`` and ``high`` left unrounded."""
    monkeypatch.setattr(reference.math, "floor", lambda v: v)
    monkeypatch.setattr(reference.math, "ceil", lambda v: v)


def _reading_the_key_head_of_another_group(reference, config, monkeypatch):
    import jax.numpy as jnp

    plain = reference._qkv

    def qkv(p, pre, x, arch, kind):
        q, k, v = plain(p, pre, x, arch, kind)
        group = arch["num_attention_heads"] // arch["num_key_value_heads"]
        return (q,) + tuple(jnp.concatenate([a[:, ::group]] * group, axis=1)
                            for a in (k, v))

    monkeypatch.setattr(reference, "_qkv", qkv)


def _sigmoid_scores(reference, config, monkeypatch):
    import jax
    import jax.numpy as jnp

    plain = reference.route

    def route(p, pre, x, arch):
        ids, _, margin, term = plain(p, pre, x, arch)
        s = jax.nn.sigmoid(x @ p[pre + "router_weight"].T)
        picked = jnp.take_along_axis(s, ids, axis=-1)
        return ids, picked / jnp.sum(picked, axis=-1, keepdims=True), \
            margin, term

    monkeypatch.setattr(reference, "route", route)


def _a_loss_without_the_balancing_term(reference, config, monkeypatch):
    monkeypatch.setattr(reference, "balancing_loss", lambda routes, arch: 0.0)


def _a_balancing_term_that_trains_the_counts(reference, config, monkeypatch):
    """``f_e`` taken from the probabilities (differentiable) in place of
    the counted pairs: another value and another gradient."""
    import jax
    import jax.numpy as jnp

    plain = reference.route

    def route(p, pre, x, arch):
        ids, weights, margin, _ = plain(p, pre, x, arch)
        experts = arch["router_outputs"]
        probs = jax.nn.softmax(x @ p[pre + "router_weight"].T, axis=-1)
        mean = jnp.mean(probs.reshape(-1, experts), axis=0)
        return ids, weights, margin, experts * jnp.sum(mean * mean)

    monkeypatch.setattr(reference, "route", route)


def _routers_that_the_task_loss_trains(reference, config, monkeypatch):
    """The routing weights carry the loss's gradient to the router, as in
    an uncut layer: the cell's routers are trained by the balancing term
    alone (the configuration's ``assumed``)."""
    config["architecture"]["router_trained_by"] = "loss"


def _a_tied_head(reference, config, monkeypatch):
    plain = reference._head
    monkeypatch.setattr(
        reference, "_head",
        lambda p, h, arch: plain(dict(p, head_weight=p["embed_weight"]), h,
                                 arch))


WRONG = [_a_window_one_key_wider, _no_window_at_all,
         _plain_rotary_in_the_full_layers, _yarn_without_its_amplitude,
         _yarn_not_truncated, _reading_the_key_head_of_another_group,
         _sigmoid_scores, _a_loss_without_the_balancing_term,
         _a_balancing_term_that_trains_the_counts,
         _routers_that_the_task_loss_trains, _a_tied_head]


@pytest.mark.parametrize("wrong", WRONG, ids=[f.__name__[1:] for f in WRONG])
def test_a_wrong_computation_fails_the_check(wrong, session_and_system,
                                             monkeypatch):
    """The comparison is symmetric: a reference with a window one key
    wider or none, without yarn's blend, amplitude or rounding, reading
    another group's keys, scoring by sigmoids, leaving the balancing term
    out or differentiating its counts, training the routers by the task
    loss, or tying the head stands for a
    system that does, against the same limits."""
    small, reference, _, system = session_and_system
    config = copy.deepcopy(small.config)
    wrong(reference, config, monkeypatch)
    lines = Lines()
    assert not check.against_reference(reference, config, system, lines)
    assert any(line.endswith("FAIL") for line in lines)


def test_the_reference_computed_in_bfloat16_fails_every_floor(
        session_and_system):
    """The reference in the nearest precision below the stated one, handed
    to the comparison as if a system had computed it: logits, loss and
    gradients each fall outside their limit."""
    small, reference, _, system = session_and_system
    logits, loss, grads = reference.outputs(
        small.config["architecture"], [(system["params"], system["x"])],
        system["y"], dtype="bfloat16")[0]
    lower = dict(system, logits=np.asarray(logits, np.float32),
                 loss=float(loss),
                 gradients={n: np.asarray(g, np.float32)
                            for n, g in grads.items()})
    lines = Lines()
    assert not check.against_reference(reference, small.config, lower, lines)
    failed = " ".join(line.split()[1] for line in lines
                      if line.endswith("FAIL"))
    for kind in ("logits", "loss", "embed_weight", "l1_moe_router_weight",
                 "dense_prefix.hidden", "swa_timed.out", "gqa_timed.out",
                 "swa_timed.l0_attn_k_weight", "gqa_timed.l1_attn_q_weight"):
        assert kind in failed, (kind, lines)


def test_the_check_computed_in_bfloat16_fails(session_and_system):
    """The system's side in the nearest precision below the stated one."""
    small, reference, session, system = session_and_system
    make = session._make_step
    session._make_step = lambda dtype: make("bfloat16")
    try:
        lower = session.system_outputs(reference)
    finally:
        session._make_step = make
    np.testing.assert_array_equal(lower["x"], system["x"])
    lines = Lines()
    assert not check.against_reference(reference, small.config, lower, lines)
    assert any(line.endswith("FAIL") for line in lines)


def test_the_step_holds_no_router_bias_and_warms_up_by_itself(
        session_and_system):
    """The step's state: no ``router_bias`` leaf, trained or carried (a
    softmax router has none); the counters and nothing else beside the
    weights.  ``warm_up`` runs the step once, a group, and the traffic's
    ``warmup_groups`` groups, and says the loads after each."""
    small, _, session, system = session_and_system
    traffic = small.traffic
    steps = 1 + (1 + traffic["warmup_groups"]) * traffic["steps_per_fetch"]
    dispatched, dispatch = [], session.dispatch
    session.dispatch = lambda: (dispatched.append(1), dispatch())[1]
    try:
        session.warm_up()
    finally:
        del session.dispatch
    assert len(dispatched) == steps
    names = [p.name for p in session.step.trainable] \
        + [p.name for p in session.step.aux]
    assert not any("router_bias" in n for n in names)
    assert all(p.name.endswith(("held_pairs", "max_load", "balance_term"))
               for p in session.step.aux) and len(session.step.aux) == 9
    said = [line for line in session.ctx.say if "warm-up group" in line]
    assert len(said) == traffic["warmup_groups"]
    assert all("largest held expert over the mean" in line
               and "balancing term" in line for line in said)
    counters = session.read_counters()
    assert 1.0 <= counters["l1_moe_balance_term"] < 2.0


@pytest.mark.parametrize("chunk", [16, 64, 48])
def test_the_timed_rows_loss_over_chunks_is_the_loss_over_the_row(chunk):
    """``prefix_loss`` reads the logits ``chunk`` positions at a time (48
    does not divide the row: one chunk then); value, stream and gradients
    are those of ``cross_entropy`` over the whole row's logits."""
    import jax
    import jax.numpy as jnp

    reference = manifest.load_module(os.path.join(
        REPO, "benchmark", "references", "mellum2_moe.py"))
    rng = np.random.RandomState(3)
    arch = {"norm_eps": 1e-6}
    p = {"embed_weight": jnp.asarray(rng.randn(97, 32), jnp.float32),
         "head_weight": jnp.asarray(rng.randn(97, 32), jnp.float32),
         "norm_weight": jnp.asarray(1 + 0.1 * rng.randn(32), jnp.float32)}
    tokens = jnp.asarray(rng.randint(0, 97, (2, 64)), jnp.int32)

    def whole(p):
        hidden = reference.rms_norm(p["embed_weight"][tokens],
                                    p["norm_weight"], 1e-6)
        return reference.cross_entropy(
            hidden @ p["head_weight"].T, jnp.roll(tokens, -1, axis=1),
            63), hidden

    (want, stream), grads = jax.value_and_grad(whole, has_aux=True)(p)
    (got, hidden), chunked = jax.value_and_grad(
        lambda p: reference.prefix_loss(p, tokens, arch, chunk),
        has_aux=True)(p)
    assert abs(float(got) - float(want)) < 1e-6 * float(want)
    np.testing.assert_array_equal(np.asarray(hidden), np.asarray(stream))
    for n in p:
        assert check.relative_error(chunked[n], grads[n]) < 1e-5, n


def test_the_four_shares_of_a_routed_layer_sum_to_the_uncut_layer():
    """Four chips hold experts 0-15, 16-31, 32-47 and 48-63 of 64, routed by
    softmax scores without a bias.  Their routed outputs (there is no shared
    expert to count once) add up to the uncut layer's, which is the
    reference's with all 64 held; the balancing term is every share's
    alike: it is the router's, over all 64."""
    import jax
    import jax.numpy as jnp

    import mxnet_tpu as mx
    from mxnet_tpu import autograd
    from mxnet_tpu.gluon.nn import RoutedExperts

    reference = manifest.load_module(os.path.join(
        REPO, "benchmark", "references", "mellum2_moe.py"))

    def layer(held, prefix):
        mx.random.seed(5)
        blk = RoutedExperts(32, 16, 64, 8, held_experts=held, weight_std=0.3,
                            shared=False, route_epsilon=0.0,
                            scoring="softmax", balance_loss_weight=0.001,
                            prefix=prefix)
        blk.initialize(ctx=mx.cpu())
        return blk

    whole = layer((0, 64), "whole_")
    full = {n[len("whole_"):]: p.data().asnumpy()
            for n, p in whole.collect_params().items()}
    assert not any("shared" in n or "router_bias" in n for n in full)
    x = mx.nd.array(np.random.RandomState(2).randn(2, 32, 32)
                    .astype(np.float32))
    total, terms = 0, []
    for first in (0, 16, 32, 48):
        share = layer((first, 16), "share%d_" % first)
        for name, p in share.collect_params().items():
            value = full[name[len(share.prefix):]]
            if "experts_" in name:
                value = value[first:first + 16]
            p.set_data(mx.nd.array(value))
        total = total + share(x).asnumpy()
        with autograd.train_mode():
            y, term = share(x)
        terms.append(float(term.asnumpy()))
        assert float(share.balance_term.data().asnumpy()[0]) \
            == pytest.approx(terms[-1] / 0.001, rel=1e-6)
    arch = dict(num_experts_per_tok=8, route_epsilon=0.0, router_outputs=64,
                held_experts=[0, 64])
    with jax.default_matmul_precision("highest"):
        uncut = whole(x).asnumpy()
        routes = []
        want = np.asarray(reference.moe(
            {"m_" + n: jnp.asarray(v) for n, v in full.items()}, "m_",
            jnp.asarray(x.asnumpy()), arch, routes))
    scale = np.abs(want).max()
    assert np.abs(uncut - want).max() < 2e-5 * scale
    assert np.abs(total - want).max() < 2e-5 * scale
    assert terms == pytest.approx([0.001 * float(routes[0][2])] * 4,
                                  rel=1e-5)


# --------------------------------------------------------- one whole run


def cpu_gate(chips, root):
    import jax

    return jax.devices()[:chips], device.load_peaks(root)["TPU v5 lite"]


def tiny_checkout(cell, root):
    """The benchmark's files under ``root`` with the cell's configuration
    at the tiny size and one warm-up group."""
    shutil.copytree(os.path.join(REPO, "benchmark"),
                    os.path.join(root, "benchmark"),
                    ignore=shutil.ignore_patterns("__pycache__"))
    shutil.copy(os.path.join(REPO, "BENCHMARK.json"), root)
    entry = cell.manifest.named("configs", cell.config_name)
    with open(os.path.join(root, entry["file"]), "w") as f:
        json.dump(tiny(cell.config), f)
    # 97 ids are learnt by heart within the real warm-up's 71 steps
    with open(cell.manifest.find("traffic", cell.traffic_name, ".json")
              .replace(REPO, root), "w") as f:
        json.dump(dict(cell.traffic, warmup_groups=1), f)


def test_one_whole_run_of_the_cell_at_a_tiny_size(cell, tmp_path, capsys):
    """``run.main`` through the cell's own files, the configuration's sizes
    replaced: a result line, correct, with the program counter's metric;
    the device-trace readers find no device plane on a CPU and leave their
    metrics out."""
    root = str(tmp_path)
    tiny_checkout(cell, root)
    assert run.main(["--workload", CELL, "--seed", "3400000321",
                     "--seconds", "0.5", "--trace", "1"],
                    gate=cpu_gate, root=root) == 0
    lines = capsys.readouterr().out.strip().splitlines()
    result = json.loads(lines[-1])
    assert result["correct"] is True and result["failed"] == 0, lines[-30:]
    metrics = result["metrics"]
    assert metrics["entry.compiles_in_window"]["value"] == 0
    assert metrics["moe.mellum_max_expert_load_ratio"]["value"] >= 1.0
    assert 0 < metrics["step.mfu"]["value"] < 100
    for name in ("kernels.swa_attention_roofline",
                 "kernels.mellum_full_attention_roofline",
                 "attention.swa_gqa_ms_per_step",
                 "moe.mellum_routed_ms_per_step",
                 "lm_head.mellum_ms_per_step",
                 "kernels.gqa_attention_roofline", "moe.routed_ms_per_step",
                 "moe.lfm2_max_expert_load_ratio"):
        assert name not in metrics
    assert any("candidate rows rejected" in line for line in lines)
    assert sum("warm-up group" in line for line in lines) == 1
    assert sum("groups, s (dispatches + fetch)" in line
               for line in lines) == 2            # the window and the tail
    assert sum("check after_step." in line and line.endswith("ok")
               for line in lines) == 4
    assert sum(line.endswith("ok") and ("swa_timed." in line
                                        or "gqa_timed." in line)
               for line in lines) == 8
    assert any("M parameters" in line for line in lines)


def test_the_spread_tool_drives_the_entry_seed_after_seed(cell, tmp_path,
                                                          capsys):
    """``tools/cell_spread.py``: the cell's entry built, warmed up and
    measured as ``run.main`` does it, for each seed in one process; a line
    a seed with the groups' times and the routers' counters, then the
    quartile distance over the median."""
    sys.path.insert(0, os.path.join(REPO, "tools"))
    import cell_spread

    assert cell_spread.quartile_spread([1.0, 2.0, 3.0, 4.0, 5.0]) \
        == pytest.approx((4.5 - 1.5) / 3.0)
    root = str(tmp_path)
    tiny_checkout(cell, root)
    assert cell_spread.main([CELL, "3400000331", "3400000332",
                             "--warmup-groups", "2", "--seconds", "0.3"],
                            gate=cpu_gate, root=root) == 0
    lines = capsys.readouterr().out.strip().splitlines()
    seeds = [json.loads(line[5:]) for line in lines
             if line.startswith("SEED ")]
    assert [s["seed"] for s in seeds] == [3400000331, 3400000332]
    assert all(s["ok"] and len(s["group_s"]) * 10 == s["steps"]
               and sum(n.endswith("held_pairs") for n in s["counters"]) == 3
               for s in seeds)
    assert sum("warm-up group" in line for line in lines) == 4
    summary = json.loads(lines[-1][len("SPREAD "):])
    assert summary["seeds"] == 2 and summary["warmup_groups"] == 2
    assert summary["all_ok"] and summary["quartile_spread"] >= 0
    with open(os.path.join(root, "chiprun_out", "cell_spread.jsonl")) as f:
        assert len(f.readlines()) == 2
