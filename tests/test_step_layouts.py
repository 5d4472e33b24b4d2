"""PR 27: ``GluonTrainStep`` (replicated/dp path) holds each leaf of its
functional state with its dimensions in the order the step program's
compiler lays it out in: learned once from an ahead-of-time compile with
``Layout.AUTO``, moved there once, read back in the model's shapes.

The CPU compiler chooses the default order for every leaf, so the cases
that need another order put one in place of the compiler's answer."""

import numpy as np
import pytest

import mxnet_tpu as mx
from mxnet_tpu import gluon, optimizer as opt_mod, profiler, runtime_stats
from mxnet_tpu.gluon import nn
from mxnet_tpu.parallel.gluon_step import GluonTrainStep
from mxnet_tpu.parallel.mesh import create_mesh


FORMS = pytest.mark.parametrize("zero", [False, True],
                                ids=["in_order", "flat_shards"])
RULES = pytest.mark.parametrize("with_optimizer", [False, True],
                                ids=["fused_sgd", "optimizer"])


def _net(prefix):
    mx.random.seed(7)
    net = nn.HybridSequential(prefix=prefix)
    with net.name_scope():
        net.add(nn.Conv2D(8, 3, padding=1), nn.BatchNorm(),
                nn.Activation("relu"), nn.Flatten(), nn.Dense(4))
    net.initialize(ctx=mx.cpu())
    net(mx.nd.zeros((2, 3, 6, 6), ctx=mx.cpu()))
    return net


def _step(net, with_optimizer=False, mesh=None, **kwargs):
    import jax

    if with_optimizer:
        kwargs["optimizer"] = opt_mod.create("adam", learning_rate=0.01)
    else:
        kwargs.update(lr=0.1, momentum=0.9, wd=1e-4)
    mesh = mesh or {"dp": 2}
    n = int(np.prod(list(mesh.values())))
    return GluonTrainStep(
        net, gluon.loss.SoftmaxCrossEntropyLoss(),
        mesh=create_mesh(mesh, devices=jax.devices()[:n]), **kwargs)


def _batch(n=8):
    rs = np.random.RandomState(0)
    return (rs.rand(n, 3, 6, 6).astype(np.float32),
            rs.randint(0, 4, (n,)).astype(np.int32))


def _relayouts():
    return runtime_stats.snapshot()["counters"].get(
        "step_state_relayouts", 0)


@pytest.fixture
def minor_first(monkeypatch):
    """The compiler's answer replaced: every leaf minor dimension first,
    as a TPU holds a convolution's weight (HWIO for the model's OIHW)."""
    real = GluonTrainStep._compilers_orders

    def reversed_orders(self, x, y, rest):
        return tuple(tuple(tuple(reversed(order)) for order in tree)
                     for tree in real(self, x, y, rest))

    monkeypatch.setattr(GluonTrainStep, "_compilers_orders",
                        reversed_orders)


def _three_steps(step, x, y):
    mx.random.seed(11)
    return [np.asarray(step(x, y)) for _ in range(3)]


def _default_layout_steps(plain, x, y):
    """Three steps of ``_step_py`` under a plain ``jax.jit``, the state
    as the model has it: -> (losses, final state)."""
    import jax

    from mxnet_tpu import random as mxrandom

    mx.random.seed(11)
    default = jax.jit(plain._step_py)
    state = (plain.train_vals, plain.opt_state, plain.aux_vals)
    losses = []
    for _ in range(3):
        loss, *state, _gnorm = default(
            *state, x, y, mxrandom.next_key(), plain._rule.host_scalars())
        losses.append(np.asarray(loss))
    return losses, jax.tree.leaves(state)


def _read(step):
    return list(step.train_vals + step.opt_state + step.aux_vals)


@RULES
def test_the_compilers_orders_and_bit_for_bit_the_default_step(
        with_optimizer):
    """The ahead-of-time compile with ``Layout.AUTO`` answers for every
    leaf; on the CPU the answer is the model's own order, and the step
    that runs is, bit for bit, ``_step_py`` under a plain ``jax.jit``."""
    net = _net("bit%d_" % with_optimizer)
    step = _step(net, with_optimizer)
    plain = _step(net, with_optimizer)
    x, y = step.put_batch(*_batch())
    before = _relayouts()
    got = _three_steps(step, x, y)
    assert _relayouts() - before == 1
    for tree, orders in zip(step._held, step._orders):
        assert [sorted(o) for o in orders] \
            == [list(range(v.ndim)) for v in tree]
    assert step._relaid == sum(
        o != tuple(range(len(o))) for tree in step._orders for o in tree)
    want, state = _default_layout_steps(plain, x, y)
    if step._relaid == 0:
        assert all(np.array_equal(a, b) for a, b in zip(got, want))
        assert all(np.array_equal(np.asarray(a), np.asarray(b))
                   for a, b in zip(_read(step), state))
    else:
        np.testing.assert_allclose(got, want, rtol=1e-5)


@RULES
def test_the_state_moves_once_and_reads_in_the_models_shapes(
        minor_first, with_optimizer):
    net = _net("lay%d_" % with_optimizer)
    step = _step(net, with_optimizer)
    plain = _step(net, with_optimizer)
    shapes = [v.shape for v in _read(step)]
    x, y = step.put_batch(*_batch())
    before = _relayouts()
    got = _three_steps(step, x, y)
    assert _relayouts() - before == 1
    # held minor first, read as the model has them
    assert [v.shape for tree in step._held for v in tree] \
        == [s[::-1] for s in shapes]
    assert [v.shape for v in _read(step)] == shapes
    assert step._relaid == sum(len(s) > 1 for s in shapes) > 0
    want, state = _default_layout_steps(plain, x, y)
    np.testing.assert_allclose(got, want, rtol=1e-5)
    if not with_optimizer:
        # (Adam turns the rounding noise of a gradient that is zero, the
        # bias ahead of a batch norm, into steps of the size of lr)
        for a, b in zip(_read(step), state):
            np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                       rtol=1e-4, atol=1e-6)

    for _ in range(10):
        loss = step(x, y)
    assert np.isfinite(float(np.asarray(loss)))
    # a short last batch: one more trace of the same jit, nothing moves
    traces = step._step._cache_size()
    step(*_batch(4))
    step(x, y)
    assert step._step._cache_size() == traces + 1
    assert _relayouts() - before == 1
    assert [v.shape for tree in step._held for v in tree] \
        == [s[::-1] for s in shapes]


def test_a_sharded_leaf_keeps_its_axes_in_the_held_order(minor_first):
    """``param_spec_fn``: the partition follows the dimension it names."""
    from jax.sharding import PartitionSpec as P

    def spec(name, shape):
        return P("tp") if name.endswith("dense0_weight") else P()

    net = _net("tp_")
    step = _step(net, mesh={"dp": 2, "tp": 2}, param_spec_fn=spec)
    plain = _step(net, mesh={"dp": 2, "tp": 2}, param_spec_fn=spec)
    x, y = step.put_batch(*_batch())
    got = _three_steps(step, x, y)
    want, _ = _default_layout_steps(plain, x, y)
    np.testing.assert_allclose(got, want, rtol=1e-5)
    for tree in step._held[:2]:
        dense = [v for p, v in zip(step.trainable, tree)
                 if p.name.endswith("dense0_weight")]
        assert [(v.shape, v.sharding.spec) for v in dense] \
            == [((288, 4), P(None, "tp"))]


def test_what_was_learned_is_kept_beside_the_compile_cache(
        minor_first, monkeypatch, tmp_path):
    """With a persistent compile cache, the next process reads the orders
    and does not compile the program that is only read."""
    from mxnet_tpu.parallel import gluon_step

    net = _net("kept_")
    x, y = _batch()
    first = _step(net)
    first(x, y)                         # no cache directory: nothing kept
    assert list(tmp_path.iterdir()) == []

    monkeypatch.setattr(gluon_step, "_orders_dir", lambda: str(tmp_path))
    second = _step(net)
    loss = float(np.asarray(second(x, y)))
    kept, = tmp_path.iterdir()
    assert kept.name.startswith("mxtpu-step-orders-")

    def never(self, x, y, rest):
        raise AssertionError("the orders were kept: nothing to compile")

    monkeypatch.setattr(GluonTrainStep, "_compilers_orders", never)
    third = _step(net)
    assert float(np.asarray(third(x, y))) == loss
    assert third._orders == second._orders == first._orders
    # another batch, another answer to ask for; a file that does not fit
    # the state is not believed
    with pytest.raises(AssertionError, match="nothing to compile"):
        _step(net)(*_batch(4))
    kept.write_text("[[[0]], [], []]")
    with pytest.raises(AssertionError, match="nothing to compile"):
        _step(net)(x, y)


def test_the_orders_one_rule_learned_are_not_the_other_rules(
        minor_first, monkeypatch, tmp_path):
    """The kept answer is named after the rule too: a step with another
    rule asks the compiler itself and keeps its own file."""
    from mxnet_tpu.parallel import gluon_step

    monkeypatch.setattr(gluon_step, "_orders_dir", lambda: str(tmp_path))
    net = _net("rule_")
    x, y = _batch()
    _step(net)(x, y)
    fused, = tmp_path.iterdir()
    asked = []
    learn = GluonTrainStep._compilers_orders

    def counted(self, x, y, rest):
        asked.append(type(self._rule.opt).__name__)
        return learn(self, x, y, rest)

    monkeypatch.setattr(GluonTrainStep, "_compilers_orders", counted)
    _step(net, with_optimizer=True)(x, y)
    assert asked == ["Adam"]
    assert len(list(tmp_path.iterdir())) == 2
    _step(net)(x, y)
    _step(net, with_optimizer=True)(x, y)
    assert asked == ["Adam"]            # both read their own file now
    assert fused in tmp_path.iterdir()


@FORMS
@RULES
def test_sync_to_params_round_trip(minor_first, zero, with_optimizer):
    """Whatever form holds the state and whatever rule updates it, the
    parameters come back in the model's shapes with the step's values."""
    net = _net("rt%d%d_" % (zero, with_optimizer))
    step = _step(net, with_optimizer, zero=zero)
    plain = _step(net, with_optimizer)
    shapes = [p.data().shape for p in step.trainable + step.aux]
    x, y = _batch()
    before = _relayouts()
    got = _three_steps(step, x, y)
    want, state = _default_layout_steps(plain, *plain.put_batch(x, y))
    np.testing.assert_allclose(got, want, rtol=1e-5)
    assert _relayouts() - before == (0 if zero else 1)
    held = step._held[0][0]
    assert held.shape == ((3 * 3 * 3 * 8,) if zero else (3, 3, 3, 8))
    step(x, y)
    assert held.is_deleted()        # donated by the step, as it was held

    step.sync_to_params()
    synced = [p.data() for p in step.trainable + step.aux]
    assert [v.shape for v in synced] == shapes
    if zero:
        # a flat shard, unpadded and in the parameter's shape
        for p, flat in zip(synced, step.train_vals):
            assert np.array_equal(p.asnumpy().ravel(),
                                  np.asarray(flat)[:p.size])
    else:
        for p, v in zip(synced, step.train_vals + step.aux_vals):
            assert np.array_equal(p.asnumpy(), np.asarray(v))
    # and the parameters feed the eager API as ever
    assert net(mx.nd.array(x)).shape == (8, 4)

    # assigned as they read, kept as held
    step.train_vals = [np.asarray(v) * 0 for v in step.train_vals]
    assert step._held[0][0].shape == held.shape
    assert not np.asarray(step.train_vals[0]).any()


def test_the_launch_span_carries_relaid_leaves(minor_first):
    step = _step(_net("span_"))
    x, y = _batch()
    step(x, y)
    profiler.set_state("run")
    try:
        float(np.asarray(step(x, y)))
    finally:
        profiler.set_state("stop")
    launch, = [e for e in profiler._state["events"]
               if e["name"] == "mxtpu.step.launch"]
    profiler.dumps(reset=True)
    assert launch["args"] == {"leaves": step._leaves, "relaid_leaves": 4}


@RULES
def test_zero_holds_flat_shards_and_nothing_moves(minor_first,
                                                  with_optimizer):
    net = _net("zl%d_" % with_optimizer)
    before = _relayouts()
    step = _step(net, with_optimizer, zero=True)
    x, y = _batch()
    step(x, y)
    step(x, y)
    # the model's order for every leaf, whatever a compiler would answer
    assert all(o == tuple(range(len(o)))
               for tree in step._orders for o in tree)
    assert step._relaid == 0 and _relayouts() == before
    assert all(v.ndim == 1 for v in step.train_vals + step.opt_state)
    assert "input_output_alias" in step.program_for(
        *step.put_batch(x, y)).as_text()


@FORMS
@RULES
def test_the_program_takes_the_rules_scalars_and_aliases_the_state(
        zero, with_optimizer):
    """``program_for`` stands in for the key and the host scalars: as
    many scalar arguments as the rule has slots (the fused rule's empty
    tuple adds none), every state leaf donated and aliased to a result."""
    import re

    import jax

    step = _step(_net("pf%d%d_" % (zero, with_optimizer)), with_optimizer,
                 zero=zero)
    program = step.program_for(*step.put_batch(*_batch()))
    (*state, _x, _y, _key, scalars), _kwargs = program.args_info
    assert len(scalars) == len(step._rule.slots)
    assert (len(scalars) > 0) == with_optimizer
    leaves = jax.tree.leaves(state)
    assert len(leaves) == len(_read(step)) and all(
        leaf.donated for leaf in leaves)
    assert len(jax.tree.leaves(program.args_info)) \
        == len(leaves) + 3 + len(scalars)
    header = program.as_text().split("\n", 1)[0]
    aliased = re.findall(r"\{\d+\}: \((\d+), \{\}", header)
    assert len(set(aliased)) == len(leaves)


def test_the_zero_methods_of_a_replicated_step_raise():
    from mxnet_tpu.base import MXNetError

    step = _step(_net("nz_"))
    for call in (step.zero_shard_payloads, lambda: step.save_zero(1),
                 lambda: step.restore_zero({})):
        with pytest.raises(MXNetError, match="not built with zero=True"):
            call()
    assert not hasattr(step, "zero_layout")


# --------------------------------------------------- the v5e's compiler
# On the CPU the compiler's order is the model's.  The TPU's compiler is
# installed here and compiles for a chip that is described, not attached
# (PERF.md §3): the same small network, its shardings swapped for the
# described chip's.


@pytest.fixture(scope="module")
def v5e():
    import os

    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(
            platform="tpu", topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip("no v5e:2x2 topology can be described here: %s" % e)


def test_on_the_v5e_no_state_leaf_is_copied_at_the_programs_edges(v5e):
    import re

    import jax
    import jax.numpy as jnp
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

    mx.random.seed(7)
    net = nn.HybridSequential(prefix="v5e_")
    with net.name_scope():
        for width in (64, 128):
            net.add(nn.Conv2D(width, 3, padding=1, layout="NHWC",
                              use_bias=False),
                    nn.BatchNorm(axis=3), nn.Activation("relu"))
        net.add(nn.GlobalAvgPool2D(layout="NHWC"), nn.Dense(10))
    net.initialize(ctx=mx.cpu())
    net(mx.nd.zeros((1, 16, 16, 64), ctx=mx.cpu()))
    step = GluonTrainStep(
        net, gluon.loss.SoftmaxCrossEntropyLoss(),
        mesh=create_mesh({"dp": 1}, devices=jax.devices()[:1]),
        lr=0.1, momentum=0.9, wd=1e-4, compute_dtype="bfloat16")
    mesh = Mesh(np.array(v5e.devices[:1]), ("dp",))
    repl, batch = NamedSharding(mesh, P()), NamedSharding(mesh, P("dp"))
    step._repl, step._rest_in = repl, (batch, batch, repl, repl)
    step._form.shards = jax.tree.map(lambda _s: repl, step._form.shards)
    x = jax.ShapeDtypeStruct((32, 16, 16, 64), jnp.float32)
    y = jax.ShapeDtypeStruct((32,), jnp.int32)
    rest = [jax.ShapeDtypeStruct((2,), jnp.uint32), ()]

    def copies_at_the_entry(orders):
        step._orders = orders
        held = [tuple(jax.ShapeDtypeStruct(
            tuple(v.shape[i] for i in order), v.dtype)
            for v, order in zip(tree, tree_orders))
            for tree, tree_orders in zip(step._held, orders)]
        text = step._jit().lower(*held, x, y, *rest).compile().as_text()
        entry = text[text.index("\nENTRY "):]
        return len(re.findall(r" copy\(", entry[:entry.index("\n}")]))

    as_the_model = tuple(tuple(tuple(range(v.ndim)) for v in tree)
                         for tree in step._held)
    # each OHWI weight and its momentum: into the weight-gradient
    # fusion's layout (the second's on the way in too) and back out
    assert copies_at_the_entry(as_the_model) >= 4
    orders = step._compilers_orders(x, y, rest)
    assert [o for tree in orders for o in tree if len(o) == 4] \
        == [(1, 2, 3, 0)] * 4          # HWIO, weights and momenta
    assert copies_at_the_entry(orders) == 0


# what ``benchmark/harness/attention_cost.py`` counts for the three kernels
# of the language-model cell (2 x 32 heads x 4,096, 192 / 128, causal)
_CELLS_KERNEL_FLOPS = [2 * 32 * 4096 * 4096 / 2 * n for n in (640, 1024, 1280)]


def _kernel_instructions(text):
    """Every ``tpu_custom_call`` of a compiled module, written as the
    profiler names it: its operands' types inline."""
    import os
    import re
    import sys

    sys.path.insert(0, os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))))
    from benchmark.harness import attention_cost, hlo_cost

    lines = [hlo_cost.split_instruction(line) for line in text.splitlines()]
    types = {name: result for name, _, (result, _, _) in lines}
    found = []
    for name, opcode, (result, operands, attrs) in lines:
        if attention_cost.KERNEL_TARGET in attrs:
            operands = re.sub(r"%([\w.\-]+)", lambda m: "%s %s" % (
                types[m.group(1)], m.group(0)), operands)
            found.append("%%%s = %s %s(%s)%s" % (name, result, opcode,
                                                 operands, attrs))
    return found


def _counted_kernel_flops(text):
    """Sorted ``attention_cost.kernel_flops`` of every ``tpu_custom_call``
    of a compiled module (None for one the benchmark does not know)."""
    from benchmark.harness import attention_cost

    return sorted(attention_cost.kernel_flops(instruction)
                  for instruction in _kernel_instructions(text))


@pytest.mark.parametrize("dtype,blocks,kv_heads,seq,d,dv", [
    ("bfloat16", (1024, 1024), 32, 4096, 192, 128),
    ("float32", (256, 512), 32, 4096, 192, 128),
    # what the jaxpr-level tests of tests/test_attention.py and the cell's
    # own runs show already: outside the tier-1 run
    pytest.param("bfloat16", (1024, 1024), 8, 8192, 64, 64,
                 marks=pytest.mark.slow),
    ("float32", (1024, 1024), 8, 8192, 64, 64)],
    ids=["latent_bfloat16", "latent_float32", "grouped_query_bfloat16",
         "grouped_query_float32"])
def test_on_the_v5e_the_flash_kernels_compile_and_the_benchmark_counts_them(
        v5e, monkeypatch, dtype, blocks, kv_heads, seq, d, dv):
    """Mosaic takes the three flash-attention kernels at the language-model
    cells' shapes (2 x 32 heads x 4,096, 192 / 128, bfloat16 as the step
    runs them and float32 as the check does; 2 x 32 query heads on 8 key
    heads x 8,192, 64 / 64, in float32 at ``highest`` matmul precision as
    that cell's check runs them: 19-22 MiB of scoped VMEM at 1,024 / 1,024,
    which the kernels ask for), and their instructions are the ones the
    benchmark counts: q, k, v (and do, lse, delta), one result for dq, two
    for the others (``harness/attention_cost.py``; with grouped-query heads
    ``harness/gqa_attention_cost.py``, and ``k`` arrives with the key
    heads' rows, not repeated)."""
    import jax
    import jax.numpy as jnp
    from jax.sharding import SingleDeviceSharding

    from mxnet_tpu.ops import attention as att

    # traced on the CPU platform the entry would take its XLA branch
    monkeypatch.setattr(att, "pallas_interpret", lambda: False)
    chip = SingleDeviceSharding(v5e.devices[0])

    def arg(heads, width):
        return jax.ShapeDtypeStruct((2, heads, seq, width), jnp.dtype(dtype),
                                    sharding=chip)

    def forward_and_backward(q, k, v, g):
        out, vjp = jax.vjp(
            lambda q, k, v: att.flash_attention(q, k, v, causal=True),
            q, k, v)
        return (out,) + vjp(g)

    assert att._block_choices(arg(32, d), arg(kv_heads, dv))[0] == blocks
    with jax.default_matmul_precision(
            "highest" if dtype == "float32" else "default"):
        text = jax.jit(forward_and_backward).lower(
            arg(32, d), arg(kv_heads, d), arg(kv_heads, dv),
            arg(32, dv)).compile().as_text()
    if kv_heads == 32:
        assert _counted_kernel_flops(text) == _CELLS_KERNEL_FLOPS
        return
    from benchmark.harness import gqa_attention_cost

    shapes = {"rows": 2, "seq": seq, "heads": 32, "kv_heads": kv_heads,
              "d": d}
    kernels = _kernel_instructions(text)
    size = jnp.dtype(dtype).itemsize
    assert sorted(gqa_attention_cost.kernel_kind(k, shapes)
                  for k in kernels) == [("dkv", size), ("dq", size),
                                        ("forward", size)]
    typed = {"bfloat16": "bf16", "float32": "f32"}[dtype] + "[%d,%d,%d]"
    for kernel in kernels:
        operands = kernel[kernel.index("custom-call("):kernel.index(
            "custom_call_target")]
        assert operands.count(typed % (2 * kv_heads, seq, d)) == 2
        assert operands.count(typed % (64, seq, d)) \
            == (1 if "forward" in str(gqa_attention_cost.kernel_kind(
                kernel, shapes)) else 2)


def test_on_the_v5e_latent_attention_hands_the_kernels_what_they_read(
        v5e, monkeypatch):
    """One ``MLAttention`` layer at the language-model cell's shape (2 x
    4,096 tokens, 2048 / 1536 / 512, 32 heads of 128 + 64 / 128,
    bfloat16), forward and backward, as the v5e's compiler lays it out:
    no gather and no scatter (a rotation by strided lanes is both), the
    three flash kernels the benchmark counts, and no more bytes than
    today's: a relayout copy of ``q``, ``k`` or their gradients is 0.4 GB,
    per-head concatenations and the sliced ``v`` brought the layer to 8.95
    GB (PERF.md, PR 31)."""
    import re

    import jax
    import jax.numpy as jnp
    from jax.sharding import SingleDeviceSharding

    from mxnet_tpu.gluon.block import staged_call
    from mxnet_tpu.ndarray import NDArray
    from mxnet_tpu.ops import attention as att

    monkeypatch.setattr(att, "pallas_interpret", lambda: False)
    chip = SingleDeviceSharding(v5e.devices[0])
    layer = nn.MLAttention(2048, num_heads=32, q_lora_rank=1536,
                           kv_lora_rank=512, qk_nope_head_dim=128,
                           qk_rope_head_dim=64, v_head_dim=128,
                           rope_theta=32e6, prefix="v5e_attn_")
    params = list(layer.collect_params().values())

    def forward(x, values):
        out, _ = staged_call(
            layer, {p: NDArray(v) for p, v in zip(params, values)}, None,
            [NDArray(x)])
        return out._data

    def forward_and_backward(x, values, g):
        out, vjp = jax.vjp(forward, x, values)
        return (out,) + vjp(g)

    def arg(shape):
        return jax.ShapeDtypeStruct(shape, jnp.bfloat16, sharding=chip)

    compiled = jax.jit(forward_and_backward).lower(
        arg((2, 4096, 2048)), [arg(p.shape) for p in params],
        arg((2, 4096, 2048))).compile()
    text = compiled.as_text()
    assert not re.search(r" (gather|scatter)\(", text)
    assert _counted_kernel_flops(text) == _CELLS_KERNEL_FLOPS
    # 4.34 GB as this was written, 1.9 GB of it the three kernels'
    # operands and results
    assert compiled.cost_analysis()["bytes accessed"] < 4.6e9


def test_on_the_v5e_the_routed_sum_adds_a_tiles_rows_in_place(
        v5e, monkeypatch):
    """``moe_experts`` forward and backward as the v5e's compiler emits
    them (a quarter of the cells' tokens; the cells' row width, tile and
    float32 sums): Mosaic takes ``_add_rows_kernel`` (a token's 8 KB copied
    row by row; a row of a 2-D sum it refuses) and ``_summed_rows``'; the
    loops add into ``(tokens, 16, 128)`` sums through it, in place:
    each call's result aliases its operand and no instruction copies a
    sum; a big step of the backward pass calls it once a tile of its
    four; the one kind of scatter-add left is the flat gradient of the
    routing weights; and every one of these instructions lies under the
    ``moe.combine`` scope that ``benchmark/harness/scope_time.py`` reads.
    A step of either backward loop writes each weight gradient once: an
    output fusion under ``moe.wgrad`` that ends in the update of the
    expert's slice of the float32 carry, and no instruction copies a
    carry.  The guard a CPU run cannot give: there XLA's scatter-add is
    the path."""
    import re

    import jax
    import jax.numpy as jnp
    from jax.sharding import SingleDeviceSharding

    from mxnet_tpu.ops import llm

    # traced on the CPU platform the loops would take XLA's scatter-add
    monkeypatch.setattr(llm, "pallas_interpret", lambda: False)
    chip = SingleDeviceSharding(v5e.devices[0])
    tokens, units, k, held, width = 4096, 2048, 4, 4, 256

    def arg(shape, dtype=jnp.bfloat16):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=chip)

    def forward_and_backward(x, ids, weights, wg, wu, wd, g):
        out, vjp = jax.vjp(
            lambda x, weights, wg, wu, wd: llm.moe_experts(
                x, ids, weights, wg, wu, wd)[0], x, weights, wg, wu, wd)
        return (out,) + vjp(g)

    text = jax.jit(forward_and_backward).lower(
        arg((tokens, units)), arg((tokens, k), jnp.int32),
        arg((tokens, k), jnp.float32), arg((held, units, width)),
        arg((held, units, width)), arg((held, width, units)),
        arg((tokens, units))).compile().as_text()
    lines = text.splitlines()
    the_sum = re.escape("f32[%d,%d,128]" % (tokens, units // 128))

    def instructions(opcode, result):
        return [line for line in lines
                if re.search(r" = %s\S* %s\(" % (result, opcode), line)]

    def kernels(name, result):
        return [line for line in instructions("custom-call", result)
                if "tpu_custom_call" in line
                and line.lstrip().startswith("%" + name)]

    big = llm.EXPERT_TILES_A_STEP
    adds = kernels("moe_add_rows", the_sum)
    # the forward loop's, a single tile's and a big step's of the backward
    assert len(adds) == 1 + 1 + big
    for line in adds:
        assert "output_to_operand_aliasing={{}: (2, {})}" in line, line
    sums = kernels("moe_summed_rows", r"bf16\[%d,%d\]" % (tokens, units))
    assert len(sums) == 2
    scatters = [line for line in lines if re.search(r" = f32\S* scatter\(",
                                                    line)]
    assert [line.split(" = ")[1].split("{")[0] for line in scatters] \
        == ["f32[%d]" % (tokens * k)] * (1 + big)
    for line in adds + sums + scatters:    # as ``scope_time._under`` reads
        assert re.search(r'op_name="[^"]*[/(]moe\.combine[/)]', line), \
            line[:300]
    assert not instructions("copy", the_sum)
    assert not re.search(r" = f32\[%d,%d\]" % (tokens, units), text)
    carries = r"f32\[%d,(%d,%d|%d,%d)\]" % (held, units, width, width, units)
    writes = [line for line in instructions("fusion", carries)
              if re.search(r'op_name="[^"]*[/(]moe\.wgrad[/)]', line)]
    assert len(writes) == 3 + 3                 # a big step's, a tile's
    for line in writes:
        assert re.search(r'[/(]moe\.experts/moe\.wgrad[/)]', line), line[:300]
        assert "kind=kOutput" in line, line[:300]
        body = text.split(re.search(r"calls=(%[\w.\-]+)", line).group(1)
                          + " (", 1)[1].split("\n}", 1)[0]
        assert re.search(r"ROOT \S+ = %s\S* dynamic-update-slice\(" % carries,
                         body), line[:300]
        assert " convolution(" in body          # the product, in the fusion
    assert not instructions("copy", carries)


# ------------------------------------ why orders, and not layouts, are held

_ASKS_FOR_A_LAYOUT = """
import jax, jax.numpy as jnp
from jax.experimental.layout import Format, Layout
from jax.sharding import SingleDeviceSharding
jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
wanted = Format(Layout(major_to_minor=(1, 2, 3, 0)),
                SingleDeviceSharding(jax.devices()[0]))
out = jax.jit(lambda v: v * 2, out_shardings=wanted)(jnp.ones((4, 3, 3, 8)))
print(out.format.layout.major_to_minor)
"""


def test_on_the_v5e_a_window_layers_kernels_run_the_band_only(v5e,
                                                              monkeypatch):
    """A small ``LayerTypesMoELM`` (a window layer and a full one, a softmax
    router under its balancing loss) through ``GluonTrainStep`` with Adam in
    bfloat16, one row of 4,096 tokens, compiled for the described chip:
    Mosaic takes the three kernels with a window inside the step program,
    and their grids hold the band only: at blocks 1,024 / 1,024 and a
    window of 256 a query block runs 2 key blocks and a key block 2 query
    blocks, where the full layer's grids count all 4."""
    import jax
    import jax.numpy as jnp
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

    from mxnet_tpu.gluon.nn import LayerTypesMoELM, NextTokenLoss
    from mxnet_tpu.ops import attention as att, llm

    monkeypatch.setattr(att, "pallas_interpret", lambda: False)
    monkeypatch.setattr(llm, "pallas_interpret", lambda: False)
    mx.random.seed(7)
    net = LayerTypesMoELM(
        vocab_size=512, hidden_size=256,
        layer_types=["sliding_attention", "full_attention"],
        moe_intermediate_size=128, router_outputs=8, held_experts=(0, 4),
        num_experts_per_tok=2, num_attention_heads=2, num_key_value_heads=1,
        head_dim=128, sliding_window=256, scoring_func="softmax",
        router_aux_loss_coef=0.001, route_epsilon=0.0, tie_embedding=False,
        prefix="swa_")
    net.initialize(ctx=mx.cpu())
    step = GluonTrainStep(
        net, NextTokenLoss(net.head),
        mesh=create_mesh({"dp": 1}, devices=jax.devices()[:1]),
        compute_dtype="bfloat16",
        optimizer=opt_mod.create("adam", learning_rate=1e-5))
    mesh = Mesh(np.array(v5e.devices[:1]), ("dp",))
    repl, batch = NamedSharding(mesh, P()), NamedSharding(mesh, P("dp"))
    step._repl, step._rest_in = repl, (batch, batch, repl, repl)
    step._form.shards = jax.tree.map(lambda _s: repl, step._form.shards)
    step._orders = tuple(tuple(tuple(range(v.ndim)) for v in tree)
                         for tree in step._held)
    held = [tuple(jax.ShapeDtypeStruct(v.shape, v.dtype) for v in tree)
            for tree in step._held]
    x = jax.ShapeDtypeStruct((1, 4096), jnp.int32)
    rest = [jax.ShapeDtypeStruct((2,), jnp.uint32),
            tuple(jax.ShapeDtypeStruct((), jnp.float32)
                  for _ in step._rule.slots)]

    def grids(jaxpr, found):
        for eqn in jaxpr.eqns:
            if eqn.primitive.name == "pallas_call":
                found.append(tuple(eqn.params["grid_mapping"].grid))
            for value in eqn.params.values():
                for sub in value if isinstance(value, (tuple, list)) \
                        else [value]:
                    sub = getattr(sub, "jaxpr", sub)
                    if hasattr(sub, "eqns"):
                        grids(sub, found)
        return found

    found = set(grids(jax.make_jaxpr(step._step_py)(*held, x, x,
                                                    *rest).jaxpr, []))
    assert {(2, 4, 4), (1, 4, 8)} <= found          # the full layer's
    assert {(2, 4, 2), (1, 4, 4)} <= found          # the window layer's
    text = step._jit().lower(*held, x, x, *rest).compile().as_text()
    from benchmark.harness import gqa_attention_cost

    shapes = {"rows": 1, "seq": 4096, "heads": 2, "kv_heads": 1, "d": 128}
    kinds = sorted(str(gqa_attention_cost.kernel_kind(k, shapes))
                   for k in _kernel_instructions(text))
    assert kinds.count("('forward', 2)") == kinds.count("('dq', 2)") \
        == kinds.count("('dkv', 2)") == 2


@pytest.mark.parametrize("dtype,heads,window", [
    ("bfloat16", 72, 512), ("float32", 72, 512), ("float32", 48, None)],
    ids=["window_group_9_bfloat16", "window_group_9_float32",
         "full_group_6_float32"])
def test_on_the_v5e_the_kernels_take_lagunas_groups_and_narrow_window(
        v5e, monkeypatch, dtype, heads, window):
    """Mosaic takes the three flash kernels at ``laguna_moe_train_seq4k``'s
    shapes: 1 x 72 query heads on 8 key heads x 4,096 of 128 with a window
    of 512 under blocks of 1,024 (both key blocks a query block runs are
    cut into tiles: the diagonal's, and the one the window's edge
    crosses), in bfloat16 as the step runs them and in float32 at
    ``highest`` as the check does, and 48 on 8 over the whole row; the
    benchmark's cost file finds each kernel by the layer's own head count."""
    import jax
    import jax.numpy as jnp
    from jax.sharding import SingleDeviceSharding

    from benchmark.harness import swa_attention_cost
    from mxnet_tpu.ops import attention as att

    monkeypatch.setattr(att, "pallas_interpret", lambda: False)
    chip = SingleDeviceSharding(v5e.devices[0])

    def arg(n):
        return jax.ShapeDtypeStruct((1, n, 4096, 128), jnp.dtype(dtype),
                                    sharding=chip)

    def forward_and_backward(q, k, v, g):
        out, vjp = jax.vjp(lambda q, k, v: att.flash_attention(
            q, k, v, causal=True, window=window), q, k, v)
        return (out,) + vjp(g)

    assert att._block_choices(arg(heads), arg(8))[0] == (1024, 1024)
    if window:
        assert att._masked_offsets(1024, 1024, window) == [0, 1]
    with jax.default_matmul_precision(
            "highest" if dtype == "float32" else "default"):
        text = jax.jit(forward_and_backward).lower(
            arg(heads), arg(8), arg(8), arg(heads)).compile().as_text()
    shapes = {"rows": 1, "seq": 4096, "heads": heads, "kv_heads": 8,
              "d": 128}
    size = jnp.dtype(dtype).itemsize
    assert sorted(swa_attention_cost.kernel_kind(k, shapes)
                  for k in _kernel_instructions(text)) == [
        ("dkv", size), ("dq", size), ("forward", size)]


def test_on_the_v5e_the_feed_forwards_gradients_read_dg_and_du_in_bfloat16(
        v5e):
    """One ``gated_silu`` at LFM2's widths (2 x 8,192 x 2,048 -> 7,168),
    bfloat16, under ``jax.grad``, as the v5e's compiler emits it: eight
    products under ``ffn.gated`` (the two of the forward pass that the
    gradient needs and the six of the backward pass), and of them only
    ``W_down``'s gradient reads float32 operands through its fusion, ``g``
    and ``u``, from which it forms ``h`` (``tools/step_text.py``'s
    ``float32_products``; left to autodiff, four more formed ``dg`` or
    ``du`` from float32 ``g``, ``u`` and ``dh`` on every pass over their
    results).  Of the layer's ``(rows, hidden)`` arrays ``g`` and ``u`` are
    written once as float32, ``dg`` and ``du`` once as bfloat16, and
    nothing else: ``dh`` is formed in the fusion that writes ``dg`` and
    ``du``."""
    import os
    import sys

    import jax
    import jax.numpy as jnp
    from jax.sharding import SingleDeviceSharding

    from mxnet_tpu.ops import llm

    sys.path.insert(0, os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))))
    from benchmark.harness import hlo_cost, scope_time
    from tools.step_text import float32_products, unfused

    chip = SingleDeviceSharding(v5e.devices[0])
    rows, units, hidden = (2, 8192), 2048, 7168

    def arg(*shape):
        return jax.ShapeDtypeStruct(shape, jnp.bfloat16, sharding=chip)

    text = jax.jit(jax.grad(
        lambda x, wg, wu, wd, dy: jnp.sum(
            llm.gated_silu(x, wg, wu, wd).astype(jnp.float32)
            * dy.astype(jnp.float32)), argnums=(0, 1, 2, 3))).lower(
        arg(*rows, units), arg(hidden, units), arg(hidden, units),
        arg(units, hidden), arg(*rows, units)).compile().as_text()
    products = float32_products(text, "ffn.gated")
    assert len(products) == 8
    module = hlo_cost.Module(text)
    under = scope_time._under(("ffn.gated",))
    written, results = [], {}
    for comp in unfused(module).values():
        for name, (opcode, (result, _, _)) in comp.items():
            if opcode in ("parameter", "get-tuple-element", "tuple",
                          "bitcast"):
                continue
            results[name] = [dims for _, dims, _ in
                             hlo_cost.shape_dims(result)]
            if under.search(module.instructions[name][1]):
                written += [(dtype, dims) for dtype, dims, _ in
                            hlo_cost.shape_dims(result)]
    reading = {name: operands for name, operands in products.items()
               if operands}
    assert len(reading) == 1 and len(*reading.values()) == 2, products
    assert results[next(iter(reading))] == [[units, hidden]]
    wide = [dtype for dtype, dims in written if dims == [*rows, hidden]]
    assert sorted(wide) == ["bf16"] * 2 + ["f32"] * 2, written


def test_on_the_v5e_the_widened_stream_is_held_flat_and_never_relaid(v5e):
    """One sublayer's hyper-connection at ``xing4_0_29b_a4b_ep8``'s widths
    (1 x 4,096 tokens, 4 streams of 3,584, bfloat16), coefficients and both
    mixes around a stand-in sublayer, under ``jax.grad``, as the v5e's
    compiler emits it: every array of the stream's size is ``(1, 4096,
    14336)`` tiled (8, 128) with no padding, no ``(1, 4096, 4, 3584)`` form
    and no copy of the stream stands anywhere (held four streams deep, as
    ``(1, 4096, 4, 3584)``, the compiler copies it into the mixes' layout
    and back); instructions lie under ``hc.coef`` and ``hc.mix``, and
    Sinkhorn's 20 iterations are one loop forward and one backward."""
    import os
    import re
    import sys

    import jax
    import jax.numpy as jnp
    from jax.sharding import SingleDeviceSharding

    from mxnet_tpu.ops import llm

    sys.path.insert(0, os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))))
    from benchmark.harness import hlo_cost, scope_time
    from tools.step_text import unfused

    chip = SingleDeviceSharding(v5e.devices[0])
    n, units, seq = 4, 3584, 4096

    def arg(*shape, dtype=jnp.bfloat16):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=chip)

    def loss(x, phi, bias, alpha, g):
        h_pre, h_post, h_res = llm.hc_coefficients(x, phi, bias, alpha)
        u = llm.hc_pre_mix(x, h_pre)
        out = llm.hc_post_mix(x, h_res, h_post, jnp.tanh(u))
        return jnp.sum(out.astype(jnp.float32) * g.astype(jnp.float32))

    text = jax.jit(jax.grad(loss, argnums=(0, 1, 2, 3))).lower(
        arg(1, seq, n * units), arg(n * units, 24),
        arg(24, dtype=jnp.float32), arg(3, dtype=jnp.float32),
        arg(1, seq, n * units)).compile().as_text()
    assert "4,3584]" not in text
    stream = re.findall(r"(bf16|f32)\[1,4096,14336\]\{([^}]*)\}", text)
    assert stream and all(layout.startswith("2,1,0:T(8,128)")
                          for _, layout in stream), set(stream)
    module = hlo_cost.Module(text)
    scopes = {"hc.coef": 0, "hc.mix": 0}
    for comp in unfused(module).values():
        for name, (opcode, (result, _, _)) in comp.items():
            if opcode in ("copy", "transpose"):
                assert "4096,14336" not in result, (name, result)
            for scope in scopes:
                if scope_time._under((scope,)).search(
                        module.instructions.get(name, (0, ""))[1]):
                    scopes[scope] += 1
    assert min(scopes.values()) > 0, scopes
    assert len(re.findall(r" while\(", text)) == 2


@pytest.mark.parametrize("part", ["prefix", "sliding_attention",
                                  "full_attention"])
def test_on_the_v5e_the_references_timed_rows_fit_beside_the_steps_state(
        v5e, part):
    """``mellum2_moe_train_seq16k``'s reference computes its rows of the
    timed shape, 1 x 16,384 in float32 at highest precision, on the chip
    after the window, where the step's state still lies (7.6 GB of 16):
    each of its three programs compiles for the described chip at the real
    widths and takes, arguments, results and temporaries together, under
    6 GB (the prefix's loss over chunks 1.7, the window layer 3.0, the
    full layer 4.8; whole logits alone would be 4.8 of temporaries)."""
    import json
    import os

    import jax
    import jax.numpy as jnp
    from jax.sharding import SingleDeviceSharding

    from benchmark.harness import manifest

    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    with open(os.path.join(root, "benchmark", "configs",
                           "mellum2_12b_ep4.json")) as f:
        config = json.load(f)
    arch = config["architecture"]
    reference = manifest.load_module(os.path.join(root, config["reference"]))
    chip = SingleDeviceSharding(v5e.devices[0])

    def of(*shape, dtype=jnp.float32):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=chip)

    hid, vocab, d = arch["hidden_size"], arch["vocab_size"], arch["head_dim"]
    row = of(1, config["input"]["shape"][0], dtype=jnp.int32)
    prefix_row, attention_row = reference.timed_programs(arch)
    with jax.default_matmul_precision("highest"):
        if part == "prefix":
            lowered = prefix_row.lower(
                {"embed_weight": of(vocab, hid), "norm_weight": of(hid),
                 "head_weight": of(vocab, hid)}, row)
        else:
            pre = "l%d_" % reference.timed_layer(arch, part)
            wide = arch["num_attention_heads"] * d
            narrow = arch["num_key_value_heads"] * d
            layer = {pre + "attn_q_weight": of(wide, hid),
                     pre + "attn_k_weight": of(narrow, hid),
                     pre + "attn_v_weight": of(narrow, hid),
                     pre + "attn_o_weight": of(hid, wide),
                     pre + "attn_qnorm_weight": of(d),
                     pre + "attn_knorm_weight": of(d)}
            rest = {"embed_weight": of(vocab, hid),
                    pre + "ln1_weight": of(hid)}
            assert set(layer) | set(rest) <= \
                reference.timed_parameters(arch)
            lowered = attention_row[part].lower(layer, rest, row)
        memory = lowered.compile().memory_analysis()
    assert memory.temp_size_in_bytes + memory.argument_size_in_bytes \
        + memory.output_size_in_bytes < 6e9


def test_a_program_from_the_compile_cache_forgets_its_result_layout(
        tmp_path):
    """The toolchain's behaviour this design stands on (jax 0.9.0; the same
    on the v5e with libtpu 0.0.34, PERF.md Findings PR 27): compiled in the
    process, a program gives its result in the layout it was compiled for;
    loaded from the persistent compile cache, in the default one.  If the
    second line ever reads (1, 2, 3, 0) too, the state could hold the
    compiler's layouts themselves (ISSUE 27's first design)."""
    import os
    import subprocess
    import sys

    env = dict(os.environ, JAX_PLATFORMS="cpu",
               JAX_COMPILATION_CACHE_DIR=str(tmp_path))
    runs = [subprocess.run([sys.executable, "-c", _ASKS_FOR_A_LAYOUT],
                           env=env, capture_output=True, text=True,
                           timeout=120) for _ in range(2)]
    assert [r.returncode for r in runs] == [0, 0], runs[1].stderr[-2000:]
    assert [r.stdout.strip().splitlines()[-1] for r in runs] \
        == ["(1, 2, 3, 0)", "(0, 1, 2, 3)"]
