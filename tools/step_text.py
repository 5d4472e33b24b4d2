#!/usr/bin/env python3
"""sha256 of the lowered (StableHLO) timed step of the benchmark's cells,
from the tree given: the same text on two trees is the same compiled
program, so a change that must leave a cell alone shows it here, on the CPU,
before any chip run (PERF.md Findings PR 30, PR 34).

    python tools/step_text.py <tree> [<cell> ...] [--out DIR] [--compile]

Run it once on a copy of the parent commit and once on the change, each
time with that tree's own code (the tree goes first on ``sys.path``).  The
CNN cells' steps are lowered on the host platform with the cell's number of
devices.  A language cell's step is traced for a described v5e with its
Pallas kernels in it; a Mosaic payload carries the source's path and line
numbers, so the text is hashed with the payloads masked, and the step's
jaxpr, which holds the kernels' bodies, beside it.  ``--out`` keeps the
texts, for a diff where two hashes differ.  ``--compile`` also compiles a
language cell's step for that v5e (Mosaic and all: what the compiler
refuses here costs no chip time) and prints the program's temporaries and
state, the two parts of ``peak_hbm_gb``.  ``float32_products`` reads a
compiled module's products under a named scope.
"""

import argparse
import hashlib
import json
import math
import os
import re
import sys


def digest(text):
    return hashlib.sha256(text.encode()).hexdigest()[:16]


def called(parts):
    """The computations an instruction calls, in the order its attributes
    name them (``hlo_cost``'s reading): a fusion's body first."""
    from benchmark.harness import hlo_cost

    return [name.lstrip("%") for group in hlo_cost._CALLED.findall(parts[2])
            for name in re.split(r",\s*", group)]


def unfused(module):
    """{computation: {instruction: (opcode, parts)}} of a
    ``hlo_cost.Module``'s computations that no fusion calls: the ones whose
    instructions run as such (the entry, loop bodies, branches)."""
    fused = {called(parts)[0] for comp in module._comps.values()
             for opcode, parts in comp.values() if opcode == "fusion"}
    return {name: comp for name, comp in module._comps.items()
            if name not in fused}


def float32_products(text, scope):
    """{instruction: [float32 operands]} of the product instructions of an
    optimized module outside fusion bodies (a fusion with a dot or a
    convolution in it, or one such instruction) whose ``op_name`` lies
    under the named scope ``scope``.  A float32 operand is a fusion's
    parameter, or a plain product's operand, of as many elements as the
    product's operand it reaches through the fusion's elementwise
    arithmetic: the fusion forms that operand from it again on every pass
    over its result.  A row's scale broadcast over the row (a norm's) or
    a value that only meets the product's result is not one.  Empty where
    there is none."""
    from benchmark.harness import hlo_cost, scope_time

    def elements(result):
        typed = hlo_cost.shape_dims(result)
        return (typed[0][0], math.prod(typed[0][1])) if typed else ("", 0)

    def operands(parts):
        return re.findall(r"%([\w.\-]+)", parts[1])

    module = hlo_cost.Module(text)
    under = scope_time._under((scope,))
    found = {}
    for comp in unfused(module).values():
        for name, (opcode, parts) in comp.items():
            flops, op_name = module.instructions.get(name, (0, ""))
            if flops <= 0 or not under.search(op_name):
                continue
            body = module._comps[called(parts)[0]] \
                if opcode == "fusion" else {name: (opcode, parts)}
            float32 = set()
            for o, p in body.values():
                if o not in ("dot", "convolution"):
                    continue
                for operand in operands(p):
                    result = (body.get(operand) or comp.get(operand)
                              or ("", ("", "", "")))[1][0]
                    size = elements(result)[1]
                    stack, seen = [operand], set()
                    while stack:
                        at = stack.pop()
                        if at in seen:
                            continue
                        seen.add(at)
                        if at in body and body[at][0] != "parameter" \
                                and at != name:
                            stack += operands(body[at][1])
                            continue
                        source = (body.get(at) or comp.get(at)
                                  or ("", ("", "", "")))[1][0]
                        if elements(source) == ("f32", size):
                            float32.add(at)
            found[name] = sorted(float32)
    return found


def cnn_step_text(root, cell, compile_it=False):
    import jax
    import jax.numpy as jnp

    import gluon_model
    from mxnet_tpu import gluon
    from mxnet_tpu.parallel.gluon_step import GluonTrainStep
    from mxnet_tpu.parallel.mesh import create_mesh

    cfg, traffic, train = cell.config, cell.traffic, cell.config["training"]
    mesh = create_mesh(dict(traffic["mesh"]),
                       devices=jax.devices()[:cell.chips])
    net = gluon_model.build_net(cfg, 7)
    gluon_model.predict_logits(net, gluon_model.check_batch(cfg, 7)[0][:1])
    step = GluonTrainStep(
        net, gluon.loss.SoftmaxCrossEntropyLoss(), mesh=mesh, lr=train["lr"],
        momentum=train["momentum"], wd=train["wd"],
        compute_dtype=traffic["compute_dtype"])
    batch = int(traffic["global_batch"])
    x = jax.ShapeDtypeStruct((batch,) + tuple(cfg["input"]["shape"]),
                             jnp.float32, sharding=step.batch_sharding)
    y = jax.ShapeDtypeStruct((batch,), jnp.int32,
                             sharding=step.label_sharding)
    rest = (jax.random.PRNGKey(0), tuple(0.0 for _ in step._rule.slots))
    step._orders = tuple(tuple(tuple(range(v.ndim)) for v in tree)
                         for tree in step._held)
    return {"text": step._jit().lower(*step._held, x, y, *rest).as_text()}


def language_step_text(root, cell, compile_it=False):
    import jax
    import jax.numpy as jnp
    import numpy as np
    from jax.experimental import topologies
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

    from benchmark import run
    from mxnet_tpu.ops import attention, llm

    attention.pallas_interpret = llm.pallas_interpret = lambda: False
    ctx = run.Context(cell, seed=7, devices=jax.devices()[:1])
    ctx.say = lambda message: None
    step = cell.entry().build(ctx)._make_step(cell.traffic["compute_dtype"])
    v5e = topologies.get_topology_desc(platform="tpu",
                                       topology_name="v5e:2x2")
    mesh = Mesh(np.array(v5e.devices[:1]), ("dp",))
    repl, batch = NamedSharding(mesh, P()), NamedSharding(mesh, P("dp"))
    step._repl, step._rest_in = repl, (batch, batch, repl, repl)
    step._form.shards = jax.tree.map(lambda _: repl, step._form.shards)
    x = jax.ShapeDtypeStruct((int(cell.traffic["global_batch"]),
                              int(cell.config["input"]["shape"][0])),
                             jnp.int32)
    rest = [jax.ShapeDtypeStruct((2,), jnp.uint32),
            tuple(jax.ShapeDtypeStruct((), jnp.float32)
                  for _ in step._rule.slots)]
    step._orders = tuple(tuple(tuple(range(v.ndim)) for v in tree)
                         for tree in step._held)
    held = [tuple(jax.ShapeDtypeStruct(v.shape, v.dtype) for v in tree)
            for tree in step._held]
    lowered = step._jit().lower(*held, x, x, *rest)
    if compile_it:
        memory = lowered.compile().memory_analysis()
        print("%s compiled: temporaries %.3f GB, arguments %.3f GB"
              % (cell.name, memory.temp_size_in_bytes / 1e9,
                 memory.argument_size_in_bytes / 1e9), flush=True)
    text = lowered.as_text()
    text = re.sub(r'backend_config = "[^"]*"',
                  'backend_config = "<payload>"', text)
    text = re.sub(r"backend_config = \{[^\n]*", "backend_config = <payload>",
                  text)
    jaxpr = str(jax.make_jaxpr(step._step_py)(*held, x, x, *rest))
    jaxpr = re.sub(r"/[^ \"']*/mxnet_tpu/", "<root>/mxnet_tpu/", jaxpr)
    return {"text": text, "jaxpr": re.sub(r" at 0x[0-9a-f]+", "", jaxpr)}


def main(argv=None):
    parser = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    parser.add_argument("tree")
    parser.add_argument("cells", nargs="*")
    parser.add_argument("--out")
    parser.add_argument("--compile", action="store_true")
    args = parser.parse_args(argv)
    root = os.path.abspath(args.tree)
    os.environ.setdefault("XLA_FLAGS",
                          "--xla_force_host_platform_device_count=4")
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    os.chdir(root)
    sys.path[:0] = [root, os.path.join(root, "benchmark", "entries")]

    import mxnet_tpu
    from benchmark.harness import manifest

    if not os.path.abspath(mxnet_tpu.__file__).startswith(root + os.sep):
        raise SystemExit("mxnet_tpu came from %s, not from %s"
                         % (mxnet_tpu.__file__, root))
    with open(os.path.join(root, "BENCHMARK.json")) as f:
        names = args.cells or [w["name"] for w in json.load(f)["workloads"]]
    for name in names:
        cell = manifest.Manifest(root).cell(name)
        lower = cnn_step_text if cell.traffic.get(
            "entry", "gluon_train_step") == "gluon_train_step" \
            else language_step_text
        texts = lower(root, cell, args.compile)
        print(name, " ".join(
            "%s %s (%d lines)" % (kind, digest(t), len(t.splitlines()))
            for kind, t in sorted(texts.items())), flush=True)
        if args.out:
            os.makedirs(args.out, exist_ok=True)
            for kind, t in texts.items():
                with open(os.path.join(args.out, "%s.%s.txt"
                                       % (name, kind)), "w") as f:
                    f.write(t)
    return 0


if __name__ == "__main__":
    sys.exit(main())
