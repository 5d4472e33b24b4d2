"""Pipeline parallelism (GPipe) over the 'pp' mesh axis.

The reference's closest capability is manual model parallelism via
`group2ctx` ctx-groups (src/executor/graph_executor.cc:1628) and
step-wise `PartialForward` (graph_executor.cc:68); it has no pipeline
schedule.  This module goes beyond parity with a TPU-native GPipe:

- each 'pp' rank holds ONE stage's parameters (stacked pytree sharded on
  the leading axis);
- microbatches stream through the ring: every tick each rank applies its
  stage, then `lax.ppermute` passes activations to the next rank over
  ICI — the classic fill/steady/drain schedule, M + P - 1 ticks for M
  microbatches on P stages;
- the whole schedule is a `lax.scan` inside `shard_map`, so XLA overlaps
  the neighbour transfer with the next tick's compute, and `jax.grad`
  differentiates straight through it (ppermute's transpose is the
  reverse-direction ppermute) — backward runs the reverse pipeline
  automatically, no hand-written 1F1B machinery.

Stages must be shape-homogeneous (activation in == activation out),
the standard case for stacked transformer blocks; the embed/head live
outside the pipelined middle.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
from jax import lax, shard_map

__all__ = ["GPipe", "stack_stage_params"]


def stack_stage_params(per_stage_params):
    """[stage0_tree, stage1_tree, ...] → one tree with leading stage axis
    (shard it over 'pp')."""
    return jax.tree_util.tree_map(lambda *xs: jnp.stack(xs),
                                  *per_stage_params)


class GPipe:
    """Compile `stage_fn` into a pipelined forward over mesh axis 'pp'.

    Parameters
    ----------
    stage_fn : (stage_params, x) -> y with y.shape == x.shape; with
        ``has_aux`` the signature is (stage_params, x, aux) ->
        (y, new_aux) where aux is this stage's mutable state (BatchNorm
        running stats), threaded through the schedule per rank
    mesh : jax Mesh with a 'pp' axis covering all its devices' stages
    n_microbatches : how many microbatches the global batch splits into
        (≥ n_stages keeps the bubble fraction at (P-1)/(M+P-1))
    axis : mesh axis name
    has_aux : stages carry aux state.  Aux updates chain across the
        stage's microbatches (EMA applied once per VALID tick — fill
        and drain ticks, where a rank chews zero-padding, leave the aux
        untouched), so the semantics match training with
        microbatch-sized batches — the standard GPipe BatchNorm
        contract.

    Call with (stacked_params, x) — or (stacked_params, x, stacked_aux)
    with ``has_aux`` — where stacked trees have a leading stage axis and
    x is the GLOBAL batch (dim 0 divisible by n_microbatches); returns
    the transformed global batch (plus the updated stacked aux).
    """

    def __init__(self, stage_fn, mesh, n_microbatches=None, axis="pp",
                 has_aux=False, batch_spec=None, param_specs=None):
        self.mesh = mesh
        self.axis = axis
        self.n_stages = mesh.shape[axis]
        self.n_micro = n_microbatches or self.n_stages
        self.has_aux = has_aux
        # ONE schedule implementation: aux-free stage fns are adapted to
        # the (params, x, aux) -> (y, aux) signature with an empty aux
        # tree, so the subtle fill/steady/drain logic exists once
        if has_aux:
            self.stage_fn = stage_fn
        else:
            self.stage_fn = lambda p, x, aux: (stage_fn(p, x), aux)

        from jax.sharding import PartitionSpec as P

        # batch_spec: how x (and the output) is laid over the OTHER
        # mesh axes — e.g. P('dp', None) composes the pipeline with
        # data parallelism (each dp slice streams its own microbatches).
        # param_specs: a pytree(-prefix) of specs for the stacked stage
        # params when stage weights also shard over other axes (e.g.
        # P('pp', None, 'tp') for Megatron column-parallel stages); the
        # default P(axis) shards the stage dim only.
        self._fn = shard_map(
            self._device_program, mesh=mesh, check_vma=False,
            in_specs=(P(axis) if param_specs is None else param_specs,
                      P() if batch_spec is None else batch_spec,
                      P(axis)),
            out_specs=(P() if batch_spec is None else batch_spec,
                       P(axis)))

    def _device_program(self, params, x, aux):
        """Runs per-device: params/aux carry a leading stage axis of
        size 1 (this rank's stage); x is the full global batch.  Aux
        rides the scan carry; a tick's update is kept only when the
        tick processed one of this rank's M real microbatches (rank i
        is valid for i <= t <= i + M - 1) — fill/drain ticks chew
        zero-padding and must not touch stage state."""
        axis, M = self.axis, self.n_micro
        params = jax.tree_util.tree_map(lambda a: a[0], params)
        aux0 = jax.tree_util.tree_map(lambda a: a[0], aux)
        i = lax.axis_index(axis)
        P = self.n_stages

        gb = x.shape[0]
        assert gb % M == 0, "global batch %d %% %d microbatches" % (gb, M)
        micro = x.reshape((M, gb // M) + x.shape[1:])

        perm = [(j, (j + 1) % P) for j in range(P)]
        state = jnp.zeros_like(micro[0])
        outs = jnp.zeros_like(micro)

        def tick(carry, t):
            state, outs, aux = carry
            # stage 0 ingests microbatch t during the fill phase
            inp = micro[jnp.clip(t, 0, M - 1)]
            cur = jnp.where(i == 0, jnp.where(t < M, inp, state), state)
            y, new_aux = self.stage_fn(params, cur, aux)
            valid = (t >= i) & (t <= i + M - 1)
            aux = jax.tree_util.tree_map(
                lambda n, o: jnp.where(valid, n, o), new_aux, aux)
            # the last stage emits microbatch m = t - (P - 1)
            m = t - (P - 1)
            written = lax.dynamic_update_index_in_dim(
                outs, y, jnp.clip(m, 0, M - 1), 0)
            outs = jnp.where((i == P - 1) & (m >= 0), written, outs)
            state = lax.ppermute(y, axis, perm)
            return (state, outs, aux), None

        (_, outs, aux_f), _ = lax.scan(tick, (state, outs, aux0),
                                       jnp.arange(M + P - 1))
        # result lives on the last rank; make it mesh-invariant
        outs = lax.psum(jnp.where(i == P - 1, outs, jnp.zeros_like(outs)),
                        axis)
        return (outs.reshape((gb,) + x.shape[1:]),
                jax.tree_util.tree_map(lambda a: a[None], aux_f))

    def __call__(self, stacked_params, x, stacked_aux=None):
        out, aux = self._fn(stacked_params, x,
                            {} if stacked_aux is None else stacked_aux)
        if self.has_aux:
            return out, aux
        return out
