"""``mxnet_tpu.parallel`` — meshes, sharded training steps, collectives.

This is the TPU-native replacement for the reference's distributed stack
(SURVEY.md §2.3): instead of NCCL reduce (kvstore_nccl.h), P2P/tree
reduce (comm.h, comm_tree.h, gpu_topology.h) and the ps-lite parameter
server (kvstore_dist*.h), everything is a ``jax.sharding.Mesh`` +
sharding annotations; XLA inserts psum/all-gather/reduce-scatter over
ICI (in-slice) and DCN (cross-slice).
"""

from .mesh import (create_mesh, data_parallel_sharding, get_default_mesh,  # noqa: F401
                   host_allreduce, set_default_mesh)
from .gluon_step import GluonTrainStep  # noqa: F401
from .ring_attention import ring_attention, ulysses_attention  # noqa: F401
