"""Parallelism layer: device meshes, sharded training steps, collectives.

This package is the TPU-native replacement for the reference's entire
distribution stack (SURVEY.md §2.2): KVStore comm strategies
(src/kvstore/comm.h), NCCL (kvstore_nccl.h), the ps-lite parameter server
(kvstore_dist.h), and the engine's copy threads all collapse into XLA
collectives over a ``jax.sharding.Mesh``:

- data parallelism   → batch sharded on the 'data' mesh axis; gradient psum
  inserted by GSPMD (≙ kvstore push/pull + NCCL allreduce)
- tensor parallelism → parameters sharded on 'model' (exceeds reference)
- optimizer sharding → optimizer state sharded on 'data' (ZeRO-style; ≙ the
  parameter server holding the optimizer, kvstore_dist_server.h:187)
- multi-host        → jax.distributed + the same mesh spanning hosts
"""
from .mesh import make_mesh, current_mesh, set_default_mesh
from .step import TrainStep, exit_weighted_loss
from .ring import ring_attention, sequence_shard

__all__ = ["make_mesh", "current_mesh", "set_default_mesh", "TrainStep",
           "exit_weighted_loss", "ring_attention", "sequence_shard"]
