"""mxnet_tpu.parallel — the distributed layer, TPU-native.

The reference (SURVEY.md §2d/§2e) is a data-parallel framework with three
gradient-sync transports behind the KVStore interface (in-process reduce —
src/kvstore/comm.h; NCCL — src/kvstore/kvstore_nccl.h; ps-lite parameter
server — src/kvstore/kvstore_dist.h) plus placement-based model parallelism
(`group2ctx` + nnvm PlaceDevice).

The TPU-native design inverts this: ONE collective substrate — XLA
collectives over ICI within a slice, DCN across slices — under explicit
`jax.sharding` annotations on a device mesh.  Modules:

  mesh      — DeviceMesh: named-axis device meshes (dp/fsdp/tp/pp/sp/ep)
  sharding  — PartitionSpec rules: regex -> spec param sharding,
              batch sharding, constraint helpers
  spmd      — SPMDTrainer: whole-training-step-in-one-XLA-program
              (forward+backward+psum+optimizer), the TPU perf path that
              subsumes Trainer+KVStore for scale-out
  dist      — multi-host bootstrap (jax.distributed) keeping the
              reference launcher's DMLC_* env contract, DCN allreduce,
              barrier
  ulysses   — all-to-all sequence parallelism (DeepSpeed-Ulysses layout)
  moe       — mixture of experts that holds a share of its experts:
              sigmoid top-k routing without drops, grouped products,
              expert parallelism over 'ep'
  ring      — ring attention: sequence/context parallelism over the 'sp'
              mesh axis via shard_map + ppermute (beyond-reference)
  pipeline  — pipeline parallelism over the 'pp' axis (beyond-reference)
"""
from __future__ import annotations

from .mesh import DeviceMesh, make_mesh, current_mesh, get_mesh
from .sharding import (ShardingRules, named_sharding, replicated,
                       shard_batch, constraint, DEFAULT_RULES)
from .spmd import SPMDTrainer
from .checkpoint import save_sharded, load_sharded
from . import dist
from . import ring
from . import ulysses
from . import moe
from . import pipeline

__all__ = [
    "DeviceMesh", "make_mesh", "current_mesh", "get_mesh",
    "ShardingRules", "named_sharding", "replicated", "shard_batch",
    "constraint", "DEFAULT_RULES",
    "SPMDTrainer",
    "dist", "ring", "ulysses", "moe", "pipeline",
]
