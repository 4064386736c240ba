"""Distributed / parallel execution — SPMD over a jax.sharding.Mesh.

This package replaces ALL of the reference's parallelism machinery with the
TPU-native SPMD design (SURVEY.md §2.5):

* ``MultiGradientMachine`` ring data-parallel (gserver/gradientmachines/
  MultiGradientMachine.h:44-97)         -> :mod:`data_parallel` (batch sharded over the
  ``data`` mesh axis; XLA inserts ``psum`` over ICI).
* pserver sharded params + RemoteParameterUpdater (pserver/ParameterServer2.h,
  trainer/RemoteParameterUpdater.h)     -> collective DP; optimizer state sharded with
  ZeRO-style ``reduce_scatter`` when requested.
* ``ParallelNeuralNetwork`` per-layer device placement (--parallel_nn)
                                        -> :mod:`tensor_parallel` sharding annotations +
  :mod:`pipeline` stage partitioning over a ``pipe`` mesh axis.
* NCCL ops (operators/nccl_op.cc:19-148) -> :mod:`collectives` named XLA collectives.
* (modern capability extension, no 2017 analog) :mod:`ring_attention` — sequence-dim
  sharding with blockwise attention over a ``seq`` mesh axis via ``ppermute``.
* sparse/embedding parallel (SparseRowMatrix + remote sparse updates, §2.5)
                                        -> :mod:`tensor_parallel` ShardedEmbedding, and its
  modern extension :mod:`moe` — expert parallelism (top-k token-choice MoE,
  experts + tokens sharded over an ``expert`` axis, all_to_all dispatch).
"""

from .mesh import (MeshSpec, current_mesh, make_mesh, local_mesh,
                   mesh_axis_size, use_mesh)
from .sharding import (replicate, shard, shard_batch, shard_params,
                       with_sharding_constraint, ShardingRules, SpecLayout)
from .collectives import (all_reduce, all_gather, reduce_scatter, broadcast,
                          all_to_all, permute_ring, axis_index)
from .data_parallel import DataParallel, Zero1DataParallel, Zero1State
from .tensor_parallel import ColumnParallelLinear, RowParallelLinear, ShardedEmbedding
from .ring_attention import (ring_attention, blockwise_attention,
                             ring_self_attention, sharded_flash_attention,
                             ulysses_attention)
from .pipeline import PipelineStage, pipeline_1f1b, pipeline_spmd
from .moe import ExpertParallelMoE, init_moe_params, moe_ffn_dense
from . import multihost

__all__ = [
    "MeshSpec", "make_mesh", "local_mesh", "mesh_axis_size",
    "current_mesh", "use_mesh",
    "replicate", "shard", "shard_batch", "shard_params",
    "with_sharding_constraint", "ShardingRules", "SpecLayout",
    "all_reduce", "all_gather", "reduce_scatter", "broadcast", "all_to_all",
    "permute_ring", "axis_index",
    "DataParallel",
    "Zero1DataParallel",
    "Zero1State",
    "ColumnParallelLinear", "RowParallelLinear", "ShardedEmbedding",
    "ring_attention", "blockwise_attention", "ring_self_attention",
    "sharded_flash_attention", "ulysses_attention",
    "PipelineStage", "pipeline_spmd", "pipeline_1f1b", "multihost",
    "ExpertParallelMoE", "init_moe_params", "moe_ffn_dense",
]
