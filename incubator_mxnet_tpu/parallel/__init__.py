"""`mx.parallel` — first-class SPMD parallelism over the TPU device mesh.

This is the TPU-native generalization of the reference's distributed stack
(`src/kvstore/` NCCL/ps-lite, SURVEY.md §2.4): instead of push/pull servers
and reduction trees, training steps are jit-compiled SPMD programs over a
`jax.sharding.Mesh`, with XLA inserting ICI/DCN collectives:

* `mesh.py` — mesh construction (dp/tp/pp/sp axes) incl. multi-host
* `collectives.py` — named-axis collective wrappers (the NCCL verbs)
* `data_parallel.py` — shard_map data-parallel train step (kvstore 'tpu'
  semantics as one fused program)
* `tensor_parallel.py` — parameter-sharding rules (the model-parallel
  `group2ctx` answer, declarative)
* `ring_attention.py` — ring attention over the sp axis: blockwise softmax
  with ppermute'd KV shards (long-context support beyond the reference's
  bucketing strategy)
* `pipeline.py` — pipeline-parallel microbatch schedule over `pp`
* `expert_parallel.py` — which experts of a routed layer a chip holds
"""
from .mesh import make_mesh, mesh_axes, local_mesh, rebuild
from .gluon_bridge import (shard_block, block_shardings,
                           shard_state_for_zero, put)
from .collectives import (all_reduce, all_gather, reduce_scatter, ppermute,
                          broadcast, supervised)
from .data_parallel import data_parallel_step, replicate, unreplicate
from .tensor_parallel import shard_params, ShardingRules
from .expert_parallel import ExpertShare
from .ring_attention import ring_attention, blockwise_attention
from .pipeline import pipeline_step, pipeline_train_step
from .zero import zero_train_step, zero_update, zero_init_state
