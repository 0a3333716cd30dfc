"""ray_tpu.parallel — GSPMD parallelism over TPU device meshes.

The TPU-native replacement for everything the reference delegates to
torch.distributed/NCCL (ref: SURVEY.md §2.3): data/FSDP/tensor parallelism
as sharding rules over a jax.sharding.Mesh, pipeline parallelism as a
shard_map microbatch rotation, and context parallelism (ring attention,
Ulysses all-to-all) — absent from the reference (§5.7) and first-class
here.
"""

from .mesh import (AXIS_ORDER, gang_mesh,  # noqa: F401
                   process_contiguous_devices)
from .sharding import (ShardingRules, logical_sharding,  # noqa: F401
                       logical_spec, with_logical_constraint)
from .partition_rules import (match_partition_rules,  # noqa: F401
                              named_tree_map, prune_spec, shard_tree,
                              tree_shardings)
from .ring_attention import ring_attention  # noqa: F401
from .ulysses import ulysses_attention  # noqa: F401
from .pipeline import pipeline_apply  # noqa: F401
