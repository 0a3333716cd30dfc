"""ray_tpu.train — the Train stack (JaxTrainer, worker groups, sessions).

Role-equivalent to the reference's ray.train (ref: SURVEY.md §2.4).  The
low-level pure-function training step lives in train_step.py; the
actor-based trainer stack (WorkerGroup/BackendExecutor/JaxTrainer) builds
on the cluster runtime.
"""

from .train_step import (TrainState, make_optimizer,  # noqa: F401
                         make_sharded_train_step, make_train_step)
from .distributed import (DistributedMesh, derive_mesh_shape,  # noqa
                          global_batch_slice, mesh_coords_for_rank,
                          put_global_batch, rules_for_model,
                          setup_distributed_mesh, shard_train_state)
from .checkpoint import Checkpoint, CheckpointManager  # noqa: F401
from .config import (CheckpointConfig, FailureConfig, Result,  # noqa
                     RunConfig, ScalingConfig, TelemetryConfig)
from .session import (checkpoint_dir, checkpoint_on_notice,  # noqa
                      data_wait, get_checkpoint, get_dataset_shard,
                      get_local_rank, get_world_rank, get_world_size,
                      interrupted, interruption, iter_device_batches,
                      load_sharded_checkpoint, report,
                      save_sharded_checkpoint, stats)
from .sharded_checkpoint import (load_sharded,  # noqa: F401
                                 save_sharded, verify_checkpoint)
from .trainer import (DataParallelTrainer, JaxTrainer,  # noqa: F401
                      TorchTrainer)
from .worker_group import PreemptionError, WorkerGroup  # noqa: F401
from .v2 import (ControllerState, ElasticScalingPolicy,  # noqa: F401
                 FailureDecision, FailurePolicy, FixedScalingPolicy,
                 JaxTrainerV2, RestartBackoff, TrainControllerV2)
