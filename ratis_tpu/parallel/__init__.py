"""Multi-device scaling of the quorum engine (SURVEY.md §2.9: the
multi-raft group batch is this framework's data-parallel axis)."""

from ratis_tpu.parallel.mesh import (GROUP_AXIS, device_state_shardings,
                                     engine_shardings, make_group_mesh,
                                     shard_batch, shard_device_state,
                                     sharded_engine_step,
                                     sharded_resident_step)

__all__ = ["GROUP_AXIS", "device_state_shardings", "engine_shardings",
           "make_group_mesh", "shard_batch", "shard_device_state",
           "sharded_engine_step", "sharded_resident_step"]
