from repro.sharding.specs import (can_shard_flat, data_axis_size, dp_axes,
                                  run_batch_specs, shard_map_flat,
                                  shard_run_batch)

__all__ = ["dp_axes", "run_batch_specs", "shard_run_batch",
           "data_axis_size", "can_shard_flat", "shard_map_flat"]
