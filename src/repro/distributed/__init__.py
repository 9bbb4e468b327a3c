"""Distribution layer: sharded triangle counts and resilient, resumable counts."""
from repro.distributed.tc import (
    Sharded2DExecutor,
    ShardedColsExecutor,
    TC_PLACEMENTS,
    clear_sharded_executor_cache,
    distributed_tc_count,
    distributed_tc_count_async,
    pooled_sharded_2d_executor,
    pooled_sharded_executor,
    shard_worklist,
)
from repro.distributed.resilient import (
    RecoveryState,
    ResilienceConfig,
    TCCheckpoint,
    resilient_tc_count,
    resume_tc_count,
)

__all__ = [
    "RecoveryState",
    "ResilienceConfig",
    "TCCheckpoint",
    "resilient_tc_count",
    "resume_tc_count",
    "Sharded2DExecutor",
    "ShardedColsExecutor",
    "TC_PLACEMENTS",
    "clear_sharded_executor_cache",
    "distributed_tc_count",
    "distributed_tc_count_async",
    "pooled_sharded_2d_executor",
    "pooled_sharded_executor",
    "shard_worklist",
]
