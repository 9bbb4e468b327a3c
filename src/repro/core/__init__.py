"""TCIM core — the paper's contribution as composable JAX modules.

Public API:
    tcim_count / tcim_count_graph   end-to-end bitwise triangle counting
    tcim_vertex_counts              per-vertex counts and local clustering
    build_sbf / build_worklist      sparsity-aware compression + scheduling
    plan_execution / ExecutionPlan  placement + owner-grouped work stripes
    Executor / ExecutorPool         device-resident fused execute stage
    simulate_lru                    data reuse/exchange behavioral model
    tcim_latency_energy             MRAM latency/energy analytical model
"""
from repro.core.bitmat import bitpack_matrix, bitunpack_matrix, popcount_u32
from repro.core.executor import (
    CountFuture,
    EXECUTOR_MODES,
    Executor,
    ExecutorPool,
    MultiCountFuture,
    MultiGraphExecutor,
)
from repro.core.plan import (
    PLACEMENTS,
    SCHEDULES,
    SPLITS,
    DeviceTopology,
    ExecutionPlan,
    FusionPlan,
    StripeSchedule,
    StripeStep,
    WorkStripe,
    plan_fusion,
    build_stripe_schedule,
    balance_grid_bounds,
    bottleneck_range_bounds,
    clamp_chunk_pairs,
    even_range_bounds,
    plan_execution,
    range_owners,
    remaining_worklist,
    replan_fixed,
    weighted_range_bounds,
)
from repro.core.sbf import (
    SBFUpdate,
    SlicedBitmap,
    UpdateLanes,
    Worklist,
    build_sbf,
    build_worklist,
    build_worklist_pairs,
    sbf_stats,
    update_sbf,
)
from repro.core.build import (
    DeviceCapacityError,
    DeviceBuild,
    DeviceBuildFuture,
    DeviceWorklist,
    device_build,
    device_build_async,
    device_build_graph,
    device_build_sbf,
    device_build_worklist,
    device_build_trace_counts,
    device_delta_worklist,
)
from repro.core.streaming import (
    STREAM_BACKENDS,
    DeltaResult,
    StreamingTCState,
    tcim_count_delta,
)
from repro.core.tcim import (
    BACKENDS,
    BUILDS,
    TCFuture,
    TCResult,
    TCVertexResult,
    tcim_count,
    tcim_count_graph,
    tcim_vertex_counts,
)
from repro.core.cachesim import CacheStats, simulate_lru
from repro.core.energymodel import (
    MramConstants,
    PAPER_TABLE5,
    tcim_latency_energy,
)
from repro.core import baselines

__all__ = [
    "bitpack_matrix",
    "bitunpack_matrix",
    "popcount_u32",
    "SlicedBitmap",
    "Worklist",
    "SBFUpdate",
    "UpdateLanes",
    "build_sbf",
    "build_worklist",
    "build_worklist_pairs",
    "update_sbf",
    "sbf_stats",
    "CountFuture",
    "Executor",
    "ExecutorPool",
    "MultiCountFuture",
    "MultiGraphExecutor",
    "EXECUTOR_MODES",
    "PLACEMENTS",
    "SCHEDULES",
    "SPLITS",
    "DeviceTopology",
    "ExecutionPlan",
    "FusionPlan",
    "StripeSchedule",
    "StripeStep",
    "WorkStripe",
    "plan_fusion",
    "build_stripe_schedule",
    "balance_grid_bounds",
    "bottleneck_range_bounds",
    "clamp_chunk_pairs",
    "even_range_bounds",
    "plan_execution",
    "range_owners",
    "remaining_worklist",
    "replan_fixed",
    "weighted_range_bounds",
    "DeviceCapacityError",
    "DeviceBuild",
    "DeviceBuildFuture",
    "DeviceWorklist",
    "device_build",
    "device_build_async",
    "device_build_graph",
    "device_build_sbf",
    "device_build_worklist",
    "device_build_trace_counts",
    "device_delta_worklist",
    "STREAM_BACKENDS",
    "DeltaResult",
    "StreamingTCState",
    "tcim_count_delta",
    "BACKENDS",
    "BUILDS",
    "TCFuture",
    "TCResult",
    "tcim_count",
    "tcim_count_graph",
    "TCVertexResult",
    "tcim_vertex_counts",
    "CacheStats",
    "simulate_lru",
    "MramConstants",
    "PAPER_TABLE5",
    "tcim_latency_energy",
    "baselines",
]
