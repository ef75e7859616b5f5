"""repro.comm — the unified collective-plan subsystem.

Promotes the paper's tuned-broadcast stack into a collective-communication
library: one op family (bcast / reduce / allreduce / allgather /
reduce_scatter) sharing the schedule IR (``core.schedules``), the numpy
simulator, the analytic cost models, and the per-op tuner
(``Tuner.select(M, n, op=..., inter_pod=...)``).

Layering (DESIGN.md Sec. 3):

    core.schedules (IR)  ->  comm.schedules (per-op builders)
                         ->  comm.plan      (CollectivePlan: decide + build)
                         ->  comm.executors (shard_map replay, fused loops)
                         ->  comm.api       (pbcast/pallreduce/... + *_tree)
                         ->  comm.streams   (multi-stream link scheduler;
                                             comm.overlap = 1-stream case)
                         ->  comm.tables    (validated experiments/ artifacts)

Consumers: ``train.train_step`` (sync_mode='tuned_allreduce'),
``serve.engine.distribute_weights``, ``launch.hillclimb_bcast``,
``benchmarks/``. ``core.bcast`` remains as a thin compatibility facade.
"""
from ..core.tuner import OPS, Decision, OnlineTuner, Tuner, default_tuner
from .compress import (
    CompressedWire,
    CompressionState,
    WireFormat,
    normalize_wire_format,
    wire_chunk_bytes,
)
from .api import (
    apply_plan,
    apply_plan_resilient,
    hierarchical_allreduce_axes,
    pallgather,
    pallgatherv,
    pallreduce,
    pallreduce_tree,
    pbcast,
    pbcast_tree,
    palltoallv,
    preduce,
    preduce_scatter,
)
from .executors import execute_collective, execute_compiled, execute_inkernel
from .faults import (
    DeadRankError,
    FallbackExhaustedError,
    FaultError,
    FaultSpec,
    MeshHealth,
    TransientDropError,
    WeightSyncError,
)
from .overlap import (
    OverlapPlan,
    execute_overlap,
    overlap_allreduce_tree,
    plan_overlap,
    simulate_overlap,
)
from .plan import (
    CollectivePlan,
    cache_stats,
    decide,
    expected_wire_bytes,
    plan_cache_clear,
    plan_cached,
    plan_collective,
    plan_degraded,
)
from .resilience import FallbackEvent, FallbackPolicy, StragglerReport, Watchdog
from .streams import (
    StreamEntry,
    StreamGraph,
    StreamGraphError,
    StreamSpec,
    dispatch_schedule,
    execute_stream_entry,
    execute_streams,
    graph_key,
    plan_streams,
    simulate_streams,
)
from .tables import (
    TableSchemaError,
    load_bench,
    load_compile_table,
    load_compress_table,
    load_fault_table,
    load_inkernel_table,
    load_overlap_table,
    load_streams_table,
    load_tuner_table,
    tuner_from_table,
)

__all__ = [
    "OPS",
    "Decision",
    "Tuner",
    "OnlineTuner",
    "default_tuner",
    "WireFormat",
    "CompressedWire",
    "CompressionState",
    "normalize_wire_format",
    "wire_chunk_bytes",
    "CollectivePlan",
    "plan_collective",
    "plan_degraded",
    "plan_cached",
    "plan_cache_clear",
    "cache_stats",
    "decide",
    "expected_wire_bytes",
    "execute_collective",
    "execute_compiled",
    "execute_inkernel",
    "apply_plan",
    "apply_plan_resilient",
    "pbcast",
    "pbcast_tree",
    "preduce",
    "preduce_scatter",
    "pallreduce",
    "pallgather",
    "pallgatherv",
    "palltoallv",
    "pallreduce_tree",
    "hierarchical_allreduce_axes",
    "OverlapPlan",
    "plan_overlap",
    "simulate_overlap",
    "execute_overlap",
    "overlap_allreduce_tree",
    "StreamSpec",
    "StreamEntry",
    "StreamGraph",
    "StreamGraphError",
    "graph_key",
    "plan_streams",
    "simulate_streams",
    "dispatch_schedule",
    "execute_streams",
    "execute_stream_entry",
    "TableSchemaError",
    "load_tuner_table",
    "load_bench",
    "load_overlap_table",
    "load_streams_table",
    "load_compile_table",
    "load_fault_table",
    "load_inkernel_table",
    "load_compress_table",
    "tuner_from_table",
    "FaultError",
    "DeadRankError",
    "TransientDropError",
    "FallbackExhaustedError",
    "WeightSyncError",
    "FaultSpec",
    "MeshHealth",
    "FallbackPolicy",
    "FallbackEvent",
    "StragglerReport",
    "Watchdog",
]
