# The paper's primary contribution: FastFlow's lock-free streaming layer as
# ONE skeleton vocabulary (skeleton.py: Pipeline/Farm/Feedback IR) with three
# backends — host thread flavour (threads + Lamport SPSC rings + the graph
# runtime), host process flavour (spawned vertices over shared-memory SPSC
# rings — the GIL-escaping procs backend), and device flavour (one shard_map
# mesh program over collective-permute SPSC channels).
# `lower(skel, backend=...)` picks the runtime.
#
# The device-side modules (dchannel/dfarm/dpipeline) import JAX, which costs
# seconds of interpreter start-up; the procs backend spawns one process per
# vertex and every child imports this package.  Those modules are therefore
# loaded lazily (PEP 562): `from repro.core import farm_map` still works, but
# a vertex process that only needs the host runtime never pays for XLA.
from .spsc import EOS, SPSCQueue
from .lockq import LockQueue
from .shm import ShmCounters, ShmFlag, ShmRing
from .sched import (SCHEDULERS, BudgetBackpressure, CostModel, KeyAffinity,
                    OnDemand, RoundRobin, Scheduler, WorkStealing,
                    calibrate_handoff_us, clear_handoff_cache, make_scheduler,
                    spread_cpus)
from .obs import (Histogram, MetricsRegistry, RunReport, Trace, Tracer,
                  VertexTracer)
from .skeleton import (GO_ON, AllToAll, EmitMany, Farm, FarmStats, Feedback,
                       FnNode, FusedNode, KeyBatch,
                       LatencyReservoir, LoweringError, MeshProgram, Pipeline,
                       Skeleton, Source, Stage, as_skeleton,
                       compose, ff_node, fuse, lower, walk_stats)
from .graph import Accelerator, Graph, Net, ThreadProgram, build
from .procgraph import (ProcAccelerator, ProcGraph, ProcProgram,
                        pool_shutdown, pool_stats)
from .a2a import A2AMeshProgram, stable_hash
from .stream_ops import (FOLDS, Fold, KeyedReduce, partition_by,
                         reduce_by_key, window)
from .oocore import (CombiningReader, MemoryBudget, ShardReader, SpillFold,
                     rekey_reduce, shard_reduce, shard_source)
from .autotune import (Profile, StageProfile, TunedProgram, auto_batch,
                       plan_mesh, profile, retune, ring_capacity)
from .monitor import (BottleneckReport, DriftWatcher, Monitor, SLOMonitor,
                      Timeline, analyze)
from .farm import TaskFarm
from .allocator import PagePool, PoolExhausted
from .mdf import MDFExecutor, MDFTask

# device-flavour names, resolved on first touch (see module docstring)
_LAZY = {
    "RingChannel": ".dchannel", "chain_send": ".dchannel",
    "double_buffered_ring": ".dchannel", "ring_send": ".dchannel",
    "combine": ".dfarm", "dispatch": ".dfarm", "farm_map": ".dfarm",
    "farm_until": ".dfarm", "roundrobin_dest": ".dfarm",
    "negotiate_stage_axis": ".dpipeline", "pipeline_apply": ".dpipeline",
    "pipeline_utilisation": ".dpipeline",
}

__all__ = [
    "EOS", "SPSCQueue", "LockQueue", "ShmRing", "ShmCounters", "ShmFlag",
    "GO_ON", "EmitMany", "KeyBatch", "Accelerator", "Farm", "Feedback",
    "Graph", "Net",
    "Pipeline", "AllToAll",
    "Skeleton", "Source", "Stage", "compose",
    "LoweringError", "MeshProgram", "ThreadProgram", "as_skeleton", "build",
    "lower", "fuse", "FusedNode",
    "ProcAccelerator", "ProcGraph", "ProcProgram",
    "pool_stats", "pool_shutdown", "spread_cpus",
    "A2AMeshProgram", "stable_hash",
    "FOLDS", "Fold", "KeyedReduce", "partition_by", "reduce_by_key",
    "window",
    "MemoryBudget", "SpillFold", "ShardReader", "CombiningReader",
    "shard_source", "shard_reduce", "rekey_reduce",
    "SCHEDULERS", "Scheduler", "RoundRobin", "OnDemand", "WorkStealing",
    "CostModel", "KeyAffinity", "BudgetBackpressure", "make_scheduler",
    "calibrate_handoff_us", "clear_handoff_cache",
    "Profile", "StageProfile", "TunedProgram", "profile", "retune",
    "plan_mesh", "auto_batch", "ring_capacity",
    "FarmStats", "LatencyReservoir", "FnNode", "TaskFarm", "ff_node",
    "PagePool", "PoolExhausted",
    "MDFExecutor", "MDFTask",
    "Tracer", "VertexTracer", "Trace", "MetricsRegistry", "Histogram",
    "RunReport", "walk_stats",
    "Monitor", "Timeline", "DriftWatcher", "SLOMonitor",
    "BottleneckReport", "analyze",
] + sorted(_LAZY)


def __getattr__(name):
    target = _LAZY.get(name)
    if target is None:
        raise AttributeError(f"module {__name__!r} has no attribute {name!r}")
    import importlib

    value = getattr(importlib.import_module(target, __name__), name)
    globals()[name] = value  # cache: next access skips this hook
    return value
