"""Distributed graph coloring with iterative recoloring — core library.

Every driver takes ``mesh``: ``None`` simulates the P shards on one device
(P vmap lanes), a mesh runs them on its shard axis — the same program,
bitwise the same results.

Public API:
  Graph, PartitionedGraph, partition_graph      — graph substrate
  pad_partition, bucket_graphs, GraphBucket      — batched shape buckets
  compute_order                                  — vertex-visit orderings
  ColorConfig, color_graph                       — speculative coloring
  RecolorConfig, recolor_step, arc_sim           — iterative recoloring
  recolor_iterations                             — ND-RAND%x schedules
  PipelineConfig, run_pipeline                   — fused device-resident
                                                   color→recolor pipeline
  color_many                                     — batched multi-graph
                                                   pipeline (DESIGN.md §8)
  PlanSignature, plan_signature                  — compiled-program identity
  program_cache_stats, program_cache_clear       — process-wide program-cache
                                                   counters (hits/misses/traces)
  resolve_scheme                                 — trace-time sparse-vs-
                                                   allgather decision ("auto")
  id_policy, check_int32_limits                  — id-width policy: int32
                                                   under 2**31, int64 past it
  shard_axis_of, batch_axis_size, mesh_axes      — mesh axis-name contract
                                                   (DESIGN.md §10)
  message_stats                                  — piggybacking accounting
  presets.speed / presets.quality                — the paper's parameter sets
  select_colors                                  — shared bitset color-selection
                                                   entry (Pallas/XLA backends)

``pipeline_sim`` and ``pipeline_sharded`` are two names of ``run_pipeline``
that the benchmark's executors import.
"""
from repro.kernels.ops import select_colors, select_colors_d2

from . import ordering, presets, rmat, selection
from .comm import (AUTO, AXIS, BATCH_AXIS, AxisComm, CommConfig,
                   allgather_bytes_per_exchange, batch_axis_of,
                   batch_axis_size, mesh_axes, resolve_scheme, shard_axis_of,
                   stats_to_host)
from .graph import (CommPlan, Graph, GraphBucket, PartitionedGraph,
                    bucket_graphs, build_comm_plan, check_int32_limits,
                    id_policy, pad_partition, partition_graph, plan_fits,
                    remap_plan_arrays)
from .ordering import compute_order
from .piggyback import message_stats
from .pipeline import (PipelineConfig, bucket_signature, color_many,
                       color_then_recolor, engine_init_program,
                       engine_put_program, engine_step_program,
                       pipeline_carry_spmd, pipeline_sharded, pipeline_sim,
                       pipeline_step_spmd, plan_signature,
                       recolor_iterations, resolve_pipeline_cfg,
                       run_pipeline)
from .programs import (PlanSignature, program_cache_clear,
                       program_cache_contains, program_cache_stats)
from .recolor import ND, NI, RAND, RV, RecolorConfig, arc_sim, recolor_step
from .speculative import ColorConfig, color_graph, color_spmd
from .validate import assert_valid, check_coloring, colors_from_views

__all__ = [
    "AUTO", "AXIS", "AxisComm", "BATCH_AXIS", "ColorConfig", "CommConfig",
    "CommPlan", "Graph", "GraphBucket", "ND", "NI", "PartitionedGraph",
    "PipelineConfig", "PlanSignature", "RAND", "RV", "RecolorConfig",
    "allgather_bytes_per_exchange", "arc_sim", "assert_valid",
    "batch_axis_of", "batch_axis_size", "bucket_graphs", "bucket_signature",
    "build_comm_plan", "check_coloring", "check_int32_limits", "color_graph",
    "color_many", "color_spmd", "color_then_recolor", "colors_from_views",
    "compute_order", "engine_init_program", "engine_put_program",
    "engine_step_program", "id_policy", "mesh_axes", "message_stats",
    "ordering", "pad_partition", "partition_graph", "pipeline_carry_spmd",
    "pipeline_sharded", "pipeline_sim", "pipeline_step_spmd", "plan_fits",
    "plan_signature", "presets", "program_cache_clear",
    "program_cache_contains", "program_cache_stats", "recolor_iterations",
    "recolor_step", "remap_plan_arrays", "resolve_pipeline_cfg",
    "resolve_scheme", "rmat", "run_pipeline", "select_colors",
    "select_colors_d2", "selection", "shard_axis_of", "stats_to_host",
]
