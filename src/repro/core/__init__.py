"""Core library: the paper's contribution (Weight Balanced p-way Vertex Cut).

Public API:
  IRGraph                      — weighted dataflow graph (LLVM-graph analogue)
  vertex_cut / ALGORITHMS      — the 6 greedy cuts + random (paper §4)
  edge_cut / EDGE_CUT_METHODS  — CompNet + METIS-like baselines (paper §6.1)
  memory_centric_mapping       — Algorithm 2 cluster→core scheduling
  simulate                     — NUMA multicore cost simulation (paper §6)
  run_pipeline                 — the planning pipeline, cut → map → simulate
  BACKENDS                     — the engine table's backends
  build_graph / BENCHMARKS     — the paper's 10 traced benchmarks (Table 3)
  expected_replication_random  — Eq. (10) theory
"""
from .graph import IRGraph
from .powerlaw import (expected_replication_random,
                       expected_replication_random_empirical,
                       synthesize_powerlaw_graph, zipf_degrees)
from .engines import BACKENDS
from .vertex_cut import ALGORITHMS, ShardCutState, VertexCutResult, vertex_cut
from .edge_cut import EDGE_CUT_METHODS, EdgeCutResult, edge_cut
from .mapping import (Machine, MappingResult, cluster_interaction_graphs,
                      memory_centric_mapping, round_robin_mapping)
from .simulator import SimReport, simulate, vertex_bytes_model
from .pipeline import coerce_graph, run_pipeline
from .benchgraphs import BENCHMARKS, Tracer, all_benchmark_names, build_graph

__all__ = [
    "IRGraph", "vertex_cut", "VertexCutResult", "ALGORITHMS",
    "BACKENDS", "ShardCutState",
    "edge_cut", "EdgeCutResult", "EDGE_CUT_METHODS",
    "Machine", "MappingResult", "memory_centric_mapping",
    "round_robin_mapping", "cluster_interaction_graphs",
    "SimReport", "simulate", "run_pipeline", "vertex_bytes_model",
    "coerce_graph",
    "BENCHMARKS", "Tracer", "all_benchmark_names", "build_graph",
    "expected_replication_random", "expected_replication_random_empirical",
    "synthesize_powerlaw_graph", "zipf_degrees",
]
