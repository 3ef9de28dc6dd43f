"""The planning pipeline, composed once: source → graph → cut → map →
simulate (the end-to-end path of the paper's Fig. 1).

`plan` is the one composition behind `run_pipeline`,
`planner.plan_graph` and the plan service's cold plans;
`map_and_simulate` is its last two stages, which the incremental
planner runs on a cut of its own.  Its section spans are `plan.cut`
(the ingest and the cut), `plan.map` and `plan.simulate`.
"""
from __future__ import annotations

import os

import numpy as np

from .. import obs
from .edge_cut import EDGE_CUT_METHODS, edge_cut
from .engines import Engines, resolve
from .graph import IRGraph
from .mapping import (Machine, cluster_interaction_graphs,
                      memory_centric_mapping)
from .simulator import CACHE_LINE, simulate, vertex_bytes_model
from .vertex_cut import ALGORITHMS, VertexCutResult, vertex_cut

__all__ = ["plan", "map_and_simulate", "run_pipeline", "coerce_graph"]


def coerce_graph(g, weight_model: str = "bytes") -> IRGraph:
    """Accept an `IRGraph` or a path to one, in any serialization the
    repo knows: an `.npz` snapshot, a `.rtb[.gz|.zst]` binary trace
    container, or a TRACE_SCHEMA v0 NDJSON dynamic trace (plain or
    compressed — see `repro.trace.load_graph` for the suffix dispatch).
    The whole pipeline takes either an object or a path."""
    if isinstance(g, IRGraph):
        return g
    if isinstance(g, (str, os.PathLike)):
        from .. import trace        # `load_graph` is looked up per call
        return trace.load_graph(g, weight_model=weight_model)
    raise TypeError(f"expected IRGraph or path, got {type(g).__name__}")


def plan(source, p: int, method: str = "wb_libra", lam: float = 1.0, *,
         seed: int = 0, edge_order: str = "auto",
         machine: "Machine | None" = None,
         backend: "str | Engines" = "fast", weight_model: str = "bytes",
         workers: int = 1, merge_period: "int | None" = None,
         divergence: "float | None" = None):
    """Plan `source` (an `IRGraph` or a path to one, see `coerce_graph`)
    and return (graph, partition, mapping, report).

    `backend` names a record of the engine table (`engines`) — or is
    the record — or is "dist": the sharded streaming partitioner of
    `repro.dist`, which ingests trace paths through the parallel parse
    front end and cuts on `workers` shard workers merging every
    `merge_period` edges — fully every round, or when the per-cluster
    load drift exceeds `divergence` × the mean cluster load — and maps
    and simulates on "fast" (`workers=1` is bit-identical to "fast").
    The edge-cut baselines (`EDGE_CUT_METHODS`) cut with `edge_cut`
    whatever the backend.
    """
    dist = backend == "dist"
    engines = resolve("fast" if dist else backend)
    if method not in ALGORITHMS and method not in EDGE_CUT_METHODS:
        raise ValueError(f"unknown method {method!r}")
    with obs.span("plan.cut", cat="section",
                  backend="dist" if dist else engines.name, p=p):
        if dist:
            from ..dist import dist_ingest, dist_vertex_cut
            if isinstance(source, (str, os.PathLike)) \
                    and not os.fspath(source).endswith(".npz"):
                source = dist_ingest(source, workers=workers)
        g = coerce_graph(source, weight_model)
        if method in EDGE_CUT_METHODS:
            part = edge_cut(g, p, method=method, seed=seed)
        elif dist:
            part = dist_vertex_cut(g, p, method=method, lam=lam, seed=seed,
                                   edge_order=edge_order, workers=workers,
                                   merge_period=merge_period,
                                   divergence=divergence)
        else:
            part = vertex_cut(g, p, method=method, lam=lam, seed=seed,
                              edge_order=edge_order, backend=engines)
    mapping, report = map_and_simulate(g, part, machine, engines)
    return g, part, mapping, report


def map_and_simulate(g: IRGraph, part, machine: "Machine | None" = None,
                     backend: "str | Engines" = "fast"):
    """Map a finished vertex or edge cut of `g` onto `machine` (default:
    the near-square mesh for its p) and score it; returns (mapping,
    report)."""
    engines = resolve(backend)
    p = part.p
    with obs.span("plan.map", cat="section", backend=engines.name):
        if isinstance(part, VertexCutResult):
            comm, shared = cluster_interaction_graphs(
                part, p, vertex_bytes_model(g), backend=engines)
        else:
            # inter-cluster comm graph from cut edges (one line per
            # dependency)
            comm = np.zeros((p, p))
            cu, cv = part.parts[g.src], part.parts[g.dst]
            cross = cu != cv
            np.add.at(comm, (cu[cross], cv[cross]), CACHE_LINE)
            comm = comm + comm.T
            shared = np.zeros_like(comm)
        mapping = memory_centric_mapping(
            comm, shared, machine or Machine.for_clusters(p),
            backend=engines)
    with obs.span("plan.simulate", cat="section", backend=engines.name):
        report = simulate(g, part, mapping, backend=engines)
    return mapping, report


def run_pipeline(g, p: int, method: str, lam: float = 1.0,
                 machine: "Machine | None" = None, seed: int = 0,
                 backend: str = "fast", workers: int = 1,
                 merge_period: "int | None" = None,
                 divergence: "float | None" = None):
    """partition -> map -> simulate, returning (partition, mapping,
    report): `plan` without the graph.  Profile a run with
    `obs.profiled("out.json")` or `REPRO_PROFILE=out.json` (see
    docs/observability.md)."""
    _, part, mapping, report = plan(
        g, p, method, lam, seed=seed, machine=machine, backend=backend,
        workers=workers, merge_period=merge_period, divergence=divergence)
    return part, mapping, report
