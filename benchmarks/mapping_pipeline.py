"""Mapping/simulator pipeline scaling: the Fig. 1 path *after* the cut.

Times the array-native map-and-score stage — `cluster_interaction_graphs`
(replica-CSR segment ops) + `memory_centric_mapping` (masked-argmin
placement) + `simulate` (CSR replica-sync triples) — against the
reference oracle loops on a power-law graph at the paper's cluster
scales, p in {8, 64, 256, 1024}.  The partition itself is computed once
per p with the fast engine and shared by all backends, so the rows
isolate the mapping/simulator layer this suite gates.

A third `pallas` column runs the same stage through the on-accelerator
segment-sum kernel; it is committed baseline coverage, so the suite
*requires* a working Pallas layer and fails loudly with the probe's
error otherwise.  On CPU CI that column measures *interpret mode* (the
honest number for the container target — expect it well above the
numpy fast path; the gate only holds it to its own baseline, and its
quality fields pin the model outputs to the other backends').

Rows carry both throughput (`us_per_cluster`) and the pipeline's quality
outputs (`exec_time`, `data_comm_bytes` — Tables 6-9 quantities), so the
CI gate catches algorithmic regressions as well as slowdowns.  Emits the
usual CSV rows plus machine-readable `BENCH_mapping_pipeline.json`
(see benchmarks/check_regression.py).
"""
from __future__ import annotations

from repro.core import (Machine, cluster_interaction_graphs,
                        memory_centric_mapping, simulate,
                        synthesize_powerlaw_graph, vertex_bytes_model,
                        vertex_cut)
from repro.core.pallas.cost import interaction_cost, keyed_sum_cost

from .common import emit, timed_phases, write_bench_json
from .roofline import roofline_fraction

N = 100_000              # >=170k edges at alpha=2.2
PS = (8, 64, 256, 1024)
REPEATS = 5
# repeats per backend: the reference rows double as the machine-speed
# calibration probe in check_regression.py (best-of-2); the pallas rows
# get an untimed warmup call first (jax compiles op-by-op per novel
# shape — the reference-probe calibration cannot track compile-cache
# state, so compiles must never score) and then best-of-3
BACKEND_REPEATS = {"fast": REPEATS, "reference": 2, "pallas": 3}


def _merge_costs(*costs: dict) -> dict:
    return {"flops": sum(c["flops"] for c in costs),
            "hbm_bytes": sum(c["hbm_bytes"] for c in costs)}


def _map_and_score(g, cut, vb, machine, backend):
    comm, shared = cluster_interaction_graphs(cut, cut.p, vb,
                                              backend=backend)
    mapping = memory_centric_mapping(comm, shared, machine, backend=backend)
    return simulate(g, cut, mapping, backend=backend)


def run() -> list[dict]:
    g = synthesize_powerlaw_graph(n=N, alpha=2.2, seed=0)
    vb = vertex_bytes_model(g)
    rows = []
    by_key = {}
    # the pallas column is *gated coverage* (its rows live in the
    # committed baseline): a broken pallas layer raises from its first
    # call rather than dropping the column
    backends = ("fast", "reference", "pallas")
    for p in PS:
        cut = vertex_cut(g, p, method="wb_libra")
        machine = Machine.for_clusters(p)
        for backend in backends:
            if backend == "pallas":
                _map_and_score(g, cut, vb, machine, backend)  # warm compiles
            rep, us, phases = timed_phases(_map_and_score, g, cut, vb,
                                           machine, backend,
                                           repeats=BACKEND_REPEATS[backend])
            per_cluster = us / p
            row = {"n": N, "edges": g.num_edges, "p": p, "backend": backend,
                   "us_per_cluster": round(per_cluster, 3),
                   "us_total": round(us, 1),
                   "exec_time": rep.exec_time,
                   "data_comm_bytes": rep.data_comm_bytes,
                   "phases": phases}
            if backend == "pallas":
                # device work: interaction reductions + the simulator's
                # three keyed sums (per-cluster compute, per-core fold,
                # replica-sync wait — the triple stream is ~|members|)
                members = len(cut.replica_csr()[1])
                cost = _merge_costs(
                    interaction_cost(members, p),
                    keyed_sum_cost(g.num_edges, p),
                    keyed_sum_cost(p, machine.n_cores),
                    keyed_sum_cost(members, machine.n_cores))
                row["hlo_flops"] = cost["flops"]
                row["hlo_hbm_bytes"] = cost["hbm_bytes"]
                row["roofline_fraction"] = round(roofline_fraction(
                    cost["flops"], cost["hbm_bytes"], us), 6)
            rows.append(row)
            by_key[(p, backend)] = row
            emit(f"mapping_pipeline/p{p}/{backend}", us,
                 f"us_per_cluster={per_cluster:.2f}")

    # headline ratio at the paper's extreme scale (p=1024 planning)
    fast = by_key[(1024, "fast")]
    ref = by_key[(1024, "reference")]
    speedup = ref["us_total"] / max(fast["us_total"], 1e-9)
    emit("mapping_pipeline/speedup_p1024", fast["us_total"],
         f"fast_vs_reference={speedup:.1f}x")

    write_bench_json("mapping_pipeline", rows,
                     meta={"speedup_p1024": round(speedup, 2)})
    return rows


if __name__ == "__main__":
    run()
