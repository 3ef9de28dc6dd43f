"""Paper §4.4 complexity claim: Weight Balanced Libra is O(|E|·|C|) —
measured as edge throughput across |E| and |C| for both streaming
engines.  The fast backend (array-native; C kernel when a compiler is
present) is benchmarked against the reference oracle loop at the paper's
1024-cluster scale and on a >=500k-edge power-law graph; the reference
is swept only at the 32k-vertex scale where it finishes in seconds.

Emits the usual CSV rows plus machine-readable
`BENCH_partitioner_scaling.json` (see benchmarks/check_regression.py for
the CI perf gate against the committed baseline).
"""
from __future__ import annotations

from repro.core import synthesize_powerlaw_graph, vertex_cut
from repro.core._native import native_available
from repro.core.pallas.cost import partitioner_finalize_cost

from .common import emit, timed_phases, write_bench_json
from .roofline import roofline_fraction

# (n, p sweep, backends); the reference oracle only runs at <=32k vertices
SMALL_NS = (2_000, 8_000, 32_000)
SMALL_PS = (8, 64, 512)
BIG_N = 300_000          # >=500k edges at alpha=2.2 (paper §4.4 scale)
BIG_PS = (512, 1024)
REPEATS = 5
# pallas rows get an untimed warmup (jax compiles must never score —
# the reference-probe calibration cannot track compile-cache state)
BACKEND_REPEATS = {"fast": REPEATS, "reference": 2, "pallas": 3}


def _row(g, n, p, backend, repeats=REPEATS):
    if backend == "pallas":
        vertex_cut(g, p, method="wb_libra", backend=backend)  # warm compiles
    r, us, phases = timed_phases(vertex_cut, g, p, method="wb_libra",
                                 backend=backend, repeats=repeats)
    per_edge = us / max(g.num_edges, 1)
    row = {"n": n, "edges": g.num_edges, "p": p, "backend": backend,
           "us_per_edge": round(per_edge, 4), "us_total": round(us, 1),
           "replication_factor": round(r.replication_factor, 4),
           "phases": phases}
    if backend == "pallas":
        # lowered-HLO cost of the on-accelerator finalize, judged against
        # the roofline over its measured (finalize-phase) time
        cost = partitioner_finalize_cost(n, g.num_edges, p)
        row["hlo_flops"] = cost["flops"]
        row["hlo_hbm_bytes"] = cost["hbm_bytes"]
        row["roofline_fraction"] = round(roofline_fraction(
            cost["flops"], cost["hbm_bytes"],
            phases.get("finalize") or us), 6)
    emit(f"partitioner_scaling/E{g.num_edges}/p{p}/{backend}", us,
         f"us_per_edge={per_edge:.3f}")
    return row


def run() -> list[dict]:
    engine = "native" if native_available() else "python"
    rows = []
    by_key = {}
    # the pallas column (fast stream + on-accelerator finalize; interpret
    # mode on CPU) runs the small sweep only — same rows as the reference
    # calibration probe, gated against its own baseline.  Its rows are
    # committed baseline coverage, so a broken pallas layer fails loudly
    # from its first call rather than as a "coverage lost" gate message.
    backends = ("fast", "reference", "pallas")
    for n in SMALL_NS:
        g = synthesize_powerlaw_graph(n=n, alpha=2.2, seed=0)
        for p in SMALL_PS:
            for backend in backends:
                # reference rows double as the machine-speed calibration
                # probe in check_regression.py — keep them best-of-2
                row = _row(g, n, p, backend,
                           repeats=BACKEND_REPEATS[backend])
                rows.append(row)
                by_key[(n, p, backend)] = row

    # headline ratio at the paper's scaling point (32k vertices, p=512)
    fast = by_key[(32_000, 512, "fast")]
    ref = by_key[(32_000, 512, "reference")]
    speedup = ref["us_per_edge"] / max(fast["us_per_edge"], 1e-9)
    emit("partitioner_scaling/speedup_E32k_p512", fast["us_total"],
         f"fast_vs_reference={speedup:.1f}x")

    # paper §4.4 scale: >=500k edges, up to 1024 clusters (fast only —
    # the reference loop needs minutes here); best-of-2 so one scheduler
    # hiccup cannot bake a ~5x-loose row into a committed baseline
    g = synthesize_powerlaw_graph(n=BIG_N, alpha=2.2, seed=0)
    for p in BIG_PS:
        rows.append(_row(g, BIG_N, p, "fast", repeats=2))

    write_bench_json("partitioner_scaling", rows,
                     meta={"engine": engine,
                           "speedup_E32k_p512": round(speedup, 2)})
    return rows


if __name__ == "__main__":
    run()
