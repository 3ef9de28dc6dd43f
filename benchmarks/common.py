"""Shared helpers for the paper-table benchmarks."""
from __future__ import annotations

import json
import os
import platform
import time

import numpy as np

from repro import obs
from repro.core import all_benchmark_names, build_graph

VERTEX_METHODS = ("pg", "libra", "w_pg", "wb_pg", "w_libra", "wb_libra")
EDGE_METHODS = ("compnet", "metis")
ALL_METHODS = EDGE_METHODS + VERTEX_METHODS

CACHE_DIR = ".cache/benchgraphs"

# Span-name -> phase attribution for BENCH row "phases" dicts.  Only
# cat=="op" spans are summed — "section" envelopes (plan.*) and
# "wait" spans wrap or overlap the ops and would double-count.
PHASE_OF = {
    "trace.ingest": "parse",
    "parse.shard": "parse",
    "parse.merge": "parse",
    "cut.stream": "cut",
    "dist.cut": "cut",
    "dist.merge": "merge",
    "cut.finalize": "finalize",
    "dist.finalize": "finalize",
    "map.place": "map",
    "map.cluster_graphs": "map",
    "sim.run": "simulate",
    "serve.fingerprint": "fingerprint",
    "serve.cache_load": "cache",
    "serve.cache_store": "cache",
}


def phases_of(events) -> dict:
    """Fold a collector's op spans into {phase: total_us} via PHASE_OF."""
    out: dict = {}
    for ev in events:
        if ev.get("ph") != "X" or ev.get("cat", "op") != "op":
            continue
        phase = PHASE_OF.get(ev["name"])
        if phase is not None:
            out[phase] = round(out.get(phase, 0.0) + ev.get("dur", 0.0), 1)
    return out


def graphs(scale: str = "reduced", names=None):
    for name in (names or all_benchmark_names()):
        yield build_graph(name, scale=scale, cache_dir=CACHE_DIR)


def timed(fn, *args, **kw):
    # perf_counter, not time.time(): the gated rows need a monotonic
    # clock — wall time can step backwards under NTP adjustment
    t0 = time.perf_counter()
    out = fn(*args, **kw)
    return out, (time.perf_counter() - t0) * 1e6  # us


def timed_best(fn, *args, repeats: int = 1, **kw):
    """Best-of-N timing — the robust estimator for perf-gated rows."""
    best_us, out = float("inf"), None
    for _ in range(max(1, repeats)):
        o, us = timed(fn, *args, **kw)
        if us < best_us:
            best_us, out = us, o
    return out, best_us


def timed_phases(fn, *args, repeats: int = 1, **kw):
    """Best-of-N timing with phase attribution.

    Each repeat runs under a scoped collector; returns
    ``(out, best_us, phases)`` where ``phases`` maps phase name to
    total op-span microseconds for the *best* repeat, so the breakdown
    is consistent with the gated number.
    """
    best_us, out, phases = float("inf"), None, {}
    for _ in range(max(1, repeats)):
        with obs.scoped() as col:
            o, us = timed(fn, *args, **kw)
        if us < best_us:
            best_us, out, phases = us, o, phases_of(col.events)
    return out, best_us, phases


def emit(name: str, us: float, derived: str) -> None:
    """Assignment-required CSV line: name,us_per_call,derived."""
    print(f"{name},{us:.1f},{derived}")


def bench_output_path(suite: str) -> str:
    out_dir = os.environ.get("BENCH_OUTPUT_DIR", ".")
    return os.path.join(out_dir, f"BENCH_{suite}.json")


def write_bench_json(suite: str, rows: list, meta: dict | None = None) -> str:
    """Machine-readable benchmark emission consumed by the CI perf gate."""
    doc = {
        "suite": suite,
        "meta": {
            "timestamp": time.strftime("%Y-%m-%dT%H:%M:%S%z"),
            "python": platform.python_version(),
            "numpy": np.__version__,
            "machine": platform.machine(),
            **(meta or {}),
        },
        "rows": rows,
    }
    path = bench_output_path(suite)
    os.makedirs(os.path.dirname(path) or ".", exist_ok=True)
    with open(path, "w") as f:
        json.dump(doc, f, indent=2, default=float)
        f.write("\n")
    return path
