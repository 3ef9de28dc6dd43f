"""Trace-ingestion front end: parse throughput + downstream cut quality.

Streams synthetic TRACE_SCHEMA v0 NDJSON (>=1M lines at the headline
point) through every ingestion engine and reports edges/second, then
partitions the ingested graph with WB-Libra and reports the replication
factor — so a regression in either the parsers or the graph they build
fails CI (`benchmarks/baselines/trace_ingest.json`).

Engines benchmarked (the `backend` column; see docs/trace-format.md):

  * ``fast``      — the sequential streaming interpreter (scanner forced
                    off via `REPRO_TRACE_SCANNER=0`), the semantic
                    reference for both fast paths;
  * ``auto``      — the default dispatch (`REPRO_TRACE_SCANNER` unset):
                    the scanner at any size;
  * ``scan``      — the structural-index scanner (`repro.trace.scan`,
                    one native tokenizing pass), pinned on with
                    `REPRO_TRACE_SCANNER=1`;
  * ``binary``    — reading the `.rtb` columnar container produced by
                    one-time conversion (`repro.trace.binfmt`);
  * ``reference`` — a deliberately naive ingester (materialise every
                    record dict, single unchunked pass) kept both as the
                    readable oracle — the bench asserts graph equality
                    against the streaming engine — and as the host-speed
                    calibration probe for `check_regression.py`.

Every engine's graph is asserted bit-identical to the ``fast`` graph
before its row is emitted.  The ingestion-wall gate lives in the meta:
``speedup_binary_1M`` (binary vs fast edges/s, same run, same machine)
must stay >= 10x — asserted here and re-checked in CI via
``check_regression.py --min-speedup 10 --speedup-key speedup_binary_1M``.
Streaming-mode discipline is asserted outright: the peak Python edge
buffer must stay bounded by the chunk size, not the trace length.
"""
from __future__ import annotations

import contextlib
import json
import os

import numpy as np

from repro import obs
from repro.core import vertex_cut
from repro.core.graph import IRGraph
from repro.trace import (SCANNER_ENV, ingest_trace_with_stats, read_trace_bin,
                         resolve_weight_model, synthesize_trace, type_bytes,
                         write_trace_bin)

from .common import emit, timed, timed_phases, write_bench_json

CACHE_DIR = ".cache/traces"
SMALL_LINES = 100_000
BIG_LINES = 1_000_000
CHUNK_EDGES = 1 << 16
CUT_P = 64
MIN_BINARY_SPEEDUP = 10.0       # the tentpole's ingestion-wall gate

_convert_us: dict = {}          # lines -> one-time .rtb conversion cost


@contextlib.contextmanager
def _scanner(state: str):
    """Pin the NDJSON scanner on ("1"), off ("0"), or default dispatch
    ("auto" — env unset) for one timing."""
    old = os.environ.get(SCANNER_ENV)
    if state == "auto":
        os.environ.pop(SCANNER_ENV, None)
    else:
        os.environ[SCANNER_ENV] = state
    try:
        yield
    finally:
        if old is None:
            os.environ.pop(SCANNER_ENV, None)
        else:
            os.environ[SCANNER_ENV] = old


def reference_ingest(path: str, weight_model: str = "bytes") -> IRGraph:
    """Naive oracle: all records as dicts, one unchunked pass."""
    weight_fn = resolve_weight_model(weight_model)
    with open(path, "r", encoding="utf-8") as f:
        records = [json.loads(line) for line in f if line.strip()]
    defs: dict = {}
    src, dst, w, n = [], [], [], 0
    for rec in records:
        if "kind" in rec:
            continue
        fn = rec.get("fn", "?")
        nid = n
        n += 1
        use_tys = rec.get("use_tys")
        for i, u in enumerate(rec.get("uses", [])):
            if (fn, u) in defs:
                pid, pbytes = defs[(fn, u)]
            elif u.startswith("const:"):
                pid, pbytes, n = n, None, n + 1
            else:
                pid, pbytes, n = n, None, n + 1
                defs[(fn, u)] = (pid, None)
            src.append(pid)
            dst.append(nid)
            w.append(weight_fn(rec["op"],
                               use_tys[i] if use_tys is not None else None,
                               pbytes))
        if rec.get("def") is not None:
            ty = rec.get("def_ty")
            defs[(fn, rec["def"])] = (
                nid, type_bytes(ty) if isinstance(ty, str) else None)
    return IRGraph(n=n, src=np.asarray(src, np.int32),
                   dst=np.asarray(dst, np.int32),
                   w=np.asarray(w, np.float64), name="reference")


def _trace_path(lines: int) -> str:
    os.makedirs(CACHE_DIR, exist_ok=True)
    path = os.path.join(CACHE_DIR, f"synth_{lines}_seed0.ndjson")
    if not os.path.exists(path):
        synthesize_trace(path, lines, seed=0)
    return path


def _bin_path(lines: int, model: str) -> str:
    """One-time NDJSON -> .rtb conversion (the cost `convert` amortises)."""
    path = os.path.join(CACHE_DIR, f"synth_{lines}_seed0_{model}.rtb")
    if not os.path.exists(path):
        g, stats = ingest_trace_with_stats(_trace_path(lines),
                                           weight_model=model,
                                           chunk_edges=CHUNK_EDGES)
        _, us = timed(write_trace_bin, path, g, stats)
        _convert_us[lines] = round(us, 1)
    return path


def _reference_spanned(path: str, model: str) -> IRGraph:
    # the naive oracle has no internal telemetry; the bench wraps it so
    # its rows still carry a parse-phase breakdown
    with obs.span("trace.ingest", engine="reference"):
        return reference_ingest(path, model)


def _row(lines: int, model: str, backend: str, with_quality: bool):
    path = _trace_path(lines)
    if backend == "fast":
        with _scanner("0"):
            (g, stats), us, phases = timed_phases(
                ingest_trace_with_stats, path, weight_model=model,
                chunk_edges=CHUNK_EDGES)
        assert stats.engine == "stream", stats.engine
        # streaming discipline: buffer bounded by chunk, not trace size
        assert stats.peak_chunk_edges <= CHUNK_EDGES + 8, \
            f"edge buffer {stats.peak_chunk_edges} exceeds chunk bound"
    elif backend == "scan":
        with _scanner("1"):
            (g, stats), us, phases = timed_phases(
                ingest_trace_with_stats, path, weight_model=model,
                chunk_edges=CHUNK_EDGES)
        assert stats.engine == "scan", \
            f"scanner fell back to {stats.engine!r} on {path}"
    elif backend == "auto":
        with _scanner("auto"):
            (g, stats), us, phases = timed_phases(
                ingest_trace_with_stats, path, weight_model=model,
                chunk_edges=CHUNK_EDGES)
        engine_used = stats.engine
    elif backend == "binary":
        bpath = _bin_path(lines, model)
        (g, stats), us, phases = timed_phases(read_trace_bin, bpath,
                                              repeats=3)
        assert stats.engine == "binary", stats.engine
    else:
        g, us, phases = timed_phases(_reference_spanned, path, model)
    row = {"lines": lines, "model": model, "backend": backend,
           "edges": g.num_edges,
           "us_per_edge": round(us / max(g.num_edges, 1), 4),
           "us_total": round(us, 1),
           "edges_per_s": round(g.num_edges / (us / 1e6), 1),
           "phases": phases}
    if backend == "auto":
        row["engine"] = engine_used
    if with_quality:
        cut = vertex_cut(g, CUT_P, method="wb_libra", backend="fast")
        row["replication_factor"] = round(cut.replication_factor, 4)
    emit(f"trace_ingest/L{lines}/{model}/{backend}", us,
         f"edges_per_s={row['edges_per_s']:.0f}")
    return row, g


def _assert_identical(g: IRGraph, ref: IRGraph, what: str) -> None:
    assert g.n == ref.n, (what, g.n, ref.n)
    assert np.array_equal(g.src, ref.src), what
    assert np.array_equal(g.dst, ref.dst), what
    assert np.array_equal(g.w, ref.w), what


def run() -> list[dict]:
    rows = []
    small, g_fast = _row(SMALL_LINES, "bytes", "fast", with_quality=True)
    rows.append(small)
    ref, g_ref = _row(SMALL_LINES, "bytes", "reference", with_quality=False)
    rows.append(ref)
    # the naive oracle must agree with the streaming engine bit-for-bit
    _assert_identical(g_fast, g_ref, "fast-vs-reference L100k")
    rows.append(_row(SMALL_LINES, "memop-latency", "fast",
                     with_quality=False)[0])
    for backend in ("scan", "binary"):
        r, g = _row(SMALL_LINES, "bytes", backend, with_quality=False)
        _assert_identical(g, g_fast, f"{backend} L100k")
        rows.append(r)
    auto_small, g = _row(SMALL_LINES, "bytes", "auto", with_quality=False)
    _assert_identical(g, g_fast, "auto L100k")
    # the default dispatch scans at any size
    assert auto_small["engine"] == "scan", auto_small["engine"]
    rows.append(auto_small)
    big, g_big = _row(BIG_LINES, "bytes", "fast", with_quality=True)
    rows.append(big)
    scan_big, g = _row(BIG_LINES, "bytes", "scan", with_quality=False)
    _assert_identical(g, g_big, "scan L1M")
    rows.append(scan_big)
    auto_big, g = _row(BIG_LINES, "bytes", "auto", with_quality=False)
    _assert_identical(g, g_big, "auto L1M")
    assert auto_big["engine"] == "scan", auto_big["engine"]
    rows.append(auto_big)
    bin_big, g = _row(BIG_LINES, "bytes", "binary", with_quality=False)
    _assert_identical(g, g_big, "binary L1M")
    rows.append(bin_big)

    speedup = ref["us_per_edge"] / max(small["us_per_edge"], 1e-9)
    sp_forced = scan_big["edges_per_s"] / max(big["edges_per_s"], 1e-9)
    sp_scan = auto_big["edges_per_s"] / max(big["edges_per_s"], 1e-9)
    sp_bin = bin_big["edges_per_s"] / max(big["edges_per_s"], 1e-9)
    emit("trace_ingest/speedup_L100k", small["us_total"],
         f"fast_vs_reference={speedup:.2f}x")
    emit("trace_ingest/speedup_1M", big["us_total"],
         f"auto={sp_scan:.2f}x forced_scan={sp_forced:.2f}x "
         f"binary={sp_bin:.2f}x")
    # the default dispatch must never lose to the stream engine
    assert sp_scan >= 1.0, \
        f"auto ingest dispatch {sp_scan:.2f}x loses to the stream engine"
    # the ingestion-wall gate: convert-once must beat re-parsing 10x
    assert sp_bin >= MIN_BINARY_SPEEDUP, \
        f"binary ingest speedup {sp_bin:.1f}x < {MIN_BINARY_SPEEDUP}x gate"
    write_bench_json("trace_ingest", rows,
                     meta={"chunk_edges": CHUNK_EDGES, "cut_p": CUT_P,
                           "edges_per_s_1M": bin_big["edges_per_s"],
                           "edges_per_s_stream_1M": big["edges_per_s"],
                           "speedup_L100k": round(speedup, 2),
                           "speedup_scan_1M": round(sp_scan, 2),
                           "speedup_scan_forced_1M": round(sp_forced, 2),
                           "speedup_binary_1M": round(sp_bin, 2),
                           "convert_us_1M": _convert_us.get(BIG_LINES)})
    return rows


if __name__ == "__main__":
    run()
