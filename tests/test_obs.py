"""Telemetry layer (`repro.obs`): zero-cost-when-disabled contract,
Perfetto export schema, process-pool event merging, summarize math,
and the warning-origin contract of the dist engine's fallbacks.
"""
from __future__ import annotations

import json
import os
import subprocess
import sys
import time
import warnings

import numpy as np
import pytest

from repro import obs
from repro.core import run_pipeline, synthesize_powerlaw_graph
from repro.dist import dist_vertex_cut
from repro.obs.export import (chrome_trace, events_from_chrome,
                              load_profile, write_profile)
from repro.obs.summarize import render_summary, summarize_events
from repro.trace import synthesize_trace


@pytest.fixture(scope="module")
def trace_path(tmp_path_factory):
    path = tmp_path_factory.mktemp("obs_traces") / "synth.ndjson"
    synthesize_trace(str(path), 20_000, seed=0)
    return str(path)


@pytest.fixture(autouse=True)
def _obs_clean():
    """Every test starts and ends with telemetry disabled."""
    obs.disable()
    yield
    obs.disable()


# ---------------------------------------------------------------------- #
# disabled contract
# ---------------------------------------------------------------------- #
def test_disabled_is_noop_and_cheap():
    assert not obs.enabled()
    # the disabled span is a shared singleton — no allocation per call
    assert obs.span("a") is obs.span("b", lane="x", big=1)
    t0 = time.perf_counter()
    for _ in range(100_000):
        with obs.span("hot", lane="w", n=1) as sp:
            sp.set(k=2)
        obs.counter("c")
        obs.event("e")
    dt = time.perf_counter() - t0
    # budget: ~10us/iteration would already be pathological; the
    # measured cost is ~0.5us.  Generous bound for shared CI runners.
    assert dt < 1.0, f"100k disabled spans took {dt:.3f}s"


def test_disabled_records_nothing(trace_path):
    cut = dist_vertex_cut(trace_path, 8, workers=2, merge_period=4000)
    assert cut.assignment is not None
    assert obs.current() is None


# ---------------------------------------------------------------------- #
# collection + Perfetto export schema
# ---------------------------------------------------------------------- #
def _collect_sample():
    with obs.scoped(merge=False) as col:
        with obs.span("outer", lane="main", cat="section"):
            with obs.span("work", lane="main", n=3):
                time.sleep(0.001)
            t = time.perf_counter()
            obs.complete("remote", t - 0.002, t, lane="w1")
        obs.event("blip", lane="main", reason="test")
        obs.counter("edges", 42)
        obs.counter("edges", 8)
        obs.gauge("depth", 7)
    return col


def test_perfetto_export_schema():
    col = _collect_sample()
    doc = chrome_trace(col)
    evs = doc["traceEvents"]
    assert doc["displayTimeUnit"] == "ms"
    meta = [e for e in evs if e["ph"] == "M"]
    body = [e for e in evs if e["ph"] != "M"]
    # one thread_name metadata record per lane, unique tids
    assert {m["name"] for m in meta} == {"thread_name"}
    lanes = {m["args"]["name"] for m in meta}
    assert lanes == {"main", "w1"}
    assert len({m["tid"] for m in meta}) == len(meta)
    for e in body:
        assert e["ph"] in ("X", "i")
        assert isinstance(e["pid"], int) and isinstance(e["tid"], int)
        assert e["ts"] >= 0
        if e["ph"] == "X":
            assert e["dur"] >= 0
        else:
            assert e["s"] == "t"
    # timestamps monotone non-decreasing per lane (exporter sorts)
    by_tid: dict = {}
    for e in body:
        assert e["ts"] >= by_tid.get(e["tid"], 0)
        by_tid[e["tid"]] = e["ts"]
    # counters/gauges ride along under the repro key
    assert doc["repro"]["counters"]["edges"] == 50
    assert doc["repro"]["gauges"]["depth"] == 7


def test_export_roundtrip(tmp_path):
    col = _collect_sample()
    path = tmp_path / "prof.json"
    write_profile(str(path), col)
    doc = load_profile(str(path))
    events = events_from_chrome(doc)
    assert {e["lane"] for e in events} == {"main", "w1"}
    names = {e["name"] for e in events}
    assert {"outer", "work", "remote", "blip"} <= names
    # lanes recovered by name, not tid — summarize works on the rehydrated
    # events exactly as on the live ones
    s = summarize_events(events)
    assert s["wall_us"] > 0
    assert render_summary(s, doc["repro"]["counters"])


# ---------------------------------------------------------------------- #
# summarize math
# ---------------------------------------------------------------------- #
def test_summary_decomposition_sums_to_wall():
    with obs.scoped(merge=False) as col:
        t = time.perf_counter()
        # lane a: [0, 10ms]; lane b: [5ms, 15ms] -> 5 serial + 5 parallel
        # + 5 serial, wall 15ms, no idle
        obs.complete("a", t, t + 0.010, lane="a")
        obs.complete("b", t + 0.005, t + 0.015, lane="b")
    s = summarize_events(col.events)
    assert s["wall_us"] == pytest.approx(15_000, rel=1e-6)
    assert s["parallel_us"] == pytest.approx(5_000, rel=1e-6)
    assert s["serial_us"] == pytest.approx(10_000, rel=1e-6)
    assert s["idle_us"] == pytest.approx(0, abs=1e-6)
    assert (s["serial_us"] + s["parallel_us"] + s["idle_us"]
            == pytest.approx(s["wall_us"], rel=1e-6))
    assert s["serial_fraction"] == pytest.approx(2 / 3, rel=1e-6)
    # waits and sections never count as busy time
    with obs.scoped(merge=False) as col2:
        t = time.perf_counter()
        obs.complete("env", t, t + 0.010, lane="a", cat="section")
        obs.complete("stall", t, t + 0.010, lane="b", cat="wait")
        obs.complete("real", t, t + 0.002, lane="b")
    s2 = summarize_events(col2.events)
    assert s2["serial_us"] == pytest.approx(2_000, rel=1e-6)
    assert s2["parallel_us"] == pytest.approx(0, abs=1e-6)


# ---------------------------------------------------------------------- #
# process-pool event merging
# ---------------------------------------------------------------------- #
def _pipelined_events(trace_path):
    with obs.scoped(merge=False) as col:
        dist_vertex_cut(trace_path, 8, workers=4, merge_period=2000,
                        pool="process")
    return col.events


def test_process_pool_event_merge_deterministic(trace_path):
    """W=4 pipelined run over a process pool: worker timings ship home
    over the result channel and merge into the coordinator's collector.
    The event *structure* (names, lanes, per-phase counts) is a pure
    function of the input — only timestamps may differ between runs."""
    runs = [_pipelined_events(trace_path) for _ in range(2)]
    shapes = [sorted((e["name"], e["lane"]) for e in evs) for evs in runs]
    assert shapes[0] == shapes[1]
    lanes = {e["lane"] for e in runs[0]}
    assert {"coord"} <= lanes
    assert any(ln.startswith("cut/w") for ln in lanes)
    assert any(ln.startswith("parse/p") for ln in lanes)
    names = {e["name"] for e in runs[0]}
    assert {"dist.cut", "parse.shard", "dist.parse_wait",
            "dist.finalize"} <= names
    # every event survived the export path with its lane intact
    doc = chrome_trace_from_events(runs[0])
    back = events_from_chrome(doc)
    assert sorted((e["name"], e["lane"]) for e in back) == shapes[0]


def chrome_trace_from_events(events):
    col = obs.Collector()
    col.events.extend(events)
    return chrome_trace(col)


# ---------------------------------------------------------------------- #
# profile hooks: obs.profiled() around run_pipeline, and REPRO_PROFILE
# ---------------------------------------------------------------------- #
def test_run_pipeline_profile_writes_trace(tmp_path):
    g = synthesize_powerlaw_graph(300, 2.0, seed=0)
    out = tmp_path / "pipe.json"
    with obs.profiled(str(out)):
        run_pipeline(g, 4, "wb_libra")
    doc = json.loads(out.read_text())
    names = {e.get("name") for e in doc["traceEvents"]}
    assert {"plan.cut", "plan.map", "plan.simulate"} <= names
    # the collector died with the context — nothing leaks into the test
    assert obs.current() is None


def test_repro_profile_env(tmp_path, trace_path):
    out = tmp_path / "env.json"
    code = ("from repro.dist import dist_vertex_cut; "
            f"dist_vertex_cut({trace_path!r}, 8, workers=2, "
            "merge_period=4000)")
    env = dict(os.environ, REPRO_PROFILE=str(out),
               PYTHONPATH="src")
    r = subprocess.run([sys.executable, "-c", code], env=env,
                       cwd=os.path.dirname(os.path.dirname(
                           os.path.abspath(__file__))),
                       capture_output=True, text=True)
    assert r.returncode == 0, r.stderr
    doc = json.loads(out.read_text())
    assert any(e.get("name") == "dist.finalize"
               for e in doc["traceEvents"])
    # and the summarize CLI renders it
    r = subprocess.run([sys.executable, "-m", "repro.obs", "summarize",
                        str(out)], env=env,
                       cwd=os.path.dirname(os.path.dirname(
                           os.path.abspath(__file__))),
                       capture_output=True, text=True)
    assert r.returncode == 0, r.stderr
    assert "serial fraction" in r.stdout


# ---------------------------------------------------------------------- #
# warning origins (stacklevel contract)
# ---------------------------------------------------------------------- #
def test_gil_warning_points_at_caller(no_native):
    g = synthesize_powerlaw_graph(200, 2.0, seed=1)
    with warnings.catch_warnings(record=True) as rec:
        warnings.simplefilter("always")
        dist_vertex_cut(g, 8, workers=2, pool="thread", merge_period=4000)
    gil = [w for w in rec if "GIL" in str(w.message)]
    assert gil and gil[0].filename == __file__


def test_process_fallback_warning_points_at_caller(monkeypatch,
                                                   trace_path):
    from repro.dist import engine

    class Boom:
        def __init__(self, *a, **kw):
            raise ImportError("no pipes here")

    monkeypatch.setattr(engine, "_ProcessPool", Boom)
    g = synthesize_powerlaw_graph(200, 2.0, seed=1)
    # two-phase route: dist_vertex_cut -> _make_pool (stacklevel 3)
    with warnings.catch_warnings(record=True) as rec:
        warnings.simplefilter("always")
        a = dist_vertex_cut(g, 8, workers=2, pool="process",
                            merge_period=4000)
    fb = [w for w in rec if "falling back to serial" in str(w.message)]
    assert fb and fb[0].filename == __file__
    # pipelined route is one frame deeper:
    # dist_vertex_cut -> _pipelined_cut -> _make_pool (stacklevel 4)
    with warnings.catch_warnings(record=True) as rec:
        warnings.simplefilter("always")
        b = dist_vertex_cut(trace_path, 8, workers=2, pool="process",
                            merge_period=4000)
    fb = [w for w in rec if "falling back to serial" in str(w.message)]
    assert fb and fb[0].filename == __file__
    # the fallback still computes the right answer
    ref = dist_vertex_cut(g, 8, workers=2, pool="serial",
                          merge_period=4000)
    np.testing.assert_array_equal(a.assignment, ref.assignment)
    assert b.assignment is not None
