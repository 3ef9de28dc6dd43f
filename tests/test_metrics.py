"""Metrics registry (`repro.obs.metrics`) + its consumers: histogram
math and merging, the module-level `obs.observe` contract, dist
worker-metric folding across pool kinds, the live `PlanService.metrics`
snapshot, LRU plan-cache eviction accounting, the round-timeline
Perfetto exporter, and the `check_regression --attribute` phase blame.
"""
from __future__ import annotations

import json
import os
import subprocess
import sys

import numpy as np
import pytest

from repro import obs
from repro.obs.export import events_from_chrome, timeline_trace
from repro.obs.metrics import DEFAULT_BUCKETS_US, Histogram, MetricsRegistry
from repro.serve import PlanRequest, PlanService
from repro.serve.cache import PlanBundle, PlanCache
from repro.trace import synthesize_trace

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)        # benchmarks/ is a repo-root package
from benchmarks import check_regression  # noqa: E402


@pytest.fixture(autouse=True)
def _obs_clean():
    obs.disable()
    yield
    obs.disable()


@pytest.fixture(scope="module")
def trace_path(tmp_path_factory):
    path = str(tmp_path_factory.mktemp("metrics") / "synth.ndjson")
    synthesize_trace(path, 20_000, seed=0)
    return path


# ---------------------------------------------------------------------- #
# histogram math
# ---------------------------------------------------------------------- #
def test_histogram_single_sample_reports_the_sample():
    h = Histogram()
    h.observe(3.7)
    assert h.count == 1 and h.sum == 3.7
    # interpolation is clamped to the observed min/max
    assert h.percentile(50) == 3.7
    assert h.percentile(99) == 3.7


def test_histogram_percentile_interpolates():
    h = Histogram(bounds=(10.0, 20.0, 30.0))
    for v in (5.0, 15.0, 25.0, 28.0):
        h.observe(v)
    assert h.min == 5.0 and h.max == 28.0
    assert 0.0 < h.percentile(10) <= 10.0
    assert h.percentile(100) == 28.0
    assert Histogram().percentile(50) == 0.0          # empty -> 0


def test_histogram_overflow_bucket():
    h = Histogram(bounds=(1.0, 2.0))
    h.observe(100.0)
    assert h.counts == [0, 0, 1]
    assert h.percentile(99) == 100.0                  # clamped to max
    # a value on a bound falls in that bound's bucket
    h.observe(1.0)
    h.observe(1.5)
    h.observe(2.0)
    assert h.counts == [1, 2, 1]


def test_default_grid_is_under_ten_percent_wide():
    b = DEFAULT_BUCKETS_US
    assert b[0] == 1.0 and b[-1] == 1e8               # 1 us .. 100 s
    ratios = [y / x for x, y in zip(b, b[1:])]
    assert min(ratios) > 1.0 and max(ratios) <= 1.1


@pytest.mark.parametrize("sigma", [0.3, 2.0, 4.0])
def test_default_grid_percentiles_within_ten_percent(sigma):
    # latencies from a few us to minutes: every percentile read from the
    # buckets lies within 10% of the exact nearest-rank percentile
    x = np.random.default_rng(7).lognormal(np.log(2e3), sigma, 5000)
    x = x[(x >= 1.0) & (x <= 1e8)]
    h = Histogram()
    for v in x:
        h.observe(float(v))
    for q in (1, 10, 50, 90, 99, 99.9):
        exact = float(np.percentile(x, q, method="inverted_cdf"))
        assert abs(h.percentile(q) - exact) <= 0.1 * exact, q


def test_histogram_merge_adds_counts():
    a, b = Histogram(), Histogram()
    for v in (1.0, 10.0, 100.0):
        a.observe(v)
    for v in (2.0, 20.0):
        b.observe(v)
    a.merge(b)
    assert a.count == 5
    assert a.sum == pytest.approx(133.0)
    assert a.min == 1.0 and a.max == 100.0
    # merging mismatched bucket grids is a hard error, not silent skew
    with pytest.raises(ValueError, match="buckets"):
        a.merge(Histogram(bounds=(1.0, 2.0)))
    with pytest.raises(ValueError, match="sorted"):
        Histogram(bounds=(2.0, 1.0))


def test_histogram_snapshot_roundtrip():
    h = Histogram()
    for v in (3.0, 30.0, 300.0, 3000.0):
        h.observe(v)
    snap = h.snapshot()
    assert snap["bounds"] == list(DEFAULT_BUCKETS_US)
    assert snap["count"] == 4 and snap["p50"] == h.percentile(50)
    h2 = Histogram.from_snapshot(json.loads(json.dumps(snap)))
    assert h2.counts == h.counts
    assert h2.percentile(99) == h.percentile(99)
    assert (h2.min, h2.max, h2.sum) == (h.min, h.max, h.sum)


# ---------------------------------------------------------------------- #
# registry
# ---------------------------------------------------------------------- #
def test_registry_instruments_and_snapshot():
    reg = MetricsRegistry()
    reg.counter("hits")
    reg.counter("hits", 2)
    reg.gauge("depth", 7)
    reg.observe("lat_us", 12.0)
    reg.observe("lat_us", 24.0)
    assert len(reg) == 3
    snap = reg.snapshot()
    assert snap["counters"]["hits"] == 3.0
    assert snap["gauges"]["depth"] == 7
    assert snap["histograms"]["lat_us"]["count"] == 2
    assert reg.percentile("lat_us", 50) > 0
    assert reg.percentile("never_observed", 50) == 0.0
    reg.reset()
    assert len(reg) == 0


def test_registry_merge_registry_and_snapshot_dict():
    a, b = MetricsRegistry(), MetricsRegistry()
    a.counter("c", 1)
    a.observe("h", 10.0)
    b.counter("c", 2)
    b.gauge("g", 5)
    b.observe("h", 20.0)
    b.observe("h2", 1.0)
    a.merge(b)                                   # live registry
    a.merge(json.loads(json.dumps(b.snapshot())))  # crossed-process dict
    snap = a.snapshot()
    assert snap["counters"]["c"] == 5.0
    assert snap["gauges"]["g"] == 5
    assert snap["histograms"]["h"]["count"] == 3
    assert snap["histograms"]["h2"]["count"] == 2


def test_module_observe_zero_cost_and_scoped_merge():
    assert not obs.enabled()
    obs.observe("lat", 1.0)                      # disabled: pure no-op
    with obs.scoped(merge=False) as outer:
        obs.observe("lat", 5.0)
        with obs.scoped() as inner:              # merge=True default
            obs.observe("lat", 7.0)
            obs.observe("inner_only", 1.0)
        assert inner.metrics.snapshot()["histograms"]["lat"]["count"] == 1
    snap = outer.metrics.snapshot()["histograms"]
    assert snap["lat"]["count"] == 2             # child folded into outer
    assert snap["inner_only"]["count"] == 1
    assert obs.current() is None


# ---------------------------------------------------------------------- #
# dist: worker metrics fold identically across pool kinds
# ---------------------------------------------------------------------- #
def _dist_metrics(trace_path, pool):
    from repro.dist import dist_vertex_cut
    with obs.scoped(merge=False) as col:
        dist_vertex_cut(trace_path, 8, workers=4, merge_period=2000,
                        pool=pool)
    return col.metrics.snapshot()["histograms"]


def test_dist_metrics_serial_vs_process(trace_path):
    """Worker durations ship home over the result channels and the
    coordinator observes them — so the merged histograms exist without
    shared memory, and the deterministic ones (round edge counts) are
    bit-identical between a serial and a process-pool run."""
    serial = _dist_metrics(trace_path, "serial")
    proc = _dist_metrics(trace_path, "process")
    for snap in (serial, proc):
        assert {"dist.round_edges", "dist.cut_us", "dist.parse_wait_us",
                "dist.finalize_us"} <= set(snap)
    # round partitioning is a pure function of the input: exact equality
    assert serial["dist.round_edges"] == proc["dist.round_edges"]
    # timings differ run to run, but the *sample counts* cannot
    assert serial["dist.cut_us"]["count"] == proc["dist.cut_us"]["count"]
    assert serial["dist.cut_us"]["count"] > 0


def test_repro_profile_process_pool_keeps_coordinator_profile(
        tmp_path, trace_path):
    """REPRO_PROFILE + a process-pool dist run: worker processes must
    not clobber the coordinator's profile, and the dump carries the
    merged worker metrics (the registry rides in repro.metrics)."""
    out = tmp_path / "prof.json"
    code = ("from repro.dist import dist_vertex_cut; "
            f"dist_vertex_cut({trace_path!r}, 8, workers=4, "
            "merge_period=2000, pool='process')")
    env = dict(os.environ, REPRO_PROFILE=str(out), PYTHONPATH="src")
    r = subprocess.run([sys.executable, "-c", code], env=env, cwd=REPO,
                       capture_output=True, text=True)
    assert r.returncode == 0, r.stderr
    doc = json.loads(out.read_text())
    names = {e.get("name") for e in doc["traceEvents"]}
    assert "dist.finalize" in names              # the coordinator's dump
    hists = doc["repro"]["metrics"]["histograms"]
    assert hists["dist.cut_us"]["count"] > 0
    assert hists["dist.round_edges"]["count"] > 0


# ---------------------------------------------------------------------- #
# LRU plan cache
# ---------------------------------------------------------------------- #
def _bundle(tag: int) -> PlanBundle:
    return PlanBundle(
        assignment=np.full(16, tag, np.int32),
        loads=np.ones(4), edge_counts=np.full(4, 4, np.int64),
        replica_indptr=np.arange(9, dtype=np.int64),
        replica_flat=np.zeros(8, np.int32),
        core_of=np.arange(4), core_times=np.ones(4),
        exec_time=1.0, comm_bytes=2.0, graph_name=f"g{tag}",
        n_vertices=8, total_weight=16.0, p=4, method="wb_libra", lam=1.0)


def test_plan_cache_lru_eviction_counts(tmp_path):
    reg = MetricsRegistry()
    cache = PlanCache(str(tmp_path / "plans"), max_entries=2, metrics=reg)
    for i in range(3):
        cache.put(f"fp{i}", _bundle(i))
    # fp0 was least recently used -> evicted; fp1/fp2 resident
    assert list(cache._hot) == ["fp1", "fp2"]
    assert cache.evictions == 1
    assert reg.snapshot()["counters"]["serve.cache.evictions"] == 1
    # an evicted bundle is never lost: disk restore re-promotes it and
    # pushes out the new LRU tail
    got = cache.get("fp0")
    assert got is not None and got.graph_name == "g0"
    assert list(cache._hot) == ["fp2", "fp0"]
    assert cache.evictions == 2
    # hot hits refresh recency: fp2 touched -> fp0 becomes the tail
    cache.get("fp2")
    cache.put("fp3", _bundle(3))
    assert list(cache._hot) == ["fp2", "fp3"]
    assert cache.hot_bytes == sum(
        cache._bundle_nbytes(b) for b in cache._hot.values())


def test_plan_cache_byte_bound(tmp_path):
    one = PlanCache._bundle_nbytes(_bundle(0))
    cache = PlanCache(str(tmp_path / "plans"), max_bytes=2 * one)
    for i in range(3):
        cache.put(f"fp{i}", _bundle(i))
    assert len(cache._hot) == 2
    assert cache.hot_bytes <= 2 * one
    assert cache.evictions == 1


# ---------------------------------------------------------------------- #
# live service metrics
# ---------------------------------------------------------------------- #
def test_service_metrics_live_snapshot(tmp_path, trace_path):
    svc = PlanService(cache_dir=str(tmp_path / "plans"))
    req = PlanRequest(source=trace_path, p=8, lam=1.1)
    svc.plan(req)
    svc.plan(req)
    svc.plan(req)
    m = svc.metrics()
    assert m["plans"] == 3 and m["hits"] == 2 and m["misses"] == 1
    assert m["hit_rate"] == round(2 / 3, 4)
    assert m["tiers"]["cold"]["count"] == 1
    assert m["tiers"]["memory"]["count"] == 2
    assert m["plan_latency_p99_us"] >= m["plan_latency_p50_us"] > 0
    # hits resolve in the hot map: far cheaper than the cold plan
    assert m["tiers"]["memory"]["p99_us"] < m["tiers"]["cold"]["p50_us"]
    assert m["plans_per_s"] > 0 and m["uptime_s"] > 0
    assert m["evictions"] == 0
    # the registry is always on — no obs collector was ever active
    assert obs.current() is None


def test_service_metrics_percentiles_within_ten_percent(tmp_path):
    svc = PlanService(cache_dir=str(tmp_path / "plans"))
    us = np.random.default_rng(3).lognormal(np.log(5e5), 1.0, 400)
    for v in us:
        svc._record("cold", float(v))
    m = svc.metrics()
    for q, got in ((50, m["plan_latency_p50_us"]),
                   (99, m["plan_latency_p99_us"]),
                   (50, m["tiers"]["cold"]["p50_us"]),
                   (99, m["tiers"]["cold"]["p99_us"])):
        exact = float(np.percentile(us, q, method="inverted_cdf"))
        assert abs(got - exact) <= 0.1 * exact, q


def test_service_bounded_hot_map_evicts_and_recovers(tmp_path, trace_path):
    other = str(tmp_path / "other.ndjson")
    synthesize_trace(other, 8_000, seed=3)
    svc = PlanService(cache_dir=str(tmp_path / "plans"),
                      max_hot_entries=1)
    r_a = svc.plan(PlanRequest(source=trace_path, p=8, lam=1.1))
    svc.plan(PlanRequest(source=other, p=8, lam=1.1))  # evicts the first
    m = svc.metrics()
    assert m["evictions"] == 1 and m["hot_entries"] == 1
    # the evicted plan comes back from disk as a hit, not a re-plan
    r2 = svc.plan(PlanRequest(source=trace_path, p=8, lam=1.1))
    assert r2.cache == "disk"
    np.testing.assert_array_equal(r2.bundle.assignment,
                                  r_a.bundle.assignment)
    m = svc.metrics()
    assert m["misses"] == 2 and m["tiers"]["disk"]["count"] == 1
    assert svc.registry.snapshot()["counters"]["serve.plans.disk"] == 1


def test_cli_metrics_subcommand(tmp_path, trace_path, capsys):
    from repro.serve.__main__ import main
    reqs = str(tmp_path / "reqs.json")
    with open(reqs, "w") as f:
        json.dump([{"source": trace_path, "p": 8, "lam": 1.1},
                   {"source": trace_path, "p": 8, "lam": 1.1}], f)
    rc = main(["--cache-dir", str(tmp_path / "plans"), "metrics", reqs])
    assert rc == 0
    doc = json.loads(capsys.readouterr().out)
    assert doc["plans"] == 2 and doc["hits"] == 1
    assert doc["hit_rate"] == 0.5
    assert doc["tiers"]["cold"]["count"] == 1
    # without a replay file: an empty but well-formed snapshot
    rc = main(["--cache-dir", str(tmp_path / "plans"), "metrics"])
    assert rc == 0
    doc = json.loads(capsys.readouterr().out)
    assert doc["plans"] == 0 and doc["hit_rate"] == 0.0


# ---------------------------------------------------------------------- #
# round-timeline Perfetto export
# ---------------------------------------------------------------------- #
def _sample_timeline() -> dict:
    return {"workers": 2, "merge_period": 100, "full_merges": 1,
            "round_merges": 2, "finalize_us": 500.0,
            "rounds": [
                {"round": 0, "edges": 200, "parse_wait_us": 50.0,
                 "cut_us": [100.0, 120.0], "merge_us": 30.0,
                 "full_merge": True},
                {"round": 1, "edges": 150, "parse_wait_us": 10.0,
                 "cut_us": [90.0, 80.0], "merge_us": 0.0},
            ]}


def test_timeline_trace_synthetic_tracks():
    doc = timeline_trace(_sample_timeline())
    events = events_from_chrome(doc)
    assert {e["lane"] for e in events} == {"coord", "cut/w0", "cut/w1"}
    by_name: dict = {}
    for e in events:
        by_name.setdefault(e["name"], []).append(e)
    assert len(by_name["dist.parse_wait"]) == 2
    assert len(by_name["dist.cut"]) == 4
    assert len(by_name["dist.merge"]) == 1       # merge_us=0 is skipped
    assert len(by_name["dist.finalize"]) == 1
    # round 0 dataflow on the synthetic clock: parse_wait, then the two
    # cut spans in parallel, then the merge after the slowest cut
    cuts0 = [e for e in by_name["dist.cut"] if e["args"]["round"] == 0]
    assert all(e["ts"] == pytest.approx(50.0) for e in cuts0)
    assert by_name["dist.merge"][0]["ts"] == pytest.approx(50.0 + 120.0)
    # waits stay cat=wait so the summarizer never counts them busy
    assert by_name["dist.parse_wait"][0]["cat"] == "wait"
    assert doc["repro"]["gauges"]["timeline.workers"] == 2


def test_timeline_cli_from_bench_json(tmp_path, trace_path, capsys):
    """End to end: a real engine timeline lands in a bench-style JSON
    meta and the `python -m repro.obs timeline` subcommand exports it."""
    from repro.dist import dist_vertex_cut
    from repro.obs.__main__ import main
    tl: dict = {}
    dist_vertex_cut(trace_path, 8, workers=2, merge_period=4000,
                    pool="serial", timeline=tl)
    assert tl["rounds"]
    bench = tmp_path / "BENCH_fake.json"
    bench.write_text(json.dumps(
        {"suite": "dist_scaling", "rows": [], "meta": {"timeline_w4": tl}}))
    out = tmp_path / "tl_trace.json"
    rc = main(["timeline", str(bench), "-o", str(out)])
    assert rc == 0
    assert "perfetto" in capsys.readouterr().out
    doc = json.loads(out.read_text())
    lanes = {e["args"]["name"] for e in doc["traceEvents"]
             if e.get("ph") == "M"}
    assert "coord" in lanes
    assert any(ln.startswith("cut/w") for ln in lanes)
    # a bench JSON without the timeline key fails loudly
    empty = tmp_path / "empty.json"
    empty.write_text(json.dumps({"rows": [], "meta": {}}))
    assert main(["timeline", str(empty), "-o", str(out)]) == 1


# ---------------------------------------------------------------------- #
# check_regression --attribute: the guilty phase is named
# ---------------------------------------------------------------------- #
def _write(path, rows):
    with open(path, "w") as f:
        json.dump({"suite": "t", "rows": rows, "meta": {}}, f)
    return str(path)


def test_attribute_names_regressing_phase(tmp_path, capsys):
    base = _write(tmp_path / "base.json", [
        {"backend": "reference", "case": "r", "us_per_edge": 10.0},
        {"backend": "fast", "case": "a", "us_per_edge": 10.0,
         "phases": {"parse": 40.0, "cut": 60.0}},
        {"backend": "fast", "case": "b", "us_per_edge": 12.0,
         "phases": {"parse": 50.0, "cut": 70.0}},
    ])
    run = _write(tmp_path / "run.json", [
        {"backend": "reference", "case": "r", "us_per_edge": 10.0},
        {"backend": "fast", "case": "a", "us_per_edge": 50.0,
         "phases": {"parse": 42.0, "cut": 458.0}},
        {"backend": "fast", "case": "b", "us_per_edge": 60.0,
         "phases": {"parse": 52.0, "cut": 548.0}},
    ])
    rc = check_regression.main([run, base, "--factor", "2.0",
                                "--attribute"])
    out = capsys.readouterr().out
    assert rc == 1
    assert "phase attribution for backend=fast" in out
    assert "regressing phase: cut" in out
    # the blamed phase leads the delta table (worst first)
    table = out.split("phase attribution")[1].splitlines()
    assert table[1].split()[0] == "cut"


def test_speedup_gate_skips_on_one_core_host(tmp_path, capsys):
    """A 1-core host can't demonstrate a W-way speedup: the ratio check
    is skipped (W time-sliced workers measure the scheduler, not the
    code), but the meta key must still be present, and a multi-core
    host still gates the scaled floor."""
    rows = [{"backend": "reference", "case": "r", "us_per_edge": 10.0}]
    base = _write(tmp_path / "base.json", rows)
    gate = ["--min-speedup", "3.0", "--speedup-key", "speedup_w4",
            "--speedup-cores", "4"]
    run = tmp_path / "run.json"
    run.write_text(json.dumps({"suite": "t", "rows": rows,
                               "meta": {"host_cores": 1,
                                        "speedup_w4": 0.42}}))
    assert check_regression.main([str(run), base, *gate]) == 0
    assert "SKIP      speedup_w4" in capsys.readouterr().out
    # the same ratio on a 4-core host fails the scaled floor
    run.write_text(json.dumps({"suite": "t", "rows": rows,
                               "meta": {"host_cores": 4,
                                        "speedup_w4": 0.42}}))
    assert check_regression.main([str(run), base, *gate]) == 1
    capsys.readouterr()
    # a missing key is lost coverage even on a 1-core host
    run.write_text(json.dumps({"suite": "t", "rows": rows,
                               "meta": {"host_cores": 1}}))
    assert check_regression.main([str(run), base, *gate]) == 1


def test_attribute_silent_when_gate_passes(tmp_path, capsys):
    rows = [{"backend": "reference", "case": "r", "us_per_edge": 10.0},
            {"backend": "fast", "case": "a", "us_per_edge": 10.0,
             "phases": {"parse": 40.0, "cut": 60.0}}]
    base = _write(tmp_path / "base.json", rows)
    run = _write(tmp_path / "run.json", rows)
    rc = check_regression.main([run, base, "--factor", "2.0",
                                "--attribute"])
    out = capsys.readouterr().out
    assert rc == 0
    assert "phase attribution" not in out
