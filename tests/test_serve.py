"""Plan service tests: content-addressed cache, incremental
repartitioning bit-identity, batched serving, CLI."""
import io
import json
import os

import numpy as np
import pytest

from repro.core.graph import IRGraph
from repro.core.vertex_cut import vertex_cut
from repro.serve import (IncrementalPlanner, PlanRequest, PlanService,
                         plan_fingerprint)
from repro.serve.fingerprint import clear_stat_memo, content_digest
from repro.trace.ingest import TraceSession, ingest_trace
from repro.trace.synth import synthesize_trace

P = 16
LAM = 1.1
TRACES = os.path.join(os.path.dirname(__file__), "..", "examples",
                      "traces")


@pytest.fixture(scope="module")
def trace_path(tmp_path_factory):
    path = str(tmp_path_factory.mktemp("serve") / "trace.ndjson")
    synthesize_trace(path, 12_000, seed=0)
    return path


# ----------------------------- fingerprint ---------------------------- #
def test_fingerprint_stable_and_knob_sensitive(trace_path):
    fp1 = plan_fingerprint(trace_path, P, "wb_libra", LAM)
    assert fp1 == plan_fingerprint(trace_path, P, "wb_libra", LAM)
    assert fp1 != plan_fingerprint(trace_path, P + 1, "wb_libra", LAM)
    assert fp1 != plan_fingerprint(trace_path, P, "w_libra", LAM)
    assert fp1 != plan_fingerprint(trace_path, P, "wb_libra", LAM + 0.1)
    assert fp1 != plan_fingerprint(trace_path, P, "wb_libra", LAM, seed=1)


def test_fingerprint_tracks_content(tmp_path, trace_path):
    other = str(tmp_path / "other.ndjson")
    synthesize_trace(other, 12_000, seed=1)
    assert (plan_fingerprint(trace_path, P, "wb_libra", LAM)
            != plan_fingerprint(other, P, "wb_libra", LAM))
    # graph-object fingerprints hash the canonical edge arrays
    g = IRGraph(n=4, src=np.array([0, 1]), dst=np.array([2, 3]),
                w=np.array([1.0, 2.0]), name="a")
    g2 = IRGraph(n=4, src=np.array([0, 1]), dst=np.array([2, 3]),
                 w=np.array([1.0, 2.5]), name="a")
    assert content_digest(g) != content_digest(g2)


def test_fingerprint_stat_memo_skips_rehash(tmp_path):
    path = str(tmp_path / "t.ndjson")
    synthesize_trace(path, 1_000, seed=0)
    clear_stat_memo()
    d1 = content_digest(path)
    assert content_digest(path) == d1          # memo hit, same digest
    d_cold = content_digest(path, use_stat_memo=False)
    assert d_cold == d1


# -------------------------- service / cache --------------------------- #
def test_service_cold_then_memory_then_disk(tmp_path, trace_path):
    cache = str(tmp_path / "plans")
    svc = PlanService(cache_dir=cache)
    req = PlanRequest(source=trace_path, p=P, lam=LAM)
    r1 = svc.plan(req)
    assert r1.cache == "cold"
    r2 = svc.plan(req)
    assert r2.cache == "memory"
    np.testing.assert_array_equal(r1.bundle.assignment,
                                  r2.bundle.assignment)
    # warm restart: a fresh service over the same cache dir loads from
    # the checkpoint store without planning
    svc2 = PlanService(cache_dir=cache)
    r3 = svc2.plan(req)
    assert r3.cache == "disk"
    np.testing.assert_array_equal(r1.bundle.assignment,
                                  r3.bundle.assignment)
    np.testing.assert_array_equal(r1.bundle.replica_flat,
                                  r3.bundle.replica_flat)
    np.testing.assert_array_equal(r1.bundle.core_of, r3.bundle.core_of)
    assert r3.bundle.exec_time == r1.bundle.exec_time
    assert r3.bundle.comm_bytes == r1.bundle.comm_bytes
    assert svc2.stats()["disk_entries"] == 1


def test_service_bundle_matches_direct_pipeline(tmp_path, trace_path):
    svc = PlanService(cache_dir=str(tmp_path / "plans"))
    r = svc.plan(PlanRequest(source=trace_path, p=P, lam=LAM))
    g = ingest_trace(trace_path)
    cut = vertex_cut(g, P, method="wb_libra", lam=LAM, backend="fast")
    np.testing.assert_array_equal(r.bundle.assignment, cut.assignment)
    assert r.bundle.replication_factor == pytest.approx(
        cut.replication_factor)


def test_plan_many_dedups_and_serves(tmp_path, trace_path):
    other = str(tmp_path / "other.ndjson")
    synthesize_trace(other, 4_000, seed=2)
    svc = PlanService(cache_dir=str(tmp_path / "plans"))
    reqs = [PlanRequest(source=trace_path, p=P, lam=LAM),
            PlanRequest(source=other, p=P, lam=LAM),
            PlanRequest(source=trace_path, p=P, lam=LAM)]  # duplicate
    out = svc.plan_many(reqs)
    assert [r.cache for r in out] == ["cold", "cold", "memory"]
    assert out[0].fingerprint == out[2].fingerprint
    assert out[0].fingerprint != out[1].fingerprint
    np.testing.assert_array_equal(out[0].bundle.assignment,
                                  out[2].bundle.assignment)
    assert svc.stats() == {**svc.stats(), "hits": 1, "misses": 2}


# ------------------------ incremental planner ------------------------- #
def test_trace_session_matches_one_shot(trace_path):
    lines = open(trace_path).read().splitlines(keepends=True)
    sess = TraceSession()
    sess.feed(io.StringIO("".join(lines[:5_000])))
    sess.feed(io.StringIO("".join(lines[5_000:])))
    g_inc = sess.graph("t")
    g_one = ingest_trace(trace_path, name="t")
    assert g_inc.n == g_one.n
    np.testing.assert_array_equal(g_inc.src, g_one.src)
    np.testing.assert_array_equal(g_inc.dst, g_one.dst)
    np.testing.assert_array_equal(g_inc.w, g_one.w)


def test_incremental_single_quantum_matches_vertex_cut(trace_path):
    pl = IncrementalPlanner(p=P, method="wb_libra", lam=LAM,
                            quantum=1 << 22)
    pl.append(trace_path)
    g, cut, mapping, rep = pl.plan()
    ref = vertex_cut(ingest_trace(trace_path), P, method="wb_libra",
                     lam=LAM, edge_order="trace", backend="fast")
    np.testing.assert_array_equal(cut.assignment, ref.assignment)
    np.testing.assert_array_equal(cut.replica_indptr, ref.replica_indptr)
    np.testing.assert_array_equal(cut.replica_flat, ref.replica_flat)
    np.testing.assert_array_equal(cut.loads, ref.loads)
    np.testing.assert_array_equal(cut.edge_counts, ref.edge_counts)


@pytest.mark.parametrize("method", ["libra", "w_libra", "wb_libra"])
def test_incremental_window_invariance(trace_path, method):
    """Warm incremental == cold over the concatenated trace, bit for
    bit — the incremental-repartition contract (window boundaries and
    interleaved plan() calls never change the output)."""
    lines = open(trace_path).read().splitlines(keepends=True)
    cuts = []
    windows = [[len(lines)],                       # one shot (the cold cut)
               [7_000, len(lines)],                # two windows
               [2_000, 5_000, 9_000, len(lines)]]  # four, plan mid-way
    for bounds in windows:
        pl = IncrementalPlanner(p=P, method=method, lam=LAM, quantum=2048)
        start = 0
        for end in bounds:
            pl.append(io.StringIO("".join(lines[start:end])))
            start = end
            pl.plan()        # interleaved plans must not perturb state
        _, cut, _, rep = pl.plan()
        cuts.append((cut, rep))
    cold, cold_rep = cuts[0]
    for cut, rep in cuts[1:]:
        np.testing.assert_array_equal(cut.assignment, cold.assignment)
        np.testing.assert_array_equal(cut.replica_indptr,
                                      cold.replica_indptr)
        np.testing.assert_array_equal(cut.replica_flat, cold.replica_flat)
        np.testing.assert_array_equal(cut.loads, cold.loads)
        assert rep.exec_time == cold_rep.exec_time
        assert rep.data_comm_bytes == cold_rep.data_comm_bytes


def test_incremental_rejects_pg_methods():
    with pytest.raises(ValueError, match="Libra-rule"):
        IncrementalPlanner(p=4, method="wb_pg")
    with pytest.raises(ValueError, match="lambda"):
        IncrementalPlanner(p=4, lam=0.5)


# -------------------------------- CLI --------------------------------- #
def test_cli_plan_and_cache(tmp_path, trace_path, capsys):
    from repro.serve.__main__ import main
    cache = str(tmp_path / "plans")
    rc = main(["--cache-dir", cache, "plan", trace_path, "-p", str(P),
               "--lam", str(LAM)])
    assert rc == 0
    doc = json.loads(capsys.readouterr().out)
    assert doc["cache"] == "cold" and doc["p"] == P
    rc = main(["--cache-dir", cache, "plan", trace_path, "-p", str(P),
               "--lam", str(LAM)])
    assert rc == 0
    assert json.loads(capsys.readouterr().out)["cache"] == "disk"
    rc = main(["--cache-dir", cache, "cache"])
    assert rc == 0
    assert doc["fingerprint"] in capsys.readouterr().out


def test_cli_batch(tmp_path, trace_path, capsys):
    from repro.serve.__main__ import main
    reqs = str(tmp_path / "reqs.json")
    with open(reqs, "w") as f:
        json.dump([{"source": trace_path, "p": P, "lam": LAM},
                   {"source": trace_path, "p": P, "lam": LAM}], f)
    rc = main(["--cache-dir", str(tmp_path / "plans"), "batch", reqs])
    assert rc == 0
    doc = json.loads(capsys.readouterr().out)
    assert [r["cache"] for r in doc["responses"]] == ["cold", "memory"]
    assert doc["stats"]["hits"] == 1 and doc["stats"]["misses"] == 1


# ------------------- one pipeline behind every entry ------------------ #
@pytest.mark.parametrize("backend", ["fast", "pallas"])
def test_entry_points_plan_alike(tmp_path, backend):
    """The served cold plan, `run_pipeline` and `plan_graph` are one
    composition: on a real trace they agree field for field, exactly."""
    from repro.core import run_pipeline
    from repro.core.planner import plan_graph
    trace = os.path.join(TRACES, "toy_loop.ndjson")
    bundle = PlanService(cache_dir=str(tmp_path / "plans"),
                         backend=backend).plan(
        PlanRequest(source=trace, p=8, lam=LAM)).bundle
    cut, mapping, rep = run_pipeline(trace, 8, "wb_libra", lam=LAM,
                                     backend=backend)
    report = plan_graph(trace, 8, lam=LAM, backend=backend)
    for field in ("assignment", "loads", "edge_counts", "replica_indptr",
                  "replica_flat"):
        np.testing.assert_array_equal(getattr(bundle, field),
                                      getattr(cut, field), err_msg=field)
        np.testing.assert_array_equal(getattr(report.cut, field),
                                      getattr(cut, field), err_msg=field)
    np.testing.assert_array_equal(bundle.core_of, mapping.core_of)
    np.testing.assert_array_equal(bundle.core_times, rep.core_times)
    assert bundle.exec_time == rep.exec_time == report.exec_time
    assert bundle.comm_bytes == rep.data_comm_bytes == report.comm_bytes
    assert (bundle.n_vertices, bundle.total_weight) == (
        report.graph.n, report.graph.total_weight)
