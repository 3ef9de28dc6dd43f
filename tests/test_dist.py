"""Distributed sharded partitioner (`repro.dist`): contracts and merges.

The subsystem's determinism contract, tested without hypothesis (the
property suite lives in test_dist_property.py):

  * `workers=1` is bit-identical to the single-stream fast engine, for
    the raw cut and through `run_pipeline(backend="dist")`;
  * `workers>1` is a pure function of (graph, p, method, lam, seed,
    merge_period, W) — identical across repeated runs — and still a
    valid vertex cut;
  * the sharded parallel parse produces the *same graph* as the
    sequential streaming ingester for any worker count on well-formed
    traces (plain and gzip sources, process and serial pools);
  * the `ShardCutState` resume path and the `_arrayops` merge helpers
    behave as the engines' chunked/merged building blocks.
"""
import gzip
import json
import os

import numpy as np
import pytest

from repro.core import (IRGraph, ShardCutState, run_pipeline,
                        synthesize_powerlaw_graph, vertex_cut)
from repro.core._arrayops import merge_deltas, merge_limb_masks
from repro.dist import (dist_ingest, dist_ingest_with_stats,
                        dist_vertex_cut, shard_bounds, shard_byte_ranges)
from repro.trace import ingest_trace_with_stats, synthesize_trace

METHODS = ("wb_libra", "w_pg", "pg", "libra")


@pytest.fixture(scope="module")
def graph():
    return synthesize_powerlaw_graph(n=4000, alpha=2.2, seed=1)


@pytest.fixture(scope="module")
def trace_path(tmp_path_factory):
    path = tmp_path_factory.mktemp("traces") / "synth.ndjson"
    synthesize_trace(str(path), 20_000, seed=0)
    return str(path)


# ---------------------------------------------------------------------- #
# engine contracts
# ---------------------------------------------------------------------- #
@pytest.mark.parametrize("method", METHODS)
def test_workers1_bit_identical_to_fast(graph, method):
    ref = vertex_cut(graph, 64, method=method, seed=3, backend="fast")
    for merge_period in (1 << 16, 997):    # chunking must not matter
        got = dist_vertex_cut(graph, 64, method=method, seed=3,
                              workers=1, merge_period=merge_period)
        np.testing.assert_array_equal(got.assignment, ref.assignment)
        assert got.replication_factor == ref.replication_factor
        np.testing.assert_array_equal(got.loads, ref.loads)
        np.testing.assert_array_equal(got.replica_flat, ref.replica_flat)


@pytest.mark.parametrize("workers", (2, 4, 7))
def test_multi_worker_deterministic(graph, workers):
    a = dist_vertex_cut(graph, 32, seed=5, workers=workers,
                        merge_period=1000)
    b = dist_vertex_cut(graph, 32, seed=5, workers=workers,
                        merge_period=1000)
    np.testing.assert_array_equal(a.assignment, b.assignment)
    assert a.replication_factor == b.replication_factor


def test_multi_worker_valid_cut(graph):
    p = 16
    r = dist_vertex_cut(graph, p, workers=4, merge_period=500)
    assert len(r.assignment) == graph.num_edges
    assert (r.assignment >= 0).all() and (r.assignment < p).all()
    assert np.isclose(r.loads.sum(), graph.total_weight)
    # replica sets contain every incident edge's cluster
    replicas = r.replicas
    for e in range(0, graph.num_edges, 97):
        c = int(r.assignment[e])
        assert c in replicas[graph.src[e]]
        assert c in replicas[graph.dst[e]]


def test_merge_period_changes_are_deterministic(graph):
    """Different merge periods may change quality, never validity or
    reproducibility."""
    rfs = []
    for mp_ in (250, 4000):
        a = dist_vertex_cut(graph, 32, workers=4, merge_period=mp_)
        b = dist_vertex_cut(graph, 32, workers=4, merge_period=mp_)
        assert np.array_equal(a.assignment, b.assignment)
        rfs.append(a.replication_factor)
    assert all(rf > 0 for rf in rfs)


def test_run_pipeline_dist_matches_fast(graph):
    """Acceptance contract: backend="dist", workers=1 reproduces
    backend="fast" bit for bit through partition -> map -> simulate."""
    pf, mf, rf = run_pipeline(graph, 16, "wb_libra", backend="fast")
    pd, md, rd = run_pipeline(graph, 16, "wb_libra", backend="dist",
                              workers=1)
    np.testing.assert_array_equal(pd.assignment, pf.assignment)
    assert pd.replication_factor == pf.replication_factor
    np.testing.assert_array_equal(md.core_of, mf.core_of)
    assert rd.exec_time == rf.exec_time
    assert rd.data_comm_bytes == rf.data_comm_bytes


def test_run_pipeline_dist_multiworker(graph):
    part, mapping, rep = run_pipeline(graph, 16, "wb_libra",
                                      backend="dist", workers=3,
                                      merge_period=2000)
    assert part.p == 16
    assert rep.exec_time > 0
    assert len(mapping.core_of) == 16


def test_random_method_delegates(graph):
    a = dist_vertex_cut(graph, 8, method="random", seed=2, workers=4)
    b = vertex_cut(graph, 8, method="random", seed=2, backend="fast")
    np.testing.assert_array_equal(a.assignment, b.assignment)


def test_dist_rejects_bad_args(graph):
    with pytest.raises(ValueError):
        dist_vertex_cut(graph, 8, method="nope")
    with pytest.raises(ValueError):
        dist_vertex_cut(graph, 0)
    with pytest.raises(ValueError):
        dist_vertex_cut(graph, 8, lam=0.5)
    with pytest.raises(ValueError):
        dist_vertex_cut(graph, 8, merge_period=0)
    with pytest.raises(ValueError):
        dist_vertex_cut(graph, 8, backend="reference")


# ---------------------------------------------------------------------- #
# shard state + merge hooks
# ---------------------------------------------------------------------- #
def test_shard_state_chunked_equals_one_shot(graph):
    p = 24
    ref = vertex_cut(graph, p, method="wb_libra", backend="fast")
    deg = graph.degrees()
    bound = 1.0 * graph.total_weight / p
    # wb_libra auto order is trace order with the Libra pre-swap
    swap = deg[graph.src] > deg[graph.dst]
    su = np.ascontiguousarray(
        np.where(swap, graph.dst, graph.src), np.int32)
    sv = np.ascontiguousarray(
        np.where(swap, graph.src, graph.dst), np.int32)
    w = np.ascontiguousarray(graph.w, np.float64)
    st = ShardCutState.create(graph.n, p, deg, bound, True)
    out = np.empty(graph.num_edges, np.int32)
    for a in range(0, graph.num_edges, 1234):
        b = min(a + 1234, graph.num_edges)
        st.stream_chunk(su[a:b], sv[a:b], w[a:b], out[a:b])
    np.testing.assert_array_equal(out, ref.assignment)
    np.testing.assert_array_equal(st.loads, ref.loads)


def test_shard_state_rejects_non_fast_backends(graph):
    with pytest.raises(ValueError):
        ShardCutState.create(10, 4, np.zeros(10, np.int64), np.inf, True,
                             backend="pallas")


def test_merge_limb_masks():
    a = np.array([0b0011, 0, 0b1000], dtype=np.uint64)
    b = np.array([0b0100, 0b0001, 0], dtype=np.uint64)
    got = merge_limb_masks([a, b])
    np.testing.assert_array_equal(
        got, np.array([0b0111, 0b0001, 0b1000], np.uint64))
    np.testing.assert_array_equal(merge_limb_masks([a]), a)
    # inputs untouched
    assert a[0] == 0b0011 and b[0] == 0b0100
    with pytest.raises(ValueError):
        merge_limb_masks([])


def test_merge_deltas():
    snap = np.array([10.0, 20.0, 0.0])
    l1 = snap + np.array([1.0, 0.0, 2.0])
    l2 = snap + np.array([0.0, 5.0, 1.0])
    got = merge_deltas(snap, [l1, l2])
    np.testing.assert_allclose(got, [11.0, 25.0, 3.0])
    # integer exactness
    snap_i = np.array([7, 9], dtype=np.int64)
    got_i = merge_deltas(snap_i, [snap_i - 3, snap_i - 4])
    np.testing.assert_array_equal(got_i, [0, 2])


def test_shard_bounds():
    assert shard_bounds(10, 1) == [0, 10]
    assert shard_bounds(10, 2) == [0, 5, 10]
    b = shard_bounds(7, 3)
    assert b[0] == 0 and b[-1] == 7 and len(b) == 4
    assert shard_bounds(2, 8) == [0, 1, 2]      # W capped at m
    assert shard_bounds(0, 4) == [0, 0]


# ---------------------------------------------------------------------- #
# sharded parallel parse
# ---------------------------------------------------------------------- #
def _stats_no_peak(stats):
    d = stats.summary()
    d.pop("peak_chunk_edges")       # per-shard buffer high-water mark
    d.pop("engine")                 # provenance tag, not a semantic stat
    return d


@pytest.mark.parametrize("workers", (1, 2, 5))
@pytest.mark.parametrize("pool", ("serial", "process"))
def test_sharded_parse_matches_sequential(trace_path, workers, pool):
    g0, s0 = ingest_trace_with_stats(trace_path)
    g, s = dist_ingest_with_stats(trace_path, workers=workers, pool=pool)
    assert g.n == g0.n
    np.testing.assert_array_equal(g.src, g0.src)
    np.testing.assert_array_equal(g.dst, g0.dst)
    np.testing.assert_array_equal(g.w, g0.w)
    assert _stats_no_peak(s) == _stats_no_peak(s0)
    if workers == 1:
        # single shard: exact stats up to provenance (engine + buffer
        # high-water mark differ when the scanner handles the seq path)
        assert _stats_no_peak(s) == _stats_no_peak(s0)


def test_sharded_parse_gzip(trace_path, tmp_path):
    gz = tmp_path / "t.ndjson.gz"
    with open(trace_path) as f, gzip.open(gz, "wt", encoding="utf-8") as z:
        z.write(f.read())
    g0, _ = ingest_trace_with_stats(trace_path)
    g, _ = dist_ingest_with_stats(str(gz), workers=4)
    np.testing.assert_array_equal(g.src, g0.src)
    np.testing.assert_array_equal(g.w, g0.w)


def test_cross_shard_def_resolution(tmp_path):
    """Defs in early shards must bind later shards' uses — including the
    producer-bytes weight recompute — exactly like the rolling tables."""
    lines = [json.dumps({"fn": "f", "bb": "b0", "op": "load",
                         "def": f"v{i}", "def_ty": "i32", "uses": []})
             for i in range(40)]
    lines += [json.dumps({"fn": "f", "bb": "b1", "op": "add",
                          "def": f"x{i}", "def_ty": "<4 x float>",
                          "uses": [f"v{i % 40}",
                                   f"x{i - 1}" if i else "v0"]})
              for i in range(400)]
    path = tmp_path / "defs.ndjson"
    path.write_text("\n".join(lines) + "\n")
    g0, s0 = ingest_trace_with_stats(str(path))
    assert set(g0.w.tolist()) == {4.0, 16.0}    # recompute has teeth
    for workers in (2, 3, 9):
        g, s = dist_ingest_with_stats(str(path), workers=workers)
        assert g.n == g0.n
        np.testing.assert_array_equal(g.src, g0.src)
        np.testing.assert_array_equal(g.w, g0.w)
        assert _stats_no_peak(s) == _stats_no_peak(s0)


def test_sharded_parse_keep_labels(tmp_path):
    lines = [json.dumps({"fn": "f", "bb": "b", "op": f"op{i}",
                         "def": f"v{i}", "uses": [f"v{i-1}"] if i else []})
             for i in range(200)]
    path = tmp_path / "lab.ndjson"
    path.write_text("\n".join(lines) + "\n")
    g0, _ = ingest_trace_with_stats(str(path), keep_labels=True)
    g, _ = dist_ingest_with_stats(str(path), workers=3, keep_labels=True)
    assert list(g.node_labels) == list(g0.node_labels)


def test_sharded_parse_on_error_skip(tmp_path):
    lines = [json.dumps({"fn": "f", "bb": "b", "op": "add",
                         "def": f"v{i}", "uses": []}) for i in range(60)]
    lines[10] = "not json"
    lines[40] = json.dumps({"op": 3})            # non-string op
    path = tmp_path / "bad.ndjson"
    path.write_text("\n".join(lines) + "\n")
    g, s = dist_ingest_with_stats(str(path), workers=3, on_error="skip")
    assert s.skipped == 2
    assert g.n == 58


def test_shard_byte_ranges_cover_file(trace_path):
    size = os.path.getsize(trace_path)
    with open(trace_path, "rb") as f:
        data = f.read()
    for workers in (1, 2, 3, 8):
        ranges = shard_byte_ranges(trace_path, workers)
        assert ranges[0][0] == 0 and ranges[-1][1] == size
        for (a0, b0), (a1, b1) in zip(ranges, ranges[1:]):
            assert b0 == a1                      # contiguous
        for a, b in ranges[:-1]:
            assert data[b - 1:b] == b"\n"        # newline-aligned cuts


def test_unicode_line_separators_inside_strings(tmp_path):
    """U+2028/NEL/form-feed are legal raw inside JSON strings and must
    not be treated as line breaks by the sharded parse (only \\n is) —
    plain byte-range and in-memory block paths alike."""
    lines = [json.dumps({"fn": "f", "bb": "b", "op": f"op {i}x",
                         "def": f"v{i}", "uses": [f"v{i-1}"] if i else []},
                        ensure_ascii=False)
             for i in range(30)]
    path = tmp_path / "u.ndjson"
    path.write_text("\n".join(lines) + "\n", encoding="utf-8")
    g0, s0 = ingest_trace_with_stats(str(path))
    assert s0.records == 30
    for workers in (1, 3):
        g, s = dist_ingest_with_stats(str(path), workers=workers)
        assert s.records == 30 and s.skipped == 0
        np.testing.assert_array_equal(g.src, g0.src)
        np.testing.assert_array_equal(g.w, g0.w)
    gz = tmp_path / "u.ndjson.gz"
    with open(path, "rb") as f, gzip.open(gz, "wb") as z:
        z.write(f.read())
    g, s = dist_ingest_with_stats(str(gz), workers=3)
    assert s.records == 30 and s.skipped == 0
    np.testing.assert_array_equal(g.src, g0.src)


def test_more_workers_than_lines(tmp_path):
    path = tmp_path / "tiny.ndjson"
    path.write_text(json.dumps({"fn": "f", "bb": "b", "op": "add",
                                "def": "v0", "uses": []}) + "\n")
    g, s = dist_ingest_with_stats(str(path), workers=16)
    assert g.n == 1 and s.records == 1


def test_dist_ingest_rejects_non_paths():
    with pytest.raises(TypeError):
        dist_ingest_with_stats(["{}"], workers=2)
    with pytest.raises(ValueError):
        dist_ingest_with_stats("x.ndjson", pool="threads")


# ---------------------------------------------------------------------- #
# path inputs + pipeline plumbing
# ---------------------------------------------------------------------- #
def test_dist_cut_from_trace_path(trace_path):
    # pipeline=False two-phases the path input: ingest + cut must match
    # handing over the pre-ingested graph exactly
    g = dist_ingest(trace_path, workers=2)
    a = dist_vertex_cut(trace_path, 16, workers=2, merge_period=4000,
                        pipeline=False)
    b = dist_vertex_cut(g, 16, workers=2, merge_period=4000)
    np.testing.assert_array_equal(a.assignment, b.assignment)


def test_dist_cut_from_trace_path_pipelined(trace_path):
    # the auto-pipelined path is deterministic and a valid cut, but its
    # prefix-snapshot swap/bound legitimately differs from two-phase
    g = dist_ingest(trace_path, workers=2)
    tl = {}
    a = dist_vertex_cut(trace_path, 16, workers=2, merge_period=4000,
                        timeline=tl)
    b = dist_vertex_cut(trace_path, 16, workers=2, merge_period=4000)
    np.testing.assert_array_equal(a.assignment, b.assignment)
    assert tl["mode"] == "pipelined" and len(tl["rounds"]) >= 1
    assert a.p == 16 and len(a.assignment) == g.num_edges
    # replica CSR must agree with the assignment-derived sets
    from repro.core._arrayops import replica_csr
    indptr, flat = replica_csr(g.n, 16, g.src, g.dst, a.assignment)
    np.testing.assert_array_equal(a.replica_indptr, indptr)
    np.testing.assert_array_equal(a.replica_flat, flat)


def test_dist_cut_from_npz_path(tmp_path, graph):
    npz = tmp_path / "g.npz"
    graph.save_npz(str(npz))
    a = dist_vertex_cut(str(npz), 8, workers=1)
    b = vertex_cut(graph, 8, backend="fast")
    np.testing.assert_array_equal(a.assignment, b.assignment)


def test_run_pipeline_dist_trace_path(trace_path):
    part, mapping, rep = run_pipeline(trace_path, 8, "wb_libra",
                                      backend="dist", workers=2)
    assert part.p == 8 and rep.exec_time > 0


def test_cli_partition_workers(trace_path, capsys):
    from repro.trace.__main__ import main
    assert main(["partition", trace_path, "-p", "4", "--workers", "2"]) == 0
    out = json.loads(capsys.readouterr().out)
    assert out["p"] == 4 and out["replication_factor"] >= 1.0


def test_empty_graph_dist():
    g = IRGraph(n=3, src=np.zeros(0, np.int32), dst=np.zeros(0, np.int32),
                w=np.zeros(0), name="empty")
    r = dist_vertex_cut(g, 4, workers=2)
    assert len(r.assignment) == 0
    assert r.replication_factor == 0.0


# ---------------------------------------------------------------------- #
# worker pools, pipelined dataflow, adaptive merges
# ---------------------------------------------------------------------- #
@pytest.mark.parametrize("pool", ("serial", "thread", "process"))
def test_two_phase_pool_equivalence(graph, pool):
    """The pool choice never affects the result (serial is the oracle)."""
    ref = dist_vertex_cut(graph, 16, workers=3, merge_period=1000,
                          pool="serial")
    tl = {}
    got = dist_vertex_cut(graph, 16, workers=3, merge_period=1000,
                          pool=pool, timeline=tl)
    np.testing.assert_array_equal(got.assignment, ref.assignment)
    assert tl["mode"] == "two-phase"
    from repro.core._native import native_available
    expect = pool if (pool != "thread" or native_available()) else "thread"
    assert tl["pool"] == expect


@pytest.mark.parametrize("pool", ("serial", "thread", "process"))
def test_pipelined_pool_equivalence(trace_path, pool):
    ref = dist_vertex_cut(trace_path, 16, workers=3, merge_period=700,
                          pool="serial")
    tl = {}
    got = dist_vertex_cut(trace_path, 16, workers=3, merge_period=700,
                          pool=pool, timeline=tl)
    np.testing.assert_array_equal(got.assignment, ref.assignment)
    assert tl["mode"] == "pipelined" and tl["pool"] == pool


def test_pipelined_determinism_tiny_rounds(trace_path):
    """Racy interleavings (tiny rounds, many merges) must not leak into
    the output: repeated runs are bit-identical for a fixed config."""
    runs = [dist_vertex_cut(trace_path, 8, workers=4, merge_period=97)
            for _ in range(3)]
    for r in runs[1:]:
        np.testing.assert_array_equal(runs[0].assignment,
                                      r.assignment)


def test_pipelined_independent_of_parse_workers(trace_path):
    """Shard-count of the parse side must not affect the cut (round
    boundaries are global edge offsets, not parse-shard boundaries)."""
    a = dist_vertex_cut(trace_path, 8, workers=2, merge_period=1500)
    for pw in (1, 3, 7):
        b = dist_vertex_cut(trace_path, 8, workers=2, merge_period=1500,
                            parse_workers=pw)
        np.testing.assert_array_equal(a.assignment, b.assignment)


def test_auto_pool_matches_engine(trace_path):
    """auto routes native -> threads, pure-Python -> processes, so the
    no-native CI job exercises the process pool end to end."""
    from repro.core._native import native_available
    tl = {}
    dist_vertex_cut(trace_path, 8, workers=2, merge_period=4000,
                    timeline=tl)
    if native_available():
        assert tl["engine"] == "native" and tl["pool"] == "thread"
    else:
        assert tl["engine"] == "python" and tl["pool"] == "process"


def test_thread_pool_python_engine_warns(graph, no_native):
    with pytest.warns(RuntimeWarning, match="GIL"):
        r = dist_vertex_cut(graph, 8, workers=2, pool="thread",
                            merge_period=4000)
    ref = dist_vertex_cut(graph, 8, workers=2, pool="serial",
                          merge_period=4000)
    np.testing.assert_array_equal(r.assignment, ref.assignment)


def test_pipeline_forced_ineligible_raises(graph, trace_path):
    with pytest.raises(ValueError, match="pipeline=True"):
        dist_vertex_cut(graph, 8, workers=2, pipeline=True)   # not a path
    with pytest.raises(ValueError, match="pipeline=True"):
        dist_vertex_cut(trace_path, 8, workers=1, pipeline=True)
    with pytest.raises(ValueError, match="pipeline=True"):
        dist_vertex_cut(trace_path, 8, workers=2, method="pg",
                        pipeline=True)                        # PG rule
    with pytest.raises(ValueError, match="pipeline"):
        dist_vertex_cut(trace_path, 8, workers=2, pipeline="sometimes")


def test_adaptive_merge_determinism_and_savings(trace_path):
    """divergence defers full merges deterministically; divergence=None
    reproduces the fixed every-round schedule."""
    tl_fixed, tl_adapt = {}, {}
    fixed = dist_vertex_cut(trace_path, 16, workers=3, merge_period=500,
                            timeline=tl_fixed)
    a1 = dist_vertex_cut(trace_path, 16, workers=3, merge_period=500,
                         divergence=1.0, timeline=tl_adapt)
    a2 = dist_vertex_cut(trace_path, 16, workers=3, merge_period=500,
                         divergence=1.0)
    np.testing.assert_array_equal(a1.assignment, a2.assignment)
    assert tl_fixed["full_merges"] == tl_fixed["round_merges"]
    assert tl_adapt["full_merges"] < tl_adapt["round_merges"]
    # a loose bound still ends with a valid cut of comparable quality
    assert len(a1.assignment) == len(fixed.assignment)
    assert a1.replication_factor <= fixed.replication_factor * 1.25


def test_adaptive_merge_quality_sweep(graph):
    """Adaptive merges (tight bound) must not degrade cut quality vs the
    fixed every-round schedule beyond tolerance, across a (p, W) sweep."""
    for p, w in ((8, 2), (32, 4)):
        fixed = dist_vertex_cut(graph, p, workers=w, merge_period=2000)
        adapt = dist_vertex_cut(graph, p, workers=w, merge_period=2000,
                                divergence=0.05)
        assert (adapt.replication_factor
                <= fixed.replication_factor * 1.05), (p, w)


def test_divergence_validation(graph):
    with pytest.raises(ValueError, match="divergence"):
        dist_vertex_cut(graph, 8, workers=2, divergence=-0.1)


def test_shard_state_grow_and_adopt_loads(no_native):
    st = ShardCutState.create(4, 128, np.zeros(4, np.int64), np.inf,
                              True)
    assert st.kernel is None                # the bitmask loop
    st.masks[: 4 * st.limbs] = 7
    st.rem[:] = 5
    st.grow(9)
    assert len(st.rem) == 9 and len(st.masks) == 9 * st.limbs
    assert (st.masks[: 4 * st.limbs] == 7).all()
    assert (st.masks[4 * st.limbs:] == 0).all()
    assert (st.rem[:4] == 5).all() and (st.rem[4:] == 0).all()
    st.grow(3)                      # shrink is a no-op
    assert len(st.rem) == 9
    st2 = ShardCutState.create(3, 8, np.zeros(3, np.int64), np.inf,
                               True)
    assert st2.fresh
    st2.adopt_loads(np.arange(8, dtype=np.float64))
    assert not st2.fresh and st2.loads[7] == 7.0
    # adopt with rem=None leaves rem untouched (Libra never reads it)
    st2.rem[:] = 9
    st2.adopt(np.zeros(8), None, np.zeros(3 * st2.limbs, np.uint64))
    assert (st2.rem == 9).all()


def test_masks_to_replica_csr_matches_sort_based(graph):
    from concurrent.futures import ThreadPoolExecutor
    from repro.core._arrayops import masks_to_replica_csr, replica_csr

    for p in (3, 64, 130):
        cut = vertex_cut(graph, p, method="wb_libra", backend="fast")
        limbs = (p + 63) // 64
        masks = np.zeros(graph.n * limbs, dtype=np.uint64)
        for arrs, v in ((graph.src, None), (graph.dst, None)):
            idx = arrs.astype(np.int64) * limbs + cut.assignment // 64
            np.bitwise_or.at(masks, idx,
                             np.uint64(1) << (cut.assignment % 64
                                              ).astype(np.uint64))
        want = replica_csr(graph.n, p, graph.src, graph.dst, cut.assignment)
        got = masks_to_replica_csr(masks, graph.n, limbs, p)
        np.testing.assert_array_equal(got[0], want[0])
        np.testing.assert_array_equal(got[1], want[1])
        with ThreadPoolExecutor(max_workers=4) as ex:
            sharded = masks_to_replica_csr(masks, graph.n, limbs, p,
                                           executor=ex, shards=7)
        np.testing.assert_array_equal(sharded[0], want[0])
        np.testing.assert_array_equal(sharded[1], want[1])
        # short masks pad as empty rows
        trunc = masks_to_replica_csr(masks[: (graph.n - 2) * limbs],
                                     graph.n, limbs, p)
        assert trunc[0][-1] <= want[0][-1]


def test_timeline_shape(graph):
    tl = {}
    dist_vertex_cut(graph, 8, workers=2, merge_period=3000, timeline=tl)
    assert tl["mode"] == "two-phase" and tl["workers"] == 2
    assert tl["finalize_us"] >= 0 and len(tl["rounds"]) >= 1
    r0 = tl["rounds"][0]
    assert len(r0["cut_us"]) == 2 and "merge_us" in r0
