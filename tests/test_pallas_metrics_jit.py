"""Compile-count probes for the jitted pallas metrics glue.

The metrics layer pads every traced core to power-of-two shape buckets
precisely so that novel graph shapes stop paying op-by-op compiles.
These tests hold it to that: same-bucket inputs must be pure cache hits
(zero new traces), probed through `metrics.trace_count()` — a counter
bumped only while jax traces a core.
"""
import numpy as np

from repro.core import synthesize_powerlaw_graph, vertex_cut
from repro.core.mapping import cluster_interaction_graphs
from repro.core.pallas import metrics
from repro.core.simulator import vertex_bytes_model

P = 16


def test_replica_csr_cache_hits_across_same_bucket_graphs():
    # n in (900, 950) shares the 1024 vertex bucket; edge counts land in
    # the same padded stream bucket too
    g1 = synthesize_powerlaw_graph(n=900, alpha=2.2, seed=0)
    g2 = synthesize_powerlaw_graph(n=950, alpha=2.2, seed=7)
    r1 = vertex_cut(g1, P, backend="pallas")        # warm the cache
    before = metrics.trace_count("replica_csr")
    assert before >= 1
    r2 = vertex_cut(g2, P, backend="pallas")
    assert metrics.trace_count("replica_csr") == before, \
        "same-bucket graph re-traced replica_csr (padding regressed)"
    # and the cached result still matches the numpy oracle
    for g, r in ((g1, r1), (g2, r2)):
        ref = vertex_cut(g, P, backend="fast")
        np.testing.assert_array_equal(r.assignment, ref.assignment)
        np.testing.assert_array_equal(r.replica_indptr, ref.replica_indptr)
        np.testing.assert_array_equal(r.replica_flat, ref.replica_flat)
        # log-normal weights: float32 loads under the kernel's contract
        np.testing.assert_allclose(r.loads, ref.loads, rtol=1e-6)


def test_star_and_interaction_cache_hits_on_repeat():
    g = synthesize_powerlaw_graph(n=700, alpha=2.2, seed=3)
    cut = vertex_cut(g, P, backend="pallas")
    vb = vertex_bytes_model(g)
    c1, s1 = cluster_interaction_graphs(cut, P, vb, backend="pallas")
    before = metrics.trace_count()
    c2, s2 = cluster_interaction_graphs(cut, P, vb, backend="pallas")
    assert metrics.trace_count() == before, \
        "identical interaction inputs re-traced a metrics core"
    np.testing.assert_array_equal(np.asarray(c1), np.asarray(c2))
    np.testing.assert_array_equal(np.asarray(s1), np.asarray(s2))
    # oracle equality: integer counts and cache-line bytes are exact
    cf, sf = cluster_interaction_graphs(cut, P, vb, backend="fast")
    np.testing.assert_array_equal(np.asarray(c1), cf)
    np.testing.assert_array_equal(np.asarray(s1), sf)


def test_star_triples_bucketed_cache():
    g1 = synthesize_powerlaw_graph(n=500, alpha=2.2, seed=1)
    g2 = synthesize_powerlaw_graph(n=480, alpha=2.2, seed=9)
    cut1 = vertex_cut(g1, P, backend="fast")
    cut2 = vertex_cut(g2, P, backend="fast")
    metrics.star_triples(*cut1.replica_csr(),
                         vertex_bytes_model(g1))     # warm
    before = metrics.trace_count("star_triples")
    o, r, b = metrics.star_triples(*cut2.replica_csr(),
                                   vertex_bytes_model(g2))
    assert metrics.trace_count("star_triples") == before
    from repro.core._arrayops import star_triples as np_star
    on, rn, bn = np_star(*cut2.replica_csr(), vertex_bytes_model(g2))
    np.testing.assert_array_equal(np.asarray(o), on)
    np.testing.assert_array_equal(np.asarray(r), rn)
    np.testing.assert_array_equal(np.asarray(b), bn)


def test_trace_count_monotone_and_queryable():
    assert metrics.trace_count() >= metrics.trace_count("replica_csr") >= 0
    assert metrics.trace_count("no_such_core") == 0


def test_wide_vertex_bytes_exact_through_star_cores():
    """Byte weights whose triple stream passes 2^31 ride the star cores
    as int32 limbs: the comm matrix and the crossing bytes equal the
    numpy path exactly, and the wait stays within float32 rounding."""
    from repro.core import _arrayops
    from repro.core.mapping import Machine
    g = synthesize_powerlaw_graph(n=3000, alpha=2.2, seed=5)
    r = vertex_cut(g, P, method="wb_libra")
    indptr, flat = r.replica_csr()
    vb = np.random.default_rng(1).integers(1, 2 ** 33, g.n).astype(float)
    comm, shared = metrics.interaction_from_csr(indptr, flat, P, vb)
    want_comm, want_shared = _arrayops.interaction_from_csr(indptr, flat,
                                                            P, vb)
    assert comm.max() >= 2 ** 31
    np.testing.assert_array_equal(comm, want_comm)
    np.testing.assert_array_equal(shared, want_shared)

    mach = Machine.for_clusters(P)
    core_of = np.arange(P) % mach.n_cores
    wait, sent = metrics.replica_sync(indptr, flat, vb, core_of, mach)
    owners, replicas, b = _arrayops.star_triples(indptr, flat, vb)
    oc, dc = core_of[owners], core_of[replicas]
    cross = oc != dc
    assert sent == float(b[cross].sum()) >= 2 ** 31
    hops = (np.abs(oc // mach.cols - dc // mach.cols)
            + np.abs(oc % mach.cols - dc % mach.cols))
    want_wait = np.zeros(mach.n_cores)
    np.add.at(want_wait, dc[cross],
              ((hops * mach.hop_latency + mach.coherence_penalty)
               / mach.mshr_overlap + b / mach.link_bw)[cross])
    np.testing.assert_allclose(wait, want_wait, rtol=1e-6)
