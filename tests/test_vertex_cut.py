"""Invariants of the Weight Balanced p-way Vertex Cut (paper §4)."""
import numpy as np
import pytest

from repro.core import (ALGORITHMS, build_graph,
                        expected_replication_random,
                        expected_replication_random_empirical,
                        synthesize_powerlaw_graph, vertex_cut)


@pytest.fixture(scope="module")
def fft_graph():
    return build_graph("fft", scale="reduced", cache_dir=None)


@pytest.fixture(scope="module")
def pl_graph():
    return synthesize_powerlaw_graph(n=2000, alpha=2.2, seed=1)


@pytest.mark.parametrize("backend", ("fast", "python", "reference"))
@pytest.mark.parametrize("method", ALGORITHMS)
def test_every_edge_assigned_exactly_once(fft_graph, method, backend,
                                          request):
    if backend == "python":             # "fast" on the bitmask loop
        request.getfixturevalue("no_native")
        backend = "fast"
    r = vertex_cut(fft_graph, p=8, method=method, backend=backend)
    assert len(r.assignment) == fft_graph.num_edges
    assert r.assignment.min() >= 0 and r.assignment.max() < 8
    # loads/counts are consistent with the assignment
    counts = np.bincount(r.assignment, minlength=8)
    np.testing.assert_array_equal(counts, r.edge_counts)
    assert np.isclose(r.loads.sum(), fft_graph.total_weight)


@pytest.mark.parametrize("method", ALGORITHMS)
def test_replica_sets_cover_assignments(fft_graph, method):
    r = vertex_cut(fft_graph, p=4, method=method)
    for e in range(fft_graph.num_edges):
        c = r.assignment[e]
        assert c in r.replicas[fft_graph.src[e]]
        assert c in r.replicas[fft_graph.dst[e]]


def test_wb_libra_respects_lambda_bound(pl_graph):
    """Paper Eq. (3): max cluster weight < λ·Σw/p (+ one edge overshoot,
    since the check precedes the placement)."""
    for lam in (1.0, 1.01, 1.1):
        r = vertex_cut(pl_graph, p=8, method="wb_libra", lam=lam)
        bound = lam * pl_graph.total_weight / 8
        assert r.loads.max() <= bound + pl_graph.w.max() + 1e-9


def test_wb_beats_w_on_imbalance(pl_graph):
    """§4.4: the explicit constraint improves edge-weight balance."""
    for fam in ("pg", "libra"):
        w = vertex_cut(pl_graph, p=8, method=f"w_{fam}")
        wb = vertex_cut(pl_graph, p=8, method=f"wb_{fam}")
        assert wb.edge_weight_imbalance <= w.edge_weight_imbalance + 1e-9


def test_wb_near_ideal_balance(pl_graph):
    """§4.4: λ=1 gives imbalance 1+ε for small ε."""
    r = vertex_cut(pl_graph, p=8, method="wb_libra", lam=1.0)
    assert r.edge_weight_imbalance < 1.05


def test_greedy_beats_random_theory(pl_graph):
    """Fig. 8: greedy replication factors sit below the Eq. (10) bound.
    (Bound computed over active vertices, matching the measured factor.)"""
    deg = pl_graph.degrees()
    deg = deg[deg > 0]
    for p in (4, 16, 64):
        bound_emp = expected_replication_random_empirical(deg, p)
        for method in ("w_pg", "wb_pg", "w_libra", "wb_libra"):
            r = vertex_cut(pl_graph, p=p, method=method)
            assert r.replication_factor_active <= bound_emp + 1e-6, \
                f"{method} p={p}"


def test_random_cut_matches_eq10(pl_graph):
    """Random placement empirically matches Eq. (6) within a few %."""
    p = 8
    r = vertex_cut(pl_graph, p=p, method="random", seed=3)
    deg = pl_graph.degrees()
    expected = expected_replication_random_empirical(deg[deg > 0], p)
    measured = r.replication_factor_active
    assert abs(measured - expected) / expected < 0.05


def test_eq10_closed_form_monotone_in_p():
    vals = [expected_replication_random(5000, 2.2, p) for p in (2, 4, 8, 16)]
    assert all(b > a for a, b in zip(vals, vals[1:]))
    assert all(1.0 <= v <= p for v, p in zip(vals, (2, 4, 8, 16)))


def test_libra_cuts_high_degree_vertices(pl_graph):
    """Libra's rule: high-degree vertices are the replicated ones."""
    r = vertex_cut(pl_graph, p=16, method="wb_libra")
    deg = pl_graph.degrees()
    sizes = np.array([len(a) if a else 0 for a in r.replicas])
    hubs = deg >= np.percentile(deg[deg > 0], 99)
    leaves = (deg > 0) & (deg <= 2)
    assert sizes[hubs].mean() > sizes[leaves].mean()


def test_single_cluster_degenerate(fft_graph):
    r = vertex_cut(fft_graph, p=1, method="wb_libra")
    assert r.replication_factor_active == 1.0
    assert r.edge_weight_imbalance == pytest.approx(1.0)


def test_edge_order_modes(pl_graph):
    a = vertex_cut(pl_graph, p=8, method="wb_libra", edge_order="trace")
    b = vertex_cut(pl_graph, p=8, method="wb_libra", edge_order="shuffled")
    for r in (a, b):
        assert np.isclose(r.loads.sum(), pl_graph.total_weight)
    with pytest.raises(ValueError):
        vertex_cut(pl_graph, p=8, edge_order="bogus")


def test_invalid_args(fft_graph):
    with pytest.raises(ValueError):
        vertex_cut(fft_graph, p=8, method="nope")
    with pytest.raises(ValueError):
        vertex_cut(fft_graph, p=0)
    with pytest.raises(ValueError):
        vertex_cut(fft_graph, p=8, lam=0.5)
