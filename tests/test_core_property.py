"""Hypothesis property tests on the system's invariants (paper §4.2)."""
import numpy as np
import pytest

pytest.importorskip(
    "hypothesis",
    reason="property tests need the [test] extra: pip install -e .[test]")
from hypothesis import given, settings, strategies as st  # noqa: E402

from repro.core import IRGraph, vertex_cut  # noqa: E402
from repro.core.powerlaw import expected_replication_random_empirical  # noqa: E402


@st.composite
def small_graphs(draw):
    n = draw(st.integers(min_value=2, max_value=40))
    m = draw(st.integers(min_value=1, max_value=120))
    src = draw(st.lists(st.integers(0, n - 1), min_size=m, max_size=m))
    dst = draw(st.lists(st.integers(0, n - 1), min_size=m, max_size=m))
    w = draw(st.lists(st.floats(0.1, 100.0), min_size=m, max_size=m))
    return IRGraph(n=n, src=np.array(src), dst=np.array(dst),
                   w=np.array(w), name="hyp")


@given(g=small_graphs(),
       p=st.integers(2, 8),
       method=st.sampled_from(["pg", "libra", "w_pg", "wb_pg",
                               "w_libra", "wb_libra"]))
@settings(max_examples=60, deadline=None)
def test_partition_invariants(g, p, method):
    r = vertex_cut(g, p=p, method=method)
    # every edge exactly once, in range
    assert len(r.assignment) == g.num_edges
    assert (r.assignment >= 0).all() and (r.assignment < p).all()
    # total weight conserved
    assert np.isclose(r.loads.sum(), g.total_weight)
    # replica sets consistent: edge cluster ∈ A(u) ∩ A(v)
    for e in range(g.num_edges):
        c = r.assignment[e]
        assert c in r.replicas[g.src[e]]
        assert c in r.replicas[g.dst[e]]
    # A(v) only contains clusters that actually host an adjacent edge
    host = [set() for _ in range(g.n)]
    for e in range(g.num_edges):
        host[g.src[e]].add(int(r.assignment[e]))
        host[g.dst[e]].add(int(r.assignment[e]))
    for v in range(g.n):
        got = r.replicas[v] or set()
        assert got == host[v]
    # replication factor bounded by min(degree, p)
    deg = g.degrees()
    for v in range(g.n):
        got = r.replicas[v] or set()
        assert len(got) <= min(max(deg[v], 1), p)


@given(g=small_graphs(), p=st.integers(2, 8))
@settings(max_examples=40, deadline=None)
def test_wb_bound_soft(g, p):
    """λ-bounded variants never exceed bound + max single edge weight."""
    r = vertex_cut(g, p=p, method="wb_libra", lam=1.0)
    bound = g.total_weight / p
    assert r.loads.max() <= bound + g.w.max() + 1e-9


@given(st.integers(2, 64), st.floats(1.5, 3.0))
@settings(max_examples=40, deadline=None)
def test_eq6_bounds(p, alpha):
    """Eq. (6) expectation lies in [1, p] for any degree sequence."""
    rng = np.random.default_rng(0)
    deg = rng.zipf(alpha, size=200).clip(max=199)
    e = expected_replication_random_empirical(deg, p)
    assert 1.0 <= e <= p


def test_partition_invariants_pallas_backend():
    """The §4.2 invariants hold verbatim on the Pallas finalize path:
    its cut and replica sets equal the numpy backends' exactly, and its
    float32 loads (log-normal weights) stay within the kernel's rtol
    1e-6 contract (two seeded graphs keep the interpret-mode jit cache
    footprint small; the exhaustive end-to-end sweep lives in
    tests/test_pallas_pipeline.py)."""
    rng = np.random.default_rng(11)
    for n, m, p in ((25, 90, 4), (40, 120, 8)):
        g = IRGraph(n=n, src=rng.integers(0, n, m),
                    dst=rng.integers(0, n, m),
                    w=rng.lognormal(size=m), name="pallas_inv")
        r = vertex_cut(g, p=p, method="wb_libra", backend="pallas")
        ref = vertex_cut(g, p=p, method="wb_libra", backend="fast")
        np.testing.assert_array_equal(r.assignment, ref.assignment)
        np.testing.assert_allclose(r.loads, ref.loads, rtol=1e-6)
        np.testing.assert_array_equal(r.edge_counts, ref.edge_counts)
        np.testing.assert_array_equal(r.replica_indptr, ref.replica_indptr)
        np.testing.assert_array_equal(r.replica_flat, ref.replica_flat)
        assert np.isclose(r.loads.sum(), g.total_weight)
        for e in range(g.num_edges):
            c = r.assignment[e]
            assert c in r.replicas[g.src[e]]
            assert c in r.replicas[g.dst[e]]


def test_submodularity_modularity_identity():
    """Paper Thm 4.2: f(X)+f(Y) = f(X∩Y)+f(X∪Y) for assignment sets —
    the objective is modular (hence submodular) over replica-set unions."""
    rng = np.random.default_rng(0)
    n, p = 30, 6
    for _ in range(20):
        X = [set(rng.choice(p, size=rng.integers(0, 4), replace=False))
             for _ in range(n)]
        Y = [set(rng.choice(p, size=rng.integers(0, 4), replace=False))
             for _ in range(n)]

        def f(sets):
            return sum(len(s) for s in sets) / n

        inter = [x & y for x, y in zip(X, Y)]
        union = [x | y for x, y in zip(X, Y)]
        lhs = f(X) + f(Y)
        rhs = f(inter) + f(union)
        assert np.isclose(lhs, rhs)


def test_monotonicity():
    """Paper Thm 4.3: adding an assignment never decreases f."""
    rng = np.random.default_rng(1)
    n, p = 20, 5
    A = [set(rng.choice(p, size=rng.integers(0, 3), replace=False))
         for _ in range(n)]

    def f(sets):
        return sum(len(s) for s in sets) / n

    base = f(A)
    for v in range(n):
        for c in range(p):
            grown = [set(s) for s in A]
            grown[v].add(c)
            assert f(grown) >= base - 1e-12
