"""Mapping invariants + fast-vs-reference backend equivalence.

The array-native mapping engine must be *bit-identical* to the reference
oracle (same greedy decisions, same lowest-index tie-breaking), and both
must uphold Algorithm 2's invariants: every cluster placed, the per-core
cluster threshold respected whenever capacity exists, and deterministic
output for a fixed input.  Seeded randomized sweeps run everywhere; the
hypothesis section digs deeper when the [test] extra is installed.
"""
import numpy as np
import pytest

from repro.core import (IRGraph, Machine, cluster_interaction_graphs,
                        memory_centric_mapping, vertex_bytes_model,
                        vertex_cut)

MACHINES = [
    Machine(rows=4, cols=4),
    Machine(rows=2, cols=3, n_regions=6, cluster_threshold=8),
    Machine(rows=5, cols=2, n_regions=5, cluster_threshold=2),
    Machine(rows=1, cols=8, n_regions=4, cluster_threshold=16),
]


def _random_interaction(rng, p):
    """Random symmetric (comm, shared) pair shaped like real cut output."""
    comm = rng.random((p, p)) * (rng.random((p, p)) < 0.3)
    comm = np.triu(comm, 1)
    comm = comm + comm.T
    shared = np.floor(rng.random((p, p)) * 6) * (rng.random((p, p)) < 0.4)
    shared = np.triu(shared, 1)
    shared = shared + shared.T
    np.fill_diagonal(shared, np.floor(rng.random(p) * 20))
    return comm, shared


def _check_invariants(mapping, machine, p):
    assert len(mapping.core_of) == p
    assert (mapping.core_of >= 0).all()                 # every cluster placed
    assert (mapping.core_of < machine.n_cores).all()
    counts = np.bincount(mapping.core_of, minlength=machine.n_cores)
    if machine.n_cores * machine.cluster_threshold >= p:
        # threshold respected whenever capacity exists
        assert counts.max() <= machine.cluster_threshold
    else:
        # oversubscribed machine: still as balanced as the threshold allows
        assert counts.max() <= p


@pytest.mark.parametrize("machine", MACHINES)
@pytest.mark.parametrize("p", [1, 2, 7, 16, 40, 130])
def test_random_interactions_fast_matches_reference(machine, p):
    rng = np.random.default_rng(p * 31 + machine.n_cores)
    for trial in range(3):
        comm, shared = _random_interaction(rng, p)
        ref = memory_centric_mapping(comm, shared, machine,
                                     backend="reference")
        fast = memory_centric_mapping(comm, shared, machine, backend="fast")
        np.testing.assert_array_equal(fast.core_of, ref.core_of,
                                      err_msg=f"p={p} trial={trial}")
        _check_invariants(fast, machine, p)
        # deterministic for a fixed input
        again = memory_centric_mapping(comm, shared, machine, backend="fast")
        np.testing.assert_array_equal(fast.core_of, again.core_of)


@pytest.mark.parametrize("p", [2, 8, 64])
def test_real_cut_interactions_fast_matches_reference(p):
    """End-to-end over real vertex-cut replica sets, all machines."""
    rng = np.random.default_rng(7)
    n, m = 300, 1500
    g = IRGraph(n=n, src=rng.integers(0, n, m), dst=rng.integers(0, n, m),
                w=rng.lognormal(size=m), name="rand")
    cut = vertex_cut(g, p, method="wb_libra")
    vb = vertex_bytes_model(g)
    cf, sf = cluster_interaction_graphs(cut, p, vb, backend="fast")
    cr, sr = cluster_interaction_graphs(cut.replicas, p, vb,
                                        backend="reference")
    np.testing.assert_allclose(cf, cr, rtol=1e-12)
    np.testing.assert_array_equal(sf, sr)
    # the Pallas segment-sum port must match the fast path bit for bit
    # (same key sets; counts and cache-line bytes are exact int32 sums)
    cp, sp_ = cluster_interaction_graphs(cut, p, vb, backend="pallas")
    np.testing.assert_array_equal(cp, cf)
    np.testing.assert_array_equal(sp_, sf)
    for machine in MACHINES:
        ref = memory_centric_mapping(cr, sr, machine, backend="reference")
        fast = memory_centric_mapping(cf, sf, machine, backend="fast")
        np.testing.assert_array_equal(fast.core_of, ref.core_of)
        _check_invariants(fast, machine, p)


def test_explicit_cluster_order_respected():
    p = 6
    comm, shared = _random_interaction(np.random.default_rng(0), p)
    order = np.array([5, 3, 1, 0, 2, 4])
    a = memory_centric_mapping(comm, shared, MACHINES[0],
                               cluster_order=order, backend="fast")
    b = memory_centric_mapping(comm, shared, MACHINES[0],
                               cluster_order=order, backend="reference")
    np.testing.assert_array_equal(a.core_of, b.core_of)


# deeper randomized search when the [test] extra is installed ----------- #
try:
    from hypothesis import given, settings, strategies as st
    HAVE_HYPOTHESIS = True
except ImportError:
    HAVE_HYPOTHESIS = False

if HAVE_HYPOTHESIS:
    @st.composite
    def interactions(draw):
        p = draw(st.integers(min_value=1, max_value=40))
        seed = draw(st.integers(min_value=0, max_value=2**31 - 1))
        rng = np.random.default_rng(seed)
        return _random_interaction(rng, p) + (p,)

    @st.composite
    def machines(draw):
        rows = draw(st.integers(min_value=1, max_value=6))
        cols = draw(st.integers(min_value=1, max_value=6))
        n_regions = draw(st.integers(min_value=1, max_value=8))
        thr = draw(st.integers(min_value=1, max_value=8))
        return Machine(rows=rows, cols=cols, n_regions=n_regions,
                       cluster_threshold=thr)

    @given(ip=interactions(), machine=machines())
    @settings(max_examples=60, deadline=None)
    def test_property_mapping_invariants_and_equivalence(ip, machine):
        comm, shared, p = ip
        ref = memory_centric_mapping(comm, shared, machine,
                                     backend="reference")
        fast = memory_centric_mapping(comm, shared, machine, backend="fast")
        np.testing.assert_array_equal(fast.core_of, ref.core_of)
        _check_invariants(fast, machine, p)
        again = memory_centric_mapping(comm, shared, machine,
                                       backend="fast")
        np.testing.assert_array_equal(fast.core_of, again.core_of)

    @given(st.integers(1, 6), st.integers(1, 6), st.integers(1, 12))
    @settings(max_examples=60, deadline=None)
    def test_property_region_ids_complete(rows, cols, n_regions):
        """Every region id in [0, n_regions) appears when the mesh has
        room for the region grid; ids never leave the valid range."""
        m = Machine(rows=rows, cols=cols, n_regions=n_regions)
        regs = {m.region_of(c) for c in range(m.n_cores)}
        assert all(0 <= r < n_regions for r in regs)
        rb, cb = m.region_grid()
        assert rb * cb == max(1, n_regions)
        if rb <= rows and cb <= cols:
            assert regs == set(range(n_regions))
