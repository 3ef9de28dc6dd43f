"""Fast engines must be bit-identical to the reference oracle.

The array-native engines (pure-Python bitmask loop and the optional C
kernel) implement the same streaming case rules as the reference loop
with the same deterministic (load, cluster-id) tie-breaking — so for
every method, p, and λ they must produce the *identical* assignment,
hence identical replication factor, loads, and λ-bound compliance.
Constant-weight and unweighted runs stress the tie-breaking paths.
"""
import numpy as np
import pytest

from repro import obs
from repro.core import ALGORITHMS, IRGraph, engines, vertex_cut

P_VALUES = (2, 8, 64, 512)


def _graphs():
    rng = np.random.default_rng(7)
    out = []
    # weighted, lognormal (generic)
    n, m = 120, 700
    out.append(IRGraph(n=n, src=rng.integers(0, n, m),
                       dst=rng.integers(0, n, m),
                       w=rng.lognormal(size=m), name="lognormal"))
    # constant weights: every load comparison can tie
    n, m = 60, 500
    out.append(IRGraph(n=n, src=rng.integers(0, n, m),
                       dst=rng.integers(0, n, m),
                       w=np.full(m, 0.5), name="ties"))
    # hub-heavy with self-loops: exercises big replica sets + case 1
    n, m = 200, 800
    hub = rng.integers(0, 6, m)
    leaf = rng.integers(0, n, m)
    out.append(IRGraph(n=n, src=hub, dst=leaf,
                       w=rng.lognormal(size=m), name="hubs"))
    return out


GRAPHS = _graphs()


@pytest.mark.parametrize("p", P_VALUES)
@pytest.mark.parametrize("method", ALGORITHMS)
def test_fast_backends_match_reference(method, p, fast_engine):
    for g in GRAPHS:
        for lam in (1.0, 1.25):
            ref = vertex_cut(g, p, method=method, lam=lam, seed=3,
                             backend="reference")
            got = vertex_cut(g, p, method=method, lam=lam, seed=3,
                             backend="fast")
            np.testing.assert_array_equal(got.assignment, ref.assignment,
                                          err_msg=f"{g.name} lam={lam}")
            np.testing.assert_array_equal(got.loads, ref.loads)
            assert got.replication_factor == ref.replication_factor
            assert (got.edge_weight_imbalance
                    == ref.edge_weight_imbalance)
            if method in ("wb_pg", "wb_libra"):
                bound = lam * g.total_weight / p
                cushion = g.w.max() if g.num_edges else 0.0
                assert got.loads.max() <= bound + cushion + 1e-9


def test_edge_cases_match_reference(fast_engine):
    cases = [
        IRGraph(n=3, src=np.array([], dtype=int), dst=np.array([], dtype=int),
                w=np.array([]), name="empty"),
        IRGraph(n=2, src=np.array([0]), dst=np.array([1]),
                w=np.array([2.0]), name="one_edge"),
        IRGraph(n=4, src=np.array([0, 1, 2, 2]), dst=np.array([0, 1, 2, 3]),
                w=np.ones(4), name="self_loops"),
        IRGraph(n=4, src=np.array([0, 1]), dst=np.array([1, 2]),
                w=np.zeros(2), name="zero_weights"),
    ]
    for g in cases:
        for p in (1, 2, 512):
            for method in ALGORITHMS:
                ref = vertex_cut(g, p, method=method, backend="reference")
                got = vertex_cut(g, p, method=method, backend="fast")
                np.testing.assert_array_equal(got.assignment, ref.assignment,
                                              err_msg=f"{g.name} p={p}")


def test_replica_csr_matches_bruteforce():
    g = GRAPHS[0]
    r = vertex_cut(g, 8, method="wb_libra")
    expect = [set() for _ in range(g.n)]
    for e in range(g.num_edges):
        expect[int(g.src[e])].add(int(r.assignment[e]))
        expect[int(g.dst[e])].add(int(r.assignment[e]))
    for v in range(g.n):
        got = r.replicas[v] or set()
        assert got == expect[v]
    assert len(r.replica_flat) == sum(len(s) for s in expect)


def test_negative_weights_rejected(request):
    g = IRGraph(n=3, src=np.array([0, 1]), dst=np.array([1, 2]),
                w=np.array([1.0, -0.5]), name="neg")
    for backend in ("fast", "reference"):
        with pytest.raises(ValueError, match="weights"):
            vertex_cut(g, 4, method="wb_libra", backend=backend)
    request.getfixturevalue("no_native")      # and on the bitmask loop
    with pytest.raises(ValueError, match="weights"):
        vertex_cut(g, 4, method="wb_libra", backend="fast")
    # unweighted methods ignore weights and must still work
    r = vertex_cut(g, 4, method="libra")
    assert len(r.assignment) == 2


def test_backend_validation():
    g = GRAPHS[0]
    for name in ("fast", "pallas", "reference"):
        assert engines.resolve(name).name == name
        assert engines.resolve(engines.resolve(name)) is \
            engines.resolve(name)
    for bad in ("native", "python", "bogus"):
        with pytest.raises(ValueError, match="'fast', 'pallas', 'reference'"):
            engines.resolve(bad)
        with pytest.raises(ValueError, match="'fast', 'pallas', 'reference'"):
            vertex_cut(g, 4, backend=bad)


def test_monkeypatched_no_native_falls_back(no_native):
    g = GRAPHS[1]
    ref = vertex_cut(g, 8, backend="reference")
    with obs.scoped() as col:
        got = vertex_cut(g, 8, backend="fast")   # the bitmask loop
    np.testing.assert_array_equal(got.assignment, ref.assignment)
    (stream,) = [e for e in col.events if e["name"] == "cut.stream"]
    assert stream["args"]["engine"] == "python"


# deeper randomized search when the [test] extra is installed ----------- #
try:
    from hypothesis import given, settings, strategies as st
    HAVE_HYPOTHESIS = True
except ImportError:
    HAVE_HYPOTHESIS = False

if HAVE_HYPOTHESIS:
    @st.composite
    def small_graphs(draw):
        n = draw(st.integers(min_value=2, max_value=40))
        m = draw(st.integers(min_value=1, max_value=120))
        src = draw(st.lists(st.integers(0, n - 1), min_size=m, max_size=m))
        dst = draw(st.lists(st.integers(0, n - 1), min_size=m, max_size=m))
        # coarse weights make load ties likely
        w = draw(st.lists(st.sampled_from([0.5, 1.0, 2.0]),
                          min_size=m, max_size=m))
        return IRGraph(n=n, src=np.array(src), dst=np.array(dst),
                       w=np.array(w), name="hyp")

    @given(g=small_graphs(), p=st.sampled_from([2, 8, 64, 512]),
           method=st.sampled_from([m for m in ALGORITHMS if m != "random"]),
           lam=st.sampled_from([1.0, 1.5]))
    @settings(max_examples=40, deadline=None)
    def test_property_fast_matches_reference(g, p, method, lam):
        ref = vertex_cut(g, p, method=method, lam=lam, backend="reference")
        for forced in (True, False):       # the bitmask loop, then "fast"
            with pytest.MonkeyPatch.context() as mp:
                if forced:
                    mp.setattr(engines, "native_engine", lambda: None)
                got = vertex_cut(g, p, method=method, lam=lam,
                                 backend="fast")
            np.testing.assert_array_equal(got.assignment, ref.assignment)
            np.testing.assert_array_equal(got.loads, ref.loads)
