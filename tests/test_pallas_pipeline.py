"""End-to-end `backend="pallas"` equivalence: partition→metrics→mapping.

The Pallas engine must match the numpy backends at the pipeline's
observable outputs, under the kernel's precision contract
(`repro.core.pallas.segsum`): identical assignment, edge counts and
replica CSR; loads identical for integer-valued weights and within
rtol 1e-6 for other floats; bit-identical `core_of` (its interaction
graphs are exact integer sums); simulated bytes and sync terms
identical to the fast engine, and times within rtol 1e-6 of it and of
the reference oracle.  Runs over the seeded sweep graphs from the
backend-equivalence suite plus one real ingested NDJSON trace from
`examples/traces/`.
"""
import os

import numpy as np

from repro.core import coerce_graph, run_pipeline, synthesize_powerlaw_graph
from repro.core.pallas import narrow
from test_backend_equivalence import GRAPHS

FLOAT_RTOL = 1e-6           # float32 sums: 3u-7u relative, u = 2^-24

TRACES = os.path.join(os.path.dirname(__file__), "..", "examples", "traces")
SWEEP_GRAPHS = GRAPHS + [synthesize_powerlaw_graph(n=3000, alpha=2.2, seed=1)]


def _assert_pipeline_equivalent(g, p, method="wb_libra", lam=1.0):
    ref_part, ref_map, ref_rep = run_pipeline(g, p, method, lam=lam,
                                              backend="reference")
    fast_part, fast_map, fast_rep = run_pipeline(g, p, method, lam=lam,
                                                 backend="fast")
    pal_part, pal_map, pal_rep = run_pipeline(g, p, method, lam=lam,
                                              backend="pallas")
    # cut: identical to both numpy engines
    np.testing.assert_array_equal(pal_part.assignment, ref_part.assignment)
    if narrow(coerce_graph(g).w).dtype == np.int32:
        np.testing.assert_array_equal(pal_part.loads, ref_part.loads)
    else:
        np.testing.assert_allclose(pal_part.loads, ref_part.loads,
                                   rtol=FLOAT_RTOL)
    np.testing.assert_array_equal(pal_part.edge_counts,
                                  ref_part.edge_counts)
    np.testing.assert_array_equal(pal_part.replica_indptr,
                                  fast_part.replica_indptr)
    np.testing.assert_array_equal(pal_part.replica_flat,
                                  fast_part.replica_flat)
    # mapping: bit-identical core_of
    np.testing.assert_array_equal(pal_map.core_of, ref_map.core_of)
    np.testing.assert_array_equal(pal_map.core_of, fast_map.core_of)
    # simulator: exact byte and sync terms, float32 times
    for field in ("data_comm_bytes", "sync_time", "sync_bytes"):
        assert getattr(pal_rep, field) == getattr(fast_rep, field), field
        np.testing.assert_allclose(getattr(pal_rep, field),
                                   getattr(ref_rep, field),
                                   rtol=1e-12, err_msg=field)
    for rep in (ref_rep, fast_rep):
        np.testing.assert_allclose(pal_rep.exec_time, rep.exec_time,
                                   rtol=FLOAT_RTOL)
        np.testing.assert_allclose(pal_rep.core_times, rep.core_times,
                                   rtol=FLOAT_RTOL)


def test_sweep_graphs_pallas_equivalent_p8():
    for g in SWEEP_GRAPHS:
        _assert_pipeline_equivalent(g, 8)


def test_sweep_graphs_pallas_equivalent_p64():
    # two shapes at the larger p keep the jit-cache footprint (and the
    # tier-1 wall clock) bounded: the hub-heavy graph stresses big
    # replica sets, the power-law graph the realistic degree tail
    for g in (SWEEP_GRAPHS[2], SWEEP_GRAPHS[-1]):
        _assert_pipeline_equivalent(g, 64)


def test_methods_and_lambda_pallas_equivalent():
    g = SWEEP_GRAPHS[0]
    for method, lam in (("w_pg", 1.0), ("libra", 1.0), ("wb_libra", 1.25)):
        _assert_pipeline_equivalent(g, 8, method=method, lam=lam)


def test_ingested_trace_pallas_equivalent():
    """One real NDJSON trace through the full path, all three backends."""
    trace = os.path.join(TRACES, "toy_loop.ndjson")
    _assert_pipeline_equivalent(trace, 8)


def test_pallas_backend_validation():
    import pytest

    from repro.core.engines import resolve
    from repro.core.pallas import metrics
    for name in ("fast", "pallas", "reference"):
        assert resolve(name).name == name
    for bad in ("native", "python", "bogus"):
        with pytest.raises(ValueError, match="'fast', 'pallas', 'reference'"):
            resolve(bad)
    # the pallas record streams and places on the fast engine and runs
    # every reduction through the device ports
    pallas, fast = resolve("pallas"), resolve("fast")
    assert pallas.stream is fast.stream and pallas.place is fast.place
    assert (pallas.replica_csr, pallas.cut_sums, pallas.interaction,
            pallas.keyed_sum, pallas.replica_sync) == (
        metrics.replica_csr, metrics.cut_sums, metrics.interaction_from_csr,
        metrics.host_keyed_sum, metrics.replica_sync)
