"""Telemetry at the host-device boundary and on the profiler's clock:
the `device.put` / `device.get` spans of the Pallas path (interpret mode
on the CPU), obs spans inside a JAX profiler trace, the spans of a
`.npz` load and of a plan-cache store, and compiles as `jax.compile`
spans with `jit.trace` events.
"""
from __future__ import annotations

import os
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import obs
from repro.checkpoint.store import CheckpointManager
from repro.core import synthesize_powerlaw_graph
from repro.core.pallas import boundary, to_device, to_host
from repro.core.pallas import metrics as pallas_metrics
from repro.core.pallas.segsum import _next_pow2
from repro.serve import PlanRequest, PlanService
from repro.trace import load_graph

DEVICE_LAYERS = ("cut.finalize", "map.cluster_graphs", "sim.run")


@pytest.fixture(autouse=True)
def _obs_clean():
    obs.disable()
    yield
    obs.disable()


@pytest.fixture(scope="module")
def npz_source(tmp_path_factory):
    g = synthesize_powerlaw_graph(n=3000, alpha=2.2, seed=5)
    path = str(tmp_path_factory.mktemp("boundary") / "g.npz")
    g.save_npz(path)
    return path


def _spans(col, name):
    return [e for e in col.events if e["ph"] == "X" and e["name"] == name]


def _inside(inner, outer):
    return (outer["ts"] <= inner["ts"]
            and inner["ts"] + inner["dur"] <= outer["ts"] + outer["dur"])


def test_pallas_cold_plan_boundary_spans(tmp_path, npz_source):
    req = PlanRequest(source=npz_source, p=8, lam=1.1)
    plain = PlanService(cache_dir=str(tmp_path / "a"),
                        backend="pallas").plan(req)
    with obs.scoped() as col:
        traced = PlanService(cache_dir=str(tmp_path / "b"),
                             backend="pallas").plan(req)
    assert plain.cache == traced.cache == "cold"
    for field in ("assignment", "loads", "edge_counts", "replica_indptr",
                  "replica_flat", "core_of", "core_times"):
        np.testing.assert_array_equal(getattr(traced.bundle, field),
                                      getattr(plain.bundle, field))
    assert traced.bundle.exec_time == plain.bundle.exec_time
    assert traced.bundle.comm_bytes == plain.bundle.comm_bytes

    layers = [e for e in col.events if e["name"] in DEVICE_LAYERS]
    puts, gets = _spans(col, "device.put"), _spans(col, "device.get")
    assert puts and gets and len(puts) + len(gets) <= 30
    for e in puts + gets:
        assert sum(_inside(e, layer) for layer in layers) == 1, e
        assert e["args"]["bytes"] > 0
    assert {e["cat"] for e in puts} == {"op"}
    assert {e["cat"] for e in gets} == {"wait"}
    for layer in layers:
        inside = [e["name"] for e in puts + gets if _inside(e, layer)]
        assert {"device.put", "device.get"} <= set(inside), layer["name"]

    # the finalize's first copy is the replica CSR's padded (vertex,
    # cluster) streams; its last fetch of the CSR is indptr and members
    (fin,) = _spans(col, "cut.finalize")
    fin_puts = [e for e in puts if _inside(e, fin)]
    fin_gets = [e for e in gets if _inside(e, fin)]
    g = load_graph(npz_source)
    assert fin_puts[0]["args"]["bytes"] == 2 * 4 * _next_pow2(2 * g.num_edges)
    assert fin_gets[0]["args"]["bytes"] == 4          # the CSR's length
    assert fin_gets[1]["args"]["bytes"] == 4 * (
        g.n + 1 + len(traced.bundle.replica_flat))


def test_helpers_bytes_and_values():
    a = np.arange(10, dtype=np.int32)
    b = np.linspace(0.0, 1.0, 6, dtype=np.float32)
    with obs.scoped() as col:
        da, db = to_device((a, b))
        ha, hb = to_host((da * 2, db))
    (put,), (get,) = _spans(col, "device.put"), _spans(col, "device.get")
    assert put["args"]["bytes"] == a.nbytes + b.nbytes
    assert get["args"]["bytes"] == a.nbytes + b.nbytes
    assert (put["cat"], get["cat"]) == ("op", "wait")
    assert isinstance(ha, np.ndarray) and ha.dtype == np.int32
    np.testing.assert_array_equal(ha, a * 2)
    np.testing.assert_array_equal(hb, b)


def test_helpers_record_nothing_when_off(monkeypatch):
    assert not obs.enabled()
    # no span object is made: the shared no-op stands in for each crossing
    assert boundary._span("device.put", (np.zeros(3),)) is obs.span("x")
    monkeypatch.setattr(obs.core, "_Span", None)      # would fail if made
    x = np.arange(5, dtype=np.int32)
    np.testing.assert_array_equal(to_host(to_device(x) + 1), x + 1)
    assert obs.current() is None


def test_span_lands_in_the_profiler_trace(tmp_path):
    from jax.profiler import ProfileData
    with obs.scoped() as col:
        with jax.profiler.trace(str(tmp_path)):
            with obs.span("test.profiled", n=1):
                time.sleep(0.02)
    (ev,) = _spans(col, "test.profiled")
    found = []
    for dirpath, _, files in os.walk(tmp_path):
        for f in files:
            if f.endswith(".xplane.pb"):
                space = ProfileData.from_file(os.path.join(dirpath, f))
                found += [e.duration_ns for plane in space.planes
                          for line in plane.lines for e in line.events
                          if e.name == "test.profiled"]
    assert len(found) == 1
    assert abs(found[0] / 1e3 - ev["dur"]) < 1e3      # within 1 ms


def test_npz_load_and_store_spans(tmp_path, npz_source):
    with obs.scoped() as col:
        g = load_graph(npz_source)
        state = {"a": np.arange(1000, dtype=np.int64),
                 "b": np.ones(10, np.float32)}
        CheckpointManager(str(tmp_path / "ck")).save(0, state)
    (ingest,) = _spans(col, "trace.ingest")
    assert ingest["args"] == {"engine": "npz",
                              "bytes": os.path.getsize(npz_source),
                              "edges": g.num_edges}
    (write,), (commit,) = _spans(col, "store.write"), _spans(col,
                                                             "store.commit")
    shard = tmp_path / "ck" / "step_00000000" / "shard_0.npz"
    assert write["args"] == {"raw_bytes": 8040,
                             "written_bytes": os.path.getsize(shard)}
    assert write["ts"] + write["dur"] <= commit["ts"]
    assert (tmp_path / "ck" / "step_00000000" / "COMMIT").exists()


def test_forced_recompile_records_compile_and_trace():
    v = jnp.zeros(8, jnp.int32)
    pn = 1237           # a static value no other call compiles
    with obs.scoped() as col:
        pallas_metrics._csr_core(v, v, pn)
        pallas_metrics._csr_core(v, v, pn)            # a cache hit
    traces = [e for e in col.events if e["name"] == "jit.trace"]
    assert [e["args"] for e in traces] == [{"core": "replica_csr"}]
    compiles = _spans(col, "jax.compile")
    assert any("csr_core" in e["args"]["fun"] for e in compiles)
    assert all(e["dur"] > 0 for e in compiles)


# the spans the benchmark reads (bench/metrics/, bench/boundary.py), each
# with the section that must enclose it on a served cold plan
SERVED_TREE = {
    "trace.ingest": "plan.cut",
    "cut.stream": "plan.cut",
    "cut.finalize": "plan.cut",
    "segsum.widen": "cut.finalize",
    "map.cluster_graphs": "plan.map",
    "map.place": "plan.map",
    "sim.run": "plan.simulate",
    "plan.cut": "serve.plan_cold",
    "plan.map": "serve.plan_cold",
    "plan.simulate": "serve.plan_cold",
    "store.write": "serve.cache_store",
    "store.commit": "serve.cache_store",
}
# device.put + device.get spans of one cold plan of the graph below, and
# the bytes they carry
SERVED_TRANSFERS = 20
SERVED_TRANSFER_BYTES = 546676


def test_served_cold_plan_span_tree(tmp_path):
    """One traced cold plan through the served path: every span the
    benchmark reads sits under its section, and the host-device copies
    keep their count.  Integer byte weights past 2^31 in total take the
    finalize's wide keyed sums, as the DeepSeek-V3 cell does."""
    g = synthesize_powerlaw_graph(n=3000, alpha=2.2, seed=5)
    rng = np.random.default_rng(0)
    g.w = rng.integers(1, 1 << 24, g.num_edges).astype(np.float64)
    assert g.w.sum() >= 2 ** 31
    path = str(tmp_path / "wide.npz")
    g.save_npz(path)
    req = PlanRequest(source=path, p=8, lam=1.1)
    with obs.scoped() as col:
        resp = PlanService(cache_dir=str(tmp_path / "plans"),
                           backend="pallas").plan(req)
    assert resp.cache == "cold"
    spans = [e for e in col.events if e["ph"] == "X"]
    names = {e["name"] for e in spans}
    assert {"serve.fingerprint", "serve.plan_cold", "serve.cache_store",
            *SERVED_TREE} <= names
    for child, parent in SERVED_TREE.items():
        for e in _spans(col, child):
            assert any(_inside(e, s) for s in _spans(col, parent)), \
                (child, parent)
    (ingest,) = _spans(col, "trace.ingest")
    assert ingest["args"]["engine"] == "npz"
    transfers = _spans(col, "device.put") + _spans(col, "device.get")
    for e in transfers:
        assert any(_inside(e, s) for name in DEVICE_LAYERS
                   for s in _spans(col, name)), e
    assert len(transfers) == SERVED_TRANSFERS
    assert sum(e["args"]["bytes"] for e in transfers) \
        == SERVED_TRANSFER_BYTES
