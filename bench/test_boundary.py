"""The host-device boundary metrics on synthetic obs spans whose sums are
known by hand, and their silence on a program that records no boundary
spans.  Run with `pytest bench/`."""
import importlib.util
import os
import sys

import pytest

BENCH = os.path.dirname(os.path.abspath(__file__))
sys.path[:0] = [BENCH]


def _metric(name):
    path = os.path.join(BENCH, "metrics", name + ".py")
    spec = importlib.util.spec_from_file_location("metric_" + name, path)
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


def _x(name, ts, dur, **args):
    ev = {"name": name, "ph": "X", "ts": float(ts), "dur": float(dur),
          "lane": "main", "cat": "op"}
    if args:
        ev["args"] = args
    return ev


# two plans (us): each device layer holds puts, gets and one compile;
# the spans outside the layers count only where a metric names them
SPANS = [
    _x("trace.ingest", 0, 300, engine="npz", bytes=10, edges=4),
    _x("cut.finalize", 1000, 1000),
    _x("device.put", 1100, 100, bytes=2_000_000),
    _x("device.get", 1400, 400, bytes=500_000),
    _x("jax.compile", 1700, 200, fun="_csr_core"),  # overlaps the get
    _x("map.cluster_graphs", 3000, 500),
    _x("device.put", 3000, 50, bytes=1_000_000),
    _x("device.get", 3400, 100, bytes=500_000),
    _x("sim.run", 4000, 400),
    _x("device.get", 4100, 300, bytes=1_000_000),
    _x("serve.cache_store", 5000, 900),
    _x("store.write", 5100, 600, raw_bytes=90, written_bytes=30),
    _x("store.commit", 5700, 100),
    _x("trace.ingest", 6000, 700, engine="stream"),
    {"name": "jit.trace", "ph": "i", "ts": 1650.0, "lane": "main",
     "cat": "instant", "args": {"core": "replica_csr"}},
]
CTX = {"plans": 2, "spans": SPANS}


def test_boundary_metrics_by_hand():
    assert _metric("device_wait_ms").read(CTX) == pytest.approx(0.4)
    assert _metric("transfer_mb").read(CTX) == pytest.approx(2.5)
    # finalize 1000 - put 100 - get/compile union [1400, 1900) 500;
    # cluster graphs 500 - 50 - 100; simulator 400 - 300
    assert _metric("host_glue_ms").read(CTX) == pytest.approx(
        (400 + 350 + 100) / 1e3 / 2)
    assert _metric("npz_load_ms").read(CTX) == pytest.approx(0.15)
    assert _metric("store_write_ms").read(CTX) == pytest.approx(0.3)
    assert _metric("compile_ms").read(CTX) == pytest.approx(0.1)


def test_layers_split_into_glue_wait_and_put():
    # with no compile, glue + wait + put is the device layers' time
    spans = [e for e in SPANS if e["name"] != "jax.compile"]
    ctx = {"plans": 2, "spans": spans}
    layers = sum(e["dur"] for e in spans if e["name"] in
                 ("cut.finalize", "map.cluster_graphs", "sim.run"))
    put = sum(e["dur"] for e in spans if e["name"] == "device.put")
    assert (_metric("host_glue_ms").read(ctx)
            + _metric("device_wait_ms").read(ctx)
            + put / 1e3 / 2) == pytest.approx(layers / 1e3 / 2)


def test_a_program_without_boundary_spans():
    # the spans a program records without the boundary helpers: the
    # boundary metrics find nothing to read, and no compile reads 0
    old = [e for e in SPANS if e["name"] in
           ("cut.finalize", "map.cluster_graphs", "sim.run",
            "serve.cache_store")]
    ctx = {"plans": 2, "spans": old}
    for name in ("device_wait_ms", "transfer_mb", "host_glue_ms",
                 "npz_load_ms", "store_write_ms"):
        assert _metric(name).read(ctx) is None, name
    assert _metric("compile_ms").read(ctx) == 0.0
