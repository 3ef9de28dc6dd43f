"""The profiler reduction and the device metrics on a synthetic trace
whose busy time, per-op time and idle gaps are known by hand
(testdata/synthetic.xplane.txt), and the roofline's byte count at a
tiny size.  Run with `pytest bench/`."""
import importlib.util
import os
import sys

import numpy as np
import pytest

BENCH = os.path.dirname(os.path.abspath(__file__))
sys.path[:0] = [BENCH, os.path.join(os.path.dirname(BENCH), "src")]

import reference  # noqa: E402
import trace_reduce  # noqa: E402


def _metric(name):
    path = os.path.join(BENCH, "metrics", name + ".py")
    spec = importlib.util.spec_from_file_location("metric_" + name, path)
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


@pytest.fixture(scope="module")
def planes(tmp_path_factory):
    from jax.profiler import ProfileData
    with open(os.path.join(BENCH, "testdata", "synthetic.xplane.txt")) as f:
        text = f.read()
    path = tmp_path_factory.mktemp("xplane") / "synthetic.xplane.pb"
    path.write_bytes(ProfileData.text_proto_to_serialized_xspace(text))
    return trace_reduce.load(str(path))


# host perf_counter seconds of the two requests, and obs spans (us) on
# that clock: the trace's clock is 10 s behind it
REQUESTS = [(10.0, 10.00003), (10.00003, 10.00005)]
SPANS = [
    {"name": "serve.plan_cold", "ph": "X", "ts": 10e6, "dur": 30.0},
    {"name": "cut.stream", "ph": "X", "ts": 10e6 + 8, "dur": 12.0},
    {"name": "serve.cache_store", "ph": "X", "ts": 10e6 + 41, "dur": 8.0},
    # an instant event, as the NDJSON scanner's fallback emits, has no
    # duration and is no span
    {"name": "trace.scan_fallback", "ph": "i", "ts": 10e6 + 10},
]


def test_busy_idle_ops_and_gaps(planes):
    r = trace_reduce.reduce(planes, REQUESTS, SPANS)
    assert r["chips"] == 1
    assert r["window_s"] == pytest.approx(50e-6)
    # [1000, 8000) + [20000, 24000) + [40000, 41000) + [49000, 50000)
    assert r["busy_s"] == pytest.approx(13e-6)
    assert r["ops"] == pytest.approx({"jit__reduce/%segment_sum.1": 9e-6,
                                      "jit__reduce/fusion.1": 3e-6,
                                      "jit__reduce/sort.2": 1e-6,
                                      "jit__reduce/copy.3": 1e-6})
    # idle: [0, 1000) in plan_cold; [8000, 20000) in cut.stream;
    # [24000, 40000) split where plan_cold ends at 30000; [41000, 49000)
    # in cache_store
    assert r["gaps"] == pytest.approx({"serve.plan_cold": 7e-6,
                                       "cut.stream": 12e-6,
                                       "no span": 10e-6,
                                       "serve.cache_store": 8e-6})
    assert r["gap_count"] == 4
    assert trace_reduce.top(r["gaps"], 2) == [
        ["cut.stream", pytest.approx(12e-6)], ["no span", pytest.approx(10e-6)]]


def test_device_metrics(planes):
    profile = trace_reduce.reduce(planes, REQUESTS, SPANS)
    ctx = {"plans": 2, "profile": profile, "spans": SPANS, "sizes": [],
           "device_kind": "TPU v5 lite"}
    assert _metric("segsum_kernel_ms").read(ctx) == pytest.approx(4.5e-3)
    assert _metric("device_busy_ms").read(ctx) == pytest.approx(6.5e-3)
    assert _metric("device_idle_pct").read(ctx) == pytest.approx(74.0)
    assert _metric("cache_store_ms").read(ctx) == pytest.approx(4e-3)
    assert _metric("parse_ms").read(ctx) is None      # nothing to read


def test_no_annotation_is_an_error(planes):
    with pytest.raises(ValueError):
        trace_reduce.reduce([p for p in planes if p[0] != "/host:CPU"],
                            REQUESTS)


def test_roofline_bytes_at_a_tiny_size():
    # edges 0-1, 1-2, 2-3, 3-0, 0-2 into p=2 clusters by hand
    graph = {"n": 4, "src": np.array([0, 1, 2, 3, 0]),
             "dst": np.array([1, 2, 3, 0, 2]),
             "w": np.array([1.0, 1.0, 1.0, 1.0, 1.0])}
    plan = reference.plan(graph, 2, 1.0)
    sizes = np.diff(plan["replica_indptr"])
    s = {"m": 5, "n": 4, "r": int(sizes.sum()),
         "k": int(sizes.sum() - np.count_nonzero(sizes)),
         "pairs": int(sum(x * (x - 1) // 2 for x in sizes if x >= 2)),
         "p": 2, "cores": 2}
    roof = _metric("reductions_roofline")
    words = ((4 * 5 + 4 + 1 + s["r"]) + (2 * 5 + 2) + (5 + 2)
             + (s["r"] + 2) + (3 * s["k"] + 4) + (2 * s["pairs"] + 4)
             + (2 * 5 + 2) + (2 * 2 + 2) + (3 * s["k"] + 2 + 1))
    assert roof.plan_bytes(s) == 4 * words
    # 2 plans of that size in 1 us of busy time on a v5e
    ctx = {"plans": 2, "sizes": [s, s], "device_kind": "TPU v5 lite",
           "profile": {"busy_s": 1e-6, "window_s": 1e-5}}
    assert roof.read(ctx) == pytest.approx(
        100 * 2 * 4 * words / 819e9 / 1e-6)
    with pytest.raises(KeyError):
        roof.read({**ctx, "device_kind": "cpu"})
