"""Plan cache on-disk encoding: replica counts in narrow exact integers.

`PlanCache.put` stores `replica_indptr` as per-vertex counts and every
non-negative integer array at its narrowest unsigned dtype; `get` must
give back exactly what `put` received, and still read the raw layout
earlier versions wrote."""
import json
import os
import zipfile

import numpy as np
import pytest

from repro import obs
from repro.serve import PlanRequest, PlanService
from repro.serve.cache import _ARRAY_FIELDS, PlanBundle, PlanCache
from repro.trace.synth import synthesize_trace

FP = "f" * 32


def _bundle(p: int, n_vertices: int = 500, n_edges: int = 2000,
            seed: int = 0) -> PlanBundle:
    """A bundle with the program's dtypes: cluster ids reach p - 1, and
    every third vertex has no replica."""
    rng = np.random.default_rng(seed)
    assignment = rng.integers(0, p, n_edges).astype(np.int32)
    if n_edges:
        assignment[0] = p - 1
    counts = rng.integers(1, 4, n_vertices)
    counts[::3] = 0
    indptr = np.concatenate([[0], np.cumsum(counts)]).astype(np.int64)
    flat = rng.integers(0, p, int(indptr[-1])).astype(np.int32)
    if flat.size:
        flat[-1] = p - 1
    n_cores = min(p, 64)
    return PlanBundle(
        assignment=assignment, loads=rng.random(p),
        edge_counts=np.bincount(assignment, minlength=p).astype(np.int64),
        replica_indptr=indptr, replica_flat=flat,
        core_of=np.arange(p, dtype=np.int64) % n_cores,
        core_times=rng.random(n_cores), exec_time=1.5, comm_bytes=64.0,
        graph_name="synthetic", n_vertices=n_vertices,
        total_weight=float(n_edges), p=p, method="wb_libra", lam=1.1)


def _shard(root) -> str:
    return os.path.join(str(root), FP, "step_00000000", "shard_0.npz")


def _assert_identical(got: PlanBundle, want: PlanBundle) -> None:
    for k in _ARRAY_FIELDS:
        a, b = getattr(got, k), getattr(want, k)
        assert a.dtype == b.dtype, k
        assert a.shape == b.shape, k
        np.testing.assert_array_equal(a, b, err_msg=k)
    for k in ("exec_time", "comm_bytes", "graph_name", "n_vertices",
              "total_weight", "p", "method", "lam"):
        assert getattr(got, k) == getattr(want, k), k


@pytest.mark.parametrize("p, id_dtype", [
    (8, np.uint8), (256, np.uint8), (257, np.uint16), (1024, np.uint16),
    (70_000, np.uint32)])
def test_disk_hit_round_trips_exactly(tmp_path, p, id_dtype):
    want = _bundle(p)
    PlanCache(str(tmp_path)).put(FP, want)
    got = PlanCache(str(tmp_path)).get(FP)       # a fresh cache: disk hit
    _assert_identical(got, want)
    # a plain np.load reads the shard: cluster ids at their narrow dtype,
    # the indptr as |V|+1 counts at most 3, floats as they were
    with np.load(_shard(tmp_path)) as z:
        assert sorted(z.files) == sorted(_ARRAY_FIELDS)
        assert z["assignment"].dtype == id_dtype
        assert z["replica_flat"].dtype == id_dtype
        assert z["replica_indptr"].dtype == np.uint8
        np.testing.assert_array_equal(
            z["replica_indptr"], np.diff(want.replica_indptr, prepend=0))
        assert z["loads"].dtype == np.float64
        assert z["core_times"].dtype == np.float64


def test_zero_vertex_graph_round_trips(tmp_path):
    want = _bundle(8, n_vertices=0, n_edges=0)
    assert want.replica_indptr.tolist() == [0] and want.assignment.size == 0
    PlanCache(str(tmp_path)).put(FP, want)
    _assert_identical(PlanCache(str(tmp_path)).get(FP), want)
    with np.load(_shard(tmp_path)) as z:        # empty: stored as it is
        assert z["assignment"].dtype == np.int32
        assert z["replica_flat"].dtype == np.int32


def test_negative_integer_field_stored_as_is(tmp_path):
    want = _bundle(64)
    want.core_of = want.core_of.copy()
    want.core_of[3] = -1
    PlanCache(str(tmp_path)).put(FP, want)
    _assert_identical(PlanCache(str(tmp_path)).get(FP), want)
    with np.load(_shard(tmp_path)) as z:
        assert z["core_of"].dtype == np.int64
        assert z["edge_counts"].dtype != np.int64     # narrowed alone


def test_raw_layout_without_marker_loads_bit_identically(tmp_path):
    want = _bundle(1024, seed=3)
    step = os.path.join(str(tmp_path), FP, "step_00000000")
    os.makedirs(step)
    np.savez_compressed(os.path.join(step, "shard_0.npz"),
                        **{k: getattr(want, k) for k in _ARRAY_FIELDS})
    meta = {k: getattr(want, k) for k in (
        "exec_time", "comm_bytes", "graph_name", "n_vertices",
        "total_weight", "p", "method", "lam")}
    with open(os.path.join(step, "meta.json"), "w") as f:
        json.dump({**meta, "step": 0, "num_shards": 1}, f)
    open(os.path.join(step, "COMMIT"), "w").close()
    _assert_identical(PlanCache(str(tmp_path)).get(FP), want)


def test_store_spans_report_bundle_and_encoded_bytes(tmp_path):
    want = _bundle(1024)
    with obs.scoped() as col:
        PlanCache(str(tmp_path)).put(FP, want)
    (store,) = [e for e in col.events if e["name"] == "serve.cache_store"]
    (write,) = [e for e in col.events if e["name"] == "store.write"]
    bundle_bytes = sum(getattr(want, k).nbytes for k in _ARRAY_FIELDS)
    assert store["args"]["bundle_bytes"] == bundle_bytes
    with np.load(_shard(tmp_path)) as z:
        encoded = sum(z[k].nbytes for k in z.files)
    assert write["args"]["raw_bytes"] == encoded < bundle_bytes
    assert write["args"]["written_bytes"] == os.path.getsize(
        _shard(tmp_path))
    with zipfile.ZipFile(_shard(tmp_path)) as zf:
        assert {i.compress_type for i in zf.infolist()} == {
            zipfile.ZIP_DEFLATED}


def test_served_plan_disk_hit_matches_cold_plan(tmp_path):
    trace = str(tmp_path / "trace.ndjson")
    synthesize_trace(trace, 6_000, seed=1)
    cache = str(tmp_path / "plans")
    req = PlanRequest(source=trace, p=16, lam=1.1)
    cold = PlanService(cache_dir=cache).plan(req)
    disk = PlanService(cache_dir=cache).plan(req)
    assert (cold.cache, disk.cache) == ("cold", "disk")
    _assert_identical(disk.bundle, cold.bundle)
