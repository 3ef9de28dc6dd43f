#!/usr/bin/env python3
"""Chip smoke test: the served planner's device path on one TPU.

Serves plan requests through `PlanService(backend="pallas")` — trace →
graph → WB-Libra vertex cut → memory-centric mapping → simulated cost,
with the finalize, interaction-graph and simulator reductions on the
Pallas segment-sum kernel — on the `plan_service` benchmark's trace:
276,000 synthetic NDJSON lines (510,760 edges at seed 0), generated
from `--seed` under the checkout's `.cache/traces/`.  At p=64 and
p=1024 (method wb_libra, lambda 1.1) it serves a cold request, a
memory hit, a disk hit from a fresh service on the same cache
directory, and a second cold request on an empty directory (the cold
path once its programs are compiled).

Every bundle is checked against `PlanService(backend="fast")` under
the kernel's precision contract (`repro.core.pallas.segsum`):
`assignment`, `core_of`, edge counts, replica CSR, loads (the `bytes`
weights are integer-valued) and comm bytes identical; exec time and
core times within rtol 1e-6.  The smoke also checks that the kernel
compiles to a Mosaic `tpu_custom_call` at the run's shapes.

Earlier lines report per-tier wall times (chip wall time: a request's
outputs are host numpy arrays, so its device work has finished when it
returns), compile time, metrics-core trace counts and
`peak_bytes_in_use`.  The last line is one JSON object:
{"ok": true, "device": {"platform": "tpu", "kind": ..., "count": 1}}.

Usage:
    python chip_smoke.py [--seed 0]

It runs in one process that holds the chip for its whole life and
starts no process that touches JAX.  It exits non-zero, printing no
result, when JAX finds no TPU or any phase fails.  Compiled programs
are cached where JAX_COMPILATION_CACHE_DIR says, else in `.cache/jax`.
"""
from __future__ import annotations

import argparse
import json
import os
import shutil
import sys
import time

ROOT = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.join(ROOT, "src"))

import numpy as np  # noqa: E402

import repro  # noqa: E402,F401  (fails at once outside a checkout)

LINES = 276_000          # the plan_service bench trace: 510,760 edges
PS = (64, 1024)
LAM = 1.1
FLOAT_RTOL = 1e-6        # float32 kernel sums (segsum precision contract)
EXACT_FIELDS = ("assignment", "core_of", "edge_counts", "replica_indptr",
                "replica_flat", "loads")


def check(ok: bool, what: str) -> None:
    if not ok:
        raise SystemExit(f"chip_smoke: FAILED: {what}")


def require_tpu():
    """The first device, or exit non-zero when it is not a TPU."""
    import jax
    dev = jax.devices()[0]
    if dev.platform != "tpu":
        raise SystemExit(f"chip_smoke: no TPU found (JAX platform "
                         f"{dev.platform!r}); nothing was run")
    return dev


def use_compile_cache() -> str:
    """JAX_COMPILATION_CACHE_DIR when set (JAX reads it itself), else a
    fixed directory in the checkout, so repeated runs hit."""
    import jax
    path = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if not path:
        path = os.path.join(ROOT, ".cache", "jax")
        jax.config.update("jax_compilation_cache_dir", path)
    return path


class CompileClock:
    """Backend compile seconds and counts, from JAX's monitoring events."""

    def __init__(self):
        import jax
        self.seconds = 0.0
        self.compiles = 0
        jax.monitoring.register_event_duration_secs_listener(self._on)

    def _on(self, event: str, secs: float, **_) -> None:
        if event == "/jax/core/compile/backend_compile_duration":
            self.seconds += secs
            self.compiles += 1


def trace_path(lines: int, seed: int) -> str:
    from repro.trace import synthesize_trace
    d = os.path.join(ROOT, ".cache", "traces")
    os.makedirs(d, exist_ok=True)
    path = os.path.join(d, f"synth_{lines}_seed{seed}.ndjson")
    if not os.path.exists(path):
        synthesize_trace(path, lines, seed=seed)
    return path


def same_bundle(got, want, what: str, rtol: float = 0.0) -> None:
    """Exact fields equal; simulated times within `rtol` (0: equal)."""
    for field in EXACT_FIELDS:
        check(np.array_equal(getattr(got, field), getattr(want, field)),
              f"{what}: {field} differs")
    check(got.comm_bytes == want.comm_bytes,
          f"{what}: comm_bytes {got.comm_bytes} != {want.comm_bytes}")
    check(np.allclose(got.exec_time, want.exec_time, rtol=rtol, atol=0),
          f"{what}: exec_time {got.exec_time} vs {want.exec_time}")
    check(np.allclose(got.core_times, want.core_times, rtol=rtol, atol=0),
          f"{what}: core_times beyond rtol {rtol}")


def kernel_is_compiled(edges: int, p: int) -> bool:
    """`tpu_custom_call` in the compiled `_segsum_call` at the shapes of
    this run's per-part reductions (edge stream into p+1 slots)."""
    import jax
    import jax.numpy as jnp
    from repro.core.pallas import segsum
    check(not segsum._interpret_default(),
          "the pallas layer would run in interpret mode on this backend")
    block = segsum.DEFAULT_BLOCK
    stream = block * segsum._next_pow2(-(-segsum._next_pow2(edges) // block))
    tiles = -(-(p + 1) // segsum._TILE)
    sds = jax.ShapeDtypeStruct((stream,), jnp.int32)
    text = segsum._segsum_call.lower(sds, sds, tiles=tiles, block=block,
                                     interpret=False).compile().as_text()
    return "tpu_custom_call" in text


def timed(fn):
    t0 = time.perf_counter()
    out = fn()
    return out, time.perf_counter() - t0


def serve(path: str, p: int, cache_root: str, clock: CompileClock) -> dict:
    """All tiers at one p; returns the printed record."""
    from repro.core.pallas import metrics
    from repro.serve import PlanRequest, PlanService
    req = PlanRequest(source=path, p=p, method="wb_libra", lam=LAM)
    pal_dir = os.path.join(cache_root, f"pallas_p{p}")
    shutil.rmtree(pal_dir, ignore_errors=True)        # cold must be cold
    rec = {"p": p}

    c0, s0, t0 = clock.compiles, clock.seconds, metrics.trace_count()
    svc = PlanService(cache_dir=pal_dir, backend="pallas")
    cold, rec["cold_s"] = timed(lambda: svc.plan(req))
    check(cold.cache == "cold", f"p={p}: first request served {cold.cache}")
    rec["cold_compiles"] = clock.compiles - c0
    rec["cold_compile_s"] = clock.seconds - s0
    rec["cold_traces"] = metrics.trace_count() - t0

    mem, rec["memory_s"] = timed(lambda: svc.plan(req))
    check(mem.cache == "memory", f"p={p}: repeat served {mem.cache}")
    same_bundle(mem.bundle, cold.bundle, f"p={p} memory hit")

    disk, rec["disk_s"] = timed(
        lambda: PlanService(cache_dir=pal_dir, backend="pallas").plan(req))
    check(disk.cache == "disk", f"p={p}: restart served {disk.cache}")
    same_bundle(disk.bundle, cold.bundle, f"p={p} disk hit")

    rec_dir = pal_dir + "_recold"
    shutil.rmtree(rec_dir, ignore_errors=True)
    c1, t1 = clock.compiles, metrics.trace_count()
    again, rec["cold_compiled_s"] = timed(
        lambda: PlanService(cache_dir=rec_dir, backend="pallas").plan(req))
    check(again.cache == "cold", f"p={p}: re-cold served {again.cache}")
    same_bundle(again.bundle, cold.bundle, f"p={p} second cold plan")
    rec["recold_compiles"] = clock.compiles - c1
    rec["recold_traces"] = metrics.trace_count() - t1

    fast_dir = os.path.join(cache_root, f"fast_p{p}")
    shutil.rmtree(fast_dir, ignore_errors=True)
    fast, rec["fast_cold_host_s"] = timed(
        lambda: PlanService(cache_dir=fast_dir, backend="fast").plan(req))
    same_bundle(cold.bundle, fast.bundle, f"p={p} against backend='fast'",
                rtol=FLOAT_RTOL)
    rec["exec_time_rel_err"] = abs(cold.bundle.exec_time
                                   - fast.bundle.exec_time) \
        / fast.bundle.exec_time
    rec["edges"] = int(cold.bundle.edge_counts.sum())
    rec["replication_factor"] = cold.bundle.replication_factor
    check(kernel_is_compiled(rec["edges"], p),
          f"p={p}: no tpu_custom_call in the compiled segment-sum kernel")
    rec["tpu_custom_call"] = True
    return rec


def run(lines: int, seed: int, dev) -> list:
    clock = CompileClock()
    path = trace_path(lines, seed)
    cache_root = os.path.join(ROOT, ".cache", "plans_smoke")
    records = []
    for p in PS:
        rec = serve(path, p, cache_root, clock)
        records.append(rec)
        print("chip wall time, p={p}: cold {cold_s:.3f} s (compile "
              "{cold_compile_s:.3f} s over {cold_compiles} programs, "
              "{cold_traces} metrics-core traces), memory hit "
              "{memory_s:.6f} s, disk hit {disk_s:.6f} s, cold once "
              "compiled {cold_compiled_s:.3f} s ({recold_compiles} "
              "compiles, {recold_traces} traces)".format(**rec))
        print("checks, p={p}: {edges} edges, core_of and assignment "
              "identical to backend='fast', exec_time rel err "
              "{exec_time_rel_err:.3e}, tpu_custom_call present; fast "
              "backend cold on the host {fast_cold_host_s:.3f} s"
              .format(**rec))
    stats = dev.memory_stats() or {}
    print(f"compile: {clock.seconds:.3f} s over {clock.compiles} programs; "
          f"peak_bytes_in_use {stats.get('peak_bytes_in_use')}")
    print(json.dumps({"records": records}))
    return records


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--seed", type=int, default=0,
                    help="trace synthesis seed (default 0)")
    args = ap.parse_args(argv)
    dev = require_tpu()
    import jax
    cache = use_compile_cache()
    print(f"device: {dev.device_kind} x{len(jax.devices())}, "
          f"jax {jax.__version__}, compile cache {cache}")
    run(LINES, args.seed, dev)
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(jax.devices())}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
