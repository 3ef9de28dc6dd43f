#!/usr/bin/env python3
"""Benchmark harness: cold plans through the served planner on one TPU.

    python3 bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

A cell of `BENCHMARK.json` names a configuration (`bench/configs/`, a
graph source written by a generator in `bench/generators/`) and a traffic
mix (`bench/traffic/`).  Every mix is a closed loop with one client
re-planning one program across the balance factor lambda: each request is
`PlanRequest(source, p, method, lam)` with lambda cycling over the mix's
grid in an order drawn from the seed, and each pass over the grid goes to
a `PlanService(backend="pallas")` on a fresh, empty plan-cache
directory, so every request is a cold plan.  Set-up writes the source
(once per checkout), then serves one whole pass so that every program
the window runs is compiled; the window then serves whole passes until
`--seconds` have gone by, so every seed's window holds the same plans.

End-to-end metrics (`--trace 0`): `setup_s`, from process start to the
first timed request, and `cold_plan_s`, the window's wall time over the
plans completed in it.  Per-layer metrics (`--trace 1`): each
`bench/metrics/<name>.py` reads the window's obs spans, the profiler
trace reduction (`trace_reduce.py`) and the plans' logical sizes.

After the window a sample of the served plans, drawn from the seed, is
checked against the plain reference (`reference.py`); the numbers
compared and their limits are the last lines on standard error and the
last key of the result, which is the last line on standard output.

It runs in one process and exits non-zero, printing no result, when JAX
finds no TPU or fewer chips than the cell asks for.  Compiled programs
are kept where JAX_COMPILATION_CACHE_DIR says, else in `.cache/jax` of
the checkout; everything else it writes goes under `.cache/bench/`.
"""
from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import hashlib  # noqa: E402
import importlib.util  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402

import numpy as np  # noqa: E402

BENCH = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(BENCH)
for _p in (BENCH, os.path.join(BENCH, "generators")):
    if _p not in sys.path:
        sys.path.insert(0, _p)

import reference  # noqa: E402
import trace_reduce  # noqa: E402


def load_cell(name: str, root: str = ROOT):
    """(cell, configuration, traffic, per-layer metric entries) of a cell
    of BENCHMARK.json."""
    with open(os.path.join(root, "BENCHMARK.json")) as f:
        spec = json.load(f)
    cells = {c["name"]: c for c in spec["workloads"]}
    if name not in cells:
        raise SystemExit(f"bench: unknown workload {name!r}; "
                         f"choose from {sorted(cells)}")
    cell = cells[name]
    entry = {c["name"]: c for c in spec["configs"]}[cell["config"]]
    with open(os.path.join(root, entry["file"])) as f:
        config = json.load(f)
    with open(os.path.join(BENCH, "traffic", cell["traffic"] + ".json")) as f:
        traffic = json.load(f)
    per_layer = [m for m in spec["per_layer"]
                 if name in m.get("workloads", [name])]
    return cell, config, traffic, per_layer


def require_chips(count: int):
    """The first device, or exit non-zero when it is not a TPU or there
    are fewer than `count` of them."""
    import jax
    devices = jax.devices()
    if devices[0].platform != "tpu":
        raise SystemExit(f"bench: no TPU found (JAX platform "
                         f"{devices[0].platform!r}); nothing was run")
    if len(devices) < count:
        raise SystemExit(f"bench: the cell needs {count} chips, JAX finds "
                         f"{len(devices)}; nothing was run")
    return devices[0]


def use_compile_cache(root: str) -> str:
    """Keep every compiled program in the persistent cache:
    JAX_COMPILATION_CACHE_DIR when set, else `.cache/jax` of the
    checkout (a fixed path, so later runs there hit)."""
    import jax
    path = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if not path:
        path = os.path.join(root, ".cache", "jax")
        jax.config.update("jax_compilation_cache_dir", path)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    return path


class CompileClock:
    """Compiles and persistent-cache hits and misses, from JAX's
    monitoring events.  JAX reports a backend compile duration for every
    program it builds, a cache load included; a compile is such an event
    that was not a cache hit."""

    def __init__(self):
        import jax
        self.seconds = 0.0
        self.events = 0
        self.cache_hits = 0
        self.cache_misses = 0
        jax.monitoring.register_event_duration_secs_listener(self._duration)
        jax.monitoring.register_event_listener(self._event)

    def _duration(self, event: str, secs: float, **_) -> None:
        if event == "/jax/core/compile/backend_compile_duration":
            self.seconds += secs
            self.events += 1

    def _event(self, event: str, **_) -> None:
        if event == "/jax/compilation_cache/cache_hits":
            self.cache_hits += 1
        elif event == "/jax/compilation_cache/cache_misses":
            self.cache_misses += 1

    def snapshot(self) -> dict:
        return {"programs": self.events,
                "compiles": self.events - self.cache_hits,
                "compile_s": self.seconds, "cache_hits": self.cache_hits,
                "cache_misses": self.cache_misses}


def load_module(kind: str, name: str):
    """`bench/<kind>/<name>.py` as a module."""
    path = os.path.join(BENCH, kind, name + ".py")
    spec = importlib.util.spec_from_file_location(f"bench_{kind}_{name}",
                                                  path)
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


def lam_order(traffic: dict, seed: int) -> list:
    """The mix's balance factors in an order drawn from the seed: every
    seed plans the same lambdas, so the same programs."""
    rng = np.random.default_rng([seed, 0])
    lams = traffic["lams"]
    return [lams[i] for i in rng.permutation(len(lams))]


def write_source(config: dict, work: str) -> str:
    """The configuration's graph source, written by its generator once per
    checkout and configuration (the path holds a digest of the
    configuration); returns the path."""
    generator = load_module("generators", config["generator"])
    digest = hashlib.blake2b(json.dumps(config, sort_keys=True).encode(),
                             digest_size=8).hexdigest()
    path = os.path.join(work, "data",
                        f"{config['name']}-{digest}{generator.SUFFIX}")
    if not os.path.exists(path):
        os.makedirs(os.path.dirname(path), exist_ok=True)
        generator.write(config, path + ".tmp")
        os.replace(path + ".tmp", path)
    return path


def logical_sizes(bundle, n_cores: int) -> dict:
    """Sizes of a served plan that fix its device work (no padding)."""
    sizes = np.diff(np.asarray(bundle.replica_indptr))
    r = int(sizes.sum())
    pair_sizes = sizes[(sizes >= 2) & (sizes <= reference.PAIRWISE_CAP)]
    return {"m": len(bundle.assignment), "n": int(bundle.n_vertices), "r": r,
            "k": r - int(np.count_nonzero(sizes)),
            "pairs": int((pair_sizes * (pair_sizes - 1) // 2).sum()),
            "p": int(bundle.p), "cores": n_cores}


class Loop:
    """One client's closed loop of cold plans over fresh plan caches."""

    def __init__(self, source: str, traffic: dict, lams: list,
                 plans_dir: str):
        from repro.serve import PlanRequest
        self.requests = [PlanRequest(source=source, p=traffic["p"],
                                     method=traffic["method"], lam=lam)
                         for lam in lams]
        self.plans_dir = plans_dir
        self.passes = 0
        shutil.rmtree(plans_dir, ignore_errors=True)

    def serve(self, until: "float | None", annotate: bool) -> list:
        """One pass (`until` None), or whole passes until `perf_counter()`
        passes `until`.  Returns (request, response or None, t0, t1)."""
        import jax
        from repro.serve import PlanService
        done = []
        while True:
            svc = PlanService(cache_dir=os.path.join(
                self.plans_dir, f"pass_{self.passes}"), backend="pallas")
            self.passes += 1
            for req in self.requests:
                t0 = time.perf_counter()
                try:
                    if annotate:
                        with jax.profiler.TraceAnnotation(
                                trace_reduce.ANNOTATION):
                            resp = svc.plan(req)
                    else:
                        resp = svc.plan(req)
                    if resp.cache != "cold":
                        resp = None
                except Exception as e:  # a failed request is counted
                    print(f"bench: request failed: {e!r}", file=sys.stderr)
                    resp = None
                done.append((req, resp, t0, time.perf_counter()))
            if until is None or done[-1][3] >= until:
                return done


def check(done: list, source: str, traffic: dict, seed: int) -> dict:
    """The worst of each compared number over a sample of the window's
    completed plans, drawn from the seed."""
    served = [d for d in done if d[1] is not None]
    rng = np.random.default_rng([seed, 1])
    k = min(traffic["check_requests"], len(served))
    picks = sorted(rng.choice(len(served), size=k, replace=False))
    graph = reference.load(source)
    worst: dict = {}
    for i in picks:
        req, resp = served[i][0], served[i][1]
        want = reference.plan(graph, req.p, req.lam)
        for name, value in reference.compare(resp.bundle, want).items():
            worst[name] = max(worst.get(name, value), value)
    return worst


def run_cell(cell: dict, config: dict, traffic: dict, per_layer: list,
             seed: int, seconds: float, trace: bool, dev,
             root: str = ROOT) -> tuple:
    """Set up, measure and check one run; returns (run record, result)."""
    import jax
    from repro import obs
    from repro.core.pallas import metrics as pallas_metrics

    cache_dir = use_compile_cache(root)
    clock = CompileClock()
    work = os.path.join(root, ".cache", "bench")
    source = write_source(config, work)
    loop = Loop(source, traffic, lam_order(traffic, seed),
                os.path.join(work, "plans"))
    warm = loop.serve(None, annotate=False)
    setup_s = time.perf_counter() - T_START
    at_setup = clock.snapshot()
    traces0 = pallas_metrics.trace_count()

    collector = None
    trace_dir = os.path.join(work, "trace", cell["name"])
    if trace:
        shutil.rmtree(trace_dir, ignore_errors=True)
        collector = obs.enable()
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        opts.host_tracer_level = 1
        jax.profiler.start_trace(trace_dir, profiler_options=opts)
    t_window = time.perf_counter()
    done = loop.serve(t_window + seconds, annotate=trace)
    window_s = done[-1][3] - t_window
    if trace:
        jax.profiler.stop_trace()
        obs.disable()
    compiles_in_window = clock.snapshot()["programs"] - at_setup["programs"]
    retraces_in_window = pallas_metrics.trace_count() - traces0
    stats = dev.memory_stats() or {}
    failed = sum(1 for d in done if d[1] is None)
    times = [d[3] - d[2] for d in done]

    record = {"workload": cell["name"], "seed": seed, "plans": len(done),
              "failed": failed, "warmup_plans": len(warm),
              "window_s": window_s, "setup_s": setup_s,
              "compiles_in_window": compiles_in_window,
              "retraces_in_window": retraces_in_window,
              "setup_programs": at_setup["programs"],
              "setup_compiles": at_setup["compiles"],
              "setup_compile_s": at_setup["compile_s"],
              "cache_hits": clock.cache_hits,
              "cache_misses": clock.cache_misses,
              "compile_cache": cache_dir,
              "peak_bytes_in_use": stats.get("peak_bytes_in_use"),
              "plan_s_min_median_max": [
                  min(times), float(np.median(times)), max(times)],
              "lams": [r.lam for r in loop.requests]}
    device = {"platform": dev.platform, "kind": dev.device_kind,
              "count": len(jax.devices()),
              "memory_peak_bytes": stats.get("peak_bytes_in_use")}
    result = {"correct": None, "attempted": len(done), "failed": failed,
              "metrics": {}, "device": device}

    if trace:
        from repro.core.mapping import Machine
        planes = trace_reduce.load(_newest_xplane(trace_dir))
        requests = [(d[2], d[3]) for d in done]
        profile = trace_reduce.reduce(planes, requests, collector.events)
        n_cores = Machine.for_clusters(traffic["p"]).n_cores
        ctx = {"plans": len(done), "spans": collector.events,
               "profile": profile,
               "sizes": [logical_sizes(d[1].bundle, n_cores)
                         for d in done if d[1] is not None],
               "device_kind": dev.device_kind}
        for m in per_layer:
            value = load_module("metrics", m["name"]).read(ctx)
            if value is not None:
                result["metrics"][m["name"]] = {"value": value,
                                                "unit": m["unit"]}
        device["busy_s"] = profile["busy_s"]
        device["window_s"] = profile["window_s"]
        result["breakdown"] = {
            "device_ops": trace_reduce.top(profile["ops"]),
            "idle_gaps": trace_reduce.top(profile["gaps"])}
        record["trace_gaps"] = profile["gap_count"]
    else:
        result["metrics"] = {
            "cold_plan_s": {"value": window_s / len(done), "unit": "s"},
            "setup_s": {"value": setup_s, "unit": "s"}}

    numbers = check(done, source, traffic, seed)
    result["correct"] = bool(failed == 0 and numbers and all(
        numbers[k] <= reference.LIMITS[k] for k in numbers))
    result["checks"] = {k: {"value": v, "limit": reference.LIMITS[k]}
                        for k, v in numbers.items()}
    return record, result


def _newest_xplane(trace_dir: str) -> str:
    found = []
    for dirpath, _, files in os.walk(trace_dir):
        found += [os.path.join(dirpath, f) for f in files
                  if f.endswith(".xplane.pb")]
    if not found:
        raise RuntimeError(f"the profiler wrote no trace under {trace_dir}")
    return max(found, key=os.path.getmtime)


def report(record: dict, result: dict) -> None:
    """The run record, then the compared numbers on standard error, then
    the result as the last line of standard output."""
    print(json.dumps({"run": record}), flush=True)
    for name, c in result["checks"].items():
        print(f"check {name} {c['value']!r} limit {c['limit']!r}",
              file=sys.stderr)
    sys.stderr.flush()
    print(json.dumps(result), flush=True)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    cell, config, traffic, per_layer = load_cell(args.workload)
    dev = require_chips(cell["chips"])
    sys.path.insert(0, os.path.join(ROOT, "src"))
    if importlib.util.find_spec("repro") is None:
        raise SystemExit("bench: the program (src/repro) is not in this "
                         "checkout; nothing was run")
    record, result = run_cell(cell, config, traffic, per_layer, args.seed,
                              args.seconds, bool(args.trace), dev)
    report(record, result)
    return 0


if __name__ == "__main__":
    sys.exit(main())
