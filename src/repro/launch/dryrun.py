import os
os.environ["XLA_FLAGS"] = (os.environ.get("XLA_FLAGS", "")
                           + " --xla_force_host_platform_device_count=512")
"""Multi-pod dry-run: lower + compile every (architecture × input-shape)
cell on the production meshes and record memory/cost/collective analysis.

Usage:
    PYTHONPATH=src python -m repro.launch.dryrun                # all cells
    PYTHONPATH=src python -m repro.launch.dryrun --arch smollm-360m
    PYTHONPATH=src python -m repro.launch.dryrun --multi-pod --shape train_4k
    PYTHONPATH=src python -m repro.launch.dryrun --out results.json

The XLA_FLAGS line above MUST run before any other jax-touching import —
512 placeholder host devices stand in for the 2×16×16 production pod
slice.  Results (per cell: bytes/device, HLO FLOPs, collective bytes by
op) are appended to a JSON file consumed by benchmarks/roofline.py and
EXPERIMENTS.md.
"""
import argparse  # noqa: E402  (XLA_FLAGS must be set before anything else)
import gzip      # noqa: E402
import json      # noqa: E402
import re        # noqa: E402
import sys       # noqa: E402
import time      # noqa: E402
import traceback  # noqa: E402


def collective_bytes(hlo_text: str) -> dict:
    """Sum operand bytes of every collective op in the (SPMD-partitioned)
    HLO.  Parses shapes like `bf16[2048,7168]{1,0}` from lines whose op is
    all-gather / all-reduce / reduce-scatter / all-to-all /
    collective-permute."""
    dtype_bytes = {"f32": 4, "bf16": 2, "f16": 2, "s32": 4, "u32": 4,
                   "f64": 8, "s8": 1, "u8": 1, "pred": 1, "s64": 8,
                   "f8e4m3fn": 1, "f8e5m2": 1, "s16": 2, "u16": 2}
    ops = ("all-gather", "all-reduce", "reduce-scatter", "all-to-all",
           "collective-permute")
    totals = {op: 0.0 for op in ops}
    counts = {op: 0 for op in ops}
    shape_re = re.compile(r"(\w+)\[([\d,]*)\]")
    for line in hlo_text.splitlines():
        stripped = line.strip()
        m = re.match(r"(?:ROOT )?%?[\w.\-]+ = .*? (all-gather|all-reduce|"
                     r"reduce-scatter|all-to-all|collective-permute)"
                     r"(?:-start|-done)?\(", stripped)
        if not m:
            continue
        op = m.group(1)
        if "-done(" in stripped:
            continue  # avoid double counting async pairs
        lhs = stripped.split(" = ", 1)[1]
        out_part = lhs.split("(", 1)[0]
        b = 0.0
        for dt, dims in shape_re.findall(out_part):
            if dt not in dtype_bytes:
                continue
            n = 1
            for d in dims.split(","):
                if d:
                    n *= int(d)
            b += n * dtype_bytes[dt]
        totals[op] += b
        counts[op] += 1
    return {"bytes": totals, "counts": counts,
            "total_bytes": sum(totals.values())}


def run_cell(cell, mesh, multi_pod: bool, impl: str = "auto",
             par_override: dict | None = None,
             hlo_dir: str | None = "dryrun_hlo") -> dict:
    import jax
    from repro.launch.cells import lower_cell
    t0 = time.time()
    with jax.set_mesh(mesh):
        lowered, meta = lower_cell(cell, mesh, impl=impl,
                                   par_override=par_override)
        t_lower = time.time() - t0
        t0 = time.time()
        compiled = lowered.compile()
        t_compile = time.time() - t0
    hlo = compiled.as_text()   # post-SPMD: collectives are visible here
    if hlo_dir:
        os.makedirs(hlo_dir, exist_ok=True)
        tag = cell.name.replace("/", "_") + (
            "_2x16x16" if multi_pod else "_16x16")
        with gzip.open(os.path.join(hlo_dir, tag + ".hlo.gz"), "wt") as f:
            f.write(hlo)
    coll = collective_bytes(hlo)
    from repro.analysis import analyze_hlo
    la = analyze_hlo(hlo)      # loop-aware totals (per device, per step)
    mem = compiled.memory_analysis()
    cost = compiled.cost_analysis()
    rec = {
        **meta,
        "mesh": "2x16x16" if multi_pod else "16x16",
        "ok": True,
        "lower_s": round(t_lower, 1),
        "compile_s": round(t_compile, 1),
        "flops": cost.get("flops", -1.0) if cost else -1.0,
        "bytes_accessed": cost.get("bytes accessed", -1.0) if cost else -1.0,
        "hlo_flops": la.flops,
        "hlo_hbm_bytes": la.hbm_bytes,
        "hlo_collective_bytes": la.collective_bytes,
        "hlo_collective_bytes_bf16eq": la.collective_bytes_bf16eq,
        "hlo_collective_counts": la.collective_counts,
        "collectives": coll,
        "memory": {
            "argument_bytes": getattr(mem, "argument_size_in_bytes", -1),
            "output_bytes": getattr(mem, "output_size_in_bytes", -1),
            "temp_bytes": getattr(mem, "temp_size_in_bytes", -1),
            "generated_code_bytes":
                getattr(mem, "generated_code_size_in_bytes", -1),
        },
    }
    print(f"  memory_analysis: args={rec['memory']['argument_bytes']/1e9:.2f}GB "
          f"temps={rec['memory']['temp_bytes']/1e9:.2f}GB "
          f"(global, /{mesh.devices.size} devices)")
    print(f"  cost_analysis: flops={rec['flops']:.3e} "
          f"bytes={rec['bytes_accessed']:.3e}")
    print(f"  loop-aware: flops={la.flops:.3e} hbm={la.hbm_bytes:.3e} "
          f"coll={sum(la.collective_bytes.values()):.3e} "
          f"{la.collective_counts}")
    return rec


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--arch", default=None, help="only this architecture")
    ap.add_argument("--shape", default=None, help="only this shape")
    ap.add_argument("--multi-pod", action="store_true",
                    help="2x16x16 (512-chip) mesh instead of 16x16")
    ap.add_argument("--both-meshes", action="store_true")
    ap.add_argument("--out", default="dryrun_results.json")
    ap.add_argument("--impl", default="auto")
    args = ap.parse_args()

    from repro.launch.cells import cell_skip_reason, enumerate_cells
    from repro.launch.mesh import make_production_mesh

    cells = enumerate_cells(include_skipped=True)
    if args.arch:
        cells = [c for c in cells if c.arch == args.arch]
    if args.shape:
        cells = [c for c in cells if c.shape == args.shape]

    mesh_flags = [args.multi_pod] if not args.both_meshes else [False, True]
    results = []
    if os.path.exists(args.out):
        with open(args.out) as f:
            results = json.load(f)
    done = {(r["cell"], r["mesh"]) for r in results if r.get("ok")}

    failures = 0
    for multi_pod in mesh_flags:
        mesh = make_production_mesh(multi_pod=multi_pod)
        mname = "2x16x16" if multi_pod else "16x16"
        for cell in cells:
            if (cell.name, mname) in done:
                print(f"[skip-done] {cell.name} on {mname}")
                continue
            reason = cell_skip_reason(cell)
            if reason:
                print(f"[skip] {cell.name}: {reason}")
                results.append({"cell": cell.name, "mesh": mname,
                                "ok": None, "skip_reason": reason})
                continue
            print(f"[run ] {cell.name} on {mname} ...", flush=True)
            try:
                rec = run_cell(cell, mesh, multi_pod, impl=args.impl)
                results.append(rec)
            except Exception as e:  # noqa: BLE001 — record and continue
                failures += 1
                print(f"  FAILED: {type(e).__name__}: {e}")
                traceback.print_exc(limit=3)
                results.append({"cell": cell.name, "mesh": mname,
                                "ok": False, "error": f"{type(e).__name__}: {e}"})
            with open(args.out, "w") as f:
                json.dump(results, f, indent=1)
    print(f"\n{sum(1 for r in results if r.get('ok'))} ok, "
          f"{failures} failed -> {args.out}")
    return 1 if failures else 0


if __name__ == "__main__":
    sys.exit(main())
