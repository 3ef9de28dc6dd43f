"""Plain reference planner: what a served plan must contain, worked out
independently of the program.

The served planner turns a graph source into a plan in four steps, and
this module does each the straightforward way, importing nothing of the
program:

  load        an `.npz` snapshot (keys n, src, dst, w), or a TRACE_SCHEMA
              v0 NDJSON trace parsed record by record with `json` under the
              `bytes` weight model;
  cut         WB-Libra (paper Algorithm 1) over the edges in trace order:
              a per-edge loop over Python sets with float64 loads, the
              lowest cluster id winning ties, and the bound
              lam * sum(w) / p; then the replica sets, loads and edge
              counts;
  map         the interaction graphs (star replica traffic, one 64-byte
              cache line per replica; shared-vertex counts, pairs skipped
              above 64 replicas) and Algorithm 2's greedy placement of
              clusters onto a near-square mesh of min(p, 64) cores;
  simulate    per-core compute time, the replica-sync wait of every
              owner -> replica pair on different cores, and the
              synchronisation model.

Every keyed sum goes through `keyed_sum`.  At `precision="float64"` that
is an exact or float64 sum; `precision="bfloat16"` is the control: each
value rounded to bfloat16 and summed in float32, as a one-hot matrix
product on the matrix unit would do it.

`compare` turns a served bundle and a reference plan into the numbers that
decide `correct`, each with its limit.
"""
from __future__ import annotations

import heapq
import json
import math
import re

import numpy as np

# cost model of the simulated machine (paper Table 2: 2.4 GHz cores)
CYCLE = 1.0 / 2.4e9
INSTR_COST = 0.5 * CYCLE
CACHE_LINE = 64.0
SYNC_MSG_BYTES = 64.0
SYNC_BASE = 100 * CYCLE
PAIRWISE_CAP = 64
MAX_CORES = 64

# the mesh of Machine.for_clusters: hop latency, link bandwidth,
# coherence penalty, outstanding misses, regions
HOP_LATENCY = 5e-9
LINK_BW = 8e9
COHERENCE_PENALTY = 60e-9
MSHR_OVERLAP = 16
N_REGIONS = 4
MIN_CLUSTER_THRESHOLD = 4
COLOCATE_MIN_OVERLAP = 0.5


# ---------------------------------------------------------------------- #
# load
# ---------------------------------------------------------------------- #
_SCALAR_BYTES = {"half": 2.0, "bfloat": 2.0, "float": 4.0, "double": 8.0,
                 "fp128": 16.0, "x86_fp80": 16.0, "ppc_fp128": 16.0,
                 "ptr": 8.0, "void": 0.0, "label": 0.0, "token": 0.0,
                 "metadata": 0.0}
_AGGREGATE = re.compile(r"^[<\[]\s*(\d+)\s+x\s+(.*?)\s*[>\]]$")


def type_bytes(ty):
    """Bytes of an LLVM type string; None and unknown types are 8."""
    if ty is None:
        return 8.0
    ty = ty.strip()
    if ty.endswith("*"):
        return 8.0
    if ty in _SCALAR_BYTES:
        return _SCALAR_BYTES[ty]
    if ty.startswith("i") and ty[1:].isdigit():
        return max(float((int(ty[1:]) + 7) // 8), 1.0)
    m = _AGGREGATE.match(ty)
    if m:
        return float(m.group(1)) * type_bytes(m.group(2))
    return 8.0


def parse_trace(path: str) -> dict:
    """A TRACE_SCHEMA v0 trace as a graph: one vertex per record in
    stream order, then one fresh vertex per `const:*` use and per use of
    an id never defined in its function; an edge from each operand's
    vertex to the record's vertex, weighted by the bytes of the value
    (`use_tys[i]`, else the producer's `def_ty`, else 8; at least 1)."""
    src, dst, w = [], [], []
    defs_by_fn: dict = {}
    n = 0
    with open(path, encoding="utf-8") as f:
        for line in f:
            if not line.strip():
                continue
            rec = json.loads(line)
            defs = defs_by_fn.setdefault(rec["fn"], {})
            me = n
            n += 1
            use_tys = rec.get("use_tys")
            for i, u in enumerate(rec.get("uses") or ()):
                if u in defs:
                    vid, producer_bytes = defs[u]
                else:
                    vid, producer_bytes = n, None
                    n += 1
                    if not u.startswith("const:"):
                        defs[u] = (vid, None)     # a live-in value
                if use_tys is not None:
                    b = max(type_bytes(use_tys[i]), 1.0)
                elif producer_bytes is not None:
                    b = max(producer_bytes, 1.0)
                else:
                    b = 8.0
                src.append(vid)
                dst.append(me)
                w.append(b)
            if rec.get("def") is not None:
                def_ty = rec.get("def_ty")
                defs[rec["def"]] = (
                    me, type_bytes(def_ty) if isinstance(def_ty, str)
                    else None)
    return {"n": n, "src": np.array(src, np.int64),
            "dst": np.array(dst, np.int64), "w": np.array(w, np.float64)}


def load(path: str) -> dict:
    """The graph behind a source path: `.npz` arrays or a parsed trace."""
    if path.endswith(".npz"):
        with np.load(path, allow_pickle=False) as z:
            return {"n": int(z["n"]), "src": z["src"].astype(np.int64),
                    "dst": z["dst"].astype(np.int64),
                    "w": z["w"].astype(np.float64)}
    return parse_trace(path)


# ---------------------------------------------------------------------- #
# keyed sums: exact/float64, or the bfloat16 control
# ---------------------------------------------------------------------- #
def keyed_sum(keys, values, n: int, precision: str) -> np.ndarray:
    """Sum `values` into `n` slots by `keys`, in stream order, as float64."""
    keys = np.asarray(keys, np.int64)
    values = np.broadcast_to(np.asarray(values, np.float64), keys.shape)
    if precision == "float64":
        out = np.zeros(n, np.float64)
        np.add.at(out, keys, values)
        return out
    if precision == "bfloat16":
        import ml_dtypes
        out = np.zeros(n, np.float32)
        np.add.at(out, keys,
                  values.astype(ml_dtypes.bfloat16).astype(np.float32))
        return out.astype(np.float64)
    raise ValueError(f"unknown precision {precision!r}")


# ---------------------------------------------------------------------- #
# cut: WB-Libra over the trace-ordered stream
# ---------------------------------------------------------------------- #
def wb_libra(n: int, src, dst, w, p: int, lam: float):
    """(assignment int64[m], replica sets list) of paper Algorithm 1."""
    m = len(src)
    deg = (np.bincount(src, minlength=n) + np.bincount(dst, minlength=n))
    deg = deg.tolist()
    bound = lam * float(np.sum(w)) / p
    src, dst, w = src.tolist(), dst.tolist(), w.tolist()
    loads = [0.0] * p
    heap = [(0.0, c) for c in range(p)]
    sets: list = [None] * n
    assignment = np.empty(m, np.int64)

    def least_global():
        while True:
            load_c, c = heap[0]
            if loads[c] == load_c:
                return c
            heapq.heappop(heap)

    def least_in(s):
        best, best_load = -1, math.inf
        for c in s:
            if loads[c] < best_load or (loads[c] == best_load and c < best):
                best, best_load = c, loads[c]
        return best

    for e in range(m):
        u, v = src[e], dst[e]
        su, sv = sets[u], sets[v]
        if not su and not sv:
            c = least_global()
        elif not su or not sv:
            c = least_in(su or sv)
            if loads[c] >= bound:
                c = least_global()
        else:
            both = su & sv
            if both:
                c = least_in(both)
                if loads[c] >= bound:
                    c = least_in(su | sv)
            else:
                # Libra: try the lower-degree endpoint's clusters first
                first, second = (su, sv) if deg[u] <= deg[v] else (sv, su)
                c = least_in(first)
                if loads[c] >= bound:
                    c = least_in(second)
            if loads[c] >= bound:
                c = least_global()
        assignment[e] = c
        loads[c] += w[e]
        heapq.heappush(heap, (loads[c], c))
        if su is None:
            sets[u] = {c}
        else:
            su.add(c)
        if sv is None:
            sets[v] = {c}
        else:
            sv.add(c)
    return assignment, sets


# ---------------------------------------------------------------------- #
# map: interaction graphs and Algorithm 2
# ---------------------------------------------------------------------- #
def mesh(p: int) -> dict:
    """The near-square mesh of min(p, 64) cores that p clusters run on."""
    cores = min(p, MAX_CORES)
    rows = int(math.ceil(math.sqrt(cores)))
    cols = int(math.ceil(cores / rows))
    small = max(d for d in range(1, int(math.sqrt(N_REGIONS)) + 1)
                if N_REGIONS % d == 0)
    big = N_REGIONS // small
    bands = (big, small) if rows >= cols else (small, big)
    region = [(c // cols) * bands[0] // rows * bands[1]
              + (c % cols) * bands[1] // cols for c in range(rows * cols)]
    threshold = max(MIN_CLUSTER_THRESHOLD,
                    int(math.ceil(p / (rows * cols))))
    return {"rows": rows, "cols": cols, "cores": rows * cols,
            "region": region, "threshold": threshold}


def hops(mach: dict, a: int, b: int) -> int:
    cols = mach["cols"]
    return abs(a // cols - b // cols) + abs(a % cols - b % cols)


def place(comm, shared, mach: dict) -> np.ndarray:
    """Algorithm 2: clusters in descending total interaction; colocate
    with a placed cluster sharing a dominant data structure, else go next
    to the strongest placed communication peer, else spread to another
    region than the strongest weak peer's."""
    p = comm.shape[0]
    off = shared.copy()
    np.fill_diagonal(off, 0.0)
    own = np.maximum(np.diagonal(shared), 1.0)
    order = np.argsort(-(comm.sum(1) + off.sum(1)), kind="stable")
    n_cores, thr, region = mach["cores"], mach["threshold"], mach["region"]
    n_regions = max(region) + 1
    core_of = np.full(p, -1, np.int64)
    count = [0] * n_cores
    cursor = 0

    def nearby(anchor):
        best, best_key = anchor, None
        for c in range(n_cores):
            if c == anchor or count[c] >= thr:
                continue
            key = (count[c], hops(mach, anchor, c))
            if best_key is None or key < best_key:
                best, best_key = c, key
        return best if best_key is not None else int(np.argmin(count))

    def other_region(avoid):
        nonlocal cursor
        for k in range(n_regions):
            reg = (cursor + k) % n_regions
            if reg == avoid:
                continue
            free = [c for c in range(n_cores)
                    if region[c] == reg and count[c] < thr]
            if free:
                cursor = (reg + 1) % n_regions
                return min(free, key=lambda c: count[c])
        return int(np.argmin(count))

    for cl in order:
        placed = core_of >= 0
        mem_peer = ipc_peer = weak_peer = -1
        if placed.any():
            s = np.where(placed, off[cl], -1.0)
            j = int(np.argmax(s))
            if s[j] > COLOCATE_MIN_OVERLAP * min(own[cl], own[j]):
                mem_peer = j
            c_row = np.where(placed, comm[cl], -1.0)
            j = int(np.argmax(c_row))
            if c_row[j] > 0:
                ipc_peer = j
            both = np.where(placed, comm[cl] + off[cl], -1.0)
            j = int(np.argmax(both))
            if both[j] > 0:
                weak_peer = j
        if mem_peer >= 0:
            target = int(core_of[mem_peer])
            core_of[cl] = target if count[target] < thr else nearby(target)
        elif ipc_peer >= 0:
            core_of[cl] = nearby(int(core_of[ipc_peer]))
        else:
            avoid = region[core_of[weak_peer]] if weak_peer >= 0 else None
            core_of[cl] = other_region(avoid)
        count[core_of[cl]] += 1
    return core_of


# ---------------------------------------------------------------------- #
# the whole plan
# ---------------------------------------------------------------------- #
def plan(graph: dict, p: int, lam: float,
         precision: str = "float64") -> dict:
    """The plan a served request for (graph, p, wb_libra, lam) must give."""
    n, src, dst, w = graph["n"], graph["src"], graph["dst"], graph["w"]
    assignment, sets = wb_libra(n, src, dst, w, p, lam)

    # replica sets A(v), sorted; the owner of a cut vertex is its lowest
    members = [sorted(s) if s else [] for s in sets]
    sizes = np.array([len(a) for a in members], np.int64)
    indptr = np.zeros(n + 1, np.int64)
    np.cumsum(sizes, out=indptr[1:])
    flat = np.array([c for a in members for c in a], np.int64)
    loads = keyed_sum(assignment, w, p, precision)
    edge_counts = keyed_sum(assignment, 1.0, p, precision)

    # star triples owner -> replica, one cache line each
    owners = np.array([a[0] for a in members for _ in a[1:]], np.int64)
    replicas = np.array([c for a in members for c in a[1:]], np.int64)
    comm = keyed_sum(owners * p + replicas, CACHE_LINE, p * p,
                     precision).reshape(p, p)
    comm = comm + comm.T
    pair_keys = [x * p + y for a in members if 2 <= len(a) <= PAIRWISE_CAP
                 for i, x in enumerate(a) for y in a[i + 1:]]
    pairs = keyed_sum(np.array(pair_keys, np.int64), 1.0, p * p,
                      precision).reshape(p, p)
    shared = pairs + pairs.T + np.diag(keyed_sum(flat, 1.0, p, precision))

    mach = mesh(p)
    core_of = place(comm, shared, mach)

    cluster_t = keyed_sum(assignment, w * CYCLE + INSTR_COST, p, precision)
    core_t = keyed_sum(core_of, cluster_t, mach["cores"], precision)
    oc, dc = core_of[owners], core_of[replicas]
    cross = oc != dc
    oc, dc = oc[cross], dc[cross]
    cols = mach["cols"]
    hop = np.abs(oc // cols - dc // cols) + np.abs(oc % cols - dc % cols)
    wait = ((hop * HOP_LATENCY + COHERENCE_PENALTY) / MSHR_OVERLAP
            + CACHE_LINE / LINK_BW)
    core_wait = keyed_sum(dc, wait, mach["cores"], precision)
    replica_bytes = keyed_sum(np.zeros(len(dc), np.int64), CACHE_LINE, 1,
                              precision)[0]
    if p > 1:
        rounds = p * math.log2(p)
        sync_bytes = rounds * SYNC_MSG_BYTES * max(1.0, p / 256.0)
        sync_time = rounds * SYNC_BASE / max(1, mach["cores"])
    else:
        sync_bytes = sync_time = 0.0
    core_times = core_t + core_wait
    return {"n_vertices": n, "total_weight": float(np.sum(w)),
            "assignment": assignment, "loads": loads,
            "edge_counts": edge_counts, "replica_indptr": indptr,
            "replica_flat": flat, "core_of": core_of,
            "core_times": core_times,
            "exec_time": float(core_times.max() + sync_time),
            "comm_bytes": float(replica_bytes + sync_bytes)}


# ---------------------------------------------------------------------- #
# comparison
# ---------------------------------------------------------------------- #
# Integer-valued outputs (assignment, CSR, counts, byte loads, placement,
# comm bytes) must be identical.  Simulated times are float32 sums on the
# device; their relative limits lie between the largest error of sound
# runs and the smallest error of the bfloat16 control (PERF.md).
LIMITS = {"graph_mismatch": 0, "assignment_mismatch": 0,
          "loads_mismatch": 0, "edge_counts_mismatch": 0,
          "replica_csr_mismatch": 0, "core_of_mismatch": 0,
          "comm_bytes_err": 0, "exec_time_rel_err": 2e-7,
          "core_times_rel_err": 1e-6}


def _mismatch(got, want) -> int:
    got, want = np.asarray(got).ravel(), np.asarray(want).ravel()
    k = min(len(got), len(want))
    return int(np.count_nonzero(got[:k] != want[:k])
               + abs(len(got) - len(want)))


def compare(got, want: dict) -> dict:
    """{number: value} of a served bundle (an object with the plan
    bundle's fields, or a dict like `plan`'s) against the reference."""
    def field(name):
        return got[name] if isinstance(got, dict) else getattr(got, name)

    m = len(want["assignment"])
    graph = (int(field("n_vertices") != want["n_vertices"])
             + int(len(field("assignment")) != m)
             + int(field("total_weight") != want["total_weight"]))
    core_t_got = np.asarray(field("core_times"), np.float64)
    core_t_want = want["core_times"]
    if core_t_got.shape == core_t_want.shape:
        core_err = float(np.max(np.abs(core_t_got - core_t_want)
                                / np.maximum(np.abs(core_t_want), 1e-300)))
    else:
        core_err = math.inf
    return {
        "graph_mismatch": graph,
        "assignment_mismatch": _mismatch(field("assignment"),
                                         want["assignment"]),
        "loads_mismatch": _mismatch(field("loads"), want["loads"]),
        "edge_counts_mismatch": _mismatch(field("edge_counts"),
                                          want["edge_counts"]),
        "replica_csr_mismatch": (
            _mismatch(field("replica_indptr"), want["replica_indptr"])
            + _mismatch(field("replica_flat"), want["replica_flat"])),
        "core_of_mismatch": _mismatch(field("core_of"), want["core_of"]),
        "comm_bytes_err": abs(float(field("comm_bytes"))
                              - want["comm_bytes"]),
        "exec_time_rel_err": abs(float(field("exec_time"))
                                 - want["exec_time"]) / want["exec_time"],
        "core_times_rel_err": core_err,
    }
