"""Deterministic NUMA multi-core cost simulator (paper §6 evaluation rig).

The paper evaluates partitions by executing them in gem5 on an out-of-order
NUMA mesh (Table 2).  gem5 is out of scope here; instead we charge each
cluster an analytic cost on the same machine model used by the mapper:

  compute   — Σ of edge weights (weights *are* memory-op time, §3) plus a
              fixed per-instruction issue cost; clusters sharing a core
              serialize (the paper's threshold=4 colocations).
  replica sync (vertex cut) — for every cut vertex, its owner pushes the
              value to each replica: hops·hop_latency + bytes/link_bw,
              charged to the receiving core; zero if owner and replica
              share a core (factor-1 benefit).
  cut edges (edge cut) — every inter-cluster edge moves its payload
              between the producing and consuming cores.
  synchronisation — critical-section/coherence traffic grows superlinearly
              with the cluster count (the paper observes comm turning back
              up beyond 128 clusters); modelled as σ·P·log2(P) messages.

Outputs: overall execution time (max over cores + sync) and total
inter-core data communication, the two quantities in Tables 6–9.

Like the partitioner and the mapper, the simulator runs the functions of
the engine table's record (`engines`) for `backend=`: "fast" (default)
builds the vertex-cut (owner, dst, bytes) replica-sync triples straight
from the replica CSR with no Python loop (`_arrayops.star_triples`);
"pallas" runs the same accumulations on-accelerator through the
segment-sum kernel layer (`repro.core.pallas`); "reference" is the
original per-vertex loop over each replica set, kept as the oracle
(tests assert the fast and reference SimReports agree to rtol 1e-12,
and the pallas report, whose times are float32 device sums, to rtol
1e-6).  The cost model is written once over the record's sums.
"""
from __future__ import annotations

import dataclasses
import math

import numpy as np

from .. import obs
from ._arrayops import scatter_sum
from .edge_cut import EdgeCutResult
from .engines import Engines, resolve
from .graph import IRGraph
from .mapping import Machine, MappingResult
from .vertex_cut import VertexCutResult

__all__ = ["SimReport", "simulate", "vertex_bytes_model"]

# -- cost constants (machine-model scale; Table 2: 2.4 GHz OoO cores) ----
CYCLE = 1.0 / 2.4e9                   # edge weights are cycles (rdtsc units)
INSTR_COST = 0.5 * CYCLE              # avg non-memory issue cost (s/instr)
CACHE_LINE = 64.0                     # bytes moved per dependency/sync msg
SYNC_MSG_BYTES = 64.0                 # one cache line per sync message
SYNC_BASE = 100 * CYCLE               # critical-section entry cost (s)
WEIGHT_TO_SECONDS = CYCLE             # edge-weight unit -> seconds


@dataclasses.dataclass
class SimReport:
    graph_name: str
    method: str
    p: int
    exec_time: float                  # seconds (modelled)
    data_comm_bytes: float            # inter-core traffic
    core_times: np.ndarray
    sync_time: float
    sync_bytes: float

    def summary(self) -> dict:
        return {"graph": self.graph_name, "method": self.method, "p": self.p,
                "exec_time": self.exec_time,
                "data_comm_bytes": self.data_comm_bytes}


def vertex_bytes_model(g: IRGraph) -> np.ndarray:
    """Bytes synced per vertex replica: one cache line per value (§6.2.4 —
    the only vertex-cut traffic is replica synchronisation of cut vertices).
    """
    return np.full(g.n, CACHE_LINE)


# ---------------------------------------------------------------------- #
def simulate(g: IRGraph, partition, mapping: MappingResult,
             backend: "str | Engines" = "fast") -> SimReport:
    """Execute a partition (vertex- or edge-cut) on the mapped machine.

    `backend` applies to vertex cuts (the paper's subject); edge-cut
    baselines always score on the numpy path.
    """
    engines = resolve(backend)
    if isinstance(partition, VertexCutResult):
        with obs.span("sim.run", backend=engines.name, kind="vertex"):
            return _simulate_vertex_cut(g, partition, mapping, engines)
    if isinstance(partition, EdgeCutResult):
        with obs.span("sim.run", backend=engines.name, kind="edge"):
            return _simulate_edge_cut(g, partition, mapping)
    raise TypeError(f"unsupported partition type {type(partition)}")


def _sync_model(p: int, n_cores: int) -> tuple[float, float]:
    """Critical-section synchronisation cost/traffic, same for all methods."""
    if p <= 1:
        return 0.0, 0.0
    rounds = p * math.log2(p)
    sync_bytes = rounds * SYNC_MSG_BYTES * max(1.0, p / 256.0)
    sync_time = rounds * SYNC_BASE / max(1, n_cores)
    return sync_time, sync_bytes


def _report(g: IRGraph, r, core_t: np.ndarray, core_wait: np.ndarray,
            comm_bytes: float, mach: Machine) -> SimReport:
    sync_t, sync_b = _sync_model(r.p, mach.n_cores)
    exec_time = float((core_t + core_wait).max() + sync_t)
    return SimReport(g.name, r.method, r.p, exec_time,
                     comm_bytes + sync_b, core_t + core_wait, sync_t, sync_b)


def _cross_core_sync(src: np.ndarray, dst: np.ndarray, b: np.ndarray,
                     core_of: np.ndarray, mach: Machine
                     ) -> tuple[np.ndarray, float]:
    """Per-core wait and total bytes of the cluster-to-cluster messages
    (src -> dst clusters, b bytes each) that cross cores; a message
    between clusters on one core is coherence-free (factor 1)."""
    core_wait = np.zeros(mach.n_cores)
    oc = core_of[src].astype(np.int64)
    dc = core_of[dst].astype(np.int64)
    diff = oc != dc
    oc, dc, b = oc[diff], dc[diff], b[diff]
    hops = (np.abs(oc // mach.cols - dc // mach.cols)
            + np.abs(oc % mach.cols - dc % mach.cols))
    lat = hops * mach.hop_latency + mach.coherence_penalty
    np.add.at(core_wait, dc, lat / mach.mshr_overlap + b / mach.link_bw)
    return core_wait, float(b.sum())


def _triples_reference(indptr: np.ndarray, flat: np.ndarray,
                       vb: np.ndarray
                       ) -> tuple[np.ndarray, np.ndarray, np.ndarray]:
    """Oracle: per-vertex loop flattening (owner, dst, bytes) triples."""
    owners, dsts, sizes = [], [], []
    for v in range(len(indptr) - 1):
        a = set(flat[indptr[v]:indptr[v + 1]].tolist())
        if len(a) < 2:
            continue
        members = sorted(a)
        owners.extend([members[0]] * (len(members) - 1))
        dsts.extend(members[1:])
        sizes.extend([vb[v]] * (len(members) - 1))
    return (np.asarray(owners, dtype=np.int64),
            np.asarray(dsts, dtype=np.int64), np.asarray(sizes))


def _replica_sync(triples, indptr: np.ndarray, flat: np.ndarray,
                  vb: np.ndarray, core_of: np.ndarray, mach: Machine
                  ) -> tuple[np.ndarray, float]:
    """Host replica-sync wait: the owner of each cut vertex pushes its
    value to every replica, as (owner, dst, bytes) `triples`."""
    return _cross_core_sync(*triples(indptr, flat, vb), core_of, mach)


def _simulate_vertex_cut(g: IRGraph, r: VertexCutResult,
                         mapping: MappingResult,
                         engines: Engines) -> SimReport:
    mach = mapping.machine
    cluster_t = engines.keyed_sum(
        r.assignment, g.w * WEIGHT_TO_SECONDS + INSTR_COST, r.p)
    core_t = engines.keyed_sum(mapping.core_of, cluster_t, mach.n_cores)
    core_wait, comm_bytes = engines.replica_sync(
        *r.replica_csr(), vertex_bytes_model(g), mapping.core_of, mach)
    return _report(g, r, core_t, core_wait, comm_bytes, mach)


def _simulate_edge_cut(g: IRGraph, r: EdgeCutResult,
                       mapping: MappingResult) -> SimReport:
    mach = mapping.machine
    # edge executed at consumer's cluster
    cluster_t = scatter_sum(r.parts[g.dst],
                            g.w * WEIGHT_TO_SECONDS + INSTR_COST, r.p)
    core_t = scatter_sum(mapping.core_of, cluster_t, mach.n_cores)
    # every inter-cluster edge moves one cache line
    cu = r.parts[g.src]
    cv = r.parts[g.dst]
    cross = cu != cv
    core_wait, comm_bytes = _cross_core_sync(
        cu[cross], cv[cross], np.full(int(cross.sum()), CACHE_LINE),
        mapping.core_of, mach)
    return _report(g, r, core_t, core_wait, comm_bytes, mach)
