"""reductions_roofline: the least time the chip needs for a plan's device
work, over the device's busy time per plan, in %.

The device work of a cold plan is a fixed set of keyed reductions.  Each
reads every input key and value once and writes every output slot once,
in 4-byte words, whatever implements it (a scalar Pallas loop, a one-hot
matrix product or an XLA scatter).  The sizes are the plan's logical
ones, never padded shapes:

  m      edges                    n      vertices
  r      replica entries          k      owner -> replica pairs (r less
                                         one owner per vertex with replicas)
  pairs  shared-vertex pairs of replica sets of 2..64 clusters
  p      clusters                 cores  cores of the simulated mesh

A reduction does about one addition per value read, far below the
chip's operation peak, so the HBM bandwidth bounds it and the least time
is bytes / `hbm_bytes_per_s` of `peaks.json`.
"""
from __future__ import annotations

import json
import os

WORD = 4
PEAKS = os.path.join(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))), "peaks.json")


def plan_bytes(s: dict) -> int:
    """HBM bytes the keyed reductions of one plan must move at least."""
    m, n, r, k, pairs = s["m"], s["n"], s["r"], s["k"], s["pairs"]
    p, cores = s["p"], s["cores"]
    words = (
        (2 * m + 2 * m) + (n + 1 + r)   # replica CSR: (vertex, cluster)
                                        # of both endpoints -> indptr, flat
        + (2 * m + p)                   # loads: cluster, weight -> p
        + (m + p)                       # edge counts: cluster -> p
        + (r + p)                       # referenced vertices per cluster
        + (3 * k + p * p)               # star traffic: owner, replica,
                                        # bytes -> p^2
        + (2 * pairs + p * p)           # shared pairs: two members -> p^2
        + (2 * m + p)                   # cluster compute time -> p
        + (2 * p + cores)               # core compute time -> cores
        + (3 * k + cores + 1)           # replica-sync wait and bytes
    )
    return WORD * words


def read(ctx: dict):
    busy = ctx["profile"]["busy_s"]
    if busy <= 0 or not ctx["sizes"]:
        return None
    with open(PEAKS) as f:
        peaks = json.load(f)
    kind = ctx["device_kind"]
    if kind not in peaks:
        raise KeyError(f"no peaks for device kind {kind!r} in {PEAKS}")
    least_s = sum(plan_bytes(s) for s in ctx["sizes"]) \
        / peaks[kind]["hbm_bytes_per_s"]
    # the sizes cover the plans served in the window; busy covers them all
    return 100.0 * least_s / busy * ctx["plans"] / len(ctx["sizes"])
