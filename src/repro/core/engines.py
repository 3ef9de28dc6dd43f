"""The engine table: the one place a plan's `backend` name is read.

Every backend runs the same layers and differs only in the function it
runs at each.  `resolve` turns a name into that record once per public
call; the layers take a name or a record, so a pipeline resolves once
and hands the record down.

  reference — the original per-edge, per-vertex and per-core Python
              loops, kept as the oracle.
  fast      — flat-array numpy (the default).  The stream runs on the C
              kernel (`_fastcut.c`) when this process has one, else on
              the pure-Python bitmask loop: `stream_kernel` observes
              which.  REPRO_NO_NATIVE=1 forces the loop, as does, in
              tests, a monkeypatch of this module's `native_engine`.
  pallas    — the fast stream and placement, every reduction on the
              device through the Pallas segment-sum layer
              (`repro.core.pallas`; interpreted off a TPU).

The pipeline (`core.pipeline`) also takes "dist", the sharded stream of
`repro.dist`, which maps and simulates on `fast`.
"""
from __future__ import annotations

import dataclasses
import functools
from typing import Callable

from ._native import native_engine

__all__ = ["BACKENDS", "Engines", "resolve", "stream_kernel",
           "shard_kernel"]

BACKENDS = ("fast", "pallas", "reference")


@dataclasses.dataclass(frozen=True)
class Engines:
    """The function a backend runs at each layer of a plan."""

    name: str
    # vertex cut: (n, p, src, dst, w, deg, bound, libra_rule, perm)
    #   -> int32[m] assignment
    stream: Callable
    # finalize: (n, p, src, dst, assignment) -> (indptr, members)
    replica_csr: Callable
    # finalize: (assignment, w, p) -> (float64 loads, int64 counts),
    #   fetched to the host together
    cut_sums: Callable
    # mapping: (indptr, members, p, vertex_bytes, pairwise_cap)
    #   -> (comm[P,P], shared[P,P])
    interaction: Callable
    # mapping: (comm, off_diag, own, machine, cluster_order,
    #   colocate_min_overlap) -> int32[P] core_of
    place: Callable
    # simulator: (keys, values, n) -> float64[n] on the host
    keyed_sum: Callable
    # simulator: (indptr, members, vertex_bytes, core_of, machine)
    #   -> (float64[n_cores] wait, comm bytes)
    replica_sync: Callable


def resolve(backend: "str | Engines") -> Engines:
    """The record of `backend` (a record passes through unchanged)."""
    if isinstance(backend, Engines):
        return backend
    if backend not in BACKENDS:
        raise ValueError(f"unknown backend {backend!r}; choose from "
                         f"{BACKENDS}")
    return _record(backend)


def stream_kernel():
    """The C stream kernel when this process has one, else None (the
    fast engines' pure-Python bitmask loop streams instead)."""
    return native_engine()


def shard_kernel(backend: str = "fast"):
    """`stream_kernel()` for a resumable shard stream
    (`vertex_cut.ShardCutState`), which only the fast engines run."""
    if backend != "fast":
        raise ValueError(
            f"shard streaming runs on the fast engines only, not "
            f"{backend!r} (the greedy stream is inherently sequential)")
    return stream_kernel()


@functools.cache
def _record(name: str) -> Engines:
    # the layers import this module, so their functions are looked up
    # at the first resolve of each name; pallas (and JAX) only then
    from . import _arrayops as host
    from .mapping import (_interaction_reference, _place_fast,
                          _place_reference)
    from .simulator import _replica_sync, _triples_reference
    from .vertex_cut import _stream_fast, _stream_reference
    host_sums = dict(replica_csr=host.replica_csr, cut_sums=host.cut_sums,
                     keyed_sum=host.scatter_sum)
    if name == "reference":
        return Engines(
            name, stream=_stream_reference,
            interaction=_interaction_reference, place=_place_reference,
            replica_sync=functools.partial(_replica_sync,
                                           _triples_reference),
            **host_sums)
    if name == "fast":
        return Engines(
            name, stream=_stream_fast, interaction=host.interaction_from_csr,
            place=_place_fast,
            replica_sync=functools.partial(_replica_sync, host.star_triples),
            **host_sums)
    from .pallas import metrics as device
    return Engines(
        name, stream=_stream_fast, replica_csr=device.replica_csr,
        cut_sums=device.cut_sums, interaction=device.interaction_from_csr,
        place=_place_fast, keyed_sum=device.host_keyed_sum,
        replica_sync=device.replica_sync)
