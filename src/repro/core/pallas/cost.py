"""HLO-derived FLOP / HBM-byte costs for the Pallas kernel layer.

`benchmarks/roofline.py` judges the pallas bench rows against an
analytic roofline; the numbers come from here.  Each helper lowers the
*actual* jitted computation — `keyed_sum`'s stable-sort + segment-sum,
`replica_csr`'s `_csr_core` — at the pow2-bucketed shapes the pipeline
uses, compiles it, and feeds the compiled HLO text through
`repro.analysis.hlo_cost.analyze_hlo` (loop-aware, so the interpret-mode
grid/`fori_loop` while-loops are multiplied by their trip counts).
Results are `lru_cache`d per shape bucket: a bench suite pays a few
hundred milliseconds of lowering once per distinct bucket, which the
pow2 rounding keeps to a handful.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from ...analysis.hlo_cost import analyze_hlo
from .segsum import _MIN_PAD, _next_pow2, keyed_sum

__all__ = ["keyed_sum_cost", "replica_csr_cost",
           "partitioner_finalize_cost", "interaction_cost"]


def _bucket(x: int, floor: int = _MIN_PAD) -> int:
    return max(_next_pow2(max(int(x), 1)), floor)


def _merge(*costs: dict) -> dict:
    return {"flops": sum(c["flops"] for c in costs),
            "hbm_bytes": sum(c["hbm_bytes"] for c in costs)}


@functools.lru_cache(maxsize=None)
def _keyed_sum_cost(m: int, num_keys: int) -> "tuple[float, float]":
    fn = jax.jit(lambda k, v: keyed_sum(k, v, num_keys, interpret=True))
    text = fn.lower(
        jax.ShapeDtypeStruct((m,), jnp.int32),
        jax.ShapeDtypeStruct((m,), jnp.float32),
    ).compile().as_text()
    cost = analyze_hlo(text)
    return cost.flops, cost.hbm_bytes


def keyed_sum_cost(m: int, num_keys: int) -> dict:
    """Cost of one ``keyed_sum`` over an ``m``-element stream into
    ``num_keys`` buckets, at the pow2 bucket of both (the kernel pads
    the same way, so nearby sizes share one lowering)."""
    if m <= 0 or num_keys <= 0:
        return {"flops": 0.0, "hbm_bytes": 0.0}
    flops, hbm = _keyed_sum_cost(_bucket(m), _bucket(num_keys, 1))
    return {"flops": flops, "hbm_bytes": hbm}


@functools.lru_cache(maxsize=None)
def _csr_cost(klen: int, pn: int) -> "tuple[float, float]":
    from .metrics import _csr_core
    key = jax.ShapeDtypeStruct((klen,), jnp.int32)
    text = _csr_core.lower(key, key, pn=pn).compile().as_text()
    cost = analyze_hlo(text)
    return cost.flops, cost.hbm_bytes


def replica_csr_cost(n: int, n_edges: int) -> dict:
    """Cost of `replica_csr`'s device core for an ``n``-vertex graph
    with ``n_edges`` edges (a two-key sort of 2 (vertex, cluster) pairs
    per edge, padded like the real call; independent of ``p``)."""
    if n_edges <= 0:
        return {"flops": 0.0, "hbm_bytes": 0.0}
    flops, hbm = _csr_cost(_bucket(2 * n_edges), _bucket(n))
    return {"flops": flops, "hbm_bytes": hbm}


def partitioner_finalize_cost(n: int, m: int, p: int) -> dict:
    """Device work in `vertex_cut`'s pallas finalize: the replica CSR
    plus the two per-part reductions (loads, edge counts) over the
    ``m``-edge assignment stream."""
    return _merge(replica_csr_cost(n, m),
                  keyed_sum_cost(m, p), keyed_sum_cost(m, p))


def interaction_cost(n_members: int, p: int) -> dict:
    """Device work in `interaction_from_csr` for a replica set of
    ``n_members`` entries: the diagonal reference counts (p+1 keys) and
    the symmetrised star-comm reduction (p^2+1 keys), both streaming the
    padded member list.  The capped pairwise pass is size-class dependent
    and small next to these two; it is deliberately not modelled."""
    return _merge(keyed_sum_cost(n_members, p + 1),
                  keyed_sum_cost(n_members, p * p + 1))
