"""Device-side ports of the replica-CSR / interaction / simulator metrics.

These mirror the numpy implementations in `.._arrayops` and the
simulator but keep every intermediate a jax array, with the reductions
routed through the Pallas `segment_sum` kernel — partition → metrics →
mapping runs end-to-end on the accelerator.  They are the reductions of
the engine table's `pallas` record (`..engines`).  Everything on the
device is 32-bit (see the precision
contract in `.segsum`); results are widened to the numpy oracles'
dtypes on the host:

  * integer outputs (replica CSR, shared counts, edge counts) and the
    byte-valued comm matrix are exact — integer sums are order-free and
    their 2^31 bounds are checked on the host before tracing; byte
    weights past 2^31 ride the triple stream as (2, m) int32 limbs and
    are summed by the wide kernel — so they are bit-identical to the
    fast backend, and so is the `core_of` the mapping derives from them;
  * the simulator's replica-sync wait is a float32 sum, within the
    contract's 3u relative bound per term stream.

Compilation discipline
----------------------
The glue is **jitted end-to-end**, not dispatched op by op: each public
function runs one or two `jax.jit` cores whose shapes are padded to
powers of two (stream length, vertex count, pairwise base count), so
novel graph shapes collapse onto a handful of cache entries.
Data-dependent output sizes (the deduped CSR length, the non-owner
triple count) are computed host-side from cheap numpy bookkeeping and
applied as static slices *outside* the traced cores, with in-core
sentinels keeping padded elements out of every reduction (sentinel
keys land in a slack bucket that is sliced off).

Every traced core bumps a counter in `_TRACE_COUNTS` as a tracing side
effect (Python runs only while jax traces, i.e. on a cache miss), and
records an obs instant event `jit.trace` naming the core;
`trace_count()` exposes the counter so tests can assert cache hits
across same-bucket graphs — the probe that keeps this module honestly
jitted.  Host data crosses to the device and back only through
`.boundary` (obs spans `device.put` / `device.get`).
"""
from __future__ import annotations

import collections
import functools

import jax
import jax.numpy as jnp
import numpy as np

from ... import obs
from .boundary import to_device, to_host
from .segsum import (INT32_SUM_BOUND, LIMB_BITS, _MIN_PAD, _next_pow2,
                     join_limbs, keyed_sum, narrow, segment_sum,
                     split_limbs)

__all__ = ["replica_csr", "cut_sums", "host_keyed_sum", "star_triples",
           "interaction_from_csr", "replica_sync", "trace_count"]

_TRACE_COUNTS: "collections.Counter[str]" = collections.Counter()


def trace_count(name: "str | None" = None) -> int:
    """Times the jitted cores have been *traced* (compiled), total or by
    core name — the cache-hit probe used by the compile-count tests."""
    if name is not None:
        return _TRACE_COUNTS[name]
    return sum(_TRACE_COUNTS.values())


def _mark(name: str) -> None:
    # executes only while jax traces the enclosing function: a cache
    # hit never reaches this line
    _TRACE_COUNTS[name] += 1
    obs.event("jit.trace", core=name)


def _pad_pow2(a: np.ndarray, fill, min_len: int = _MIN_PAD) -> np.ndarray:
    """`a` padded along its last axis to a power of two."""
    n = max(_next_pow2(a.shape[-1]), min_len)
    out = np.full(a.shape[:-1] + (n,), fill, dtype=a.dtype)
    out[..., :a.shape[-1]] = a
    return out


# ---------------------------------------------------------------------- #
# replica CSR
# ---------------------------------------------------------------------- #
@functools.partial(jax.jit, static_argnames=("pn",))
def _csr_core(v, c, pn: int):
    """Sorted-unique (vertex, cluster) pairs with duplicates moved past
    the sentinel vertex `pn`, plus searchsorted indptr over pn+1
    boundaries.  Two int32 sort keys stand in for one v*p+c key, which
    would overflow int32 at n*p >= 2^31."""
    _mark("replica_csr")
    v, c = jax.lax.sort((v, c), num_keys=2)
    dup = jnp.concatenate(
        [jnp.zeros((1,), bool), (v[1:] == v[:-1]) & (c[1:] == c[:-1])])
    v, c = jax.lax.sort((jnp.where(dup, pn, v), c), num_keys=2)
    count = jnp.searchsorted(v, pn)
    indptr = jnp.searchsorted(v, jnp.arange(pn + 1, dtype=jnp.int32))
    return c, indptr, count


def replica_csr(n: int, p: int, src, dst, assignment):
    """Device port of `_arrayops.replica_csr` (sorted unique-key CSR).

    Returns numpy (indptr int64[n+1], flat int32[sum |A(v)|]),
    bit-identical to the numpy path (both reduce to the sorted unique
    (vertex, cluster) pair set).
    """
    pn = max(_next_pow2(n), _MIN_PAD)
    if 2 * len(src) >= INT32_SUM_BOUND or pn >= INT32_SUM_BOUND:
        raise OverflowError("replica CSR offsets must fit int32")
    a = np.asarray(assignment, dtype=np.int32)
    v = _pad_pow2(np.concatenate([np.asarray(src, np.int32),
                                  np.asarray(dst, np.int32)]), pn)
    c = _pad_pow2(np.concatenate([a, a]), 0)
    flat, indptr, count = _csr_core(*to_device((v, c)), pn)
    k = int(to_host(count))
    indptr, flat = to_host((indptr[:n + 1], flat[:k]))
    return np.asarray(indptr, np.int64), np.asarray(flat, np.int32)


# ---------------------------------------------------------------------- #
# keyed sums fetched to the host
# ---------------------------------------------------------------------- #
def cut_sums(assignment, w, p: int):
    """Per-cluster (float64 loads, int64 edge counts) of a cut, fetched
    in one copy: exact for counts and integer-valued weights, float32
    sums otherwise."""
    loads, counts = to_host((
        keyed_sum(assignment, w, p),
        keyed_sum(assignment, np.ones(len(assignment), np.int32), p)))
    return np.asarray(loads, np.float64), np.asarray(counts, np.int64)


def host_keyed_sum(keys, values, n: int) -> np.ndarray:
    """`keyed_sum` of host arrays, fetched as float64[n]."""
    return np.asarray(to_host(keyed_sum(keys, values, n)), np.float64)


# ---------------------------------------------------------------------- #
# star triples
# ---------------------------------------------------------------------- #
@functools.partial(jax.jit, static_argnames=("has_bytes",))
def _star_core(indptr, sizes, members, vb, m, has_bytes: bool):
    """Compact (owner, replica, bytes) triples to the front.

    Valid non-owner entries keep their stream order (stable argsort on
    a 0/1 key), which is exactly the order the numpy boolean mask
    emits — float comm accumulation order is preserved.
    """
    _mark("star_triples")
    mp = members.shape[0]
    seg_id = jnp.repeat(jnp.arange(sizes.shape[0], dtype=jnp.int32),
                        sizes, total_repeat_length=mp)
    first_pos = indptr[seg_id]
    pos = jnp.arange(mp, dtype=jnp.int32)
    non_owner = (pos != first_pos) & (pos < m)
    order = jnp.argsort(jnp.where(non_owner, 0, 1), stable=True)
    owners = members[first_pos][order]
    replicas = members[order]
    if has_bytes:                       # (mp,) values or (2, mp) limbs
        b = vb[..., seg_id][..., order]
    else:
        b = jnp.ones((mp,), jnp.int32)
    return owners, replicas, b


def _star_padded(indptr, members, vertex_bytes):
    """(owners, replicas, b) padded device arrays + valid count K.

    Byte weights are narrowed against the magnitude of the whole triple
    stream — a vertex contributes |A(v)| - 1 triples — so the comm sums
    of byte-valued weights are exact: in int32 whenever they fit, else
    in int32 limbs (`b` of shape (2, mp)).
    """
    ip = np.asarray(indptr, dtype=np.int64)
    mem = np.asarray(members)
    sizes = np.diff(ip)
    k = len(mem) - int(np.count_nonzero(sizes))
    pn = max(_next_pow2(len(sizes)), _MIN_PAD)
    ip_pad = np.full(pn + 1, ip[-1] if len(ip) else 0, dtype=np.int32)
    ip_pad[:len(ip)] = ip
    sizes_pad = _pad_pow2(sizes.astype(np.int32), 0, pn)
    mem_pad = _pad_pow2(mem.astype(np.int32), 0)
    has_bytes = vertex_bytes is not None
    if has_bytes:
        vbh = np.asarray(vertex_bytes)
        magnitude = float(np.dot(np.maximum(sizes - 1, 0),
                                 np.abs(vbh, dtype=np.float64)))
        vb = narrow(vbh, magnitude)
        if vb.dtype == np.int64:        # the wide path's limbs
            vb = split_limbs(vb)
        vb = _pad_pow2(vb, 0, pn)
    else:
        vb = np.zeros(1, np.int32)      # placeholder, untraced branch
    owners, replicas, b = _star_core(
        *to_device((ip_pad, sizes_pad, mem_pad, vb)), len(mem), has_bytes)
    return owners, replicas, b, k


def star_triples(indptr, members, vertex_bytes=None):
    """Device port of `_arrayops.star_triples` (owner, replica, bytes)."""
    owners, replicas, b, k = _star_padded(indptr, members, vertex_bytes)
    return owners[:k], replicas[:k], b[:k]


# ---------------------------------------------------------------------- #
# interaction graphs
# ---------------------------------------------------------------------- #
@functools.partial(jax.jit, static_argnames=("p",))
def _diag_core(members, m, p: int):
    """Per-cluster reference counts (integer, order-free)."""
    _mark("interaction_diag")
    pos = jnp.arange(members.shape[0], dtype=jnp.int32)
    key = jnp.where(pos < m, members, p)
    return keyed_sum(key, jnp.ones(key.shape, jnp.int32), p + 1)[:p]


@functools.partial(jax.jit, static_argnames=("p",))
def _star_comm_core(owners, replicas, b, k, p: int):
    """Symmetrised owner->replica comm matrix over p^2 keys.

    Sentinel keys (p^2) absorb the padded tail; real entries keep their
    order through `keyed_sum`'s stable sort.  Wide limbs `b` (2, mp)
    give (2, p, p) limbs: lo < 2^30 in each, so lo + lo.T fits int32.
    """
    _mark("interaction_star")
    pos = jnp.arange(owners.shape[0], dtype=jnp.int32)
    valid = pos < k
    key = jnp.where(valid, owners * p + replicas, p * p)
    bb = jnp.where(valid, b, jnp.zeros((), b.dtype))
    sums = keyed_sum(key, bb, p * p + 1)[..., :p * p]
    sums = sums.reshape(b.shape[:-1] + (p, p))
    return sums + jnp.swapaxes(sums, -1, -2)


@functools.partial(jax.jit, static_argnames=("s", "p"))
def _pair_keys_core(base, nb, members, s: int, p: int):
    """x*p+y keys for all member pairs of the size-`s` segments."""
    _mark("interaction_pairs")
    iu, ju = np.triu_indices(s, k=1)
    x = members[base[:, None] + jnp.asarray(iu, jnp.int32)[None, :]]
    y = members[base[:, None] + jnp.asarray(ju, jnp.int32)[None, :]]
    valid = (jnp.arange(base.shape[0]) < nb)[:, None]
    return jnp.where(valid, x * p + y, p * p).ravel()


@functools.partial(jax.jit, static_argnames=("p",))
def _pair_count_core(keys, p: int):
    """Pair-count matrix from sentinel-padded keys (integer sums)."""
    _mark("interaction_pair_count")
    cnt = segment_sum(jnp.ones(keys.shape, jnp.int32), jnp.sort(keys),
                      p * p + 1)[:p * p]
    return cnt.reshape(p, p)


def interaction_from_csr(indptr, members, p: int, vertex_bytes=None,
                         pairwise_cap: int = 64):
    """Device port of `_arrayops.interaction_from_csr`.

    (comm[P,P], shared[P,P]) as numpy float64, built with p^2-keyed
    segment sums instead of flat scatters; the star/pairwise key sets
    are identical to the numpy path.  `shared` is an integer count and
    `comm` is exact whenever the byte weights are integer-valued within
    the int32 bound (the cache-line byte model always is), so both are
    bit-identical to the fast backend there.
    """
    if p * p + 1 >= INT32_SUM_BOUND:
        raise OverflowError("p^2 interaction keys must fit int32")
    ip = np.asarray(indptr, dtype=np.int64)
    mem = np.asarray(members)
    if len(mem) == 0:
        return np.zeros((p, p)), np.zeros((p, p))

    # diagonal: vertices referencing each cluster (members unique per seg)
    mem_pad = to_device(_pad_pow2(mem.astype(np.int32), 0))
    shared = np.diag(np.asarray(to_host(_diag_core(mem_pad, len(mem), p)),
                                np.float64))

    # star comm: owner->replica sums over p^2 keys; owner != replica
    # always (the owner is the first sorted member), so M has an empty
    # diagonal and symmetrisation is exactly M + M.T
    owners, replicas, b, k = _star_padded(ip, mem, vertex_bytes)
    comm = np.zeros((p, p))
    if k:
        comm = to_host(_star_comm_core(owners, replicas, b, k, p))
        comm = join_limbs(comm) if b.ndim == 2 else np.asarray(comm,
                                                               np.float64)

    # capped pairwise shared counts, one size class at a time (same
    # enumeration as the numpy path; x < y strictly, so S + S.T again);
    # each (size, padded-base-count) pair compiles once and is reused
    sizes = np.diff(ip)
    classes = [int(s) for s in np.unique(sizes) if 2 <= s <= pairwise_cap]
    bases = [ip[:-1][sizes == s].astype(np.int32) for s in classes]
    mem_dev, padded = to_device((mem.astype(np.int32),
                                 [_pad_pow2(base, 0) for base in bases]))
    keys = [_pair_keys_core(pb, len(base), mem_dev, s, p)
            for s, base, pb in zip(classes, bases, padded)]
    if keys:
        total = sum(kk.shape[0] for kk in keys)
        cap = max(_next_pow2(total), _MIN_PAD)
        pad = jnp.full((cap - total,), p * p, jnp.int32)
        pairs = np.asarray(to_host(_pair_count_core(
            jnp.concatenate(keys + [pad]), p)), np.float64)
        shared = shared + pairs + pairs.T
    return comm, shared


# ---------------------------------------------------------------------- #
# simulator: replica-sync wait per core
# ---------------------------------------------------------------------- #
@functools.partial(jax.jit, static_argnames=(
    "cols", "n_cores", "hop_latency", "coherence_penalty", "mshr_overlap",
    "link_bw"))
def _sync_core(owners, replicas, b, k, core_of, cols: int, n_cores: int,
               hop_latency: float, coherence_penalty: float,
               mshr_overlap: float, link_bw: float):
    """Per-core wait and total bytes of owner->replica syncs that cross
    cores (colocated replicas are coherence-free, factor 1).  The bytes
    of wide limbs `b` (2, mp) are summed by the kernel into one slot,
    as (2, 1) limbs."""
    _mark("replica_sync")
    oc = core_of[owners]
    dc = core_of[replicas]
    pos = jnp.arange(owners.shape[0], dtype=jnp.int32)
    cross = (pos < k) & (oc != dc)
    hops = jnp.abs(oc // cols - dc // cols) + jnp.abs(oc % cols - dc % cols)
    lat = hops.astype(jnp.float32) * hop_latency + coherence_penalty
    bf = b.astype(jnp.float32)
    if b.ndim == 2:
        bf = bf[0] * float(1 << LIMB_BITS) + bf[1]
    wait = lat / mshr_overlap + bf / link_bw
    key = jnp.where(cross, dc, n_cores)
    core_wait = keyed_sum(key, wait, n_cores + 1)[:n_cores]
    crossing = jnp.where(cross, b, jnp.zeros((), b.dtype))
    if b.ndim == 2:
        comm_bytes = keyed_sum(jnp.zeros(pos.shape, jnp.int32), crossing, 1)
    else:
        comm_bytes = jnp.sum(crossing)
    return core_wait, comm_bytes


def replica_sync(indptr, members, vertex_bytes, core_of, machine):
    """Device port of the simulator's replica-sync accounting.

    Returns (core_wait float64[n_cores], comm_bytes float): the wait is
    a float32 sum under the segment-sum contract; the bytes are an
    exact sum for integer-valued byte weights.
    """
    owners, replicas, b, k = _star_padded(indptr, members, vertex_bytes)
    if not k:
        return np.zeros(machine.n_cores), 0.0
    core_wait, comm_bytes = _sync_core(
        owners, replicas, b, k, to_device(np.asarray(core_of, np.int32)),
        machine.cols, machine.n_cores, float(machine.hop_latency),
        float(machine.coherence_penalty), float(machine.mshr_overlap),
        float(machine.link_bw))
    core_wait, comm_bytes = to_host((core_wait, comm_bytes))
    if b.ndim == 2:
        comm_bytes = join_limbs(comm_bytes)[0]
    return np.asarray(core_wait, np.float64), float(comm_bytes)
