"""Tiled Pallas segment-sum kernel (sorted-segment-ids contract).

The metrics side of the pipeline — `_finalize`'s replica-set reduction,
`replica_csr`, `cluster_interaction_graphs`, and the simulator's
per-cluster/per-core accumulations — is one primitive applied over and
over: reduce a value stream by a *sorted* key stream.  This module
implements that primitive as a Pallas TPU kernel (interpret mode keeps
it runnable on CPU CI).

Kernel shape
------------
One `pallas_call` with a 1-D "arbitrary" (sequential) grid over
fixed-size blocks of the flat (segment id, value) stream.  The stream
blocks are copied into SMEM, so the scalar core reads one (id, value)
pair per `fori_loop` step; the running (segment id, partial sum) of
the stream's current segment rides in the loop carry inside a block
and in SMEM scratch across blocks.  When the id changes, the carry is
flushed into the output, which stays resident in VMEM as lane-aligned
(tiles, 8, 128) tiles: the flush rewrites the one (8, 128) tile that
holds the slot with a masked select, because Mosaic stores vectors,
never scalars, to VMEM.  Every segment is flushed exactly once — when
the next distinct id first appears, or by the last block's epilogue —
so the kernel *assigns* rather than scatter-adds, in stream order.

The wide path (below) runs the same kernel over two value streams,
the int32 limbs of each value, into two outputs, under its own name
`wide_segsum`, so a profile tells it apart from `segment_sum`.

The output holds `num_segments + 1` slots rounded up to whole tiles;
slot `num_segments` absorbs the padded tail.  At p = 1024 the p^2+1
keyed reductions take 1025 tiles (4.2 MB of VMEM), which is the
largest output the pipeline asks for; vertex-keyed reductions at
millions of segments would need an output-tiled variant (ROADMAP).

Precision contract
------------------
Mosaic has no 64-bit types, so the kernel sums int32 or float32.
Host (numpy) values are narrowed by `narrow`, per output kind, from the
stream magnitude (the sum of |x|):

  * integer-valued data — counts, and byte weights such as the
    `bytes` trace weight model or the simulator's cache-line bytes —
    whose magnitude stays below 2^31 is summed in int32 and is
    *exact*, hence bit-identical to `np.bincount`/`np.add.at` once
    widened back to float64: no partial sum can overflow.
  * integer-valued data whose magnitude lies in [2^31, 2^53) — the
    tensor-byte weights of a model's dataflow graph — takes the wide
    path: each value is split on the host into two int32 limbs,
    x = hi * 2^30 + lo with 0 <= lo < 2^30 (obs span `segsum.widen`
    around the split and the copy), and one kernel (`wide_segsum`)
    carries the low limb into the high one at every step, so both stay
    in int32.  The limb sums are joined on the host into float64 that
    is the exact sum, bit-identical to `np.bincount` in int64.  2^53
    is where float64, the reference's type, stops holding every
    integer: integer-valued data past it raises OverflowError.
  * any other float data is summed in float32 with compensated (Kahan)
    accumulation.  Each output is within 3u * sum(|x|) of the exact
    sum, u = 2^-24: u from rounding each input to float32, 2u from the
    compensated sum (plus n*u^2, below 2^-28 for n <= 2^20).  For the
    non-negative weights and times of this pipeline that is a relative
    error below 1.8e-7.

Values that are already jax arrays (inside the jitted metrics cores)
must be int32 or float32, or int32 limbs of shape (2, m) for the wide
path, whose sums come back as (2, num_segments) limbs; their callers
check the same bounds on the host before tracing.

Contract: `segment_ids` must be sorted ascending for `segment_sum`
(callers with unsorted keys use `keyed_sum`, whose stable sort keeps
each bucket's stream order); violations silently misreduce unless
`validate=True`.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from ... import obs
from .boundary import to_device, to_host

__all__ = ["segment_sum", "keyed_sum", "narrow", "split_limbs",
           "join_limbs", "DEFAULT_BLOCK"]

DEFAULT_BLOCK = 4096
_LANES, _SUBLANES = 128, 8
_TILE = _LANES * _SUBLANES
INT32_SUM_BOUND = 2 ** 31
EXACT_SUM_BOUND = 2 ** 53
LIMB_BITS = 30
_LOW = (1 << LIMB_BITS) - 1
_MIN_PAD = 8


def _next_pow2(x: int) -> int:
    return 1 << max(0, (x - 1).bit_length())


def _interpret_default() -> bool:
    """Interpret mode everywhere except a TPU backend."""
    return jax.default_backend() != "tpu"


def narrow(values, magnitude: "float | None" = None) -> np.ndarray:
    """The copy of host `values` the kernel sums: int32, int64 (wide) or
    float32.

    Integer-valued data whose stream magnitude (sum of |x|, or the
    caller's `magnitude` when one value feeds several stream entries)
    stays below 2^31 becomes int32; below 2^53 it becomes int64, which
    the wide path splits into int32 limbs.  Both are summed exactly.
    Integer-valued data past 2^53 raises OverflowError; other floats
    become float32 (see the module's precision contract).
    """
    v = np.asarray(values)
    if v.dtype == np.bool_:
        v = v.astype(np.int32)
    if v.dtype.kind not in "iuf":
        raise TypeError(f"cannot segment-sum dtype {v.dtype}")
    if magnitude is None:
        magnitude = float(np.abs(v, dtype=np.float64).sum())
    if v.dtype.kind == "f" and not bool(
            np.all(np.isfinite(v) & (v == np.round(v)))):
        return v.astype(np.float32)
    if magnitude < INT32_SUM_BOUND:
        return v.astype(np.int32)
    if magnitude >= EXACT_SUM_BOUND:
        raise OverflowError(
            f"integer stream magnitude {magnitude:.0f} reaches 2^53: "
            "its sums would not be exact in float64")
    return v.astype(np.int64)


def split_limbs(wide) -> np.ndarray:
    """(2, m) int32 limbs (hi, lo) of int64 values: x = hi * 2^30 + lo,
    0 <= lo < 2^30; |hi| < 2^23 below 2^53."""
    w = np.asarray(wide, np.int64)
    return np.stack([w >> LIMB_BITS, w & _LOW]).astype(np.int32)


def join_limbs(limbs) -> np.ndarray:
    """float64 values hi * 2^30 + lo of host limbs (2, ...), exact
    below 2^53."""
    hi, lo = np.asarray(limbs, np.int64)
    return ((hi << LIMB_BITS) + lo).astype(np.float64)


def _segsum_kernel(sid_ref, *refs, block: int, nblocks: int, wide: bool):
    """refs: the value stream(s), the output tile block(s), then the
    SMEM carry of the current segment (id, a, b).  One value stream and
    one output hold a plain sum (a; b is the Kahan compensation of a
    float sum); the wide path's two limb streams and two outputs hold
    (hi, lo) = (a, b)."""
    n = 2 if wide else 1
    data, outs = refs[:n], refs[n:2 * n]
    carry_sid, carry_a, carry_b = refs[2 * n:]
    pid = pl.program_id(0)
    dtype = outs[0].dtype
    zero = jnp.zeros((), dtype)
    compensated = jnp.issubdtype(dtype, jnp.floating)

    @pl.when(pid == 0)
    def _init():
        for out_ref in outs:
            out_ref[...] = jnp.zeros_like(out_ref)
        carry_sid[0] = sid_ref[0]
        carry_a[0] = zero
        carry_b[0] = zero

    def flush(s, acc):
        tile = s // _TILE
        sub = lax.broadcasted_iota(jnp.int32, (_SUBLANES, _LANES), 0)
        lane = lax.broadcasted_iota(jnp.int32, (_SUBLANES, _LANES), 1)
        hit = (sub == (s // _LANES) % _SUBLANES) & (lane == s % _LANES)
        for out_ref, x in zip(outs, acc):
            out_ref[tile] = jnp.where(hit, x, out_ref[tile])

    def body(j, carry):
        s, a, b = carry
        s_j = sid_ref[j]
        changed = s_j != s

        @pl.when(changed)
        def _flush():
            flush(s, (a, b)[:n])

        a = jnp.where(changed, zero, a)
        b = jnp.where(changed, zero, b)
        if wide:                        # carry the low limb's bit 30 up
            t = b + data[1][j]
            a = a + data[0][j] + (t >> LIMB_BITS)
            b = t & _LOW
        elif compensated:               # Kahan: b holds the lost bits
            y = data[0][j] - b
            t = a + y
            b = (t - a) - y
            a = t
        else:
            a = a + data[0][j]
        return s_j, a, b

    s, a, b = lax.fori_loop(
        0, block, body, (carry_sid[0], carry_a[0], carry_b[0]))
    carry_sid[0] = s
    carry_a[0] = a
    carry_b[0] = b

    @pl.when(pid == nblocks - 1)
    def _epilogue():
        # the stream's last segment never sees a successor id
        flush(s, (a, b)[:n])


@functools.partial(jax.jit, static_argnames=("tiles", "block", "interpret"))
def _segsum_call(sids, data, tiles: int, block: int, interpret: bool):
    """The kernel over a block-multiple stream: values (m,) give
    (tiles, 8, 128); wide limbs (2, m) give (2, tiles, 8, 128)."""
    nblocks = sids.shape[0] // block
    wide = data.ndim == 2
    dtype = data.dtype
    streams = list(data) if wide else [data]
    stream_spec = pl.BlockSpec((block,), lambda i: (i,),
                               memory_space=pltpu.SMEM)
    out_spec = pl.BlockSpec((tiles, _SUBLANES, _LANES), lambda i: (0, 0, 0))
    out_shape = jax.ShapeDtypeStruct((tiles, _SUBLANES, _LANES), dtype)
    out = pl.pallas_call(
        functools.partial(_segsum_kernel, block=block, nblocks=nblocks,
                          wide=wide),
        grid=(nblocks,),
        in_specs=[stream_spec] * (1 + len(streams)),
        out_specs=[out_spec] * len(streams),
        out_shape=[out_shape] * len(streams),
        scratch_shapes=[pltpu.SMEM((1,), jnp.int32),
                        pltpu.SMEM((1,), dtype),
                        pltpu.SMEM((1,), dtype)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",)),
        interpret=interpret,
        # the profiler names each kernel's ops after it: segment_sum is
        # the plain kernel alone
        name="wide_segsum" if wide else "segment_sum",
    )(sids, *streams)
    return jnp.stack(out) if wide else out[0]


@functools.partial(jax.jit, static_argnames=(
    "num_segments", "block", "interpret", "presorted"))
def _reduce(sids, data, num_segments: int, block: int, interpret: bool,
            presorted: bool):
    """Sort (unless presorted), pad to a power-of-two number of blocks
    with sentinel id `num_segments`, run the kernel, drop the slack.
    `data` is (m,) values or (2, m) wide limbs, reduced along the last
    axis."""
    if not presorted:
        order = jnp.argsort(sids, stable=True)
        sids, data = sids[order], data[..., order]
    m = sids.shape[0]
    padded = block * _next_pow2(-(-m // block))
    sids = jnp.concatenate(
        [sids, jnp.full((padded - m,), num_segments, jnp.int32)])
    data = jnp.concatenate(
        [data, jnp.zeros(data.shape[:-1] + (padded - m,), data.dtype)],
        axis=-1)
    tiles = -(-(num_segments + 1) // _TILE)
    out = _segsum_call(sids, data, tiles, block, interpret)
    return out.reshape(data.shape[:-1] + (-1,))[..., :num_segments]


def _sum(data, segment_ids, num_segments: int, *, block_size: int,
         interpret: "bool | None", validate: bool, presorted: bool):
    if num_segments < 0:
        raise ValueError("num_segments must be >= 0")
    if num_segments + 1 >= INT32_SUM_BOUND:
        raise ValueError("num_segments must fit int32 segment ids")
    host = not isinstance(data, jax.Array)
    if host:
        data = narrow(data)
        wide = data.dtype == np.int64
    else:
        wide = data.ndim == 2
        if data.dtype not in (jnp.int32, jnp.float32) or (
                wide and data.dtype != jnp.int32):
            raise TypeError(f"device values must be int32 or float32, or "
                            f"int32 limbs, not {data.dtype}; narrow them "
                            f"on the host")
    if not isinstance(segment_ids, jax.Array):
        segment_ids = np.asarray(segment_ids)
    lead = (2,) if wide and not host else ()
    if data.ndim != len(lead) + 1 or data.shape[:-1] != lead \
            or segment_ids.shape != data.shape[-1:]:
        raise ValueError("data and segment_ids must be parallel 1-D arrays")
    m = data.shape[-1]
    if validate and m:
        s = np.asarray(segment_ids)
        if presorted and (np.diff(s) < 0).any():
            raise ValueError("segment_ids must be sorted ascending")
        if s.min() < 0 or s.max() >= num_segments:
            raise ValueError("segment_ids must lie in [0, num_segments)")
    if m == 0 or num_segments == 0:
        if host and wide:
            return np.zeros(num_segments)
        return jnp.zeros(data.shape[:-1] + (num_segments,), data.dtype)
    if host:
        # pad host streams to a power-of-two bucket so nearby sizes
        # share one compiled program
        n = max(_next_pow2(m), _MIN_PAD)
        pad_ids = np.full(n, num_segments, np.int32)
        pad_ids[:m] = segment_ids
        if wide:
            with obs.span("segsum.widen", elements=m, slots=num_segments):
                limbs = np.zeros((2, n), np.int32)
                limbs[:, :m] = split_limbs(data)
                segment_ids, data = to_device((pad_ids, limbs))
        else:
            pad_data = np.zeros(n, data.dtype)
            pad_data[:m] = data
            segment_ids, data = to_device((pad_ids, pad_data))
    elif not isinstance(segment_ids, jax.Array):
        segment_ids = to_device(np.asarray(segment_ids, np.int32))
    if interpret is None:
        interpret = _interpret_default()
    out = _reduce(jnp.asarray(segment_ids, jnp.int32), data,
                  num_segments, block_size, bool(interpret), presorted)
    if host and wide:
        return join_limbs(to_host(out))
    return out


def segment_sum(data, segment_ids, num_segments: int, *,
                block_size: int = DEFAULT_BLOCK,
                interpret: "bool | None" = None,
                validate: bool = False):
    """Sum `data` into `num_segments` buckets keyed by sorted ids.

    Equivalent to the per-segment reduction over the runs (empty
    segments yield 0), accumulated in stream order under the module's
    precision contract.

    Args:
      data: 1-D values; host arrays are narrowed by `narrow`, jax
        arrays must be int32 or float32, or (2, m) int32 limbs.
      segment_ids: 1-D ascending ints parallel to `data`.
      num_segments: bucket count (ids must be < num_segments).
      block_size: flat-stream tile; segments may span any number of
        blocks (the carry handles the boundaries).
      interpret: force Pallas interpret mode (default: compiled on a
        TPU backend, interpreted elsewhere).
      validate: host-check the sorted/range contract (debug aid).

    Returns:
      jax array of shape (num_segments,), int32 or float32; (2,
      num_segments) int32 limbs for device limbs; host float64 of shape
      (num_segments,), the exact sums, for wide host data.
    """
    return _sum(data, segment_ids, num_segments, block_size=block_size,
                interpret=interpret, validate=validate, presorted=True)


def keyed_sum(keys, values, num_keys: int, *,
              block_size: int = DEFAULT_BLOCK,
              interpret: "bool | None" = None,
              validate: bool = False):
    """`segment_sum` over *unsorted* keys: stable-sort first.

    The stable sort preserves the relative order of entries sharing a
    key, so each bucket accumulates in stream order — integer-valued
    sums equal `np.bincount(keys, weights=values)` exactly.  This is
    the workhorse the metric ports call.
    """
    return _sum(values, keys, num_keys, block_size=block_size,
                interpret=interpret, validate=validate, presorted=False)
