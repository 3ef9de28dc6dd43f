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

The output holds `num_segments + 1` slots rounded up to whole tiles;
slot `num_segments` absorbs the padded tail.  At p = 1024 the p^2+1
keyed reductions take 1025 tiles (4.2 MB of VMEM), which is the
largest output the pipeline asks for; vertex-keyed reductions at
millions of segments would need an output-tiled variant (ROADMAP).

Precision contract
------------------
Mosaic has no 64-bit types, so the kernel sums int32 or float32.
Host (numpy) values are narrowed by `narrow`, per output kind:

  * integer-valued data — counts, and byte weights such as the
    `bytes` trace weight model or the simulator's cache-line bytes —
    is summed in int32 and is *exact*, hence bit-identical to
    `np.bincount`/`np.add.at` once widened back to float64.  The bound
    that makes it exact is checked on the host: the sum of |x| over the
    stream must stay below 2^31, so no partial sum can overflow;
    `narrow` raises OverflowError otherwise (integer dtypes) or falls
    back to float32 (integer-valued floats).
  * any other float data is summed in float32 with compensated (Kahan)
    accumulation.  Each output is within 3u * sum(|x|) of the exact
    sum, u = 2^-24: u from rounding each input to float32, 2u from the
    compensated sum (plus n*u^2, below 2^-28 for n <= 2^20).  For the
    non-negative weights and times of this pipeline that is a relative
    error below 1.8e-7.

Values that are already jax arrays (inside the jitted metrics cores)
must be int32 or float32; their callers check the same bounds on the
host before tracing.

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

from .boundary import to_device

__all__ = ["segment_sum", "keyed_sum", "narrow", "DEFAULT_BLOCK"]

DEFAULT_BLOCK = 4096
_LANES, _SUBLANES = 128, 8
_TILE = _LANES * _SUBLANES
INT32_SUM_BOUND = 2 ** 31
_MIN_PAD = 8


def _next_pow2(x: int) -> int:
    return 1 << max(0, (x - 1).bit_length())


def _interpret_default() -> bool:
    """Interpret mode everywhere except a TPU backend."""
    return jax.default_backend() != "tpu"


def narrow(values, magnitude: "float | None" = None) -> np.ndarray:
    """The int32 or float32 copy of host `values` the kernel sums.

    Integer-valued data whose stream magnitude (sum of |x|, or the
    caller's `magnitude` when one value feeds several stream entries)
    stays below 2^31 becomes int32, and its sums are exact.  Integer
    dtypes past that bound raise OverflowError; other floats become
    float32 (see the module's precision contract).
    """
    v = np.asarray(values)
    if v.dtype == np.bool_:
        v = v.astype(np.int32)
    if magnitude is None:
        magnitude = float(np.abs(v, dtype=np.float64).sum())
    if v.dtype.kind in "iu":
        if magnitude >= INT32_SUM_BOUND:
            raise OverflowError(
                f"integer stream magnitude {magnitude:.0f} reaches 2^31: "
                "int32 segment sums could overflow")
        return v.astype(np.int32)
    if v.dtype.kind != "f":
        raise TypeError(f"cannot segment-sum dtype {v.dtype}")
    if magnitude < INT32_SUM_BOUND and bool(np.all(v == np.round(v))):
        return v.astype(np.int32)
    return v.astype(np.float32)


def _segsum_kernel(sid_ref, data_ref, out_ref, carry_sid, carry_acc,
                   carry_comp, *, block: int, nblocks: int):
    pid = pl.program_id(0)
    dtype = out_ref.dtype
    zero = jnp.zeros((), dtype)
    compensated = jnp.issubdtype(dtype, jnp.floating)

    @pl.when(pid == 0)
    def _init():
        out_ref[...] = jnp.zeros_like(out_ref)
        carry_sid[0] = sid_ref[0]
        carry_acc[0] = zero
        carry_comp[0] = zero

    def flush(s, acc):
        tile = s // _TILE
        sub = lax.broadcasted_iota(jnp.int32, (_SUBLANES, _LANES), 0)
        lane = lax.broadcasted_iota(jnp.int32, (_SUBLANES, _LANES), 1)
        hit = (sub == (s // _LANES) % _SUBLANES) & (lane == s % _LANES)
        out_ref[tile] = jnp.where(hit, acc, out_ref[tile])

    def body(j, carry):
        s, acc, comp = carry
        s_j = sid_ref[j]
        changed = s_j != s

        @pl.when(changed)
        def _flush():
            flush(s, acc)

        acc = jnp.where(changed, zero, acc)
        comp = jnp.where(changed, zero, comp)
        x = data_ref[j]
        if compensated:                 # Kahan: comp holds the lost bits
            y = x - comp
            t = acc + y
            comp = (t - acc) - y
            acc = t
        else:
            acc = acc + x
        return s_j, acc, comp

    s, acc, comp = lax.fori_loop(
        0, block, body, (carry_sid[0], carry_acc[0], carry_comp[0]))
    carry_sid[0] = s
    carry_acc[0] = acc
    carry_comp[0] = comp

    @pl.when(pid == nblocks - 1)
    def _epilogue():
        # the stream's last segment never sees a successor id
        flush(s, acc)


@functools.partial(jax.jit, static_argnames=("tiles", "block", "interpret"))
def _segsum_call(sids, data, tiles: int, block: int, interpret: bool):
    """The kernel over a block-multiple stream; returns (tiles, 8, 128)."""
    nblocks = sids.shape[0] // block
    dtype = data.dtype
    return pl.pallas_call(
        functools.partial(_segsum_kernel, block=block, nblocks=nblocks),
        grid=(nblocks,),
        in_specs=[pl.BlockSpec((block,), lambda i: (i,),
                               memory_space=pltpu.SMEM),
                  pl.BlockSpec((block,), lambda i: (i,),
                               memory_space=pltpu.SMEM)],
        out_specs=pl.BlockSpec((tiles, _SUBLANES, _LANES),
                               lambda i: (0, 0, 0)),
        out_shape=jax.ShapeDtypeStruct((tiles, _SUBLANES, _LANES), dtype),
        scratch_shapes=[pltpu.SMEM((1,), jnp.int32),
                        pltpu.SMEM((1,), dtype),
                        pltpu.SMEM((1,), dtype)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",)),
        interpret=interpret,
        name="segment_sum",
    )(sids, data)


@functools.partial(jax.jit, static_argnames=(
    "num_segments", "block", "interpret", "presorted"))
def _reduce(sids, data, num_segments: int, block: int, interpret: bool,
            presorted: bool):
    """Sort (unless presorted), pad to a power-of-two number of blocks
    with sentinel id `num_segments`, run the kernel, drop the slack."""
    if not presorted:
        order = jnp.argsort(sids, stable=True)
        sids, data = sids[order], data[order]
    m = sids.shape[0]
    padded = block * _next_pow2(-(-m // block))
    sids = jnp.concatenate(
        [sids, jnp.full((padded - m,), num_segments, jnp.int32)])
    data = jnp.concatenate([data, jnp.zeros((padded - m,), data.dtype)])
    tiles = -(-(num_segments + 1) // _TILE)
    out = _segsum_call(sids, data, tiles, block, interpret)
    return out.reshape(-1)[:num_segments]


def _sum(data, segment_ids, num_segments: int, *, block_size: int,
         interpret: "bool | None", validate: bool, presorted: bool):
    if num_segments < 0:
        raise ValueError("num_segments must be >= 0")
    if num_segments + 1 >= INT32_SUM_BOUND:
        raise ValueError("num_segments must fit int32 segment ids")
    host = not isinstance(data, jax.Array)
    if host:
        data = narrow(data)
    elif data.dtype not in (jnp.int32, jnp.float32):
        raise TypeError(f"device values must be int32 or float32, "
                        f"not {data.dtype}; narrow them on the host")
    if not isinstance(segment_ids, jax.Array):
        segment_ids = np.asarray(segment_ids)
    if data.ndim != 1 or segment_ids.shape != data.shape:
        raise ValueError("data and segment_ids must be parallel 1-D arrays")
    m = data.shape[0]
    if validate and m:
        s = np.asarray(segment_ids)
        if presorted and (np.diff(s) < 0).any():
            raise ValueError("segment_ids must be sorted ascending")
        if s.min() < 0 or s.max() >= num_segments:
            raise ValueError("segment_ids must lie in [0, num_segments)")
    if m == 0 or num_segments == 0:
        return jnp.zeros((num_segments,), data.dtype)
    if host:
        # pad host streams to a power-of-two bucket so nearby sizes
        # share one compiled program
        n = max(_next_pow2(m), _MIN_PAD)
        pad_ids = np.full(n, num_segments, np.int32)
        pad_ids[:m] = segment_ids
        pad_data = np.zeros(n, data.dtype)
        pad_data[:m] = data
        segment_ids, data = to_device((pad_ids, pad_data))
    elif not isinstance(segment_ids, jax.Array):
        segment_ids = to_device(np.asarray(segment_ids, np.int32))
    if interpret is None:
        interpret = _interpret_default()
    return _reduce(jnp.asarray(segment_ids, jnp.int32), data,
                   num_segments, block_size, bool(interpret), presorted)


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
        arrays must be int32 or float32.
      segment_ids: 1-D ascending ints parallel to `data`.
      num_segments: bucket count (ids must be < num_segments).
      block_size: flat-stream tile; segments may span any number of
        blocks (the carry handles the boundaries).
      interpret: force Pallas interpret mode (default: compiled on a
        TPU backend, interpreted elsewhere).
      validate: host-check the sorted/range contract (debug aid).

    Returns:
      jax array of shape (num_segments,), int32 or float32.
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
