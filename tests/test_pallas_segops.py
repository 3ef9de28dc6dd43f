"""Pallas segment-sum kernel vs the numpy oracles.

The kernel's precision contract (see `repro.core.pallas.segsum`): over a
sorted segment-id stream, integer data is summed in int32 and is
*exact* — equal to the sequential (`np.add.at`/`np.bincount`) and the
pairwise (`np.add.reduceat`) oracles alike — as long as the stream's
sum of |x| stays below 2^31, which is checked.  Other float data is
summed in float32 with compensated accumulation and lands within
3u * sum(|x|) of the exact per-segment sum (u = 2^-24).  Layouts are
stressed where tiled kernels break: empty segments, one giant segment
spanning many blocks, non-divisible tails, and block-boundary
straddles.  The jitted call must match the op-by-op interpreter
(compiled-vs-interpret parity runs when a real accelerator is present).
"""
import numpy as np
import pytest

from repro.core.pallas import keyed_sum, narrow, segment_sum

U = 2.0 ** -24


def _oracle_reduceat(data, sids, nseg):
    """np.add.reduceat over the segment runs, empty segments = 0.
    (reduceat reduces *pairwise* for floats — the documented tolerance.)
    """
    out = np.zeros(nseg, dtype=data.dtype)
    if len(data) == 0:
        return out
    present, starts = np.unique(sids, return_index=True)
    out[present] = np.add.reduceat(data, starts)
    return out


def _oracle_sequential(data, sids, nseg):
    """Strict in-order accumulation — np.add.at is unbuffered/sequential,
    the order the kernel's carry chain reproduces bit for bit."""
    out = np.zeros(nseg, dtype=data.dtype)
    np.add.at(out, sids, data)
    return out


def _assert_within_contract(got, data, sids, nseg):
    """float32 result within 3u * sum(|x|) of each segment's exact sum
    (the float64 sequential oracle; its own error is ~1e-16 relative)."""
    assert got.dtype == np.float32
    want = _oracle_sequential(data.astype(np.float64), sids, nseg)
    mag = _oracle_sequential(np.abs(data.astype(np.float64)), sids, nseg)
    bound = (3 * U + 2 * len(data) * U ** 2) * mag
    err = np.abs(got.astype(np.float64) - want)
    assert (err <= bound).all(), (err - bound).max()


def _check(data, sids, nseg, block):
    got = np.asarray(segment_sum(data, sids, nseg, block_size=block))
    if narrow(data).dtype == np.int32:
        # integer-valued data sums exactly in int32: equal to both oracles
        assert got.dtype == np.int32
        got = got.astype(data.dtype)
        np.testing.assert_array_equal(got, _oracle_sequential(data, sids,
                                                              nseg))
        np.testing.assert_array_equal(got, _oracle_reduceat(data, sids,
                                                            nseg))
    else:
        _assert_within_contract(got, data, sids, nseg)


LAYOUTS = [
    # (m, nseg, block, layout) — handcrafted block-boundary stress
    (0, 5, 8, "empty-stream"),
    (7, 1, 4, "single-segment-tail"),
    (64, 1, 8, "one-giant-segment-8-blocks"),
    (33, 50, 8, "non-divisible-tail"),
    (24, 200, 8, "mostly-empty-segments"),
    (48, 3, 16, "segment-spanning-3-blocks"),
]


@pytest.mark.parametrize("m,nseg,block,layout", LAYOUTS)
@pytest.mark.parametrize("dtype", [np.float64, np.int64])
def test_handcrafted_layouts(m, nseg, block, layout, dtype):
    import zlib
    rng = np.random.default_rng(zlib.crc32(layout.encode()))
    if layout == "one-giant-segment-8-blocks":
        sids = np.zeros(m, np.int64)
    elif layout == "segment-spanning-3-blocks":
        # middle segment covers >= 3 full blocks; neighbours are slivers
        sids = np.r_[np.zeros(4), np.ones(40), np.full(4, 2)].astype(np.int64)
    else:
        sids = np.sort(rng.integers(0, nseg, m))
    data = rng.integers(-50, 50, m).astype(dtype)
    if dtype is np.float64:
        data *= np.pi                      # inexact values: rounding matters
    _check(data, sids, nseg, block)


def test_int_weights_bit_identical_large():
    # |x| < 10^5 over 20k entries keeps the stream under the 2^31 bound
    rng = np.random.default_rng(3)
    m, nseg = 20_000, 511
    sids = np.sort(rng.integers(0, nseg, m))
    data = rng.integers(-10**5, 10**5, m)
    got = np.asarray(segment_sum(data, sids, nseg))
    np.testing.assert_array_equal(got, _oracle_reduceat(data, sids, nseg))


def test_int_overflow_bound_checked():
    """An integer stream whose sum of |x| reaches 2^31 is refused; an
    integer-valued float stream past it drops to the float32 path."""
    sids = np.zeros(4, np.int64)
    big = np.full(4, 2 ** 29, np.int64)
    with pytest.raises(OverflowError, match="2\\^31"):
        segment_sum(big, sids, 1)
    assert narrow(big[:3]).dtype == np.int32
    assert narrow(big.astype(np.float64)).dtype == np.float32
    got = np.asarray(segment_sum(big.astype(np.float64), sids, 1))
    assert got.dtype == np.float32 and got[0] == 2.0 ** 31


def test_narrow_exact_for_integer_valued_floats():
    """Byte weights arrive as float64; integer-valued ones sum in int32."""
    assert narrow(np.array([64.0, 8.0, 1.0])).dtype == np.int32
    assert narrow(np.array([0.5, 1.0])).dtype == np.float32
    assert narrow(np.array([3, 4], np.int64)).dtype == np.int32
    assert narrow(np.array([True, False])).dtype == np.int32
    # a caller-supplied stream magnitude overrides the per-value sum
    assert narrow(np.array([64.0]), magnitude=2.0 ** 31).dtype == np.float32


def test_keyed_sum_matches_bincount_bit_for_bit():
    """Stable sort + exact int32 kernel == np.bincount, for the
    integer-valued float weights (bytes, counts) the pipeline sums."""
    rng = np.random.default_rng(5)
    m, nkeys = 30_000, 777
    keys = rng.integers(0, nkeys, m)
    vals = rng.integers(1, 4096, m).astype(np.float64)
    got = np.asarray(keyed_sum(keys, vals, nkeys), np.float64)
    want = np.bincount(keys, weights=vals, minlength=nkeys)
    np.testing.assert_array_equal(got, want)


def test_keyed_sum_float_weights_within_contract():
    """Non-integral weights (memop latencies, simulator times): float32
    compensated sums within 3u * sum(|x|) of np.bincount."""
    rng = np.random.default_rng(6)
    m, nkeys = 30_000, 777
    keys = rng.integers(0, nkeys, m)
    vals = rng.lognormal(size=m)
    got = np.asarray(keyed_sum(keys, vals, nkeys))
    order = np.argsort(keys, kind="stable")
    _assert_within_contract(got, vals[order], keys[order], nkeys)


def test_interpret_modes_parity():
    """Jitted interpreter vs the same call — parity across cache entries
    and dtypes; on TPU/GPU this also exercises compiled-vs-interpret."""
    import jax
    rng = np.random.default_rng(9)
    m, nseg = 1000, 37
    sids = np.sort(rng.integers(0, nseg, m))
    data = rng.standard_normal(m)
    a = np.asarray(segment_sum(data, sids, nseg, interpret=True))
    b = np.asarray(segment_sum(data, sids, nseg))  # auto mode
    np.testing.assert_array_equal(a, b)
    if jax.default_backend() == "tpu":             # pragma: no cover - accel
        c = np.asarray(segment_sum(data, sids, nseg, interpret=False))
        np.testing.assert_array_equal(c, a)


def test_validate_flags_bad_contracts():
    data = np.ones(4)
    with pytest.raises(ValueError, match="sorted"):
        segment_sum(data, np.array([0, 2, 1, 3]), 4, validate=True)
    with pytest.raises(ValueError, match="lie in"):
        segment_sum(data, np.array([0, 1, 2, 9]), 4, validate=True)
    with pytest.raises(ValueError, match="parallel"):
        segment_sum(data, np.array([0, 1]), 4)


# deeper randomized search when the [test] extra is installed ----------- #
try:
    from hypothesis import given, settings, strategies as st
    HAVE_HYPOTHESIS = True
except ImportError:
    HAVE_HYPOTHESIS = False

if HAVE_HYPOTHESIS:
    @st.composite
    def segment_layouts(draw):
        """Random sorted layouts biased toward the nasty shapes: empty
        segments, giant runs, and tails not divisible by the block."""
        nseg = draw(st.integers(1, 64))
        runs = draw(st.lists(
            st.tuples(st.integers(0, nseg - 1), st.integers(1, 70)),
            min_size=0, max_size=12))
        sids = np.sort(np.concatenate(
            [np.full(ln, s, np.int64) for s, ln in runs]
            or [np.empty(0, np.int64)]))
        block = draw(st.sampled_from([2, 8, 32, 4096]))
        return sids, nseg, block

    @given(layout=segment_layouts(),
           dtype=st.sampled_from([np.float64, np.int64]),
           seed=st.integers(0, 2**31 - 1))
    @settings(max_examples=30, deadline=None)
    def test_property_matches_reduceat(layout, dtype, seed):
        sids, nseg, block = layout
        rng = np.random.default_rng(seed)
        data = rng.integers(-100, 100, len(sids)).astype(dtype)
        if dtype is np.float64:
            data *= np.e
        _check(data, sids, nseg, block)
