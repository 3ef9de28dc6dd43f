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
    """An integer stream whose sum of |x| reaches 2^31 leaves the int32
    kernel for the exact wide path, whatever its dtype; one that
    reaches 2^53 is refused."""
    sids = np.zeros(4, np.int64)
    big = np.full(4, 2 ** 29, np.int64)
    assert narrow(big[:3]).dtype == np.int32
    assert narrow(big).dtype == np.int64
    assert narrow(big.astype(np.float64)).dtype == np.int64
    for data in (big, big.astype(np.float64)):
        got = segment_sum(data, sids, 1)
        assert got.dtype == np.float64 and got[0] == 2.0 ** 31
    with pytest.raises(OverflowError, match="2\\^53"):
        segment_sum(np.full(2, 2 ** 52, np.int64), sids[:2], 1)


def test_narrow_exact_for_integer_valued_floats():
    """Byte weights arrive as float64; integer-valued ones sum exactly."""
    assert narrow(np.array([64.0, 8.0, 1.0])).dtype == np.int32
    assert narrow(np.array([0.5, 1.0])).dtype == np.float32
    assert narrow(np.array([np.inf, 1.0])).dtype == np.float32
    assert narrow(np.array([3, 4], np.int64)).dtype == np.int32
    assert narrow(np.array([True, False])).dtype == np.int32
    # a caller-supplied stream magnitude overrides the per-value sum
    assert narrow(np.array([64.0]), magnitude=2.0 ** 31).dtype == np.int64
    with pytest.raises(OverflowError):
        narrow(np.array([64.0]), magnitude=2.0 ** 53)


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


# ---------------------------------------------------------------------- #
# the wide path: integer-valued streams with 2^31 <= sum |x| < 2^53
# ---------------------------------------------------------------------- #
def _bincount_int64(keys, values, n):
    """The exact sums: np.bincount's sequential accumulation in int64."""
    out = np.zeros(n, np.int64)
    np.add.at(out, np.asarray(keys, np.int64), np.asarray(values, np.int64))
    return out.astype(np.float64)


def _wide_stream(case, rng):
    """(keys, integer-valued float64 values, slots) for each magnitude."""
    if case == "just-under-2^31":
        v = np.full(64, (2 ** 31 - 1) // 64, np.int64)
        v[0] += (2 ** 31 - 1) - v.sum()
        return rng.integers(0, 5, 64), v.astype(np.float64), 5
    if case == "just-over-2^31":
        v = np.full(64, 2 ** 31 // 64, np.int64)
        return rng.integers(0, 5, 64), v.astype(np.float64), 5
    if case == "near-2^52":
        v = rng.integers(2 ** 40, 2 ** 41, 1500)
        v[-1] += 2 ** 52 - 2 ** 41 - v.sum()
        return rng.integers(0, 37, 1500), v.astype(np.float64), 37
    if case == "single-values-over-2^31":
        v = rng.integers(2 ** 31, 2 ** 44, 333) * rng.choice([-1, 1], 333)
        return rng.integers(0, 9, 333), v.astype(np.float64), 9
    if case == "empty-keys":
        v = rng.integers(1, 2 ** 34, 100)
        return rng.choice([3, 40, 41, 99], 100), v.astype(np.float64), 120
    # a stream of no power-of-two length whose padded sentinel tail
    # spans whole blocks
    v = rng.integers(1, 2 ** 30, 4097 + 13)
    return rng.integers(0, 64, len(v)), v.astype(np.float64), 64


WIDE_CASES = ["just-under-2^31", "just-over-2^31", "near-2^52",
              "single-values-over-2^31", "empty-keys", "sentinel-tail"]


@pytest.mark.parametrize("case", WIDE_CASES)
def test_wide_keyed_sum_bit_identical_to_int64_bincount(case):
    import zlib
    rng = np.random.default_rng(zlib.crc32(case.encode()))
    keys, vals, n = _wide_stream(case, rng)
    want = _bincount_int64(keys, vals, n)
    got = np.asarray(keyed_sum(keys, vals, n, block_size=256), np.float64)
    np.testing.assert_array_equal(got, want)
    assert np.array_equal(got, np.bincount(keys, weights=vals, minlength=n))
    order = np.argsort(keys, kind="stable")
    got = np.asarray(segment_sum(vals[order], keys[order], n, block_size=8),
                     np.float64)
    np.testing.assert_array_equal(got, want)
    wide = np.abs(vals).sum() >= 2 ** 31
    assert (narrow(vals).dtype == np.int64) == wide


def test_wide_path_past_2_53_raises():
    keys = np.zeros(3, np.int64)
    with pytest.raises(OverflowError, match="2\\^53"):
        keyed_sum(keys, np.full(3, 2.0 ** 51 + 2.0 ** 50), 1)
    with pytest.raises(OverflowError, match="2\\^53"):
        keyed_sum(keys, np.array([2 ** 53, 0, 0], np.int64), 1)


def test_int32_kernel_kept_below_2_31_and_widen_span_above():
    """Below 2^31 the int32 kernel runs as before (a jax int32 result,
    no `segsum.widen` span); above it one span names the elements and
    slots of the split, and the limbs round-trip exactly."""
    import jax
    from repro import obs
    from repro.core.pallas.segsum import join_limbs, split_limbs
    keys = np.arange(6) % 3
    col = obs.enable()
    try:
        small = keyed_sum(keys, np.full(6, 2.0 ** 28), 3)
        big = keyed_sum(keys, np.full(6, 2.0 ** 29), 3)
    finally:
        obs.disable()
    assert isinstance(small, jax.Array) and small.dtype == np.int32
    assert isinstance(big, np.ndarray) and big.dtype == np.float64
    np.testing.assert_array_equal(big, np.full(3, 2.0 ** 30))
    widen = [e for e in col.events if e["name"] == "segsum.widen"]
    assert [(e["args"]["elements"], e["args"]["slots"]) for e in widen] \
        == [(6, 3)]
    x = np.array([0, 1, -1, 2 ** 30, -2 ** 30 - 1, 2 ** 53 - 1, -2 ** 53 + 1])
    limbs = split_limbs(x)
    assert limbs.dtype == np.int32
    assert ((limbs[1] >= 0) & (limbs[1] < 2 ** 30)).all()
    np.testing.assert_array_equal(join_limbs(limbs), x.astype(np.float64))


def test_wide_device_limbs_inside_jit():
    """Device limbs (2, m) reduce to (2, n) limbs inside a jitted core,
    as the star-triple cores use them."""
    import jax
    import jax.numpy as jnp
    from repro.core.pallas.segsum import join_limbs, split_limbs
    rng = np.random.default_rng(12)
    vals = rng.integers(1, 2 ** 40, 500)
    keys = rng.integers(0, 17, 500)
    fn = jax.jit(lambda k, v: keyed_sum(k, v, 17))
    out = fn(jnp.asarray(keys, jnp.int32), jnp.asarray(split_limbs(vals)))
    assert out.shape == (2, 17) and out.dtype == jnp.int32
    np.testing.assert_array_equal(join_limbs(np.asarray(out)),
                                  _bincount_int64(keys, vals, 17))
    with pytest.raises(TypeError, match="limbs"):
        keyed_sum(jnp.asarray(keys, jnp.int32),
                  jnp.zeros((2, 500), jnp.float32), 17)


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
