"""Compile the device path for a described TPU v5e chip, on the CPU.

`jax.experimental.topologies` describes a v5e chip that is not attached;
lowering and compiling for it runs the TPU compiler (Mosaic for the
Pallas kernel), which refuses what interpret mode accepts: scalar
stores to VMEM, 64-bit types, unaligned tiles, VMEM overflow.  Nothing
runs, so these tests say nothing about results or times.  Shapes are
the `chip_smoke.py` ones: the 276k-line synthetic trace has 510,760
edges (2^20 padded CSR keys) and 316,779 vertices (2^19 bucket).

The topology is described inside a module-scoped fixture, never at
import: only one process may load the TPU library, and every test
worker imports this file.
"""
import functools
import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.core.pallas import metrics, segsum

STREAM = 1 << 20            # padded kernel stream / CSR key stream
VERTS = 1 << 19             # vertex bucket; member and triple streams
P_SMALL, P_LARGE = 64, 1024


@pytest.fixture(scope="module")
def one_chip():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    topo = topologies.get_topology_desc(platform="tpu",
                                        topology_name="v5e:2x2")
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture
def compiled_kernels(monkeypatch):
    """Steer the metrics cores onto the compiled kernel: off a TPU
    backend they would pick interpret mode while tracing."""
    monkeypatch.setattr(segsum, "_interpret_default", lambda: False)


def _sds(shape, dtype, sharding):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


def _fresh(core, **static):
    """A new jit of a cached core, so this trace sees the steered
    interpret choice rather than an earlier CPU trace."""
    return jax.jit(core.__wrapped__, static_argnames=tuple(static))


@pytest.mark.parametrize("dtype", [jnp.int32, jnp.float32])
@pytest.mark.parametrize("num_segments",
                         [P_SMALL + 1, 4097, P_LARGE * P_LARGE + 1])
def test_segsum_kernel_compiles(one_chip, num_segments, dtype):
    tiles = -(-(num_segments + 1) // segsum._TILE)
    compiled = segsum._segsum_call.lower(
        _sds((STREAM,), jnp.int32, one_chip), _sds((STREAM,), dtype, one_chip),
        tiles=tiles, block=segsum.DEFAULT_BLOCK, interpret=False).compile()
    assert "tpu_custom_call" in compiled.as_text()
    # the (tiles, 8, 128) output block stays resident in VMEM
    assert compiled.memory_analysis().output_size_in_bytes \
        == tiles * segsum._TILE * 4


@pytest.mark.parametrize("num_segments", [P_SMALL + 1, P_LARGE * P_LARGE + 1])
def test_wide_segsum_kernel_compiles(one_chip, num_segments):
    """The wide path: two int32 limb streams into two resident outputs,
    under its own kernel name."""
    tiles = -(-(num_segments + 1) // segsum._TILE)
    compiled = segsum._segsum_call.lower(
        _sds((STREAM,), jnp.int32, one_chip),
        _sds((2, STREAM), jnp.int32, one_chip),
        tiles=tiles, block=segsum.DEFAULT_BLOCK, interpret=False).compile()
    text = compiled.as_text()
    assert "tpu_custom_call" in text and "wide_segsum" in text
    assert compiled.memory_analysis().output_size_in_bytes \
        == 2 * tiles * segsum._TILE * 4


def test_csr_core_compiles(one_chip):
    key = _sds((STREAM,), jnp.int32, one_chip)
    compiled = metrics._csr_core.lower(key, key, pn=VERTS).compile()
    assert compiled.as_text()


def test_star_core_compiles(one_chip):
    i32 = functools.partial(_sds, dtype=jnp.int32, sharding=one_chip)
    compiled = metrics._star_core.lower(
        i32((VERTS + 1,)), i32((VERTS,)), i32((VERTS,)), i32((VERTS,)),
        i32(()), has_bytes=True).compile()
    assert compiled.as_text()


def test_star_comm_core_compiles(one_chip, compiled_kernels):
    i32 = functools.partial(_sds, dtype=jnp.int32, sharding=one_chip)
    core = _fresh(metrics._star_comm_core, p=P_SMALL)
    compiled = core.lower(i32((VERTS,)), i32((VERTS,)), i32((VERTS,)),
                          i32(()), p=P_SMALL).compile()
    assert "tpu_custom_call" in compiled.as_text()


@pytest.mark.parametrize("dtype", [jnp.int32, jnp.float32])
def test_keyed_sum_p1024_compiles(one_chip, dtype):
    fn = jax.jit(lambda k, v: segsum.keyed_sum(
        k, v, P_LARGE * P_LARGE + 1, interpret=False))
    compiled = fn.lower(_sds((VERTS,), jnp.int32, one_chip),
                        _sds((VERTS,), dtype, one_chip)).compile()
    assert "tpu_custom_call" in compiled.as_text()


def test_replica_sync_core_compiles(one_chip, compiled_kernels):
    i32 = functools.partial(_sds, dtype=jnp.int32, sharding=one_chip)
    static = dict(cols=32, n_cores=P_LARGE, hop_latency=1e-9,
                  coherence_penalty=2e-8, mshr_overlap=4.0, link_bw=1.6e10)
    core = _fresh(metrics._sync_core, **static)
    compiled = core.lower(i32((VERTS,)), i32((VERTS,)), i32((VERTS,)),
                          i32(()), i32((P_LARGE,)), **static).compile()
    assert "tpu_custom_call" in compiled.as_text()
