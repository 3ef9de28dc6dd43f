"""Launch-layer + HLO-analysis tests: cells enumeration, parallel plans,
sharded lowering on a small in-process mesh, loop-aware cost analysis."""
import subprocess
import sys

import jax
import jax.numpy as jnp

from repro.analysis import analyze_hlo
from repro.configs import ARCHS, SHAPES, reduced_config
from repro.configs.base import ParallelConfig
from repro.launch.cells import (Cell, cell_skip_reason, enumerate_cells,
                                parallel_plan)
from repro.optim import AdamWConfig


def test_cell_enumeration_covers_assignment():
    all_cells = enumerate_cells(include_skipped=True)
    assert len(all_cells) == len(ARCHS) * len(SHAPES) == 40
    runnable = enumerate_cells()
    skipped = [c for c in all_cells if cell_skip_reason(c)]
    # long_500k runs only for ssm + hybrid (2 archs), skipped for 8
    assert len(skipped) == 8
    assert all(c.shape == "long_500k" for c in skipped)
    assert {c.arch for c in runnable if c.shape == "long_500k"} == \
        {"rwkv6-7b", "recurrentgemma-9b"}


def test_parallel_plan_bounds_tokens():
    par, opt = parallel_plan(Cell("deepseek-v3-671b", "train_4k"))
    assert par.microbatches >= 8
    assert par.remat != "none"
    assert opt.moment_dtype == jnp.bfloat16  # >100B params
    par2, opt2 = parallel_plan(Cell("smollm-360m", "decode_32k"))
    assert par2.microbatches == 1


def test_sharded_lowering_small_mesh():
    """Compile a reduced train step on an in-process (1,2) mesh — covers
    param/batch/cache sharding rules + mesh context end-to-end."""
    from repro import models
    from repro.launch.steps import make_train_step
    from repro.optim import adamw_init
    from repro.parallel.sharding import (batch_specs, param_specs,
                                         sanitize_specs)
    from jax.sharding import AxisType, NamedSharding, PartitionSpec as P

    cfg = reduced_config(ARCHS["granite-3-2b"])
    mesh = jax.make_mesh((1, 1), ("data", "model"),
                         axis_types=(AxisType.Auto,) * 2)
    par = ParallelConfig(fsdp=True, tp=True, microbatches=1, remat="block")
    opt_cfg = AdamWConfig()
    params = jax.eval_shape(lambda k: models.init_params(cfg, k),
                            jax.random.PRNGKey(0))
    opt = jax.eval_shape(lambda p: adamw_init(p, opt_cfg), params)
    batch = {"tokens": jax.ShapeDtypeStruct((2, 16), jnp.int32)}
    p_specs = sanitize_specs(param_specs(params, cfg, par), params, mesh)
    sh = lambda t: jax.tree.map(  # noqa: E731
        lambda s: NamedSharding(mesh, s), t,
        is_leaf=lambda x: isinstance(x, P))
    b_specs = batch_specs(cfg, batch, ("data",))
    step = make_train_step(cfg, opt_cfg, par)
    with jax.set_mesh(mesh):
        lowered = jax.jit(
            step, in_shardings=(sh(p_specs),
                                sh({"m": p_specs, "v": p_specs,
                                    "step": P()}),
                                sh(b_specs))).lower(params, opt, batch)
        compiled = lowered.compile()
    assert compiled.cost_analysis() is not None


# ----------------------------- analysis ------------------------------- #
def test_analyze_hlo_scan_flops_exact():
    def f(w, x):
        def body(h, _):
            return jnp.tanh(h @ w), None
        h, _ = jax.lax.scan(body, x, None, length=8)
        return h

    w = jax.ShapeDtypeStruct((256, 256), jnp.float32)
    x = jax.ShapeDtypeStruct((32, 256), jnp.float32)
    cost = analyze_hlo(jax.jit(f).lower(w, x).compile().as_text())
    expect = 8 * 2 * 32 * 256 * 256
    assert abs(cost.flops - expect) / expect < 0.05


def test_analyze_hlo_bytes_scale_with_scan():
    def make(n):
        def f(w, x):
            def body(h, _):
                return jnp.tanh(h @ w), None
            h, _ = jax.lax.scan(body, x, None, length=n)
            return h
        return f

    w = jax.ShapeDtypeStruct((256, 256), jnp.float32)
    x = jax.ShapeDtypeStruct((32, 256), jnp.float32)
    b4 = analyze_hlo(jax.jit(make(4)).lower(w, x).compile().as_text())
    b16 = analyze_hlo(jax.jit(make(16)).lower(w, x).compile().as_text())
    assert b16.hbm_bytes > 2.5 * b4.hbm_bytes  # ~4x expected


def test_analyze_hlo_slice_not_full_array():
    """A scan that slices a big constant per step must NOT charge the
    full array per iteration (the dynamic-slice fix)."""
    def f(big, x):
        def body(h, t):
            sl = jax.lax.dynamic_slice_in_dim(big, t * 0, 32)
            return h + sl.sum(), None
        h, _ = jax.lax.scan(body, x, jnp.arange(64), length=64)
        return h

    big = jax.ShapeDtypeStruct((maxdim := 32 * 1024, 32), jnp.float32)
    x = jax.ShapeDtypeStruct((), jnp.float32)
    cost = analyze_hlo(jax.jit(f).lower(big, x).compile().as_text())
    full_per_iter = 64 * maxdim * 32 * 4
    assert cost.hbm_bytes < full_per_iter / 4


def test_analyze_hlo_collectives_in_loop():
    from jax.sharding import NamedSharding, PartitionSpec as P
    mesh = jax.make_mesh((1,), ("model",))

    def g(w, x):
        def body(h, _):
            return h @ w, None
        h, _ = jax.lax.scan(body, x, None, length=5)
        return h.sum()

    w_sh = NamedSharding(mesh, P("model", None))
    x_sh = NamedSharding(mesh, P(None, "model"))
    w = jax.ShapeDtypeStruct((64, 64), jnp.float32)
    x = jax.ShapeDtypeStruct((8, 64), jnp.float32)
    c = jax.jit(g, in_shardings=(w_sh, x_sh)).lower(w, x).compile()
    cost = analyze_hlo(c.as_text())
    # single-device mesh: no collectives required
    assert cost.total_collective_bytes >= 0.0


def test_mesh_with_vertex_cut_device_order():
    """Algorithm-2 device ordering: the mesh builder accepts a shard-comm
    matrix and produces a valid permuted mesh (subprocess: needs 512
    placeholder devices, which must not leak into this test process)."""
    code = """
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512"
import numpy as np
from repro.launch.mesh import make_mesh_with_order, make_production_mesh
rng = np.random.default_rng(0)
comm = rng.random((16, 16)); comm = comm + comm.T
m1 = make_production_mesh(multi_pod=False)
m2 = make_mesh_with_order(comm, multi_pod=False)
assert m1.devices.shape == m2.devices.shape == (16, 16)
ids1 = sorted(d.id for d in m1.devices.flat)
ids2 = sorted(d.id for d in m2.devices.flat)
assert ids1 == ids2          # same device set, permuted order
m3 = make_mesh_with_order(None, multi_pod=True)
assert m3.devices.shape == (2, 16, 16)
print("MESH_ORDER_OK")
"""
    import subprocess
    import sys
    out = subprocess.run(
        [sys.executable, "-c", code], capture_output=True, text=True,
        env={**__import__("os").environ, "PYTHONPATH": "src"},
        cwd=__import__("os").path.dirname(
            __import__("os").path.dirname(__file__)),
        timeout=300)
    assert "MESH_ORDER_OK" in out.stdout, out.stderr[-2000:]
