"""host_glue_ms: host work between device calls, in ms per plan: time inside the device layers' obs spans (`cut.finalize`, `map.cluster_graphs`, `sim.run`) that no `device.put`, `device.get` or `jax.compile` span covers."""
from boundary import (BOUNDARY, DEVICE_LAYERS, TRANSFERS, complete,
                      uncovered_us)


def read(ctx: dict):
    if not complete(ctx, TRANSFERS):
        return None         # a program that records no boundary spans
    glue = uncovered_us(complete(ctx, DEVICE_LAYERS), complete(ctx, BOUNDARY))
    return glue / 1e3 / ctx["plans"]
