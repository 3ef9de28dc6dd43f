"""compile_ms: backend compiles inside the window, in ms per plan (obs span `jax.compile`); 0.0 when there was none, as in a warm window."""
from boundary import complete, per_plan_ms


def read(ctx: dict):
    return per_plan_ms(ctx, complete(ctx, ("jax.compile",)))
