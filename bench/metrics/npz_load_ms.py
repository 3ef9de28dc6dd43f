"""npz_load_ms: loading the graph from its `.npz` snapshot, in ms per plan (obs span `trace.ingest` with `engine` "npz")."""
from boundary import complete, per_plan_ms


def read(ctx: dict):
    events = complete(ctx, ("trace.ingest",), engine="npz")
    return per_plan_ms(ctx, events) if events else None
