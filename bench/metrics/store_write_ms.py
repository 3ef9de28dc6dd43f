"""store_write_ms: compressing and writing the plan bundle's arrays, in ms per plan (obs span `store.write`)."""
from spans import ms_per_plan


def read(ctx: dict):
    return ms_per_plan(ctx, "store.write")
