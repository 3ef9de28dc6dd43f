"""cache_store_ms: persisting the plan bundle to the plan cache, in ms per plan (obs span `serve.cache_store`)."""
from spans import ms_per_plan


def read(ctx: dict):
    return ms_per_plan(ctx, "serve.cache_store")
