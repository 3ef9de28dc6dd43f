"""place_ms: Algorithm 2's placement of clusters onto cores on the host, in ms per plan (obs span `map.place`)."""
from spans import ms_per_plan


def read(ctx: dict):
    return ms_per_plan(ctx, "map.place")
