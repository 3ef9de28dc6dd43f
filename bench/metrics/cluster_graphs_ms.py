"""cluster_graphs_ms: the p-keyed and p^2-keyed interaction sums on the device, in ms per plan (obs span `map.cluster_graphs`)."""
from spans import ms_per_plan


def read(ctx: dict):
    return ms_per_plan(ctx, "map.cluster_graphs")
