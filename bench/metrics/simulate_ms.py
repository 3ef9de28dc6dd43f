"""simulate_ms: the simulator's keyed sums on the device and its host glue, in ms per plan (obs span `sim.run`)."""
from spans import ms_per_plan


def read(ctx: dict):
    return ms_per_plan(ctx, "sim.run")
