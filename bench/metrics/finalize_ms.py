"""finalize_ms: replica CSR, loads and edge counts on the device, in ms per plan (obs span `cut.finalize`)."""
from spans import ms_per_plan


def read(ctx: dict):
    return ms_per_plan(ctx, "cut.finalize")
