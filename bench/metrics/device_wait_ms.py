"""device_wait_ms: the host blocked on device results, in ms per plan (obs span `device.get`)."""
from spans import ms_per_plan


def read(ctx: dict):
    return ms_per_plan(ctx, "device.get")
