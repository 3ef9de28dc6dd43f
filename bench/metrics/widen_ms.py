"""widen_ms: splitting integer values past 2^31 into int32 limbs on the host and copying them to the device, in ms per plan (obs span `segsum.widen`)."""
from spans import ms_per_plan


def read(ctx: dict):
    return ms_per_plan(ctx, "segsum.widen")
