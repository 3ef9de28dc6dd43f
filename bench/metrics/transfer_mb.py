"""transfer_mb: bytes copied between host and device, padding included, in MB (10^6 bytes) per plan (arg `bytes` of obs spans `device.put` and `device.get`)."""
from boundary import TRANSFERS, complete


def read(ctx: dict):
    events = complete(ctx, TRANSFERS)
    if not events:
        return None
    return sum(e["args"]["bytes"] for e in events) / 1e6 / ctx["plans"]
