"""device_busy_ms: the union of the device's op intervals over the
window, in ms per plan."""


def read(ctx: dict):
    busy = ctx["profile"]["busy_s"]
    if busy <= 0:
        return None
    return busy * 1e3 / ctx["plans"]
