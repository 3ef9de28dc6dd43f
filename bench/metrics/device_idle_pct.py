"""device_idle_pct: the share of the traced window in which no operation
ran on the device, in %."""


def read(ctx: dict):
    profile = ctx["profile"]
    if profile["busy_s"] <= 0 or profile["window_s"] <= 0:
        return None
    return 100.0 * (1.0 - profile["busy_s"] / profile["window_s"])
