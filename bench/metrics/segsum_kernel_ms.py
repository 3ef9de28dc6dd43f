"""segsum_kernel_ms: device time of the Pallas segment-sum kernel, in ms
per plan: the profiler's op events whose name holds `segment_sum`."""


def read(ctx: dict):
    ops = ctx["profile"]["ops"]
    seconds = sum(s for name, s in ops.items() if "segment_sum" in name)
    if seconds <= 0:
        return None
    return seconds * 1e3 / ctx["plans"]
