"""wide_segsum_kernel_ms: device time of the wide (int32-limb) Pallas
segment-sum kernel, in ms per plan: the profiler's op events whose name
holds `wide_segsum`."""


def read(ctx: dict):
    ops = ctx["profile"]["ops"]
    seconds = sum(s for name, s in ops.items() if "wide_segsum" in name)
    if seconds <= 0:
        return None
    return seconds * 1e3 / ctx["plans"]
