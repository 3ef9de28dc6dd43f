"""cut_stream_ms: the WB-Libra greedy edge stream on the host, in ms per plan (obs span `cut.stream`)."""
from spans import ms_per_plan


def read(ctx: dict):
    return ms_per_plan(ctx, "cut.stream")
