"""parse_ms: parsing the NDJSON trace into a graph, in ms per plan (obs span `trace.ingest`)."""
from spans import ms_per_plan


def read(ctx: dict):
    return ms_per_plan(ctx, "trace.ingest")
