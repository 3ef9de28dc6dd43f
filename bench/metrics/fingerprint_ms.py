"""fingerprint_ms: content and knob fingerprint of a request, in ms per plan (obs span `serve.fingerprint`)."""
from spans import ms_per_plan


def read(ctx: dict):
    return ms_per_plan(ctx, "serve.fingerprint")
