"""Per-plan time of one obs span over the measured window."""
from __future__ import annotations


def ms_per_plan(ctx: dict, name: str) -> "float | None":
    """Milliseconds per plan spent in spans named `name`, or None when the
    window recorded no such span."""
    durs = [e["dur"] for e in ctx["spans"]
            if e.get("ph") == "X" and e["name"] == name]
    if not durs:
        return None
    return sum(durs) / 1e3 / ctx["plans"]
