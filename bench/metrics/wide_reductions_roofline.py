"""wide_reductions_roofline: the least time the chip needs for the wide
keyed reductions of the window, over the wide kernel's device time (the
profiler's op events whose name holds `wide_segsum`), in %.

Each wide reduction is recorded by its `segsum.widen` span, whose args
are its logical sizes: `elements` (keys and values in) and `slots`
(sums out).  It reads every key and both int32 limbs of every value
once and writes both limbs of every slot once, in 4-byte words.  The
kernel does a few integer operations per value read, far below the
chip's operation peak, so the HBM bandwidth bounds it and the least time
is bytes / `hbm_bytes_per_s` of `peaks.json`.
"""
from __future__ import annotations

import json
import os

WORD = 4
PEAKS = os.path.join(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))), "peaks.json")


def reduction_bytes(elements: int, slots: int) -> int:
    """HBM bytes one wide keyed reduction must move at least."""
    return WORD * (3 * elements + 2 * slots)


def read(ctx: dict):
    spans = [e for e in ctx["spans"]
             if e.get("ph") == "X" and e["name"] == "segsum.widen"]
    seconds = sum(s for name, s in ctx["profile"]["ops"].items()
                  if "wide_segsum" in name)
    if not spans or seconds <= 0:
        return None
    with open(PEAKS) as f:
        peaks = json.load(f)
    kind = ctx["device_kind"]
    if kind not in peaks:
        raise KeyError(f"no peaks for device kind {kind!r} in {PEAKS}")
    moved = sum(reduction_bytes(e["args"]["elements"], e["args"]["slots"])
                for e in spans)
    return 100.0 * moved / peaks[kind]["hbm_bytes_per_s"] / seconds
