"""Reduce a JAX profiler trace (`.xplane.pb`) of the measured window to
device busy time, per-op device time and idle gaps by host activity.

The harness wraps each request in a `jax.profiler.TraceAnnotation`
named `ANNOTATION`.  Those host events fix the traced window (first
request start to last request end) and tie the profiler's clock to the
host's `perf_counter`, on which the program's obs spans are stamped.

* busy: the union of the intervals of the device's op events
  (line `OP_LINE` of each `/device:TPU:<k>` plane) inside the window,
  averaged over the chips traced;
* ops: device seconds per op, named `<module>/<instruction>` from the
  profiler's `XLA Modules` and `XLA Ops` events (e.g.
  `jit__reduce/%segment_sum.1`);
* gaps: the stretches of the window in which no op ran, each split at
  the edges of the obs spans inside it and its pieces added to the
  innermost span open over them ("no span" where none was).
"""
from __future__ import annotations

import bisect
import re
import statistics

ANNOTATION = "bench.request"
OP_LINE = "XLA Ops"
MODULE_LINE = "XLA Modules"
_DEVICE_PLANE = re.compile(r"^/device:TPU:\d+$")
_MODULE_ID = re.compile(r"\(\d+\)$")


def load(path: str) -> list:
    """Planes of an `.xplane.pb` as plain data:
    [(plane name, [(line name, [(event name, start_ns, duration_ns)])])]."""
    from jax.profiler import ProfileData
    space = ProfileData.from_file(path)
    return [(plane.name,
             [(line.name, [(e.name, e.start_ns, e.duration_ns)
                           for e in line.events])
              for line in plane.lines])
            for plane in space.planes]


def _union(intervals: list) -> list:
    """Sorted, disjoint cover of (start, end) intervals."""
    out: list = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            if e > out[-1][1]:
                out[-1][1] = e
        else:
            out.append([s, e])
    return out


def _innermost(spans: list, t: float) -> str:
    """Name of the shortest (start, end, name) span holding time `t`."""
    best, best_len = "no span", None
    for s, e, name in spans:
        if s <= t <= e and (best_len is None or e - s < best_len):
            best, best_len = name, e - s
    return best


def _attribute(gaps: dict, spans: list, a: float, b: float) -> None:
    """Split the idle stretch [a, b) at every span edge inside it and add
    each piece to the innermost span open over it."""
    cuts = sorted({a, b} | {x for s, e, _ in spans for x in (s, e)
                            if a < x < b})
    for x, y in zip(cuts, cuts[1:]):
        label = _innermost(spans, (x + y) / 2)
        gaps[label] = gaps.get(label, 0.0) + (y - x) / 1e9


def reduce(planes: list, requests: list, spans: list = ()) -> dict:
    """Busy, per-op and idle-gap seconds of the traced window.

    `requests` are the host (start, end) `perf_counter` seconds of the
    annotated requests, in order; `spans` are obs events (`name`, `ts`
    and `dur` in perf_counter microseconds), of which only complete
    spans (`ph` "X") count; instant events have no `dur`.  Returns a dict with
    `window_s`, `busy_s`, `chips`, `ops` {name: s}, `gaps`
    {label: s} (seconds averaged over the chips) and `gap_count`.
    """
    marks = sorted((s, s + d) for _, lines in planes
                   for _, events in lines
                   for name, s, d in events if name == ANNOTATION)
    k = min(len(marks), len(requests))
    if k == 0:
        raise ValueError(f"no {ANNOTATION!r} annotations in the trace")
    offset_ns = statistics.median(marks[i][0] - requests[i][0] * 1e9
                                  for i in range(k))
    lo, hi = marks[0][0], marks[k - 1][1]
    host = [(sp["ts"] * 1e3 + offset_ns, (sp["ts"] + sp["dur"]) * 1e3
             + offset_ns, sp["name"]) for sp in spans if sp.get("ph") == "X"]
    host = [h for h in host if h[1] > lo and h[0] < hi]

    busy, ops, gaps, gap_count, chips = 0.0, {}, {}, 0, 0
    for plane, lines in planes:
        if not _DEVICE_PLANE.match(plane):
            continue
        chips += 1
        modules = sorted((s, s + d, _MODULE_ID.sub("", name))
                         for line, events in lines if line == MODULE_LINE
                         for name, s, d in events)
        starts = [m[0] for m in modules]
        intervals = []
        for line, events in lines:
            if line != OP_LINE:
                continue
            for name, s, d in events:
                s0, e0 = max(s, lo), min(s + d, hi)
                if e0 > s0:
                    intervals.append((s0, e0))
                    op = name.split(" = ", 1)[0]
                    i = bisect.bisect_right(starts, s) - 1
                    if i >= 0 and s < modules[i][1]:
                        op = f"{modules[i][2]}/{op}"
                    ops[op] = ops.get(op, 0.0) + (e0 - s0) / 1e9
        cover = _union(intervals)
        busy += sum(e - s for s, e in cover) / 1e9
        edges = [lo] + [x for iv in cover for x in iv] + [hi]
        for a, b in zip(edges[0::2], edges[1::2]):
            if b > a:
                _attribute(gaps, host, a, b)
                gap_count += 1
    per_chip = 1.0 / max(chips, 1)
    return {"window_s": (hi - lo) / 1e9, "busy_s": busy * per_chip,
            "chips": chips,
            "ops": {n: s * per_chip for n, s in ops.items()},
            "gaps": {n: s * per_chip for n, s in gaps.items()},
            "gap_count": gap_count}


def top(d: dict, k: int = 10) -> list:
    """The `k` largest entries of {name: seconds} as [[name, s], ...]."""
    return [[n, s] for n, s in sorted(d.items(), key=lambda kv: -kv[1])[:k]]
