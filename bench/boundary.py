"""Spans of the program's host-device boundary, for the metrics that read
them: `device.put` and `device.get` (`bytes` crossing), `jax.compile`,
and the device layers they nest in."""
from __future__ import annotations

DEVICE_LAYERS = ("cut.finalize", "map.cluster_graphs", "sim.run")
TRANSFERS = ("device.put", "device.get")
BOUNDARY = TRANSFERS + ("jax.compile",)


def complete(ctx: dict, names, **args) -> list:
    """The window's complete obs spans named in `names` whose args hold
    each of `args`."""
    return [e for e in ctx["spans"]
            if e.get("ph") == "X" and e["name"] in names
            and all(e.get("args", {}).get(k) == v for k, v in args.items())]


def per_plan_ms(ctx: dict, events: list) -> float:
    return sum(e["dur"] for e in events) / 1e3 / ctx["plans"]


def uncovered_us(outer: list, inner: list) -> float:
    """Microseconds of the `outer` spans that no `inner` span covers."""
    cover: list = []
    for s, e in sorted((i["ts"], i["ts"] + i["dur"]) for i in inner):
        if cover and s <= cover[-1][1]:
            cover[-1][1] = max(cover[-1][1], e)
        else:
            cover.append([s, e])
    total = 0.0
    for o in outer:
        a, b = o["ts"], o["ts"] + o["dur"]
        total += b - a - sum(max(0.0, min(b, e) - max(a, s))
                             for s, e in cover)
    return total
