"""Share of the device's idle time that the program's own phases account
for: over the traced stretch ``[lo, hi]`` of the window, the part of the
idle intervals of the chip that idles most which the union of the host
events named like a program phase (``prefixes``) covers.  100% means every
idle microsecond has a phase of the worker, the hub or the engine beside
it; what is left is time the program does not name.  No phase event at all
(a program from before the phases) reads nothing, as does a device that
was never idle."""

from benchmark.harness import trace


def overlap(a, b):
    """Total length of the intersection of two sorted, merged interval lists."""
    total, i, j = 0.0, 0, 0
    while i < len(a) and j < len(b):
        lo, hi = max(a[i][0], b[j][0]), min(a[i][1], b[j][1])
        if hi > lo:
            total += hi - lo
        if a[i][1] < b[j][1]:
            i += 1
        else:
            j += 1
    return total


def read(ctx, prefixes):
    if not ctx.get("trace"):
        return None
    t = ctx["trace"]
    rows, lo, hi = t["rows"], t["lo"], t["hi"]
    prefixes = tuple(prefixes)
    phases = trace.clip(trace.union(
        (r[3], r[3] + r[4]) for r in rows
        if r[0].startswith("/host:") and r[2].startswith(prefixes)), lo, hi)
    if not phases:
        return None
    worst = None
    for plane in trace.device_planes(rows):
        busy = trace.clip(trace.busy_intervals(rows, plane), lo, hi)
        edges = [lo] + [x for iv in busy for x in iv] + [hi]
        idle = [(a, b) for a, b in zip(edges[0::2], edges[1::2]) if b > a]
        length = sum(b - a for a, b in idle)
        if length and (worst is None or length > worst[0]):
            worst = (length, idle)
    if worst is None:
        return None
    return 100.0 * overlap(worst[1], phases) / worst[0]
