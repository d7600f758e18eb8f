"""Mean duration, in ms, of a program phase on the profiler's host planes.

The program's leaf phases (``distkeras_tpu.observability.phase``) are
TraceMe events on the ``/host:`` lines of the same ``xplane.pb`` that holds
the device's lines.  ``names`` is one event name, or a list: the durations
of every listed name are added up and divided by the occurrences of the
FIRST (``engine.dispatch`` happens once a chunk, ``engine.place`` twice).
Events are clipped to the traced stretch ``[lo, hi]`` of the window; an
event outside it does not count.  A program without the phase (the parent
of the PR that added it) has no such event: nothing is read."""


def clipped(rows, names, lo, hi):
    """{name: [clipped duration_ns, ...]} of the host events in [lo, hi]."""
    out = {n: [] for n in names}
    for plane, _line, name, start, dur in rows:
        if name in out and plane.startswith("/host:"):
            d = min(start + dur, hi) - max(start, lo)
            if d > 0:
                out[name].append(d)
    return out


def read(ctx, names):
    if not ctx.get("trace"):
        return None
    names = [names] if isinstance(names, str) else list(names)
    t = ctx["trace"]
    durs = clipped(t["rows"], names, t["lo"], t["hi"])
    if not durs[names[0]]:
        return None
    return sum(sum(d) for d in durs.values()) / len(durs[names[0]]) / 1e6
