"""A counter of the program's telemetry registry, every label set of the
name added up, over another counter.  Telemetry is switched on just before
the timed call and counters count only while it is on, so both read the
timed call alone.  A zero or missing denominator reads nothing."""


def total(counters, name):
    return sum(v for k, v in counters.items() if k.split("{", 1)[0] == name)


def read(ctx, name, over):
    counters = ctx.get("counters")
    if counters is None:
        from distkeras_tpu import observability as obs

        counters = obs.snapshot().get("counters", {})
    denominator = total(counters, over)
    if not denominator:
        return None
    return total(counters, name) / denominator
