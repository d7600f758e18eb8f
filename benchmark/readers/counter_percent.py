"""One counter of the program's telemetry registry over another, in %
(every label set of a name added up; both count only while telemetry is on:
the timed call).  A zero or missing denominator reads nothing."""


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
    return 100.0 * total(counters, name) / denominator
