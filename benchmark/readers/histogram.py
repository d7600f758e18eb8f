"""A histogram of the program's telemetry registry, over the timed call
only (the harness hands in after-minus-before sums and counts, every label
set of one name added up).  ``stat``: ``mean`` of the observations, or
``one_minus_ratio`` = 100 * (1 - sum(name) / sum(over))."""


def read(ctx, name, stat="mean", over=None, scale=1.0):
    h = ctx["histograms"].get(name)
    if not h or not h["count"]:
        return None
    if stat == "mean":
        return scale * h["sum"] / h["count"]
    if stat == "one_minus_ratio":
        o = ctx["histograms"].get(over)
        if not o or not o["sum"]:
            return None
        return 100.0 * (1.0 - h["sum"] / o["sum"])
    raise ValueError(f"unknown stat {stat!r}")
