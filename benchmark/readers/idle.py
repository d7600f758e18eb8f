"""The device's idle share over the traced stretch of the window, on the
chip that idles most: 1 - union of device-op intervals over the span."""

from benchmark.harness import trace


def read(ctx):
    if not ctx.get("trace"):
        return None
    t = ctx["trace"]
    return trace.busy_and_window(t["rows"], t["lo"], t["hi"])["idle_share"]
