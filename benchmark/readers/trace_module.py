"""Device time of the window program's XLA module, per training step, and
the whole step's share of the chip's peak (``mfu``): the FLOPs a step
requires, by the benchmark's own count, over that time over the peak."""

from benchmark.harness import trace


def read(ctx, stat="step_ms"):
    if not ctx.get("trace"):
        return None
    runs = trace.module_runs(ctx["trace"]["rows"], ctx["traffic"]["window_program"])
    if not runs:
        return None
    step_s = sum(r[2] for r in runs) / 1e9 / (len(runs) * ctx["steps_per_program"])
    if stat == "step_ms":
        return 1e3 * step_s
    if stat == "mfu":
        return 100.0 * ctx["flops_per_step"] / step_s / ctx["peaks"]["bf16_flops"]
    raise ValueError(f"unknown stat {stat!r}")
