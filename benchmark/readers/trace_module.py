"""Device time of the window program's XLA module, per training step, and
the whole step's share of the chip's peak (``mfu``): the FLOPs a step
requires, by the family's own count, over that time over the peak.  Where
the profiler stopped before the window closed (``trace.cut``: the cell's
``trace.max_seconds`` ran out), the program that was on each device then is
in the trace with a duration cut short, and is left out: averaged in at its
full number of steps it read the step 3% short in the sync cells (PR 27)."""

from benchmark.harness import trace


def read(ctx, stat="step_ms"):
    if not ctx.get("trace"):
        return None
    runs = trace.module_runs(ctx["trace"]["rows"], ctx["traffic"]["window_program"])
    if ctx["trace"].get("cut"):
        last = {plane: max(r[1] for r in runs if r[0] == plane) for plane, _, _ in runs}
        runs = [r for r in runs if r[1] < last[r[0]]]
    if not runs:
        return None
    step_s = sum(r[2] for r in runs) / 1e9 / (len(runs) * ctx["steps_per_program"])
    if stat == "step_ms":
        return 1e3 * step_s
    if stat == "mfu":
        return 100.0 * ctx["flops_per_step"] / step_s / ctx["peaks"]["bf16_flops"]
    raise ValueError(f"unknown stat {stat!r}")
