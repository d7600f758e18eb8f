"""A kernel's share of its roofline: the least time the chip could take for
the calls the trace holds over the device time they took.  The calls are
the Mosaic custom calls whose HLO name is ``%<kernel>`` or ``%<kernel>.<n>``
(``harness.trace.mosaic_calls``); the required work of one mean call is the
family's to count (``families/<family>.py::kernel_work``, from the shapes
alone), times the calls read.  The trace is cut at the cell's
``trace.max_seconds``, so at most one step's calls are partial: the step
that was running when the profiler stopped.  Nothing to read -> nothing."""

from benchmark.harness import peaks, trace


def read(ctx, kernel):
    if not ctx.get("trace"):
        return None
    durs = [d for name, d in trace.mosaic_calls(ctx["trace"]["rows"])
            if name == kernel or name.startswith(kernel + ".")]
    if not durs:
        return None
    work = ctx["family"].kernel_work(ctx["cfg"], kernel, ctx["batch"], ctx["seq_len"])
    r = peaks.roofline_share(work["flops"] * len(durs), work["bytes"] * len(durs),
                             sum(durs) / 1e9, ctx["peaks"])
    ctx["notes"].append(f"{kernel}: {len(durs)} calls, {r['bound']}-bound")
    return r["share"]
