"""A flash-attention kernel's share of its roofline: the least time the
chip could take for the calls the trace holds (FLOPs and bytes from the
shapes, by ``harness.peaks.flash_counts``) over the device time they took.
Kernels are told apart by operand count (forward: q, k, v; fused backward:
q, k, v, o, log-sum-exp, do and more).  Nothing to read -> nothing."""

from benchmark.harness import peaks, trace


def read(ctx, direction, operands_min, operands_max):
    if not ctx.get("trace"):
        return None
    durs = [d for n, d in trace.mosaic_calls(ctx["trace"]["rows"])
            if operands_min <= n <= operands_max]
    if not durs:
        return None
    cfg, c = ctx["cfg"], ctx["traffic"]["constructor"]
    counts = peaks.flash_counts(direction, int(c["batch_size"]), int(cfg["n_head"]),
                                int(cfg["n_positions"]),
                                int(cfg["n_embd"]) // int(cfg["n_head"]))
    r = peaks.roofline_share(counts["flops"] * len(durs), counts["bytes"] * len(durs),
                             sum(durs) / 1e9, ctx["peaks"])
    ctx["notes"].append(f"flash_{direction}: {len(durs)} calls, {r['bound']}-bound")
    return r["share"]
