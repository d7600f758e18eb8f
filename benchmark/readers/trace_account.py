"""Share of the traced stretch's device time in some parts of the step and
some passes, by the program's own account of its instructions.

``distkeras_tpu.observability.device_account(<window program>)`` sorts every
instruction of the compiled program the trace shows by the PART of the step
it belongs to (``attn.*``, ``moe.*``, ``ffn.dense``, ``lm.embed``,
``lm.head``, ``step.loss``, ``step.update``, ``step.commit``, ``block.other``,
``none``) and by the PASS it runs in (``forward``, ``recompute``,
``backward``, ``other``); the rules are the program's
(``observability/account.py``).  An ``XLA Ops`` event counts where its
instruction's part is in ``parts`` (any, where not given) and its pass in
``passes`` (any, where not given); an instruction the table lacks is ``none``
/ ``other``.

Over: all LEAF device time in the stretch.  A container is left out by its
OPCODE (``while(``, ``conditional(``, ``call(`` after the `` = ``, or what the
program's text says of the instruction where the event's text is cut short
of it), not by the names in ``trace.CONTAINERS``: a ``%cond.<n>`` is no leaf
here.  ``trace_scope.py``, ``trace_kernel.py`` and ``breakdown.device_ops``
keep their base, which counts such a conditional beside its body, so a share
of this reader and a share of theirs do not add up in a cell with a
``lax.cond``.  A program without ``device_account`` (the parent of the PR
that added it) reads nothing.

Once a run the whole table goes to stderr (``ctx["notes"]``): rows = parts,
columns = passes, cells = ms a step (device time over the steps in the
stretch: the window program's module time there over the time of a step, as
``trace_module.py`` counts it), the two flash kernels' rows, the time in
fusions that hold more than one part or pass, the time of instructions the
table lacks, and the largest unscoped operations."""

import re
import time

from benchmark.harness import trace

PASSES = ("forward", "recompute", "backward", "other")
UNSCOPED = ("none", "block.other")
KERNELS = ("_fwd_kernel", "_bwd_fused_kernel")
_OPCODE = re.compile(r"(?:^|[\s)}\]])([a-z][\w\-]*)\(")
_CONTAINERS = ("while", "conditional", "call")


def is_container(name, rest, account) -> bool:
    m = _OPCODE.search(rest)
    return m.group(1) in _CONTAINERS if m else name in account.containers


def steps_in_stretch(ctx) -> float:
    """Training steps the stretch holds: the window program's module time
    inside it over one step's time (whole runs only, as ``trace_module``)."""
    t = ctx["trace"]
    runs = trace.module_runs(t["rows"], ctx["traffic"]["window_program"])
    whole = runs
    if t.get("cut"):
        last = {plane: max(r[1] for r in runs if r[0] == plane) for plane, _, _ in runs}
        whole = [r for r in runs if r[1] < last[r[0]]]
    if not whole:
        return 0.0
    step_ns = sum(r[2] for r in whole) / (len(whole) * ctx["steps_per_program"])
    inside = sum(max(0.0, min(s + d, t["hi"]) - max(s, t["lo"])) for _, s, d in runs)
    return inside / step_ns


def sums(ctx, account) -> dict:
    """Leaf device time of the stretch, ns: ``cells`` {(part, pass)},
    ``mixed_parts`` / ``mixed_passes`` (fusions of more than one, by what
    decided their account), ``missing`` (instructions the table lacks),
    ``kernels`` {(kernel, pass)} and ``unscoped`` {op label}."""
    t = ctx["trace"]
    out = {"cells": {}, "kernels": {}, "unscoped": {}, "total": 0.0, "missing": 0.0,
           "mixed_parts": {"matmul": 0.0, "root": 0.0}, "mixed_passes": {"matmul": 0.0, "root": 0.0}}
    for plane, line, text, start, dur in t["rows"]:
        if line != "XLA Ops" or not plane.startswith("/device:TPU:"):
            continue
        d = min(start + dur, t["hi"]) - max(start, t["lo"])
        if d <= 0:
            continue
        head, _, rest = text.partition(" = ")
        name = head.lstrip("%")
        if is_container(name, rest, account):
            continue
        out["total"] += d
        cell = account.table.get(name)
        if cell is None:
            cell = ("none", "other")
            out["missing"] += d
        out["cells"][cell] = out["cells"].get(cell, 0.0) + d
        parts, passes, as_matmul = account.mixed.get(name, ((), (), False))
        by = "matmul" if as_matmul else "root"
        out["mixed_parts"][by] += d if len(parts) > 1 else 0.0
        out["mixed_passes"][by] += d if len(passes) > 1 else 0.0
        if name.startswith(KERNELS):
            key = (trace.op_label(text), cell[1])
            out["kernels"][key] = out["kernels"].get(key, 0.0) + d
        if cell[0] in UNSCOPED:
            label = trace.op_label(text)
            out["unscoped"][label] = out["unscoped"].get(label, 0.0) + d
    return out


def table_note(s: dict, steps: float) -> str:
    ms = lambda ns: ns / 1e6 / steps if steps else float("nan")
    share = lambda ns: 100.0 * ns / s["total"]
    parts = sorted({p for p, _ in s["cells"]})
    lines = ["device account, ms a step over %.2f steps (part x pass):" % steps,
             "  %-20s" % "part" + "".join("%11s" % c for c in PASSES + ("all", "%"))]
    for part in parts + ["all"]:
        row = [sum(v for (p, q), v in s["cells"].items()
                   if q == c and part in (p, "all")) for c in PASSES]
        lines.append("  %-20s" % part + "".join("%11.3f" % ms(v) for v in row + [sum(row)])
                     + "%11.2f" % share(sum(row)))
    for (kernel, pas), v in sorted(s["kernels"].items()):
        lines.append("  kernel %s in %s: %.3f ms a step" % (kernel, pas, ms(v)))
    for what, key in (("part", "mixed_parts"), ("pass", "mixed_passes")):
        m = s[key]
        lines.append("  in fusions of more than one %s %.2f%% (%.2f%% counted as their matmul, "
                     "%.2f%% as their root)" % (what, share(m["matmul"] + m["root"]),
                                                share(m["matmul"]), share(m["root"])))
    lines.append("  instructions the table lacks %.2f%%" % share(s["missing"]))
    top = sorted(s["unscoped"].items(), key=lambda kv: -kv[1])[:8]
    lines.append("  largest unscoped (none, block.other): "
                 + ", ".join("%s %.3f" % (k, ms(v)) for k, v in top))
    return "\n".join(lines)


def read(ctx, parts=None, passes=None):
    if not ctx.get("trace"):
        return None
    from distkeras_tpu import observability as obs

    lookup = getattr(obs, "device_account", None)
    t0 = time.perf_counter()
    account = lookup(ctx["traffic"]["window_program"]) if lookup else None
    if not account:
        return None
    s = ctx.get("trace_account")
    if s is None:
        # the first asking: the program compiles again (from the cache) and
        # its text is read
        ctx["notes"].append("device_account: %.2f s after the timed call, %d instructions of "
                            "%d bytes of compiled text" % (time.perf_counter() - t0,
                                                           len(account.table), account.text_bytes))
        s = ctx["trace_account"] = sums(ctx, account)
        if s["total"]:
            ctx["notes"].append(table_note(s, steps_in_stretch(ctx)))
    if not s["total"]:
        return None
    under = sum(v for (part, pas), v in s["cells"].items()
                if (parts is None or part in parts) and (passes is None or pas in passes))
    return 100.0 * under / s["total"]
