"""Share of the traced stretch's device time spent under a named scope.

The program wraps its layers in ``jax.named_scope``s (``attn.sliding``,
``moe.route``, ...).  A scope survives into the compiled program as an
instruction's ``op_name``, but a device event on the ``XLA Ops`` line
carries the instruction's NAME only, so the program keeps the table between
the two: ``distkeras_tpu.observability.device_scopes(<window program>)`` ->
{instruction name: op_name}, from the compiled text of the very program the
trace shows.  An event counts where its instruction's ``op_name`` holds one
of ``scopes`` (a Mosaic call sits under the scope its ``pallas_call`` was
traced in), or where its own name starts with one of ``names`` (an
instruction XLA made itself, such as the grouped matmul ``ragged-dot``,
carries no scope of the program's).  Over: every device operation in the
stretch, containers left out (their bodies are counted).  A program without
the table (the parent of the PR that added it) reads nothing."""

from benchmark.harness import trace


def read(ctx, scopes, names=()):
    if not ctx.get("trace"):
        return None
    from distkeras_tpu import observability as obs

    lookup = getattr(obs, "device_scopes", None)
    table = lookup(ctx["traffic"]["window_program"]) if lookup else None
    if not table:
        return None
    t = ctx["trace"]
    scopes, names = tuple(scopes), tuple(names)
    under = total = 0.0
    for plane, line, text, start, dur in t["rows"]:
        if line != "XLA Ops" or not plane.startswith("/device:TPU:") \
                or text.startswith(trace.CONTAINERS):
            continue
        d = min(start + dur, t["hi"]) - max(start, t["lo"])
        if d <= 0:
            continue
        total += d
        name = text.split(" = ", 1)[0].lstrip("%")
        op_name = table.get(name, "")
        if (names and name.startswith(names)) or any(s in op_name for s in scopes):
            under += d
    return 100.0 * under / total if total else None
