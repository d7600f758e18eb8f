"""Reduction of a profiler trace to device metrics.

``load_events`` turns a ``*.xplane.pb`` into plain rows; everything else
works on rows, so the reduction is checked on a small recorded trace
(``tests/benchmark/data/trace_small.json``).  A row is ``[plane, line,
name, start_ns, duration_ns]``.

What the TPU's planes hold (read off a v5e trace, PR 24): plane
``/device:TPU:<n>`` with the lines ``XLA Modules`` (one event per run of a
jitted program, named ``jit_<fn>(<id>)``), ``XLA Ops`` (one event per HLO
instruction run, named by the instruction's text; a ``%while`` contains its
body's events) and ``Async XLA Ops`` (DMA in flight); plane ``/host:CPU``
with one line per host thread of runtime and JAX TraceMe events.  A Pallas
kernel is an ``XLA Ops`` event whose text has
``custom_call_target="tpu_custom_call"``; the instruction is named after
the ``pallas_call``'s ``name=`` (``%_fwd_kernel.<n> = ...``, PR 25), so
kernels are told apart by that name.
"""

from __future__ import annotations

import glob
import os
import re
from typing import Any, Dict, Iterable, List, Optional, Sequence, Tuple

Row = Sequence[Any]
CONTAINERS = ("%while", "%conditional", "%call")
MOSAIC = 'custom_call_target="tpu_custom_call"'


def load_events(trace_dir: str) -> List[Row]:
    """Rows of the device planes' module and op lines and of every host
    line, from the newest ``*.xplane.pb`` under ``trace_dir``."""
    import jax

    files = sorted(glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                             recursive=True), key=os.path.getmtime)
    if not files:
        return []
    data = jax.profiler.ProfileData.from_file(files[-1])
    rows: List[Row] = []
    for plane in data.planes:
        device = plane.name.startswith("/device:TPU:")
        if not device and not plane.name.startswith("/host:"):
            continue
        for line in plane.lines:
            if device and line.name not in ("XLA Modules", "XLA Ops"):
                continue
            for ev in line.events:
                rows.append((plane.name, line.name, ev.name,
                             float(ev.start_ns), float(ev.duration_ns)))
    return rows


def device_planes(rows: Iterable[Row]) -> List[str]:
    return sorted({r[0] for r in rows if r[0].startswith("/device:TPU:")})


def union(intervals: Iterable[Tuple[float, float]]) -> List[Tuple[float, float]]:
    """Merged, sorted ``(start, end)`` intervals."""
    out: List[Tuple[float, float]] = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            if e > out[-1][1]:
                out[-1] = (out[-1][0], e)
        else:
            out.append((s, e))
    return out


def clip(intervals, lo: float, hi: float):
    return [(max(s, lo), min(e, hi)) for s, e in intervals
            if min(e, hi) > max(s, lo)]


def busy_intervals(rows: Iterable[Row], plane: str):
    return union((r[3], r[3] + r[4]) for r in rows
                 if r[0] == plane and r[1] == "XLA Ops")


def busy_and_window(rows: Sequence[Row], lo: Optional[float] = None,
                    hi: Optional[float] = None) -> Dict[str, Any]:
    """Seconds in which an operation ran on the device, averaged over the
    device planes, the length of the window and the fullest-idle plane's
    idle share.  The window is ``[lo, hi]`` where given, else from the
    first device operation to the last."""
    planes = device_planes(rows)
    per = {p: busy_intervals(rows, p) for p in planes}
    every = [iv for p in planes for iv in per[p]]
    if not every:
        return {"busy_s": 0.0, "window_s": 0.0, "idle_share": None, "planes": 0}
    lo = min(s for s, _ in every) if lo is None else lo
    hi = max(e for _, e in every) if hi is None else hi
    busy = {p: sum(e - s for s, e in clip(per[p], lo, hi)) for p in planes}
    window = hi - lo
    return {"busy_s": sum(busy.values()) / len(planes) / 1e9,
            "window_s": window / 1e9,
            "idle_share": 100.0 * (1.0 - min(busy.values()) / window),
            "planes": len(planes), "lo": lo, "hi": hi}


def module_runs(rows: Iterable[Row], prefix: str) -> List[Tuple[str, float, float]]:
    """``(plane, start_ns, duration_ns)`` of every run of the jitted
    program whose module name starts with ``prefix``."""
    return [(r[0], r[3], r[4]) for r in rows
            if r[1] == "XLA Modules" and r[2].startswith(prefix)]


def mosaic_calls(rows: Iterable[Row]) -> List[Tuple[str, float]]:
    """``(HLO name, duration_ns)`` of every Pallas kernel run; the name is
    the instruction's, without its ``%``: ``_fwd_kernel.7``."""
    return [(r[2].split(" = ", 1)[0].lstrip("%"), r[4])
            for r in rows if r[1] == "XLA Ops" and MOSAIC in r[2]]


def op_label(text: str) -> str:
    """A short stable label for an HLO instruction's text."""
    head = text.split(" = ", 1)[0].lstrip("%")
    base = re.sub(r"[.\d]+$", "", head)
    base = re.sub(r"_\d+$", "", base)
    return ("mosaic:" + base) if MOSAIC in text else base


def top_device_ops(rows: Iterable[Row], n: int = 10) -> List[List[Any]]:
    """The device operations that took most time (containers such as
    ``while`` left out: their bodies are counted), seconds summed over the
    device planes."""
    total: Dict[str, float] = {}
    for r in rows:
        if r[1] == "XLA Ops" and r[0].startswith("/device:TPU:") \
                and not r[2].startswith(CONTAINERS):
            label = op_label(r[2])
            total[label] = total.get(label, 0.0) + r[4] / 1e9
    return [[k, v] for k, v in sorted(total.items(), key=lambda kv: -kv[1])[:n]]


def idle_gaps(rows: Sequence[Row], lo: float, hi: float, n: int = 10,
              min_gap_ns: float = 1e5) -> List[List[Any]]:
    """The longest idle gaps by what the host was doing: each gap of a
    device plane inside ``[lo, hi]`` is named ``chip<k>:<before>-><after>:
    <host event>`` — the programs that ran before and after it (``window
    _start`` / ``window_end`` at the edges) and the host event that overlaps
    it longest; seconds are summed per name."""
    host = [(r[3], r[3] + r[4], r[2]) for r in rows
            if r[0].startswith("/host:") and r[4] >= min_gap_ns / 10
            and not r[2].startswith("$")]
    total: Dict[str, float] = {}
    for k, plane in enumerate(device_planes(rows)):
        mods = sorted((r[3], r[3] + r[4], r[2].split("(")[0]) for r in rows
                      if r[0] == plane and r[1] == "XLA Modules")
        busy = clip(busy_intervals(rows, plane), lo, hi)
        edges = [lo] + [t for iv in busy for t in iv] + [hi]
        for g0, g1 in zip(edges[0::2], edges[1::2]):
            if g1 - g0 < min_gap_ns:
                continue
            before = [m[2] for m in mods if m[0] <= g0]
            after = [m[2] for m in mods if m[0] >= g0 and m[1] >= g1]
            best, overlap = "no_host_event", 0.0
            for s, e, name in host:
                o = min(e, g1) - max(s, g0)
                if o > overlap:
                    best, overlap = name, o
            name = "chip%d:%s->%s:%s" % (
                k, before[-1] if before else "window_start",
                after[0] if after else "window_end", best)
            name = re.sub(r"[^A-Za-z0-9_.:>=-]", "_", name)[:120]
            total[name] = total.get(name, 0.0) + (g1 - g0) / 1e9
    return [[k, v] for k, v in sorted(total.items(), key=lambda kv: -kv[1])[:n]]


def annotation(rows: Iterable[Row], name: str) -> Optional[Tuple[float, float]]:
    """``(start_ns, end_ns)`` of the first host event called ``name``."""
    hits = sorted((r[3], r[3] + r[4]) for r in rows
                  if r[0].startswith("/host:") and r[2] == name)
    return hits[0] if hits else None
