"""Span tracer: context-manager spans in a bounded ring buffer.

Spans are the wall-clock complement to the metrics registry: where a
histogram says "window wall time is bimodal", the trace says WHICH windows
were slow and what they overlapped with (the pull RPC? the H2D transfer?
another worker's commit?).  The async plane's wall-vs-device
decomposition of a window was first instrumented by hand exactly this
way; this module makes that measurement a permanent, exportable signal.

Two export forms:

- **Chrome ``trace_event`` JSON** (``chrome_trace`` / ``export_chrome``):
  complete ``"ph": "X"`` events with per-thread tracks — load the file at
  ``chrome://tracing`` / https://ui.perfetto.dev and the async workers,
  PS handler threads and prefetch producer appear as parallel lanes.
- **JSONL** (``jsonl`` / ``drain``): one JSON object per span, for the
  periodic flusher and ad-hoc grepping.

The buffer is a fixed-capacity ring (``collections.deque(maxlen=...)``):
a long run keeps the most recent spans and counts what it evicted
(``dropped``) instead of growing without bound.  Like the registry,
recording is near-zero when disabled — ``span()`` returns a shared no-op
context manager.

Two kinds of span.  ``span()`` is for ENCLOSING spans (``async.window``,
``ps.commit``, ``engine.run_epoch``): ring only.  ``phase()`` is for LEAF
phases — one thing the thread was doing (``async.commit_d2h``,
``ps.apply``): the same ring record, and a ``jax.profiler.TraceAnnotation``
of the same name entered and left with it, so that under a profiler
session the phase lies on its thread's ``/host:`` line of the
``xplane.pb`` beside the device's lines.  Only leaves go there: a reader
that names a device gap by the host event that overlaps it longest would
otherwise name every gap by the enclosing span.  A phase carries the
attributes of the spans that enclose it on its thread (``worker``,
``epoch``, ``window``: what caused it); every record names its ``parent``.
"""

from __future__ import annotations

import json
import os
import threading
import time
from collections import deque
from typing import Any, Dict, Iterator, List, Optional


def _json_safe(value: Any) -> Any:
    if isinstance(value, (str, int, float, bool)) or value is None:
        return value
    try:  # numpy / jax scalars quack like floats
        return float(value)
    except (TypeError, ValueError):
        return str(value)


class _NullSpan:
    """Shared disabled-mode span: enter/exit do nothing, no allocation."""

    __slots__ = ()

    def __enter__(self) -> "_NullSpan":
        return self

    def __exit__(self, *exc) -> None:
        return None


NULL_SPAN = _NULL_SPAN = _NullSpan()

# jax.profiler.TraceAnnotation, looked up at the first phase recorded with
# telemetry on (importing this package must not import jax); False where
# jax cannot be imported: phases then stay ring-only
_annotation: Any = None


def _trace_annotation() -> Any:
    global _annotation
    if _annotation is None:
        try:
            from jax.profiler import TraceAnnotation
            _annotation = TraceAnnotation
        except ImportError:
            _annotation = False
    return _annotation


class _Span:
    __slots__ = ("_tracer", "name", "attrs", "_t0", "_depth", "_parent")

    def __init__(self, tracer: "SpanTracer", name: str, attrs: Dict[str, Any]):
        self._tracer = tracer
        self.name = name
        self.attrs = attrs

    def __enter__(self) -> "_Span":
        stack = self._tracer._stack()
        self._depth = len(stack)
        self._parent = stack[-1] if stack else None
        stack.append(self)
        self._t0 = time.perf_counter_ns()
        return self

    def __exit__(self, exc_type, exc, tb) -> None:
        t1 = time.perf_counter_ns()
        stack = self._tracer._stack()
        if stack and stack[-1] is self:
            stack.pop()
        if exc_type is not None:
            # a span that ends by raising is an ERROR span, not a silent
            # close: error=1 makes failures countable/filterable in any
            # trace viewer, error_type names the exception class
            self.attrs["error"] = 1
            self.attrs["error_type"] = exc_type.__name__
        parent = self._parent
        self._tracer._record(
            self.name, self._t0, t1, self._depth, self.attrs,
            parent=None if parent is None
            else {"name": parent.name, "ts_us": parent._t0 // 1000})


class _Phase(_Span):
    """A leaf span that also lies on the profiler's timeline."""

    __slots__ = ("_annotation",)

    def __enter__(self) -> "_Phase":
        # what caused this phase: the enclosing spans' attributes, the
        # innermost winning, under the phase's own
        inherited: Dict[str, Any] = {}
        for outer in self._tracer._stack():
            inherited.update(outer.attrs)
        if inherited:
            inherited.update(self.attrs)
            self.attrs = inherited
        cls = _trace_annotation()
        self._annotation = cls(self.name, **{
            k: _json_safe(v) for k, v in self.attrs.items()}) if cls else None
        super().__enter__()
        if self._annotation is not None:
            self._annotation.__enter__()
        return self

    def __exit__(self, exc_type, exc, tb) -> None:
        if self._annotation is not None:
            self._annotation.__exit__(exc_type, exc, tb)
        super().__exit__(exc_type, exc, tb)


class SpanTracer:
    """Bounded-ring span recorder; one per process by default (the
    ``TRACER`` in ``distkeras_tpu.observability``)."""

    def __init__(self, capacity: int = 8192, enabled: bool = False):
        if capacity <= 0:
            raise ValueError(f"capacity must be positive, got {capacity}")
        self.enabled = bool(enabled)
        self.capacity = int(capacity)
        self._events: "deque[Dict[str, Any]]" = deque(maxlen=self.capacity)
        self._lock = threading.Lock()
        self._tls = threading.local()
        self.dropped = 0  # spans evicted by the ring since the last clear()

    def _stack(self) -> List[_Span]:
        stack = getattr(self._tls, "stack", None)
        if stack is None:
            stack = []
            self._tls.stack = stack
        return stack

    def span(self, name: str, **attrs: Any):
        """``with tracer.span("async.window", worker=idx): ...`` — records
        one complete event on exit.  Attrs must be JSON-representable (or
        float()-able/str()-able; coerced at export)."""
        if not self.enabled:
            return _NULL_SPAN
        return _Span(self, name, attrs)

    def phase(self, name: str, **attrs: Any):
        """``with tracer.phase("async.commit_d2h"): ...`` — a LEAF span:
        the ring record of ``span()`` (with the enclosing spans' attributes
        under its own) and a ``jax.profiler.TraceAnnotation`` of the same
        name around the same statements, which costs half a microsecond
        while no profiler session runs.  Open no phase inside a phase.
        Disabled: the shared null span, no allocation, no jax import."""
        if not self.enabled:
            return _NULL_SPAN
        return _Phase(self, name, attrs)

    def _record(self, name: str, t0_ns: int, t1_ns: int, depth: int,
                attrs: Dict[str, Any], tid: Optional[Any] = None,
                parent: Optional[Dict[str, Any]] = None) -> None:
        event = {
            "name": name,
            "ts_us": int(t0_ns) // 1000,     # perf_counter epoch, process-local
            "dur_us": max((int(t1_ns) - int(t0_ns)) // 1000, 0),
            "tid": threading.get_ident() if tid is None else tid,
            "thread": (threading.current_thread().name if tid is None
                       else str(tid)),
            "depth": depth,
        }
        if parent is not None:
            # the enclosing span of the same thread: its name and start
            event["parent"] = parent
        if attrs:
            event["attrs"] = {k: _json_safe(v) for k, v in attrs.items()}
        with self._lock:
            if len(self._events) == self.capacity:
                self.dropped += 1
            self._events.append(event)

    def record_span(self, name: str, t0_ns: int, t1_ns: int,
                    tid: Optional[Any] = None, **attrs: Any) -> None:
        """Record a span with EXPLICIT timestamps (same monotonic epoch as
        ``time.perf_counter_ns``) — for spans measured outside Python,
        e.g. the C++ hub's commit log replayed by
        ``NativeParameterServer.sync_telemetry``.  ``tid`` overrides the
        track (default: the calling thread)."""
        if not self.enabled:
            return
        self._record(name, t0_ns, t1_ns, 0, attrs, tid=tid)

    # -- introspection / export ------------------------------------------------
    def __len__(self) -> int:
        return len(self._events)

    def events(self) -> List[Dict[str, Any]]:
        with self._lock:
            return list(self._events)

    def drain(self) -> List[Dict[str, Any]]:
        """Pop everything recorded so far (the periodic JSONL flusher's
        read: each span is exported exactly once)."""
        with self._lock:
            out = list(self._events)
            self._events.clear()
            return out

    def clear(self) -> None:
        with self._lock:
            self._events.clear()
            self.dropped = 0

    def chrome_trace(self) -> Dict[str, Any]:
        """The Chrome ``trace_event`` object (JSON-dumps-ready): complete
        ``X`` events, one track per recording thread."""
        pid = os.getpid()
        trace_events = []
        for e in self.events():
            trace_events.append({
                "name": e["name"],
                "ph": "X",
                "ts": e["ts_us"],
                "dur": e["dur_us"],
                "pid": pid,
                "tid": e["tid"],
                "args": dict(e.get("attrs") or {}, depth=e["depth"],
                             thread=e["thread"]),
            })
        return {"traceEvents": trace_events, "displayTimeUnit": "ms"}

    def export_chrome(self, path: str) -> str:
        """Write the Chrome trace JSON to ``path``; returns the path."""
        with open(path, "w") as f:
            json.dump(self.chrome_trace(), f)
        return path

    def jsonl(self) -> Iterator[str]:
        """One JSON line per recorded span (non-destructive)."""
        for e in self.events():
            yield json.dumps(e)

    def export_jsonl(self, path: str) -> str:
        with open(path, "w") as f:
            for line in self.jsonl():
                f.write(line + "\n")
        return path
