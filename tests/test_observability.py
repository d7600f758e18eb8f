"""Unified telemetry subsystem (ISSUE #1): registry semantics, span
tracing, exporters, and the end-to-end async-trainer acceptance path.

The end-to-end test is the ISSUE's acceptance criterion verbatim: a
CPU-slice ``AsyncADAG`` run (2 workers, >=3 windows) must export a valid
Chrome trace (``json.loads``-able, ``ph``/``ts``/``dur`` events for window
and pull/commit spans) and a metrics snapshot with nonzero
``ps_commits_total``, ``ps_pull_bytes_total``, the per-window wall-vs-
device histograms, and the prefetch queue-depth gauge.
"""

import json
import os
import re
import threading
import time

import numpy as np
import pytest

from distkeras_tpu import observability as obs
from distkeras_tpu.observability import (
    DEFAULT_BUCKETS,
    JsonlFlusher,
    MetricsRegistry,
    SpanTracer,
)


# -- registry semantics -------------------------------------------------------

def test_counter_gauge_histogram_basics():
    reg = MetricsRegistry(enabled=True)
    c = reg.counter("commits_total")
    c.inc()
    c.inc(2.5)
    assert c.value == 3.5
    with pytest.raises(ValueError):
        c.inc(-1)

    g = reg.gauge("depth")
    g.set(4)
    g.inc()
    g.dec(2)
    assert g.value == 3.0

    h = reg.histogram("lat_seconds")
    for v in (0.001, 0.01, 0.01, 5.0):
        h.observe(v)
    s = h.summary()
    assert s["count"] == 4 and s["min"] == 0.001 and s["max"] == 5.0
    assert s["sum"] == pytest.approx(5.021)
    # cumulative bucket counts are monotone and end at count
    cums = [c for _, c in s["buckets"]]
    assert cums == sorted(cums) and cums[-1] == 4


def test_histogram_boundary_value_lands_in_its_le_bucket():
    reg = MetricsRegistry(enabled=True)
    h = reg.histogram("h")
    h.observe(DEFAULT_BUCKETS[10])  # exactly a bound: le is inclusive
    assert [DEFAULT_BUCKETS[10], 1] in h.summary()["buckets"]


def test_labels_create_distinct_instruments():
    reg = MetricsRegistry(enabled=True)
    reg.gauge("stale", worker="0").set(1)
    reg.gauge("stale", worker="1").set(7)
    assert reg.value("stale", worker="0") == 1.0
    assert reg.value("stale", worker="1") == 7.0
    assert reg.value("stale", worker="2") is None  # value() never creates
    snap = reg.snapshot()
    assert snap["gauges"]['stale{worker="0"}'] == 1.0
    assert snap["gauges"]['stale{worker="1"}'] == 7.0


def test_kind_conflict_raises():
    reg = MetricsRegistry(enabled=True)
    reg.counter("x_total")
    with pytest.raises(TypeError, match="already registered"):
        reg.gauge("x_total")


def test_disabled_registry_is_noop():
    reg = MetricsRegistry(enabled=False)
    c, g, h = reg.counter("c"), reg.gauge("g"), reg.histogram("h")
    c.inc(5)
    g.set(9)
    h.observe(1.0)
    assert c.value == 0.0 and g.value == 0.0 and h.count == 0
    # flipping the switch makes the SAME cached instruments live
    reg.enabled = True
    c.inc(5)
    assert c.value == 5.0


def test_thread_safety_under_concurrent_writers():
    reg = MetricsRegistry(enabled=True)
    c = reg.counter("n_total")
    h = reg.histogram("v")

    def writer(i):
        for k in range(1000):
            c.inc()
            h.observe(0.001 * (i + 1))

    threads = [threading.Thread(target=writer, args=(i,)) for i in range(8)]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    assert c.value == 8000
    assert h.count == 8000


def test_prometheus_rendering():
    reg = MetricsRegistry(enabled=True)
    reg.counter("pulls_total").inc(3)
    reg.gauge("stale", worker="0").set(2)
    reg.histogram("lat_seconds").observe(0.01)
    text = reg.render_prometheus()
    assert "# TYPE pulls_total counter" in text
    assert "pulls_total 3.0" in text
    assert '# TYPE stale gauge' in text and 'stale{worker="0"} 2.0' in text
    assert "# TYPE lat_seconds histogram" in text
    assert 'lat_seconds_bucket{le="+Inf"} 1' in text
    assert "lat_seconds_count 1" in text


# -- span tracer --------------------------------------------------------------

def test_span_nesting_records_depth_and_containment():
    tr = SpanTracer(capacity=64, enabled=True)
    with tr.span("outer", kind="epoch"):
        with tr.span("inner"):
            time.sleep(0.001)
    inner, outer = tr.events()  # inner exits (and records) first
    assert inner["name"] == "inner" and outer["name"] == "outer"
    assert inner["depth"] == 1 and outer["depth"] == 0
    assert inner["ts_us"] >= outer["ts_us"]
    assert inner["ts_us"] + inner["dur_us"] <= outer["ts_us"] + outer["dur_us"] + 1
    assert outer["attrs"] == {"kind": "epoch"}


def test_ring_buffer_eviction_keeps_newest_and_counts_drops():
    tr = SpanTracer(capacity=4, enabled=True)
    for i in range(10):
        with tr.span(f"s{i}"):
            pass
    assert len(tr) == 4
    assert [e["name"] for e in tr.events()] == ["s6", "s7", "s8", "s9"]
    assert tr.dropped == 6


def test_disabled_tracer_records_nothing():
    tr = SpanTracer(capacity=4, enabled=False)
    with tr.span("x"):
        pass
    assert len(tr) == 0


def test_phase_inherits_cause_and_records_parent():
    """A leaf phase carries its enclosing spans' attributes under its own
    (what caused it) and every record names the enclosing span of its
    thread; a plain span inherits nothing."""
    tr = SpanTracer(capacity=16, enabled=True)
    with tr.span("async.window", worker=1, epoch=0, window=7):
        with tr.span("ps.commit", compress="none", window=8):
            with tr.phase("ps.commit_send", bytes=5):
                pass
        with tr.phase("async.h2d", window=9):
            pass
    send, commit, h2d, window = tr.events()
    assert send["attrs"] == {"worker": 1, "epoch": 0, "window": 8,
                             "compress": "none", "bytes": 5}
    assert send["depth"] == 2 and send["parent"] == {
        "name": "ps.commit", "ts_us": commit["ts_us"]}
    assert commit["attrs"] == {"compress": "none", "window": 8}
    assert commit["parent"]["name"] == "async.window"
    assert h2d["attrs"]["window"] == 9 and h2d["attrs"]["worker"] == 1
    assert h2d["parent"] == {"name": "async.window", "ts_us": window["ts_us"]}
    assert "parent" not in window
    # another thread's stack is its own: no parent, nothing inherited
    with tr.span("outer", worker=3):
        t = threading.Thread(target=lambda: tr.phase("ps.apply").__enter__()
                             .__exit__(None, None, None))
        t.start()
        t.join(10)
    apply_ev = [e for e in tr.events() if e["name"] == "ps.apply"][0]
    assert "parent" not in apply_ev and "attrs" not in apply_ev


def test_phase_disabled_is_the_shared_null_span_and_imports_no_jax():
    """Telemetry off: ``phase()`` hands back the one shared null span, and
    ``observability`` alone never imports jax (the hub's modules must stay
    importable beside a chip-holding parent); on, the first phase looks the
    profiler's annotation up."""
    import subprocess
    import sys

    tr = SpanTracer(enabled=False)
    assert tr.phase("async.h2d", worker=0) is obs.NULL_SPAN
    assert tr.span("async.window") is obs.NULL_SPAN and len(tr) == 0
    code = (
        "import sys\n"
        "from distkeras_tpu import observability as obs\n"
        "from distkeras_tpu.observability import tracing\n"
        "p = obs.phase('async.h2d', worker=0)\n"
        "assert p is obs.NULL_SPAN is tracing._NULL_SPAN\n"
        "with p: pass\n"
        "assert tracing._annotation is None\n"
        "assert not [m for m in sys.modules if m == 'jax' or m.startswith('jax.')]\n"
        "obs.enable()\n"
        "with obs.phase('async.h2d', worker=0): pass\n"
        "assert 'jax.profiler' in sys.modules and tracing._annotation\n"
        "assert obs.TRACER.events()[0]['name'] == 'async.h2d'\n")
    env = {k: v for k, v in os.environ.items() if k != "DKT_TELEMETRY"}
    done = subprocess.run([sys.executable, "-c", code], env=env, timeout=120,
                          capture_output=True, text=True)
    assert done.returncode == 0, done.stderr


def test_chrome_trace_export_is_valid_trace_event_json(tmp_path):
    tr = SpanTracer(capacity=16, enabled=True)
    with tr.span("a", worker=0):
        pass
    path = tr.export_chrome(str(tmp_path / "trace.json"))
    with open(path) as f:
        parsed = json.loads(f.read())
    assert isinstance(parsed["traceEvents"], list) and parsed["traceEvents"]
    for ev in parsed["traceEvents"]:
        assert ev["ph"] == "X"
        assert isinstance(ev["ts"], int) and isinstance(ev["dur"], int)
        assert "pid" in ev and "tid" in ev and "name" in ev


def test_jsonl_export_and_drain(tmp_path):
    tr = SpanTracer(capacity=16, enabled=True)
    for name in ("a", "b"):
        with tr.span(name):
            pass
    lines = list(tr.jsonl())
    assert [json.loads(l)["name"] for l in lines] == ["a", "b"]
    drained = tr.drain()
    assert len(drained) == 2 and len(tr) == 0


def test_span_error_annotated():
    """A span that ends by raising records error=1 + the exception type
    (countable/filterable in trace viewers) instead of closing silently."""
    tr = SpanTracer(enabled=True)
    with pytest.raises(RuntimeError):
        with tr.span("boom"):
            raise RuntimeError("x")
    (ev,) = tr.events()
    assert ev["attrs"]["error"] == 1
    assert ev["attrs"]["error_type"] == "RuntimeError"


def test_jsonl_flusher_writes_selfcontained_lines(tmp_path):
    reg = MetricsRegistry(enabled=True)
    tr = SpanTracer(enabled=True)
    reg.counter("c_total").inc(2)
    with tr.span("s"):
        pass
    path = str(tmp_path / "telemetry.jsonl")
    flusher = JsonlFlusher(path, reg, tracer=tr, interval=60.0)
    flusher.start()
    flusher.stop()  # final flush
    with open(path) as f:
        lines = [json.loads(l) for l in f.read().splitlines()]
    assert lines, "stop() must land at least one flush"
    assert lines[0]["metrics"]["counters"]["c_total"] == 2.0
    assert [s["name"] for s in lines[0]["spans"]] == ["s"]
    # spans are drained: a second flush does not repeat them
    flusher.flush()
    with open(path) as f:
        lines = [json.loads(l) for l in f.read().splitlines()]
    assert "spans" not in lines[-1]


# -- instrumented layers ------------------------------------------------------

def test_prefetch_feed_gauges_and_chunk_latency(telemetry, toy_dataset):
    from distkeras_tpu.data.dataset import prefetch_to_device

    chunks = toy_dataset.chunked_epoch(16, ["features", "label"],
                                      window=1, chunk_windows=8)
    seen = 0
    for _ in prefetch_to_device(chunks, lambda ch: ch["features"].shape):
        seen += 1
    assert seen == 8
    snap = obs.snapshot()
    assert snap["counters"]["feed_chunks_total"] == 8.0
    assert "feed_queue_depth" in snap["gauges"]
    assert snap["histograms"]["feed_chunk_load_seconds"]["count"] == 8


def test_prefetch_raises_when_producer_dies_without_sentinel(monkeypatch):
    """ADVICE round 5: a producer killed without its 'done'/'error'
    sentinel must surface as an error, not a silent q.get() hang."""
    from distkeras_tpu.data.dataset import prefetch_to_device

    class DeadThread:
        def __init__(self, *a, **kw):
            pass

        def start(self):
            pass  # never runs: simulates death-before-first-put

        def is_alive(self):
            return False

    monkeypatch.setattr(threading, "Thread", DeadThread)
    it = prefetch_to_device(iter([{"x": 1}]), lambda ch: ch)
    with pytest.raises(RuntimeError, match="producer thread died"):
        next(it)


def test_punchcard_telemetry_action(telemetry, tmp_path):
    from distkeras_tpu.runtime.job_deployment import Punchcard, fetch_telemetry

    obs.counter("ps_commits_total").inc(3)
    with obs.span("async.window", worker=0):
        pass
    obs.TRACER.record_span("ps.handle_commit", 1_000_000, 2_000_000,
                           worker=0, staleness=2)
    pc = Punchcard(secret="s3cret").start()
    try:
        resp = fetch_telemetry("127.0.0.1", pc.port, "s3cret",
                               trace=True, prometheus=True, fleet=True)
    finally:
        pc.stop()
    assert resp["enabled"] is True
    assert resp["metrics"]["counters"]["ps_commits_total"] == 3.0
    assert any(e["name"] == "async.window"
               for e in resp["trace"]["traceEvents"])
    assert "ps_commits_total 3.0" in resp["prometheus"]
    # the fleet_report rides the same action (issue 5): straggler ranking +
    # per-worker staleness attribution, computed daemon-side
    assert resp["fleet"]["total_commits"] == 1
    assert resp["fleet"]["commit_context_coverage"] == 1.0
    assert resp["fleet"]["workers"]["0"]["commits"] == 1


# -- end-to-end acceptance: AsyncADAG smoke run -------------------------------

WINDOW_PHASES = ("async.pull_wait", "async.h2d", "async.dispatch",
                 "async.pull_land", "async.device_wait", "async.commit_d2h",
                 "ps.commit_drain", "ps.commit_pack", "ps.commit_send")


def _check_window_phases(events, n_windows):
    """Every ``async.window``'s worker-thread leaf phases lie inside it, in
    order, without overlap, carry its worker/epoch/window, and cover it but
    for bookkeeping; the hub's ``ps.apply`` names the worker it served.
    A worker's last window prefetches nothing, so it alone has no
    ``async.pull_land``; ``ps.commit_drain`` (the guard) is in every one."""
    windows = [e for e in events if e["name"] == "async.window"]
    assert len(windows) == n_windows
    last = {}
    for win in windows:
        last[win["tid"]] = max(last.get(win["tid"], 0), win["ts_us"])
    cover = []
    for win in windows:
        lo, hi = win["ts_us"], win["ts_us"] + win["dur_us"]
        mine = sorted((e for e in events if e["tid"] == win["tid"]
                       and e["name"] in WINDOW_PHASES and lo <= e["ts_us"] <= hi),
                      key=lambda e: e["ts_us"])
        assert [e["name"] for e in mine] == [
            p for p in WINDOW_PHASES
            if p != "async.pull_land" or win["ts_us"] != last[win["tid"]]]
        for e in mine:
            assert e["ts_us"] + e["dur_us"] <= hi + 2     # whole microseconds
            assert {k: e["attrs"][k] for k in ("worker", "epoch", "window")} \
                == win["attrs"]
            assert e["parent"]["name"] in ("async.window", "ps.commit")
        for a, b in zip(mine, mine[1:]):
            assert a["ts_us"] + a["dur_us"] <= b["ts_us"] + 2
        cover.append(sum(e["dur_us"] for e in mine) / max(win["dur_us"], 1))
    # bookkeeping between phases is microseconds; a window is milliseconds.
    # The median and the total, not each window: a thread descheduled
    # between two phases on a loaded CI host must not fail the test
    assert sorted(cover)[len(cover) // 2] >= 0.9, cover
    assert sum(e["dur_us"] for e in events if e["name"] in WINDOW_PHASES) \
        >= 0.9 * sum(w["dur_us"] for w in windows)
    applies = [e for e in events if e["name"] == "ps.apply"]
    assert len(applies) == n_windows
    workers = {w["attrs"]["worker"] for w in windows}
    for e in applies:
        assert e["attrs"]["worker"] in workers and e["attrs"]["batch"] == 1
        assert e["attrs"]["lock_wait_us"] >= 0 and "clock" in e["attrs"]
        assert e["parent"]["name"] == "ps.handle_commit"
    assert sorted(e["attrs"]["clock"] for e in applies) == list(range(n_windows))
    for name in ("ps.recv_commit", "ps.send_weights"):
        assert {e["attrs"]["worker"] for e in events if e["name"] == name} == workers
    for name in ("async.seed", "async.drain"):
        assert len([e for e in events if e["name"] == name]) == len(workers)


def test_async_adag_smoke_exports_metrics_and_chrome_trace(telemetry, toy_dataset,
                                                           tmp_path):
    import distkeras_tpu as dk
    from distkeras_tpu.models.base import Model, ModelSpec

    # wide enough (1 MB of weights) that a window is milliseconds: the
    # phase coverage below is then not a measure of the spans' own cost
    spec = ModelSpec(name="mlp", config={"hidden_sizes": (512, 512), "num_outputs": 2},
                     input_shape=(8,))
    trainer = dk.AsyncADAG(Model.init(spec, seed=0),
                           loss="categorical_crossentropy", batch_size=16,
                           num_epoch=1, num_workers=2, communication_window=4,
                           learning_rate=0.05, seed=0)
    trainer.train(toy_dataset)
    # 1024 rows / 2 workers / (16 batch * 4 window) = 8 windows per worker
    assert len(trainer.history) >= 3 * 2

    snap = obs.snapshot()
    assert snap["counters"]["ps_commits_total"] > 0
    assert snap["counters"]["ps_pull_bytes_total"] > 0
    assert snap["counters"]["ps_commit_bytes_total"] > 0
    # issue-3 client-side hot-path instruments (exported through the same
    # registry the telemetry punchcard action snapshots)
    assert snap["counters"]["ps.commit_bytes"] > 0
    assert snap["histograms"]["ps.pull_latency_ms"]["count"] > 0
    assert snap["histograms"]["ps.commit_latency_ms"]["count"] > 0
    assert snap["histograms"]["ps.serialize_ms"]["count"] > 0
    assert "ps.inflight_depth" in snap["gauges"]
    # hub-side staleness distribution: one observation per applied commit
    assert snap["histograms"]["ps_commit_staleness"]["count"] \
        == snap["counters"]["ps_commits_total"]
    wall = snap["histograms"]["async_window_wall_seconds"]
    dev = snap["histograms"]["async_window_device_seconds"]
    assert wall["count"] >= 3 and dev["count"] >= 3
    assert wall["sum"] >= dev["sum"]  # the wall leg contains the device leg
    assert any(k.startswith("ps_staleness{") for k in snap["gauges"])
    # the worker loop is the plain slice walk: no feed thread, no feed
    # instruments in an async-only run
    assert not [k for k in list(snap["gauges"]) + list(snap["counters"])
                if k.startswith("async_feed")]
    _check_window_phases(obs.TRACER.events(), len(trainer.history))
    assert snap["counters"]['trainer_epochs_total{trainer="AsyncADAG"}'] == 1.0
    assert snap["histograms"]['trainer_window_loss{trainer="AsyncADAG"}']["count"] \
        == len(trainer.history)

    # the exported Chrome trace parses and carries complete (ph/ts/dur)
    # events for the window and pull/commit spans
    path = obs.TRACER.export_chrome(str(tmp_path / "smoke_trace.json"))
    with open(path) as f:
        parsed = json.loads(f.read())
    names = {e["name"] for e in parsed["traceEvents"]}
    assert {"async.window", "ps.pull", "ps.commit"} <= names
    for ev in parsed["traceEvents"]:
        assert ev["ph"] == "X" and "ts" in ev and "dur" in ev

    # the wall/device decomposition is coherent per window: device time
    # never exceeds wall time
    assert dev["max"] <= wall["max"] * 1.001


# -- prometheus exposition hardening (issue-5 satellites) ---------------------

def test_prometheus_label_value_escaping():
    """Backslash, double-quote and newline in label values are escaped per
    the text-format spec — unescaped they corrupt the whole scrape."""
    reg = MetricsRegistry(enabled=True)
    reg.counter("c_total", path='a\\b"c\nd').inc()
    text = reg.render_prometheus()
    assert 'c_total{path="a\\\\b\\"c\\nd"} 1.0' in text
    assert "\n\n" not in text  # the raw newline never leaked into a line


def test_prometheus_escape_helper_order():
    from distkeras_tpu.observability.sinks import escape_label_value

    # backslash escapes FIRST, or the quote/newline escapes double-escape
    assert escape_label_value('\\') == '\\\\'
    assert escape_label_value('"') == '\\"'
    assert escape_label_value('\n') == '\\n'
    assert escape_label_value('\\n') == '\\\\n'


def test_histogram_overflow_bucket_and_quantile_surface():
    """Values past the last fixed log bound land in the explicit +Inf
    overflow bucket, and the exposition carries the full cumulative bucket
    series plus _sum/_count — the shape histogram_quantile() needs."""
    reg = MetricsRegistry(enabled=True)
    h = reg.histogram("ps.pull_latency_ms")
    h.observe(0.5)
    h.observe(1e30)          # beyond every bound -> overflow
    h.observe(float("inf"))  # +inf -> overflow too
    h.observe(float("nan"))  # dropped: would poison sum/mean forever
    assert h.count == 3
    s = h.summary()
    assert ["+Inf", 3] in s["buckets"]
    text = reg.render_prometheus()
    assert 'ps_pull_latency_ms_bucket{le="+Inf"} 3' in text
    assert "ps_pull_latency_ms_count 3" in text
    assert "ps_pull_latency_ms_sum" in text
    # cumulative bucket series is monotone nondecreasing and ends at count
    cums = [int(line.rsplit(" ", 1)[1]) for line in text.splitlines()
            if line.startswith("ps_pull_latency_ms_bucket")]
    assert cums == sorted(cums) and cums[-1] == 3


def test_histogram_observe_n_bulk_matches_loop():
    """observe_n(v, n) — the native hub's O(1)-per-slot staleness replay —
    must equal n individual observe(v) calls."""
    reg = MetricsRegistry(enabled=True)
    bulk, loop = reg.histogram("bulk"), reg.histogram("loop")
    for v, n in ((0.0, 3), (2.0, 5), (1e30, 2)):
        bulk.observe_n(v, n)
        for _ in range(n):
            loop.observe(v)
    bulk.observe_n(1.0, 0)              # n=0: no-op
    bulk.observe_n(float("nan"), 4)     # NaN: dropped, same as observe()
    sb, sl = bulk.summary(), loop.summary()
    assert sb == sl
    assert sb["count"] == 10 and sb["min"] == 0.0


# -- distributed tracing: context propagation (issue-5 tentpole) --------------

@pytest.fixture
def hub_and_templates():
    from distkeras_tpu.runtime.parameter_server import DeltaParameterServer

    templates = [np.zeros((4, 4), np.float32), np.zeros(3, np.float32)]
    ps = DeltaParameterServer(templates, port=0)
    ps.start()
    yield ps, templates
    ps.stop()


def _wait_spans(*names, timeout=5.0):
    """The hub acks INSIDE the handler span, so a client can unblock
    before the span records (the ack-before-telemetry-tail ordering,
    ISSUE 14's motivating shape) — poll briefly instead of racing."""
    import time as _time

    deadline = _time.monotonic() + timeout
    while _time.monotonic() < deadline:
        events = obs.TRACER.events()
        got = {n: [e for e in events if e["name"] == n] for n in names}
        if all(got.values()):
            return got
        _time.sleep(0.01)
    return got


def test_trace_context_announce_tags_hub_spans(telemetry, hub_and_templates):
    from distkeras_tpu.observability import distributed as dtrace
    from distkeras_tpu.runtime.parameter_server import PSClient

    ps, templates = hub_and_templates
    ctx = dtrace.TraceContext(job_id="j1", worker_id=4,
                              span_id=dtrace.new_span_id())
    with PSClient("127.0.0.1", ps.port, templates=templates,
                  trace_context=ctx) as client:
        pulled = client.pull()
        client.commit([np.ones_like(t) for t in pulled])
        # NTP-style offset on loopback against the same physical clock:
        # tiny, and within the sample's own error bound
        assert client.clock_error_ns is not None
        assert abs(client.clock_offset_ns) <= client.clock_error_ns + 5_000_000
    got = _wait_spans("ps.handle_commit", "ps.handle_pull")
    commits, pulls = got["ps.handle_commit"], got["ps.handle_pull"]
    assert commits and pulls
    assert commits[0]["attrs"]["worker"] == 4
    assert commits[0]["attrs"]["job"] == "j1"
    assert commits[0]["attrs"]["staleness"] == 0
    assert pulls[0]["attrs"]["worker"] == 4


def test_unannounced_client_wire_unchanged(telemetry, hub_and_templates):
    """No trace_context => no T frame: the byte stream is the pre-T
    protocol exactly, and hub commit spans simply carry no worker."""
    from distkeras_tpu.runtime.parameter_server import PSClient

    ps, templates = hub_and_templates
    with PSClient("127.0.0.1", ps.port, templates=templates) as client:
        client.commit([np.ones_like(t) for t in templates])
    (commit,) = _wait_spans("ps.handle_commit")["ps.handle_commit"]
    assert "worker" not in commit["attrs"]


def test_inproc_commit_span_reads_thread_context(telemetry, hub_and_templates):
    from distkeras_tpu.observability import distributed as dtrace
    from distkeras_tpu.runtime.parameter_server import InprocPSClient

    ps, templates = hub_and_templates
    ctx = dtrace.TraceContext(job_id="j2", worker_id=7,
                              span_id=dtrace.new_span_id())
    dtrace.activate(ctx)
    try:
        client = InprocPSClient(ps, templates=templates, trace_context=ctx)
        client.pull()
        client.commit([np.ones_like(t) for t in templates])
    finally:
        dtrace.deactivate()
    (commit,) = [e for e in obs.TRACER.events()
                 if e["name"] == "ps.handle_commit"]
    assert commit["attrs"]["worker"] == 7
    assert commit["attrs"]["transport"] == "inproc"


def test_native_hub_stats_surface_python_registry_names(telemetry):
    from distkeras_tpu.observability import distributed as dtrace
    from distkeras_tpu.runtime import native
    from distkeras_tpu.runtime.parameter_server import PSClient

    if not native.native_available():
        pytest.skip(f"native hub unavailable: {native.build_error()}")
    templates = [np.zeros((4, 4), np.float32), np.zeros(3, np.float32)]
    ps = native.NativeParameterServer(templates, mode=native.MODE_DELTA)
    ps.start()
    try:
        ctx = dtrace.TraceContext(job_id="jn", worker_id=1,
                                  span_id=dtrace.new_span_id())
        with PSClient("127.0.0.1", ps.port, templates=templates,
                      trace_context=ctx) as client:
            pulled = client.pull()
            client.commit([np.ones_like(t) for t in pulled])
            client.commit([np.ones_like(t) for t in pulled])
        # inproc twin with thread-local context
        dtrace.activate(dtrace.TraceContext(job_id="jn", worker_id=5,
                                            span_id=dtrace.new_span_id()))
        try:
            weights, clock = ps.pull_direct()
            ps.commit_direct([np.ones_like(w) for w in weights], clock)
        finally:
            dtrace.deactivate()
        ps.sync_telemetry()
    finally:
        ps.stop()
    snap = obs.snapshot()
    # the SAME names the Python hub emits — hub-implementation-agnostic
    assert snap["counters"]["ps_commits_total"] == 3.0
    assert snap["counters"]["ps_pulls_total"] >= 2.0
    assert snap["counters"]["ps_commit_bytes_total"] > 0
    assert snap["counters"]["ps_pull_bytes_total"] > 0
    assert snap["histograms"]["ps_commit_staleness"]["count"] == 3
    assert "ps_live_workers" in snap["gauges"]
    # the drained commit log became attributable hub spans
    commits = [e for e in obs.TRACER.events() if e["name"] == "ps.handle_commit"]
    workers = sorted(e["attrs"].get("worker") for e in commits)
    assert workers == [1, 1, 5]
    assert all(e["attrs"]["hub"] == "native" for e in commits)
    # a second sync advances by deltas only (no double counting)
    obs.reset()
    ps.sync_telemetry()
    assert obs.snapshot()["counters"].get("ps_commits_total", 0.0) == 0.0


# -- distributed tracing: clock-aligned merge ---------------------------------

def test_merge_traces_two_subprocess_workers(telemetry, tmp_path):
    """The acceptance-shaped multi-process merge: a hub in THIS process
    (the clock reference) + two real subprocess workers, each announcing a
    context and flushing its own offset-stamped JSONL.  The merged Chrome
    trace must be monotonic per (pid, tid) track and each child's offset
    estimate must sit within its own documented error bound (same physical
    clock => true offset ~ 0)."""
    import subprocess
    import sys

    from distkeras_tpu.observability import distributed as dtrace
    from distkeras_tpu.runtime.parameter_server import DeltaParameterServer

    templates = [np.zeros((4, 4), np.float32), np.zeros(3, np.float32)]
    ps = DeltaParameterServer(templates, port=0)
    ps.start()
    trace_dir = str(tmp_path / "traces")
    try:
        import os

        child = os.path.join(os.path.dirname(__file__),
                             "multihost_child_trace.py")
        env = dict(os.environ)
        env["PYTHONPATH"] = (os.path.dirname(os.path.dirname(child))
                             + os.pathsep + env.get("PYTHONPATH", ""))
        procs = [subprocess.run(
            [sys.executable, child, str(ps.port), str(w), trace_dir],
            capture_output=True, text=True, timeout=120, env=env)
            for w in (0, 1)]
        for p in procs:
            assert p.returncode == 0, f"child failed:\n{p.stdout}\n{p.stderr}"
    finally:
        ps.stop()
    # the hub process flushes too (offset 0: it IS the reference)
    dtrace.flush_process_trace(trace_dir, job_id="mergejob", role="hub")

    metas, spans = dtrace.load_trace_dir(trace_dir)
    assert len(metas) == 3  # hub + 2 workers
    for m in metas:
        if m["role"] == "worker":
            # alignment-error contract: |estimated offset| <= its error
            # bound (+ scheduling slack) on a shared physical clock
            assert m["clock_error_ns"] is not None
            assert abs(m["clock_offset_ns"]) <= m["clock_error_ns"] + 20_000_000
            assert m["clock_error_ns"] < 1_000_000_000

    merged = dtrace.merge_traces(trace_dir)
    events = [e for e in merged["traceEvents"] if e["ph"] == "X"]
    assert merged["otherData"]["processes"] == 3
    assert merged["otherData"]["spans"] == len(events)
    assert merged["otherData"]["alignment_error_us"] >= 0
    # every child's windows and the hub's attributed commit handling made it
    names = {e["name"] for e in events}
    assert {"async.window", "ps.handle_commit", "ps.handle_pull"} <= names
    commit_workers = {e["args"].get("worker") for e in events
                      if e["name"] == "ps.handle_commit"}
    assert {0, 1} <= commit_workers
    # monotonic per (pid, tid) track after the merge sort
    by_track = {}
    for e in events:
        by_track.setdefault((e["pid"], e["tid"]), []).append(e["ts"])
    for track, ts in by_track.items():
        assert ts == sorted(ts), f"track {track} not monotonic"
    # and it round-trips through json for chrome://tracing
    path = dtrace.export_merged(trace_dir, str(tmp_path / "merged.json"))
    with open(path) as f:
        assert json.loads(f.read())["traceEvents"]


# -- distributed tracing: straggler + staleness attribution -------------------

def test_fleet_report_chaosproxy_delay_names_top_straggler(telemetry):
    """The acceptance criterion's delay leg: two workers against one hub,
    one of them routed through a ChaosProxy that delays every frame —
    fleet_report must rank the delayed worker as the top straggler."""
    from distkeras_tpu.observability import distributed as dtrace
    from distkeras_tpu.runtime.faults import DELAY, ChaosProxy, Fault, FaultPlan
    from distkeras_tpu.runtime.parameter_server import (
        DeltaParameterServer,
        PSClient,
    )

    templates = [np.zeros((8, 8), np.float32)]
    ps = DeltaParameterServer(templates, port=0)
    ps.start()
    plan = FaultPlan([Fault(conn=0, direction="s2c", frame=k, kind=DELAY,
                            delay_s=0.02) for k in range(32)])
    proxy = ChaosProxy("127.0.0.1", ps.port, plan=plan)
    proxy.start()
    try:
        def run_worker(idx, port):
            ctx = dtrace.TraceContext(job_id="chaos", worker_id=idx,
                                      span_id=dtrace.new_span_id())
            with PSClient("127.0.0.1", port, templates=templates,
                          trace_context=ctx) as client:
                for w in range(4):
                    with obs.span("async.window", worker=idx, window=w):
                        pulled = client.pull()
                        client.commit([np.full_like(t, 0.1) for t in pulled])

        run_worker(0, ps.port)      # direct: fast
        run_worker(1, proxy.port)   # proxied: every frame held 20 ms
    finally:
        proxy.stop()
        ps.stop()
    report = dtrace.fleet_report()
    assert report["top_straggler"] == "1"
    w0, w1 = report["workers"]["0"], report["workers"]["1"]
    assert w1["mean_window_ms"] > w0["mean_window_ms"]
    assert w0["windows"] == w1["windows"] == 4
    # every hub commit span carried a context (coverage = 1.0)
    assert report["commit_context_coverage"] == 1.0
    # staleness is attributed per worker (present, non-negative)
    assert w0["mean_staleness"] is not None and w0["mean_staleness"] >= 0


def test_fleet_report_flags_reconnect_storms(telemetry):
    from distkeras_tpu.observability import distributed as dtrace

    t0 = 1_000_000_000
    for k in range(3):
        obs.TRACER.record_span("ps.reconnect", t0 + k, t0 + k + 1000, worker=2)
    obs.TRACER.record_span("ps.reconnect", t0, t0 + 1000, worker=0)
    report = dtrace.fleet_report()
    assert report["reconnect_storms"] == ["2"]
    assert report["workers"]["2"]["reconnects"] == 3
    assert report["workers"]["0"]["reconnects"] == 1


# -- end-to-end acceptance: AsyncADAG over the transport x hub matrix ---------

@pytest.mark.parametrize("transport,native_ps", [
    ("socket", False),
    ("inproc", False),
    ("socket", True),
    ("inproc", True),
])
def test_e2e_async_adag_commit_context_coverage(telemetry, toy_dataset,
                                                tmp_path, monkeypatch,
                                                transport, native_ps):
    """The issue-5 acceptance run: an AsyncADAG job on each transport/hub
    combination produces a merged Chrome trace in which >=95% of hub
    commit spans carry a worker trace context."""
    import distkeras_tpu as dk
    from distkeras_tpu.models.base import Model, ModelSpec
    from distkeras_tpu.observability import distributed as dtrace

    if native_ps:
        from distkeras_tpu.runtime import native

        if not native.native_available():
            pytest.skip(f"native hub unavailable: {native.build_error()}")
    trace_dir = str(tmp_path / "traces")
    monkeypatch.setenv("DKT_TRACE_DIR", trace_dir)
    spec = ModelSpec(name="mlp", config={"hidden_sizes": (16,), "num_outputs": 2},
                     input_shape=(8,))
    trainer = dk.AsyncADAG(Model.init(spec, seed=0),
                           loss="categorical_crossentropy", batch_size=16,
                           num_epoch=1, num_workers=2, communication_window=4,
                           learning_rate=0.05, seed=0, transport=transport,
                           native_ps=native_ps, trace_context="e2ejob")
    trainer.train(toy_dataset)

    report = dtrace.fleet_report(trace_dir=trace_dir)
    assert report["total_commits"] > 0
    assert report["commit_context_coverage"] >= 0.95
    # both workers show up as attributed committers AND window owners
    assert {"0", "1"} <= set(report["workers"])
    assert all(report["workers"][w]["windows"] > 0 for w in ("0", "1"))
    merged = dtrace.merge_traces(trace_dir)
    names = {e["name"] for e in merged["traceEvents"] if e.get("ph") == "X"}
    assert {"async.window", "ps.handle_commit"} <= names


# -- CI/tooling guards (issue-5 satellites) -----------------------------------

def test_observability_imports_are_cycle_free_and_jax_free():
    """The observability package (distributed tracing included) must import
    standalone — no cycles, no jax/numpy/runtime pulled in — so the
    punchcard daemon and bare tooling can use it without a backend."""
    import subprocess
    import sys

    code = (
        "import sys\n"
        "import distkeras_tpu.observability.distributed\n"
        "import distkeras_tpu.observability.metrics\n"
        "import distkeras_tpu.observability.sinks\n"
        "import distkeras_tpu.observability.tracing\n"
        "from distkeras_tpu import observability\n"
        "observability.TraceContext  # lazy export resolves\n"
        "assert 'jax' not in sys.modules, 'observability dragged jax in'\n"
        "assert 'numpy' not in sys.modules, 'observability dragged numpy in'\n"
        "assert 'distkeras_tpu.runtime' not in sys.modules, 'import cycle'\n"
        "print('CLEAN')\n"
    )
    proc = subprocess.run([sys.executable, "-c", code],
                          capture_output=True, text=True, timeout=60)
    assert proc.returncode == 0, proc.stderr
    assert "CLEAN" in proc.stdout


def test_disabled_telemetry_hot_path_makes_zero_registry_calls(monkeypatch):
    """Overhead guard: with telemetry disabled, a full pull/commit exchange
    (client and hub hot paths) performs ZERO registry lookups and records
    zero spans — the disabled cost is one branch, not a dict get."""
    from distkeras_tpu.observability.metrics import MetricsRegistry
    from distkeras_tpu.runtime.parameter_server import (
        DeltaParameterServer,
        PSClient,
    )

    obs.disable()
    obs.reset()
    calls = []
    orig_get = MetricsRegistry._get

    def counting_get(self, kind, name, labels):
        calls.append((kind, name))
        return orig_get(self, kind, name, labels)

    monkeypatch.setattr(MetricsRegistry, "_get", counting_get)
    templates = [np.zeros((4, 4), np.float32)]
    ps = DeltaParameterServer(templates, port=0)
    ps.start()
    try:
        with PSClient("127.0.0.1", ps.port, templates=templates) as client:
            for _ in range(3):
                pulled = client.pull()
                client.commit([np.ones_like(t) for t in pulled])
    finally:
        ps.stop()
    assert calls == [], f"registry touched while disabled: {calls[:5]}"
    assert len(obs.TRACER.events()) == 0


@pytest.mark.parametrize("package", ["observability", "runtime", ".", "tests",
                                     "data", "parallel", "models", "ops",
                                     "examples", "analysis"])
def test_package_is_lint_clean(package):
    """Satellite (PR 5, extended package-by-package through PR 10, and
    consolidated by PR 12): ruff-clean check scoped to the instrumented
    packages.  The implementation now lives in ONE place —
    ``distkeras_tpu.analysis.unused_imports`` (real ruff when the
    container has it, else an AST F401 sweep + compile check) — and
    these named cells delegate, so there is one F401 implementation
    instead of N copies while a scoping change can never silently drop
    a package (the cell names are the coverage contract)."""
    import os

    from distkeras_tpu.analysis import unused_imports as ui

    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    assert package in ui.PACKAGES, \
        f"cell {package!r} dropped from analysis/unused_imports.PACKAGES"
    assert ui.package_files(root, package), \
        f"package {package!r} resolves to no files — coverage went hollow"
    findings = ui.check_package(root, package)
    assert not findings, "\n".join(str(f) for f in findings)


@pytest.mark.parametrize("module", ["streaming.py", "job_deployment.py"])
def test_runtime_stragglers_lint_clean_named(module):
    """Satellite (PR 11, delegated to the one F401 implementation by
    PR 12): the runtime modules named by ISSUE 11 — streaming.py and
    job_deployment.py — keep their own NAMED lint cells so a future
    scoping change to the package-level sweep can never silently drop
    them (the package cell scans by listdir; this one pins the two
    files by name)."""
    import os

    from distkeras_tpu.analysis import unused_imports as ui

    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    path = os.path.join(root, "distkeras_tpu", "runtime", module)
    assert os.path.exists(path), f"{module} moved without updating the guard"
    findings = ui.check_files([path], root)
    assert not findings, "\n".join(str(f) for f in findings)


def test_telemetry_disabled_leaves_async_run_unrecorded(toy_dataset):
    """Disabled-by-default contract: the instrumented async path records
    nothing unless enabled (and still trains correctly)."""
    import distkeras_tpu as dk
    from distkeras_tpu.models.base import Model, ModelSpec

    obs.reset()
    assert not obs.enabled()
    spec = ModelSpec(name="mlp", config={"hidden_sizes": (16,), "num_outputs": 2},
                     input_shape=(8,))
    trainer = dk.AsyncADAG(Model.init(spec, seed=0),
                           loss="categorical_crossentropy", batch_size=16,
                           num_epoch=1, num_workers=2, communication_window=4,
                           learning_rate=0.05, seed=0)
    trainer.train(toy_dataset)
    assert len(trainer.history) > 0
    snap = obs.snapshot()
    assert snap["counters"].get("ps_commits_total", 0.0) == 0.0
    assert len(obs.TRACER.events()) == 0


# -- leaf phases: where the program's time goes (ISSUE 25) --------------------

def _tiny_async(toy_dataset, windows=3, **kw):
    import distkeras_tpu as dk
    from distkeras_tpu.data.dataset import Dataset
    from distkeras_tpu.models.base import Model, ModelSpec

    spec = ModelSpec(name="mlp", config={"hidden_sizes": (16,), "num_outputs": 2},
                     input_shape=(8,))
    rows = 16 * 4 * windows
    ds = Dataset({c: toy_dataset[c][:rows] for c in ("features", "label")})
    trainer = dk.AsyncADAG(Model.init(spec, seed=0),
                           loss="categorical_crossentropy", batch_size=16,
                           num_epoch=1, num_workers=1, communication_window=4,
                           learning_rate=0.05, seed=0, **kw)
    trainer.train(ds)
    assert len(trainer.history) == windows
    return trainer


def test_worker_loop_runs_in_the_same_threads_with_telemetry_on_and_off(toy_dataset):
    """Switching telemetry on must not change the program it measures: no
    feed thread (or any other) exists only because telemetry is on."""
    seen = {}

    def census(on):
        def hook(worker, window):
            if window == 2:
                seen[on] = sorted(re.sub("[0-9]+", "", t.name)
                                  for t in threading.enumerate())
        return hook

    _tiny_async(toy_dataset, fault_hook=census(False))
    obs.reset()
    obs.enable()
    try:
        _tiny_async(toy_dataset, fault_hook=census(True))
        assert [e for e in obs.TRACER.events() if e["name"] == "async.h2d"]
    finally:
        obs.disable()
        obs.reset()
    assert seen[True] == seen[False] and seen[True]


def _within(seconds, fn):
    """``fn()`` on a thread, given up on after ``seconds`` (there is no
    pytest-timeout here): a profiler that hangs fails this test alone."""
    box = {}

    def run():
        try:
            box["value"] = fn()
        except BaseException as e:  # handed to the test's thread below
            box["error"] = e

    t = threading.Thread(target=run, daemon=True)
    t.start()
    t.join(seconds)
    assert not t.is_alive(), f"still running after {seconds} s"
    if "error" in box:
        raise box["error"]
    return box["value"]


def test_phases_lie_on_the_profiler_host_plane_and_enclosing_spans_do_not(
        telemetry, toy_dataset, tmp_path):
    """Under ``jax.profiler.start_trace`` the leaf phases are TraceMe events
    on the worker's and the hub handler's host lines (read back through
    ``jax.profiler.ProfileData``, as the benchmark reads them); enclosing
    spans stay in the ring only."""
    import glob

    import jax

    def traced():
        options = jax.profiler.ProfileOptions()
        options.python_tracer_level = 0
        jax.profiler.start_trace(str(tmp_path), profiler_options=options)
        try:
            _tiny_async(toy_dataset)
        finally:
            jax.profiler.stop_trace()
        (path,) = glob.glob(os.path.join(str(tmp_path), "**", "*.xplane.pb"),
                            recursive=True)
        data = jax.profiler.ProfileData.from_file(path)
        lines = {}
        for plane in data.planes:
            if plane.name.startswith("/host:"):
                for k, line in enumerate(plane.lines):     # one a thread
                    for ev in line.events:
                        if ev.name.startswith(("async.", "ps.")):
                            lines.setdefault(ev.name, []).append(
                                (k, dict(ev.stats)))
        return lines

    lines = _within(120, traced)
    assert len(lines["async.commit_d2h"]) == 3 and len(lines["ps.apply"]) == 3
    assert not {"async.window", "ps.commit", "ps.handle_commit", "ps.pull",
                "ps.handle_pull"} & set(lines)
    # the worker's and the hub handler's phases lie on different lines
    assert {l for l, _ in lines["async.commit_d2h"]} \
        .isdisjoint({l for l, _ in lines["ps.apply"]})
    assert sorted(int(s["window"]) for _, s in lines["async.commit_d2h"]) == [0, 1, 2]
    assert all("lock_wait_us" in s and int(s["worker"]) == 0
               for _, s in lines["ps.apply"])
    ring = {e["name"] for e in obs.TRACER.events()}
    assert {"async.window", "ps.commit", "ps.handle_commit"} <= ring


def test_sync_plane_phases_and_feed_wait(telemetry, toy_dataset):
    """The sync plane's host time between chunk programs: the chunk's
    transfer where ``Trainer.train``'s prefetch issues it, the keys', the
    dispatch and the loss read in ``run_epoch``; and the trainer's thread
    waiting for its next chunk."""
    import distkeras_tpu as dk
    from distkeras_tpu.models.base import Model, ModelSpec

    spec = ModelSpec(name="mlp", config={"hidden_sizes": (16,), "num_outputs": 2},
                     input_shape=(8,))
    trainer = dk.ADAG(Model.init(spec, seed=0), loss="categorical_crossentropy",
                      batch_size=16, num_epoch=1, num_workers=2,
                      communication_window=4, learning_rate=0.05, seed=0,
                      chunk_windows=2)
    trainer.train(toy_dataset)
    events = obs.TRACER.events()
    by = {}
    for e in events:
        by.setdefault(e["name"], []).append(e)
    chunks = len(by["engine.run_epoch"])     # 1024 / (32 * 4) / 2 = 4
    assert chunks == 4
    assert len(by["engine.dispatch"]) == len(by["engine.device_wait"]) == chunks
    place = by["engine.place"]
    assert sorted(e["attrs"]["what"] for e in place) == ["data"] * chunks + ["keys"] * chunks
    assert len(by["feed.wait"]) == chunks + 1          # the last finds the end
    epoch = by["trainer.epoch"][0]
    for e in by["engine.dispatch"] + by["engine.device_wait"]:
        assert e["parent"]["name"] == "engine.run_epoch"
        assert e["attrs"]["epoch"] == 0 and e["tid"] == epoch["tid"]
    # the chunk's transfer is issued from the prefetch, on the trainer's
    # thread, outside run_epoch
    assert all(e["parent"]["name"] == "trainer.epoch" and e["tid"] == epoch["tid"]
               for e in place if e["attrs"]["what"] == "data")
    assert all(e["tid"] == epoch["tid"] for e in by["feed.wait"])


def test_recv_frame_into_times_the_body_under_the_action(telemetry):
    """``body_span`` gets the frame's action byte and length before the body
    is read, and the payload comes out whole (the hub's ``ps.recv_commit``)."""
    import socket

    from distkeras_tpu.runtime import networking as net

    a, b = socket.socketpair()
    seen = []

    def body_span(action, n):
        seen.append((action, n))
        return obs.phase("ps.recv_commit", bytes=n)

    try:
        arrays = [np.arange(6, dtype=np.float32), np.ones(3, np.float32)]
        net.send_tensors(a, net.ACTION_COMMIT, arrays)
        net.send_raw_frame(a, net.empty_tensor_frame(net.ACTION_PULL))
        buf = bytearray(16)
        action, blobs = net.decode_tensor_views(
            net.recv_frame_into(b, buf, body_span=body_span))
        assert action == net.ACTION_COMMIT
        assert np.array_equal(np.frombuffer(blobs[0], np.float32), arrays[0])
        action, blobs = net.decode_tensor_views(
            net.recv_frame_into(b, buf, body_span=body_span))
        assert action == net.ACTION_PULL and not blobs
        a.sendall(b"\x00" * 9)
        with pytest.raises(net.ProtocolError):
            net.recv_frame_into(b, buf, body_span=body_span)
    finally:
        a.close()
        b.close()
    assert seen == [(net.ACTION_COMMIT, 5 + 8 + 24 + 8 + 12), (net.ACTION_PULL, 5)]
    assert [e["attrs"]["bytes"] for e in obs.TRACER.events()] == [57, 5]


@pytest.mark.parametrize("kw", [{"adaptive": True}, {"pipeline": False},
                                {"transport": "inproc"}],
                         ids=lambda kw: next(iter(kw)))
def test_apply_phase_on_the_other_commit_paths(telemetry, toy_dataset, kw):
    """``ps.apply`` under the center lock whichever way a commit reaches it:
    the adaptive combiner's batch, the blocking exchange (whose ack wait is
    each window's ``async.drain``), the in-process transport (the apply runs
    on the worker's own thread, inside its window)."""
    _tiny_async(toy_dataset, **kw)
    events = obs.TRACER.events()
    applies = [e for e in events if e["name"] == "ps.apply"]
    assert len(applies) == 3
    for e in applies:
        assert e["attrs"]["worker"] == 0 and e["attrs"]["batch"] == 1
        assert e["attrs"]["lock_wait_us"] >= 0
        assert e["parent"]["name"] == "ps.handle_commit"
    assert sorted(e["attrs"]["clock"] for e in applies) == [0, 1, 2]
    drains = [e for e in events if e["name"] == "async.drain"]
    assert len(drains) == (4 if kw.get("pipeline") is False else 1)
    windows = {e["tid"] for e in events if e["name"] == "async.window"}
    assert ({e["tid"] for e in applies} == windows) == (kw.get("transport") == "inproc")
