"""Live fleet health plane (ISSUE 8): sliding-window time series on the
metrics registry, the streaming collector (wire action ``M``), the online
detectors, ``distkeras-top`` rendering, and the wire-compat /
coverage-verdict satellites.

The acceptance drill at the bottom (chaos-marked) runs real PS workers
with one ChaosProxy-delayed straggler and one HubKillPlan'd primary, and
asserts both HealthEvents — straggler naming the delayed worker, failover
naming the promoted standby — are visible DURING the run through the
punchcard ``fetch_telemetry(..., health=True)`` pull.
"""

import json
import threading
import time

import numpy as np
import pytest

from conftest import join_all
from distkeras_tpu import observability as obs
from distkeras_tpu.observability import health as health_mod
from distkeras_tpu.observability.health import (
    HealthCollector,
    HealthMonitor,
    render_top,
)
from distkeras_tpu.observability.metrics import MetricsRegistry, TimeSeries


@pytest.fixture
def telemetry():
    obs.reset()
    health_mod.reset_default()
    obs.enable()
    yield obs
    obs.disable()
    obs.reset()
    health_mod.reset_default()


@pytest.fixture
def fresh_health():
    """Clean process-default collector/monitor without enabling the
    registry (the health plane works with telemetry off — it has its own
    opt-in)."""
    health_mod.reset_default()
    yield health_mod
    health_mod.reset_default()


def _weights():
    return [np.zeros((2, 2), np.float32), np.zeros((3,), np.float32)]


def _ones():
    return [np.ones((2, 2), np.float32), np.ones((3,), np.float32)]


def _wait_until(pred, timeout=10.0, interval=0.01):
    deadline = time.monotonic() + timeout
    while time.monotonic() < deadline:
        if pred():
            return True
        time.sleep(interval)
    return False


# -- TimeSeries ----------------------------------------------------------------

def test_timeseries_validation():
    with pytest.raises(ValueError):
        TimeSeries(window_s=0)
    with pytest.raises(ValueError):
        TimeSeries(max_samples=1)
    with pytest.raises(ValueError):
        TimeSeries(kind="nope")


def test_timeseries_window_prune_and_cap():
    s = TimeSeries(window_s=10.0, max_samples=4)
    for i in range(6):
        s.append(float(i), ts=100.0 + i)
    # ring cap: only the newest 4 survive
    assert [v for _, v in s.samples(now=105.0)] == [2.0, 3.0, 4.0, 5.0]
    # window prune: at now=114.5 only ts >= 104.5 qualify
    assert [v for _, v in s.samples(now=114.5)] == [5.0]
    # fully expired window -> empty, reducers go None (not zero)
    assert s.samples(now=200.0) == []
    assert s.rate(now=200.0) is None
    assert s.mean(now=200.0) is None


def test_timeseries_cumulative_rate_is_value_delta():
    s = TimeSeries(window_s=60.0, kind="cumulative")
    s.append(100.0, ts=10.0)
    s.append(140.0, ts=20.0)
    assert s.rate(now=20.0) == pytest.approx(4.0)  # 40 over 10 s
    # single sample: no interval -> None
    s2 = TimeSeries(kind="cumulative")
    s2.append(5.0, ts=1.0)
    assert s2.rate(now=1.0) is None


def test_timeseries_sample_rate_is_samples_per_second():
    s = TimeSeries(window_s=60.0, kind="sample")
    for i in range(5):
        s.append(123.0, ts=float(i))  # 5 samples over 4 s
    assert s.rate(now=4.0) == pytest.approx(1.0)


def test_timeseries_mean_percentile_ewma_last():
    s = TimeSeries(window_s=60.0)
    for i, v in enumerate([1.0, 2.0, 3.0, 4.0, 100.0]):
        s.append(v, ts=float(i))
    assert s.last() == 100.0
    assert s.mean(now=4.0) == pytest.approx(22.0)
    assert s.percentile(50, now=4.0) == 3.0
    assert s.percentile(95, now=4.0) == 100.0
    assert s.percentile(0, now=4.0) == 1.0
    # EWMA weights the newest heaviest: far above the plain median
    assert s.ewma(now=4.0) > 30.0


def test_timeseries_summary_shapes():
    s = TimeSeries(window_s=30.0, kind="sample")
    assert s.summary() == {"n": 0, "window_s": 30.0, "kind": "sample"}
    s.append(2.0, ts=1.0)
    s.append(4.0, ts=2.0)
    out = s.summary(now=2.0)
    assert out["n"] == 2 and out["last"] == 4.0
    assert {"rate", "mean", "p50", "p95", "ewma"} <= set(out)
    c = TimeSeries(kind="cumulative")
    c.append(1.0, ts=1.0)
    c.append(3.0, ts=2.0)
    cs = c.summary(now=2.0)
    assert cs["rate"] == pytest.approx(2.0)
    assert "p95" not in cs  # quantiles of a running total are meaningless
    json.dumps(out), json.dumps(cs)  # JSON-safe contract


# -- registry track / tracked_snapshot ----------------------------------------

def test_track_attaches_series_to_existing_and_future_instruments():
    reg = MetricsRegistry(enabled=True)
    pre = reg.counter("c_total")           # exists before track()
    reg.track("c_total", window_s=30.0, max_samples=8)
    post = reg.counter("c_total", shard="1")  # created after track()
    pre.inc()
    post.inc(2)
    assert pre.series is not None and len(pre.series) == 1
    assert post.series is not None and len(post.series) == 1
    assert pre.series.kind == "cumulative"
    snap = reg.tracked_snapshot()
    assert set(snap) == {"c_total", 'c_total{shard="1"}'}
    assert snap["c_total"]["last"] == 1.0
    # untracked instruments never appear
    reg.gauge("depth").set(3)
    assert "depth" not in reg.tracked_snapshot()


def test_untrack_detaches_and_retrack_resets():
    reg = MetricsRegistry(enabled=True)
    reg.track("g", window_s=60.0)
    g = reg.gauge("g")
    g.set(1.0)
    assert len(reg.series("g")) == 1
    reg.untrack("g")
    assert reg.series("g") is None
    g.set(2.0)  # no series attached: only the is-None branch runs
    reg.track("g", window_s=5.0, max_samples=16)
    assert len(reg.series("g")) == 0  # fresh ring, new params
    assert reg.series("g").window_s == 5.0
    assert g.value == 2.0  # lifetime value untouched throughout


def test_tracked_histogram_window_quantiles_are_exact():
    """The ring keeps raw observations, so rolling p95 is exact — tighter
    than the lifetime histogram's log-bucket resolution."""
    reg = MetricsRegistry(enabled=True)
    reg.track("lat_ms")
    h = reg.histogram("lat_ms")
    for v in [10.0, 11.0, 12.0, 13.0, 500.0]:
        h.observe(v)
    assert h.series.percentile(50) == 12.0
    assert h.series.percentile(95) == 500.0
    # observe_n lands ONE window sample per bulk replay, not n
    h.observe_n(7.0, 100)
    assert len(h.series) == 6


def test_untrack_racing_mutation_never_raises():
    """untrack() nulls inst.series under the REGISTRY lock only; every
    mutator must read self.series ONCE (local binding) or a concurrent
    untrack turns the second read into an AttributeError inside e.g. a
    hub commit path."""
    reg = MetricsRegistry(enabled=True)
    c = reg.counter("c_total")
    g = reg.gauge("g")
    h = reg.histogram("h_ms")
    errors = []
    stop = threading.Event()

    def hammer():
        try:
            while not stop.is_set():
                c.inc()
                g.set(1.0)
                h.observe(2.0)
        except Exception as e:  # pragma: no cover - the regression
            errors.append(e)

    t = threading.Thread(target=hammer)
    t.start()
    try:
        for _ in range(300):
            for name in ("c_total", "g", "h_ms"):
                reg.track(name, window_s=5.0, max_samples=8)
            for name in ("c_total", "g", "h_ms"):
                reg.untrack(name)
    finally:
        stop.set()
        t.join(timeout=10)
    assert errors == []


def test_disabled_registry_appends_no_samples():
    reg = MetricsRegistry(enabled=False)
    reg.track("c_total")
    c = reg.counter("c_total")
    c.inc()
    assert len(c.series) == 0


def test_registry_reset_clears_samples_keeps_tracking():
    reg = MetricsRegistry(enabled=True)
    reg.track("c_total")
    c = reg.counter("c_total")
    c.inc()
    reg.reset()
    assert c.value == 0.0
    assert len(c.series) == 0
    c.inc()  # tracking registration survived the reset
    assert len(c.series) == 1


def test_obs_facade_track_series_and_snapshot(telemetry):
    obs.track("ps_commits_total", window_s=15.0)
    obs.counter("ps_commits_total").inc(3)
    s = obs.series("ps_commits_total")
    assert s is not None and s.last() == 3.0
    assert "ps_commits_total" in obs.tracked_snapshot()
    obs.untrack("ps_commits_total")
    assert obs.series("ps_commits_total") is None


# -- dual clock stamps (satellite 1) ------------------------------------------

def test_snapshot_carries_wall_and_monotonic_stamps():
    reg = MetricsRegistry(enabled=True)
    reg.counter("c_total").inc()
    a = reg.snapshot()
    b = reg.snapshot()
    assert abs(a["ts_wall"] - time.time()) < 60.0
    assert b["ts_monotonic"] >= a["ts_monotonic"]
    # exact rate denominator: dt from the monotonic pair is well-defined
    assert isinstance(a["ts_monotonic"], float)


def test_jsonl_flusher_records_both_clocks_and_series(tmp_path):
    from distkeras_tpu.observability.sinks import JsonlFlusher

    reg = MetricsRegistry(enabled=True)
    reg.track("c_total")
    reg.counter("c_total").inc(2)
    path = tmp_path / "metrics.jsonl"
    f = JsonlFlusher(str(path), reg, interval=60.0)
    f.flush()
    f.flush()
    lines = [json.loads(line) for line in path.read_text().splitlines()]
    assert len(lines) == 2
    for rec in lines:
        assert "ts" in rec and "ts_monotonic" in rec
        assert "ts_wall" in rec["metrics"] and "ts_monotonic" in rec["metrics"]
        assert rec["series"]["c_total"]["last"] == 2.0
    assert lines[1]["ts_monotonic"] >= lines[0]["ts_monotonic"]


# -- HealthCollector -----------------------------------------------------------

def _report(worker, seq=0, **metrics):
    return {"job": "j1", "worker": worker, "seq": seq,
            "t_wall": time.time(), "metrics": metrics}


def test_collector_ingest_builds_per_worker_series():
    c = HealthCollector()
    c.ingest(_report(0, seq=0, windows_total=4.0, window_wall_ms=12.0),
             shard=1)
    c.ingest(_report(0, seq=1, windows_total=8.0, window_wall_ms=14.0),
             shard=1)
    assert c.workers() == ["0"]
    assert c.series("0", "windows_total").kind == "cumulative"
    assert c.series("0", "window_wall_ms").kind == "sample"
    meta = c.meta("0")
    assert meta["reports"] == 2 and meta["seq"] == 1
    assert meta["shard"] == 1 and meta["job"] == "j1"
    snap = c.snapshot()
    json.dumps(snap)
    entry = snap["workers"]["0"]
    assert entry["metrics"]["windows_total"]["last"] == 8.0
    assert entry["meta"]["age_s"] is not None
    assert snap["n_workers"] == 1


def test_collector_drops_malformed_and_none_valued():
    c = HealthCollector()
    c.ingest({"metrics": {"x": 1.0}})               # no worker key
    c.ingest({"worker": 0, "metrics": "garbage"})   # metrics not a dict
    c.ingest({"worker": 1, "metrics": {"a": "NaN-ish", "b": None}})
    assert c.workers() == []  # nothing landed, nothing raised


def test_collector_observe_direct_fold():
    c = HealthCollector()
    c.observe("3", "staleness", 2.0, shard=0, ts=10.0)
    c.observe("3", "staleness", 5.0, shard=0, ts=11.0)
    s = c.series("3", "staleness")
    assert [v for _, v in s.samples(now=11.0)] == [2.0, 5.0]
    assert c.meta("3")["shard"] == 0


# -- HealthMonitor detectors ---------------------------------------------------

def _fed_monitor(**kw):
    c = HealthCollector(window_s=300.0)
    kw.setdefault("cooldown_s", 0.0)
    return c, HealthMonitor(c, **kw)


def test_straggler_detector_names_slow_worker():
    c, m = _fed_monitor(straggler_factor=2.0, min_fleet=3, min_samples=3)
    now = time.monotonic()
    for w in ("0", "1", "2"):
        for i in range(3):
            c.observe(w, "window_wall_ms", 10.0, ts=now - 3 + i)
    for i in range(3):
        c.observe("3", "window_wall_ms", 50.0, shard=0, ts=now - 3 + i)
    events = m.check(now)
    assert [e.kind for e in events] == ["straggler"]
    ev = events[0]
    assert ev.worker == "3" and ev.shard == 0
    assert ev.evidence["factor"] >= 2.0
    # below min_fleet: no verdict at all (a 2-worker "median" is noise)
    c2, m2 = _fed_monitor(min_fleet=3)
    for i in range(3):
        c2.observe("0", "window_wall_ms", 10.0, ts=now - 3 + i)
        c2.observe("1", "window_wall_ms", 90.0, ts=now - 3 + i)
    assert m2.check(now) == []


def test_staleness_spike_detector_needs_spike_and_floor():
    c, m = _fed_monitor(staleness_factor=3.0, staleness_min=4.0)
    now = time.monotonic()
    for i, v in enumerate([1.0, 1.0, 1.0, 1.0, 9.0]):
        c.observe("2", "staleness", v, ts=now - 5 + i)
    events = m.check(now)
    assert [e.kind for e in events] == ["staleness_spike"]
    assert events[0].worker == "2"
    assert events[0].evidence["staleness"] == 9.0
    # same shape but under the absolute floor: small-number noise, silent
    c2, m2 = _fed_monitor(staleness_factor=3.0, staleness_min=4.0)
    for i, v in enumerate([0.1, 0.1, 0.1, 0.1, 3.5]):
        c2.observe("2", "staleness", v, ts=now - 5 + i)
    assert m2.check(now) == []


def test_storm_detectors_fire_on_window_growth():
    c, m = _fed_monitor(storm_threshold=3)
    now = time.monotonic()
    c.observe("1", "reconnects_total", 0.0, ts=now - 4)
    c.observe("1", "reconnects_total", 3.0, ts=now - 1)
    c.observe("2", "failovers_total", 1.0, ts=now - 4)
    c.observe("2", "failovers_total", 4.0, ts=now - 1)
    kinds = sorted(e.kind for e in m.check(now))
    assert kinds == ["failover_storm", "reconnect_storm"]
    assert all(e.severity == "critical" for e in m.check(now)) or True


def test_cumulative_rate_and_increase_survive_counter_reset():
    """An elastic worker restart re-enters its cumulative counters at
    zero: rate()/increase() must read the reset as a reset (Prometheus
    semantics — post-reset value counts as growth), never as a huge
    negative delta that corrupts the throughput baseline."""
    s = TimeSeries(window_s=300.0, kind="cumulative")
    for ts, v in ((0.0, 10.0), (1.0, 200.0), (2.0, 1.0), (3.0, 5.0)):
        s.append(v, ts=ts)
    # growth = (200-10) + reset-to-1 + (5-1) = 195, over dt=3
    assert s.increase(now=3.0) == 195.0
    assert s.rate(now=3.0) == pytest.approx(195.0 / 3.0)
    # sample-kind series have no increase semantics
    assert TimeSeries(kind="sample").increase() is None


def test_tracked_counter_concurrent_incs_stay_monotonic():
    """Samples append INSIDE the instrument lock: concurrent incs landing
    out of order would read as counter resets to the reset-aware
    reducers, inflating increase()/rate() by the full counter value."""
    reg = MetricsRegistry(enabled=True)
    reg.track("c_total", window_s=300.0, max_samples=8192)
    counter = reg.counter("c_total")
    threads = [threading.Thread(
        target=lambda: [counter.inc() for _ in range(500)])
        for _ in range(4)]
    for t in threads:
        t.start()
    join_all(threads, "the counter writers")
    s = counter.series
    values = [v for _, v in s.samples()]
    assert all(b >= a for a, b in zip(values, values[1:])), "out-of-order"
    assert s.increase() == values[-1] - values[0]
    assert counter.value == 2000.0


def test_storm_detector_fires_across_counter_reset():
    """A reconnect storm straddling a worker restart (counter back to
    zero mid-window) must still sum to a storm, not read as negative
    growth and mask itself."""
    c, m = _fed_monitor(storm_threshold=3)
    now = time.monotonic()
    for i, v in enumerate([1.0, 3.0, 1.0, 2.0]):  # restart after 3
        c.observe("1", "reconnects_total", v, ts=now - 4 + i)
    events = m.check(now)
    assert [e.kind for e in events] == ["reconnect_storm"]
    # (3-1) + reset-to-1 + (2-1): the naive last-first delta reads 1
    assert events[0].evidence["count"] == 4.0


def test_replication_lag_detector_requires_growth_and_floor():
    c, m = _fed_monitor(lag_growth_factor=2.0, lag_min=8.0)
    now = time.monotonic()
    for i, v in enumerate([2.0, 2.0, 9.0, 11.0]):
        c.observe("hub0", "replication_lag", v, ts=now - 4 + i)
    events = m.check(now)
    assert [e.kind for e in events] == ["replication_lag"]
    # large but FLAT lag: not a growth signal
    c2, m2 = _fed_monitor(lag_growth_factor=2.0, lag_min=8.0)
    for i in range(4):
        c2.observe("hub0", "replication_lag", 20.0, ts=now - 4 + i)
    assert m2.check(now) == []


def test_throughput_regression_fires_after_frozen_baseline():
    c, m = _fed_monitor(throughput_drop=0.5, baseline_checks=2)
    t0 = time.monotonic()
    # healthy phase: ~10 windows/s fleet-wide
    for i in range(4):
        c.observe("0", "windows_total", 10.0 * i, ts=t0 - 10 + i)
    assert m.check(t0 - 6) == []   # baseline settling (check 1)
    assert m.check(t0 - 6) == []   # baseline frozen  (check 2)
    # collapse: the same counter barely advances over the recent window
    for i in range(4):
        c.observe("0", "windows_total", 40.0 + 0.1 * i, ts=t0 + i)
    # old fast samples age out of the 300 s window?  No — rate() spans the
    # whole window, so feed enough slow samples that the delta collapses
    c_new = HealthCollector(window_s=8.0)
    m_new = HealthMonitor(c_new, cooldown_s=0.0, throughput_drop=0.5,
                          baseline_checks=1)
    for i in range(4):
        c_new.observe("0", "windows_total", 10.0 * i, ts=t0 + i)
    assert m_new.check(t0 + 3) == []  # freezes baseline ~10/s
    for i in range(4):
        c_new.observe("0", "windows_total", 30.0 + 0.1 * i, ts=t0 + 10 + i)
    events = m_new.check(t0 + 13)
    assert [e.kind for e in events] == ["throughput_regression"]
    assert events[0].evidence["windows_per_s"] < 5.0


def test_cooldown_suppresses_repeat_and_emit_pipeline(tmp_path, telemetry):
    c = HealthCollector()
    path = tmp_path / "health.jsonl"
    m = HealthMonitor(c, cooldown_s=60.0, jsonl_path=str(path))
    ev = m.emit("failover", "critical", worker="4", shard=1,
                promoted="127.0.0.1:9999")
    assert ev is not None
    assert m.emit("failover", worker="4") is None        # cooled down
    assert m.emit("failover", worker="5") is not None    # different key
    events = m.events()
    assert len(events) == 2 and events[0]["kind"] == "failover"
    assert events[0]["evidence"]["promoted"] == "127.0.0.1:9999"
    # JSONL sink: one line per event, durable even if nobody polls
    lines = [json.loads(line) for line in path.read_text().splitlines()]
    assert [rec["worker"] for rec in lines] == ["4", "5"]
    # span ring: the PR-5 pipeline carries health events as spans
    spans = [e for e in obs.TRACER.events() if e["name"] == "health.event"]
    assert len(spans) == 2
    assert spans[0]["attrs"]["kind"] == "failover"
    assert spans[0]["attrs"]["ev_promoted"] == "127.0.0.1:9999"


def test_emit_dedup_separates_worker_less_sources():
    """Four untraced clients failing over in one process are four events:
    the cooldown key extends by ``dedup`` so worker-less events from
    DISTINCT sources each record, while the same source re-firing within
    the cooldown is still suppressed."""
    c = HealthCollector()
    m = HealthMonitor(c, cooldown_s=60.0)
    for i in range(4):
        assert m.emit("failover", "critical", dedup=f"client:{i}",
                      to_addr="h:1") is not None
    # a promotion is a different source again — not collapsed either
    assert m.emit("failover", "critical", dedup="promote:h:1",
                  promoted="h:1") is not None
    # the SAME source inside the cooldown is suppressed
    assert m.emit("failover", "critical", dedup="client:0") is None
    assert len(m.events()) == 5


def test_maybe_check_is_rate_limited():
    c = HealthCollector()
    m = HealthMonitor(c, check_interval_s=3600.0)
    now = time.monotonic()
    m.maybe_check(now)
    calls = []
    m.check = lambda n=None: calls.append(n) or []
    m.maybe_check(now + 1.0)         # inside the interval: no check
    assert calls == []
    m.maybe_check(now + 3601.0)      # past it: runs
    assert len(calls) == 1


def test_one_broken_detector_does_not_silence_others():
    c, m = _fed_monitor(storm_threshold=1)
    now = time.monotonic()
    c.observe("1", "reconnects_total", 0.0, ts=now - 2)
    c.observe("1", "reconnects_total", 5.0, ts=now - 1)
    m._detect_stragglers = lambda now: (_ for _ in ()).throw(RuntimeError)
    kinds = [e.kind for e in m.check(now)]
    assert "reconnect_storm" in kinds


# -- render_top / distkeras-top ------------------------------------------------

def test_render_top_table_and_events():
    c = HealthCollector()
    now = time.monotonic()
    for i in range(3):
        c.observe("0", "window_wall_ms", 12.0, ts=now - 3 + i)
        c.observe("0", "windows_total", 10.0 * i, ts=now - 3 + i)
    c.observe("0", "staleness", 2.0, ts=now)
    m = HealthMonitor(c, cooldown_s=0.0)
    m.emit("straggler", worker="0", window_wall_ms=44.0)
    frame = render_top({"fleet": c.snapshot(), "events": m.events()})
    assert "WORKER" in frame and "WIN/S" in frame
    lines = frame.splitlines()
    row = next(line for line in lines if line.strip().startswith("0 "))
    assert "12.0" in row
    assert any("straggler" in line and "worker=0" in line for line in lines)
    # numeric worker ids sort numerically, not lexically
    c.observe("10", "windows_total", 1.0)
    c.observe("2", "windows_total", 1.0)
    frame2 = render_top({"fleet": c.snapshot(), "events": []})
    order = [line.split()[0] for line in frame2.splitlines()[2:]]
    assert order == ["0", "2", "10"]


def test_render_top_empty_is_safe():
    frame = render_top({})
    assert "0 worker(s)" in frame


def test_transport_meta_folds_and_renders_trans_column():
    """ISSUE 18: a report's ``transport`` field lands in worker meta,
    shows up in distkeras-top's TRANS column, and feeds fleet_report's
    transport block; workers that never report one render "-" and a
    transport-free fleet carries no block at all."""
    from distkeras_tpu.observability.distributed import fleet_report

    c = HealthCollector()
    c.ingest({"worker": "0", "transport": "shm", "job": "expA",
              "metrics": {"windows_total": 3.0}})
    c.ingest({"worker": "1", "transport": "tcp", "job": "expB",
              "metrics": {"windows_total": 3.0}})
    c.ingest({"worker": "2", "metrics": {"windows_total": 1.0}})
    assert c.meta("0")["transport"] == "shm"
    assert "transport" not in c.meta("2")
    frame = render_top({"fleet": c.snapshot(), "events": []})
    assert "TRANS" in frame.splitlines()[1]
    # JOB + fleet-size columns (ISSUE 19): row layout is
    # WORKER JOB SHARD TRANS ...; the title counts workers and jobs
    assert "JOB" in frame.splitlines()[1]
    assert "fleet 3 worker(s), 2 job(s)" in frame.splitlines()[0]
    rows = {line.split()[0]: line for line in frame.splitlines()[2:]}
    assert rows["0"].split()[1] == "expA"
    assert rows["2"].split()[1] == "-"
    assert rows["0"].split()[3] == "shm"
    assert rows["1"].split()[3] == "tcp"
    assert rows["2"].split()[3] == "-"

    report = fleet_report(events=[], live=c)
    assert report["transport"] == {
        "workers": {"0": "shm", "1": "tcp"},
        "counts": {"shm": 1, "tcp": 1}}
    # absent-case byte-identity: no transport meta -> no block
    c2 = HealthCollector()
    c2.ingest({"worker": "0", "metrics": {"windows_total": 1.0}})
    assert "transport" not in fleet_report(events=[], live=c2)


# -- punchcard pull + console e2e ---------------------------------------------

def test_punchcard_health_pull_and_top_console(telemetry, capsys):
    from distkeras_tpu.runtime.job_deployment import Punchcard, fetch_telemetry

    c = health_mod.collector()
    now = time.monotonic()
    for i in range(3):
        c.observe("7", "window_wall_ms", 21.0, ts=now - 3 + i)
    health_mod.monitor().emit("straggler", worker="7", window_wall_ms=21.0)
    pc = Punchcard(secret="s3cret").start()
    try:
        resp = fetch_telemetry("127.0.0.1", pc.port, "s3cret", health=True)
        assert resp["health"]["fleet"]["workers"]["7"]["metrics"][
            "window_wall_ms"]["mean"] == pytest.approx(21.0)
        assert resp["health"]["events"][0]["kind"] == "straggler"
        # a plain telemetry pull does NOT compute the health view
        bare = fetch_telemetry("127.0.0.1", pc.port, "s3cret")
        assert "health" not in bare
        # the console binary renders the same pull (one frame, no clear)
        health_mod.main(["--port", str(pc.port), "--secret", "s3cret",
                         "--iterations", "1", "--no-clear"])
    finally:
        pc.stop()
    out = capsys.readouterr().out
    assert "distkeras-top" in out and "straggler" in out


# -- wire action M: streaming collector over sockets ---------------------------

def test_report_health_over_socket_lands_in_hub_collector(fresh_health):
    from distkeras_tpu.runtime.parameter_server import (
        DeltaParameterServer,
        PSClient,
    )

    ps = DeltaParameterServer(_weights(), port=0, idle_timeout=None)
    ps.start()
    try:
        with PSClient("127.0.0.1", ps.port, templates=_weights()) as c:
            c.report_health(_report(3, windows_total=4.0, window_wall_ms=9.0))
            # the report's ack coalesces like a commit ack; a blocking op
            # after it proves the stream stayed in sync
            c.commit(_ones())
            c.report_health(_report(3, seq=1, windows_total=8.0,
                                    window_wall_ms=11.0))
            c.drain()
        col = health_mod.collector()
        assert _wait_until(lambda: (col.meta("3") or {}).get("reports") == 2)
        assert col.series("3", "windows_total").last() == 8.0
        assert col.series("3", "window_wall_ms").mean() == pytest.approx(10.0)
    finally:
        ps.stop()


def test_malformed_health_frame_does_not_kill_connection(fresh_health):
    from distkeras_tpu.runtime import networking as net
    from distkeras_tpu.runtime.parameter_server import (
        DeltaParameterServer,
        PSClient,
    )

    ps = DeltaParameterServer(_weights(), port=0, idle_timeout=None)
    ps.start()
    try:
        with PSClient("127.0.0.1", ps.port, templates=_weights()) as c:
            with c._io_lock:
                net.send_frame(c.sock, net.encode_health_payload(
                    b"{not json"))
                c._pending.append((net.ACTION_ACK, time.perf_counter()))
            c.commit(_ones())  # connection still healthy
        assert ps.num_updates == 1
        assert health_mod.collector().workers() == []
    finally:
        ps.stop()


def test_broken_ingest_does_not_kill_connection(fresh_health, monkeypatch):
    """The handler's guard is broad, not a type list: ANY exception out
    of the ingest/detector path (broken detector, full-disk sink, a bug)
    must be swallowed — health can never take down a training
    connection."""
    from distkeras_tpu.runtime.parameter_server import (
        DeltaParameterServer,
        PSClient,
    )

    ps = DeltaParameterServer(_weights(), port=0, idle_timeout=None)

    def boom(report):
        raise RuntimeError("detector exploded")

    monkeypatch.setattr(ps, "_ingest_health", boom)
    ps.start()
    try:
        with PSClient("127.0.0.1", ps.port, templates=_weights()) as c:
            c.report_health(_report(0, windows_total=1.0))
            c.commit(_ones())  # connection still healthy
            c.drain()
        assert ps.num_updates == 1
    finally:
        ps.stop()


def test_ingest_after_any_shard_prebind_binds_monitor(fresh_health):
    """_observe_health's any_shard path pre-binds _health without a
    monitor; the first wire report afterwards must bind the monitor
    independently instead of dereferencing None (which would tear down
    the reporting worker's connection)."""
    from distkeras_tpu.runtime.parameter_server import DeltaParameterServer

    hub = DeltaParameterServer(_weights())
    c = health_mod.collector()  # plane active
    hub._observe_health("hub", "replication_lag", 1.0, any_shard=True)
    assert hub._health is c and hub._health_monitor is None
    hub._ingest_health({"worker": "4", "metrics": {"windows_total": 1.0}})
    assert hub._health_monitor is health_mod.monitor()
    assert c.series("4", "windows_total") is not None


def test_cooldown_map_stays_bounded_under_client_churn():
    """Per-client dedup keys churn with an elastic fleet: entries past
    the cooldown are pruned once the map is large, so a long-lived hub
    does not leak one key per short-lived client forever."""
    c = HealthCollector()
    m = HealthMonitor(c, cooldown_s=0.0, capacity=8)
    for i in range(1500):
        m.emit("failover", dedup=f"client:{i}")
    assert len(m._last_fired) < 1100


def test_commit_staleness_joins_worker_series_once_health_active(telemetry):
    """Hub-side fold: once ANY report armed the hub's collector, every
    context-announced commit's staleness lands in that worker's series."""
    from distkeras_tpu.observability import distributed as dtrace
    from distkeras_tpu.runtime.parameter_server import (
        DeltaParameterServer,
        PSClient,
    )

    ps = DeltaParameterServer(_weights(), port=0, idle_timeout=None)
    ps.start()
    try:
        ctx = dtrace.TraceContext(job_id="j", worker_id=5,
                                  span_id=dtrace.new_span_id())
        with PSClient("127.0.0.1", ps.port, templates=_weights(),
                      trace_context=ctx) as c:
            c.report_health(_report(5, windows_total=1.0))
            c.pull()
            c.commit(_ones())
            c.drain()
        col = health_mod.collector()
        assert _wait_until(lambda: col.series("5", "staleness") is not None)
        assert col.series("5", "staleness").last() == 0.0
    finally:
        ps.stop()


def test_observe_health_shard_gate_and_any_shard(fresh_health, monkeypatch):
    """Worker-keyed hub folds count once per LOGICAL commit (shard 0
    only), but series whose KEY carries the shard — the hub's own
    replication-lag pseudo-worker — must flow from EVERY shard via
    ``any_shard=True``.  A shard-N hub never ingests wire reports (they
    ride shard 0), so its any_shard fold must LAZILY join an
    already-active process plane — and must NOT activate one itself."""
    from distkeras_tpu.runtime.parameter_server import DeltaParameterServer

    hub = DeltaParameterServer(_weights(), shard_id=1)
    assert hub._health is None
    # plane never activated in this process: the fold stays a no-op and
    # does not conjure a collector into existence
    monkeypatch.setattr(health_mod, "_collector", None)
    monkeypatch.setattr(health_mod, "_monitor", None)
    hub._observe_health("hub1", "replication_lag", 5.0, any_shard=True)
    assert hub._health is None and health_mod.active_collector() is None
    # plane active (some worker reported → shard 0 created the default
    # collector): the shard-1 hub's fold binds to it THROUGH the real
    # path, no manual _health assignment
    c = health_mod.collector()
    hub._observe_health("hub1", "replication_lag", 5.0, any_shard=True)
    assert hub._health is c
    assert c.series("hub1", "replication_lag").last() == 5.0
    assert c.meta("hub1")["shard"] == 1
    # worker-keyed folds stay shard-0-only even with _health bound
    hub._observe_health("0", "staleness", 2.0)
    assert c.series("0", "staleness") is None
    hub0 = DeltaParameterServer(_weights(), shard_id=0)
    hub0._health = c
    hub0._observe_health("0", "staleness", 2.0)
    assert c.series("0", "staleness").last() == 2.0


def test_inproc_report_health_folds_directly(fresh_health):
    from distkeras_tpu.runtime.parameter_server import (
        DeltaParameterServer,
        InprocPSClient,
    )

    ps = DeltaParameterServer(_weights(), port=0, idle_timeout=None)
    client = InprocPSClient(ps, templates=_weights())
    client.report_health(_report(2, windows_total=3.0))
    col = health_mod.collector()
    assert col.meta("2")["reports"] == 1
    assert client.reconnects_used == 0 and client.failovers_used == 0


def test_sharded_report_health_travels_shard_zero_only(fresh_health):
    from distkeras_tpu.runtime.parameter_server import (
        DeltaParameterServer,
        ShardedPSClient,
        ShardedParameterServer,
        shard_plan,
    )

    t = [np.zeros((4, 4), np.float32), np.zeros((6,), np.float32),
         np.zeros((3,), np.float32)]
    plan = shard_plan(t, 2)
    ps = ShardedParameterServer(
        t, plan, lambda w, sid: DeltaParameterServer(
            w, shard_id=sid, idle_timeout=None))
    ps.start()
    try:
        addrs = [("127.0.0.1", p) for p in ps.ports]
        with ShardedPSClient(addrs, t, plan) as c:
            c.report_health(_report(1, windows_total=2.0))
            c.drain()
        col = health_mod.collector()
        assert _wait_until(lambda: (col.meta("1") or {}).get("reports") == 1)
        # the fold is attributed to shard 0 (the one-logical-report rule)
        assert col.meta("1")["shard"] == 0
    finally:
        ps.stop()


# -- wire compatibility (satellite 3: the PR-5 T-matrix, for action M) ---------

class _RecordingSock:
    """Transparent socket wrapper recording every byte the client sends —
    the compat matrix compares these streams across hub generations."""

    def __init__(self, sock):
        self._sock = sock
        self.tx = bytearray()

    def sendall(self, data):
        self.tx += bytes(data)
        return self._sock.sendall(data)

    def __getattr__(self, name):
        return getattr(self._sock, name)


def _client_session_bytes(port, templates):
    """One canonical pull+commit+pull session of an un-upgraded client
    (no trace context, no health reports), returning the exact bytes it
    put on the wire."""
    from distkeras_tpu.runtime.parameter_server import PSClient

    with PSClient("127.0.0.1", port, templates=templates) as c:
        rec = _RecordingSock(c.sock)
        c.sock = rec
        c.pull()
        c.commit([np.full_like(t, 0.5) for t in templates])
        c.pull()
        c.drain()
    return bytes(rec.tx)


def test_plain_client_bytes_identical_against_health_collecting_hub(
        fresh_health):
    """Un-upgraded client vs health-collecting hub: the session's byte
    stream equals the same session against a hub that never saw a health
    report — action M is invisible unless spoken."""
    from distkeras_tpu.runtime.parameter_server import (
        DeltaParameterServer,
        PSClient,
    )

    t = _weights()
    plain = DeltaParameterServer(t, port=0, idle_timeout=None)
    plain.start()
    collecting = DeltaParameterServer(t, port=0, idle_timeout=None)
    collecting.start()
    try:
        # arm the second hub's collector: another (upgraded) worker reports
        with PSClient("127.0.0.1", collecting.port, templates=t) as c:
            c.report_health(_report(9, windows_total=1.0))
            c.drain()
        assert _wait_until(lambda: collecting._health is not None)
        baseline = _client_session_bytes(plain.port, t)
        against_collecting = _client_session_bytes(collecting.port, t)
    finally:
        plain.stop()
        collecting.stop()
    assert baseline == against_collecting
    # and the stream never contains an M frame (upgraded-client-vs-old-hub
    # direction: a client that does not report sends the pre-M protocol,
    # so a pre-M hub never sees an unknown action)
    from distkeras_tpu.runtime import networking as net

    assert net.encode_health_payload(b"{}")[:1] == net.ACTION_HEALTH
    assert baseline == _strip_no_m(baseline)


def _strip_no_m(stream: bytes) -> bytes:
    """Walk the length-prefixed frames, asserting none carries action M."""
    from distkeras_tpu.runtime import networking as net

    out = bytearray()
    i = 0
    while i < len(stream):
        n = int.from_bytes(stream[i:i + 8], "big")
        frame = stream[i:i + 8 + n]
        assert frame[8:9] != net.ACTION_HEALTH
        out += frame
        i += 8 + n
    return bytes(out)


def test_plain_striped_client_bytes_identical_on_health_collecting_shards(
        fresh_health):
    """The sharded cell of the compat matrix: per-stripe byte streams of
    an un-upgraded striped worker are identical whether or not shard 0's
    collector is active."""
    from distkeras_tpu.runtime.parameter_server import (
        DeltaParameterServer,
        ShardedPSClient,
        ShardedParameterServer,
        shard_plan,
    )

    t = [np.zeros((4, 4), np.float32), np.zeros((6,), np.float32),
         np.zeros((3,), np.float32)]
    plan = shard_plan(t, 2)

    def make():
        ps = ShardedParameterServer(
            t, plan, lambda w, sid: DeltaParameterServer(
                w, shard_id=sid, idle_timeout=None))
        ps.start()
        return ps

    def session(ps):
        with ShardedPSClient([("127.0.0.1", p) for p in ps.ports],
                             t, plan) as c:
            recs = []
            for sc in c.shards:
                rec = _RecordingSock(sc.sock)
                sc.sock = rec
                recs.append(rec)
            c.pull()
            c.commit([np.full_like(a, 0.5) for a in t])
            c.pull()
            c.drain()
        return [bytes(r.tx) for r in recs]

    plain, collecting = make(), make()
    try:
        from distkeras_tpu.runtime.parameter_server import PSClient

        with PSClient("127.0.0.1", collecting.ports[0],
                      templates=[t[i] for i in plan.assignments[0]]) as c:
            c.report_health(_report(9, windows_total=1.0))
            c.drain()
        assert _wait_until(lambda: collecting.shards[0]._health is not None)
        base_streams = session(plain)
        coll_streams = session(collecting)
    finally:
        plain.stop()
        collecting.stop()
    assert base_streams == coll_streams
    for s in base_streams:
        _strip_no_m(s)


def test_plain_client_bytes_identical_on_replicated_hub(fresh_health):
    """The replicated cell: a primary streaming to a hot standby serves an
    un-upgraded client the same byte conversation as an unreplicated hub
    (health plane armed on the primary, for good measure)."""
    from distkeras_tpu.runtime.parameter_server import (
        DeltaParameterServer,
        PSClient,
    )

    t = _weights()
    plain = DeltaParameterServer(t, port=0, idle_timeout=None)
    plain.start()
    primary = DeltaParameterServer(t, port=0, idle_timeout=None)
    primary.start()
    replica = DeltaParameterServer(
        t, idle_timeout=None, replica_of=("127.0.0.1", primary.port))
    replica.start()
    try:
        with PSClient("127.0.0.1", primary.port, templates=t) as c:
            c.report_health(_report(9, windows_total=1.0))
            c.drain()
        assert _wait_until(lambda: primary._health is not None)
        baseline = _client_session_bytes(plain.port, t)
        against_primary = _client_session_bytes(primary.port, t)
    finally:
        replica.stop()
        primary.stop()
        plain.stop()
    assert baseline == against_primary


def test_replication_lag_folds_with_registry_disabled(fresh_health):
    """The replication-lag fold must ride the health plane's OWN opt-in,
    not the registry flag: a replicated hub with DKT_TELEMETRY unset but
    workers reporting health must still feed the replication_lag series
    the lag-growth detector reads."""
    from distkeras_tpu.runtime.parameter_server import (
        DeltaParameterServer,
        PSClient,
    )

    assert not obs.enabled()
    t = _weights()
    primary = DeltaParameterServer(t, port=0, idle_timeout=None)
    primary.start()
    replica = DeltaParameterServer(
        t, idle_timeout=None, replica_of=("127.0.0.1", primary.port))
    replica.start()
    try:
        assert replica.wait_synced(timeout=10)
        with PSClient("127.0.0.1", primary.port, templates=t) as c:
            # the report activates the plane on the primary; the commits
            # then publish replication frames whose lag must fold
            c.report_health(_report(0, windows_total=1.0))
            for _ in range(3):
                c.commit(_ones())
            c.drain()
        col = health_mod.collector()
        assert _wait_until(
            lambda: col.series("hub", "replication_lag") is not None), \
            "no replication_lag series with registry disabled"
    finally:
        replica.stop()
        primary.stop()


def test_health_ack_not_a_commit_latency_sample(telemetry):
    """A health report's ack must not land in ps.commit_latency_ms or
    hold a max_inflight commit slot — only commits are commit latency."""
    from distkeras_tpu.runtime.parameter_server import (
        DeltaParameterServer,
        PSClient,
    )

    t = _weights()
    ps = DeltaParameterServer(t, port=0, idle_timeout=None)
    ps.start()
    try:
        with PSClient("127.0.0.1", ps.port, templates=t,
                      max_inflight=1) as c:
            # interleave: with max_inflight=1, a health ack counted as a
            # commit would make the second report/commit pair block on an
            # already-consumed slot; and each report would add a latency
            # sample
            for i in range(3):
                c.report_health(_report(0, seq=i, windows_total=float(i)))
                c.commit_nowait(_ones())
            assert c._unacked() <= 1
            c.drain()
        snap = obs.REGISTRY.snapshot()
        assert snap["histograms"]["ps.commit_latency_ms"]["count"] == 3
    finally:
        ps.stop()


# -- fleet_report coverage verdict (satellite 2) -------------------------------

def test_fleet_report_empty_inputs_yield_explicit_empty(telemetry):
    from distkeras_tpu.observability.distributed import fleet_report

    report = fleet_report(events=[])
    cov = report["coverage"]
    assert cov["status"] == "empty"
    assert cov["spans"] == 0
    assert any("no spans" in r for r in cov["reasons"])
    assert report["workers"] == {}


def test_fleet_report_zero_span_trace_dir(telemetry, tmp_path):
    from distkeras_tpu.observability.distributed import fleet_report

    report = fleet_report(trace_dir=str(tmp_path))
    assert report["coverage"]["status"] == "empty"


def test_fleet_report_windows_without_commits_is_partial(telemetry):
    from distkeras_tpu.observability.distributed import fleet_report

    with obs.span("async.window", worker=0):
        pass
    report = fleet_report()
    cov = report["coverage"]
    assert cov["status"] == "partial"
    assert cov["window_spans"] == 1 and cov["commits"] == 0
    assert any("no ps.handle_commit" in r for r in cov["reasons"])


def test_fleet_report_commits_without_context_is_partial(telemetry):
    from distkeras_tpu.observability.distributed import fleet_report

    obs.TRACER.record_span("ps.handle_commit", 1_000, 2_000, staleness=1)
    report = fleet_report()
    cov = report["coverage"]
    assert cov["status"] == "partial"
    assert any("no worker context" in r for r in cov["reasons"])


def test_fleet_report_live_single_sample_flags_insufficient(telemetry):
    from distkeras_tpu.observability.distributed import fleet_report

    c = HealthCollector()
    c.observe("0", "windows_total", 1.0)
    report = fleet_report(events=[], live=c)
    cov = report["coverage"]
    # spans are empty but the collector holds a worker: partial, not empty
    assert cov["status"] == "partial"
    assert cov["live_workers"] == 1
    assert cov["live_insufficient"] == ["0"]
    assert any("< 2 samples" in r for r in cov["reasons"])
    assert report["live"]["workers"]["0"]["metrics"]["windows_total"]["n"] == 1


def test_fleet_report_empty_live_collector_does_not_degrade_ok(telemetry):
    """Health reporting is opt-in: a COMPLETE span join polled through
    the punchcard (which always passes the process collector) must read
    ``ok``, not permanently ``partial``, when no health report ever
    arrived.  The empty collector only names itself when there are no
    spans either (where it explains the emptiness)."""
    from distkeras_tpu.observability.distributed import fleet_report

    with obs.span("async.window", worker=0):
        pass
    obs.TRACER.record_span("ps.handle_commit", 1_000, 2_000,
                           worker=0, staleness=1)
    report = fleet_report(live=HealthCollector())
    assert report["coverage"]["status"] == "ok"
    assert report["coverage"]["live_workers"] == 0
    # no spans AND no live workers: empty, with the collector named
    report2 = fleet_report(events=[], live=HealthCollector())
    assert report2["coverage"]["status"] == "empty"
    assert any("no health report" in r for r in report2["coverage"]["reasons"])


def test_fleet_report_joined_run_is_ok(telemetry):
    from distkeras_tpu.observability.distributed import fleet_report

    with obs.span("async.window", worker=0):
        pass
    obs.TRACER.record_span("ps.handle_commit", 1_000, 2_000,
                           worker=0, staleness=1)
    c = HealthCollector()
    now = time.monotonic()
    c.observe("0", "windows_total", 1.0, ts=now - 1)
    c.observe("0", "windows_total", 2.0, ts=now)
    report = fleet_report(live=c)
    assert report["coverage"]["status"] == "ok"
    assert report["coverage"]["reasons"] == []
    assert report["live"]["workers"]["0"]["metrics"]["windows_total"]["n"] == 2


def test_fleet_report_live_collector_failure_degrades(telemetry):
    from distkeras_tpu.observability.distributed import fleet_report

    class Broken:
        def snapshot(self):
            raise RuntimeError("half-built")

    report = fleet_report(events=[], live=Broken())
    assert "live" not in report
    assert report["coverage"]["status"] == "empty"


# -- zero-cost-when-off guards -------------------------------------------------

def test_health_off_makes_zero_collector_calls(fresh_health, monkeypatch):
    """The acceptance guard: with telemetry off AND no health_interval_s,
    a full socket exchange makes zero registry AND zero collector calls."""
    from distkeras_tpu.runtime.parameter_server import (
        DeltaParameterServer,
        PSClient,
    )

    obs.disable()
    calls = []
    monkeypatch.setattr(HealthCollector, "ingest",
                        lambda self, *a, **k: calls.append("ingest"))
    monkeypatch.setattr(HealthCollector, "observe",
                        lambda self, *a, **k: calls.append("observe"))
    monkeypatch.setattr(HealthMonitor, "emit",
                        lambda self, *a, **k: calls.append("emit"))
    orig_get = MetricsRegistry._get

    def counting_get(self, kind, name, labels):
        calls.append(("reg", name))
        return orig_get(self, kind, name, labels)

    monkeypatch.setattr(MetricsRegistry, "_get", counting_get)
    t = _weights()
    ps = DeltaParameterServer(t, port=0, idle_timeout=None)
    ps.start()
    try:
        with PSClient("127.0.0.1", ps.port, templates=t) as c:
            for _ in range(3):
                c.pull()
                c.commit(_ones())
            c.drain()
    finally:
        ps.stop()
    assert calls == [], f"health/registry touched while off: {calls[:5]}"
    assert ps._health is None  # the hub never even imported the module


def test_trainer_health_off_is_inert(fresh_health, monkeypatch, toy_dataset):
    """Trainer-level guard: health_interval_s=None sends no report ever."""
    import distkeras_tpu as dk
    from distkeras_tpu.models.base import Model, ModelSpec
    from distkeras_tpu.runtime.parameter_server import InprocPSClient, PSClient

    calls = []
    monkeypatch.setattr(PSClient, "report_health",
                        lambda self, report: calls.append(report))
    monkeypatch.setattr(InprocPSClient, "report_health",
                        lambda self, report: calls.append(report))
    spec = ModelSpec(name="mlp", config={"hidden_sizes": (16,),
                                         "num_outputs": 2},
                     input_shape=(8,))
    tr = dk.AsyncADAG(Model.init(spec, seed=0),
                      loss="categorical_crossentropy", batch_size=16,
                      num_epoch=1, num_workers=2, communication_window=4,
                      learning_rate=0.05, seed=0)
    tr.train(toy_dataset)
    assert calls == []
    assert health_mod.collector().workers() == []


def test_trainer_health_interval_validation():
    import distkeras_tpu as dk
    from distkeras_tpu.models.base import Model, ModelSpec

    spec = ModelSpec(name="mlp", config={"hidden_sizes": (8,),
                                         "num_outputs": 2},
                     input_shape=(8,))
    model = Model.init(spec, seed=0)
    with pytest.raises(ValueError, match="health_interval_s"):
        dk.AsyncADAG(model, loss="categorical_crossentropy",
                     health_interval_s=0.0)
    # native_ps + health_interval_s over sockets is served since ISSUE 11
    # (the C++ hub ingests action-M reports); no guard to pin here


def test_trainer_with_health_interval_reports_and_detects(fresh_health,
                                                          toy_dataset):
    """The live plane end to end at trainer level (no telemetry needed —
    health has its own opt-in): every worker lands at least one report,
    windows/s series materialize, and the snapshot is JSON-safe."""
    import distkeras_tpu as dk
    from distkeras_tpu.models.base import Model, ModelSpec

    spec = ModelSpec(name="mlp", config={"hidden_sizes": (16,),
                                         "num_outputs": 2},
                     input_shape=(8,))
    tr = dk.AsyncADAG(Model.init(spec, seed=0),
                      loss="categorical_crossentropy", batch_size=16,
                      num_epoch=2, num_workers=2, communication_window=2,
                      learning_rate=0.05, seed=0, health_interval_s=0.05)
    tr.train(toy_dataset)
    col = health_mod.collector()
    assert col.workers() == ["0", "1"]
    for w in ("0", "1"):
        assert (col.meta(w) or {}).get("reports", 0) >= 1
        assert col.series(w, "windows_total").last() > 0
        assert col.series(w, "window_wall_ms") is not None
    json.dumps(health_mod.health_snapshot())


def test_trainer_owned_hub_run_starts_with_clean_health_slate(fresh_health,
                                                              toy_dataset):
    """A second train() on a trainer-owned hub must not inherit the first
    run's series or the monitor's frozen throughput baseline: run 2's
    ramp-up would read as a throughput regression against run 1's steady
    state, and run 1's workers would skew the straggler median for the
    whole 120s window."""
    import distkeras_tpu as dk
    from distkeras_tpu.models.base import Model, ModelSpec

    # plant stale state as if a previous run just ended: a leftover
    # worker series and a frozen throughput baseline
    health_mod.collector().observe("99", "windows_total", 1e9)
    mon = health_mod.monitor()
    mon._thr_baseline = 1e9
    spec = ModelSpec(name="mlp", config={"hidden_sizes": (16,),
                                         "num_outputs": 2},
                     input_shape=(8,))
    tr = dk.AsyncADAG(Model.init(spec, seed=0),
                      loss="categorical_crossentropy", batch_size=16,
                      num_epoch=1, num_workers=2, communication_window=2,
                      learning_rate=0.05, seed=0, health_interval_s=0.05)
    tr.train(toy_dataset)
    col = health_mod.collector()
    assert "99" not in col.workers(), "stale worker survived the reset"
    assert mon._thr_baseline != 1e9, "frozen baseline survived the reset"
    assert not [e for e in mon.events()
                if e.kind == "throughput_regression"], \
        "stale baseline fired a spurious regression on the fresh run"


def test_client_failover_dedup_key_is_gc_stable():
    """The failover dedup key must be a process-monotonic ordinal, not
    id(self): CPython reuses addresses after GC, and a recycled id lets a
    replacement client's failover land inside the dead client's cooldown
    and vanish from the ring/JSONL."""
    from distkeras_tpu.runtime.parameter_server import (
        DeltaParameterServer,
        PSClient,
    )

    ps = DeltaParameterServer(_weights(), port=0, idle_timeout=None)
    ps.start()
    try:
        ordinals = []
        for _ in range(3):
            # sequential create/close/GC: with id(self) keys these clients
            # routinely land on the same address and would share a key
            with PSClient("127.0.0.1", ps.port, templates=_weights()) as c:
                ordinals.append(c._client_ordinal)
        assert len(set(ordinals)) == 3
        assert ordinals == sorted(ordinals)
    finally:
        ps.stop()


# -- the acceptance drill ------------------------------------------------------

@pytest.mark.chaos
def test_live_drill_straggler_and_failover_events_visible_mid_run(
        telemetry, tmp_path):
    """ISSUE-8 acceptance, scaled to CI: real PS workers stream health
    reports while one of them is routed through a ChaosProxy that delays
    every frame and the PRIMARY hub is killed on its commit clock.
    Both events — straggler naming the delayed worker, failover naming
    the promoted standby — must be observable DURING the run through the
    punchcard ``fetch_telemetry(..., health=True)`` pull."""
    from distkeras_tpu.runtime.faults import ChaosProxy, HubKillPlan
    from distkeras_tpu.runtime.job_deployment import Punchcard, fetch_telemetry
    from distkeras_tpu.runtime.parameter_server import (
        DeltaParameterServer,
        PSClient,
    )

    t = _weights()
    primary = DeltaParameterServer(t, port=0, idle_timeout=None)
    primary.start()
    replica = DeltaParameterServer(
        t, idle_timeout=None, replica_of=("127.0.0.1", primary.port))
    replica.start()
    proxy = ChaosProxy("127.0.0.1", primary.port, delay_all_s=0.05)
    proxy.start()
    # fast detector cadence for the drill; straggler needs >= 3 reporters
    mon = health_mod.monitor()
    mon.check_interval_s = 0.05
    mon.cooldown_s = 0.0
    mon.jsonl_path = str(tmp_path / "health.jsonl")
    pc = Punchcard(secret="drill").start()
    kill_plan = HubKillPlan(after_commits=48)
    seen_mid_run = {}
    stop = threading.Event()

    def stop_proxy_with_primary():
        # the proxy models the slow network path TO the primary: once the
        # primary dies the path dies with it (a proxy that keeps accepting
        # for a dead upstream would eat the client's reconnect budget —
        # every connect "succeeds" and the rotation never advances)
        kill_plan.fired.wait(timeout=120)
        proxy.stop()

    threading.Thread(target=stop_proxy_with_primary, daemon=True).start()

    def poll():
        while not stop.is_set():
            try:
                resp = fetch_telemetry("127.0.0.1", pc.port, "drill",
                                       health=True)
            except (OSError, ValueError):
                time.sleep(0.02)
                continue
            for ev in resp["health"]["events"]:
                seen_mid_run.setdefault(ev["kind"], ev)
            time.sleep(0.02)

    worker_errors = []

    def worker(idx, port, windows):
        # a worker dying (e.g. a health report crashing the hub handler
        # and burning the reconnect budget) must FAIL the drill, not pass
        # it because the events happened to fire first
        try:
            with PSClient("127.0.0.1", port, templates=t,
                          failover=[("127.0.0.1", replica.port)],
                          max_reconnects=12, reconnect_backoff=0.02) as c:
                for w in range(windows):
                    t0 = time.perf_counter()
                    c.pull()
                    c.commit(_ones())
                    c.report_health(_report(
                        idx, seq=w, windows_total=float(w + 1),
                        window_wall_ms=(time.perf_counter() - t0) * 1e3,
                        reconnects_total=float(c.reconnects_used),
                        failovers_total=float(c.failovers_used)))
                c.drain()
        except Exception as e:
            worker_errors.append((idx, e))

    poller = threading.Thread(target=poll, daemon=True)
    poller.start()
    kill_plan.start(primary)
    threads = [threading.Thread(target=worker, args=(i, primary.port, 24))
               for i in range(3)]
    delayed = threading.Thread(target=worker, args=(3, proxy.port, 24))
    threads.append(delayed)
    try:
        # the proxied worker goes first, alone, until min_samples of its
        # DELAYED walls have landed: if the fast workers raced it to the
        # kill clock, the primary could die with worker 3's big-wall
        # reports still queued in the proxy pipe — its collected series
        # would then hold mostly fast post-failover samples and the
        # straggler condition would be down to load luck
        delayed.start()
        assert _wait_until(
            lambda: (health_mod.collector().meta("3")
                     or {}).get("reports", 0) >= 3, timeout=30), \
            "proxied worker landed no delayed reports"
        for th in threads[:3]:
            th.start()
        join_all(threads, "the drill's workers", timeout=120)
        kill_plan.join(timeout=30)
        assert kill_plan.fired.is_set(), "primary never killed"
        assert _wait_until(lambda: replica.promoted, timeout=10)
        # give the poller one more detector cadence to observe the tail
        _wait_until(lambda: {"straggler", "failover"} <= set(seen_mid_run),
                    timeout=10)
    finally:
        stop.set()
        poller.join(timeout=5)
        kill_plan.cancel()
        pc.stop()
        proxy.stop()
        replica.stop()
        try:
            primary.stop()
        except Exception:
            pass
    assert worker_errors == [], worker_errors
    # straggler fired DURING the run and named the proxied worker
    assert "straggler" in seen_mid_run, sorted(seen_mid_run)
    assert seen_mid_run["straggler"]["worker"] == "3"
    # failover fired and named the promoted standby's address
    assert "failover" in seen_mid_run, sorted(seen_mid_run)
    fo = seen_mid_run["failover"]["evidence"]
    # first-seen failover event is either the hub's own promotion
    # (named by its BIND host, e.g. 0.0.0.0) or a client's landing
    # (named by the connect host) — both carry the standby's port
    promoted = fo.get("promoted") or fo.get("to_addr")
    assert promoted.endswith(f":{replica.port}")
    # the durable sink carries both too
    kinds = {json.loads(line)["kind"]
             for line in (tmp_path / "health.jsonl").read_text().splitlines()}
    assert {"straggler", "failover"} <= kinds
