"""The readers of the program's phase spans and counters on a small rows
file in the shape ``harness.trace.load_events`` gives (``data/
trace_spans_small.json``), and the new ``per_layer`` entries against the
files they name.  The file has two parts.  ``constructed``: two window
programs of 1 s at 1 s and 4 s on one chip, the worker's, the hub
handler's and a sync trainer's phases on three host lines, one runtime
event, in whole milliseconds, so that every reading below can be checked by
hand.  ``recorded``: two windows of a traced ``lm590m_async`` run on a TPU
v5e (PR 25), with the names, planes and lines the chip's trace really has.
"""

import json
import os
import sys

import pytest

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))
from bench_fixtures import ROOT  # noqa: E402

from benchmark.harness import spec  # noqa: E402

with open(os.path.join(os.path.dirname(os.path.abspath(__file__)),
                       "data", "trace_spans_small.json")) as _f:
    _DATA = json.load(_f)
ROWS = [tuple(r) for r in _DATA["constructed"]]
RECORDED = _DATA["recorded"]
MS = 1e6
NEW_READERS = ("trace_span", "counter", "idle_attributed")
BENCH = spec.load_benchmark()


def ctx(lo=1000, hi=5000, rows=ROWS, **more):
    return dict({"trace": {"rows": rows, "lo": lo * MS, "hi": hi * MS}}, **more)


def metric(name, c):
    read, args = spec.load_reader(name)
    return read(c, **args)


def test_span_mean_is_clipped_to_the_traced_stretch():
    # async.h2d: [500, 1500] and [4500, 4700] ms
    assert metric("async_h2d_ms", ctx()) == pytest.approx((500 + 200) / 2)
    assert metric("async_h2d_ms", ctx(lo=0)) == pytest.approx((1000 + 200) / 2)
    assert metric("async_h2d_ms", ctx(hi=4600)) == pytest.approx((500 + 100) / 2)
    # an event wholly outside the stretch does not count: ps.apply at 5200
    assert metric("hub_apply_ms", ctx()) == pytest.approx(300)
    assert metric("hub_apply_ms", ctx(hi=6000)) == pytest.approx((300 + 600) / 2)
    assert metric("async_commit_d2h_ms", ctx()) == pytest.approx(600)


def test_an_absent_name_or_no_trace_reads_nothing():
    assert metric("async_pull_wait_ms", ctx()) is None      # no such event
    assert metric("feed_wait_ms_per_window", ctx()) is None
    assert metric("async_h2d_ms", {"trace": None}) is None
    assert metric("async_h2d_ms", ctx(lo=2000, hi=4000)) is None   # none inside
    # a device event of the name is not a host phase
    rows = [("/device:TPU:0", "XLA Ops", "async.h2d", 1000 * MS, 10 * MS)]
    assert metric("async_h2d_ms", ctx(rows=rows)) is None


def test_a_list_of_names_adds_up_per_occurrence_of_the_first():
    # two chunks: (50 + 50 + 100) and (50 + 50 + 200) ms
    assert metric("engine_host_ms", ctx()) == pytest.approx((200 + 300) / 2)
    # the first name decides whether there is anything to read
    rows = [r for r in ROWS if r[2] != "engine.dispatch"]
    assert metric("engine_host_ms", ctx(rows=rows)) is None


def test_idle_attributed_share_of_a_gap_half_covered():
    # idle in [1000, 5000]: the gap [2000, 4000]; async.commit_d2h and
    # ps.commit_drain cover [2000, 3000] of it; XlaDelinearize is no phase
    assert metric("idle_attributed_share", ctx()) == pytest.approx(50.0)
    # a lead-in before the first program is idle too: [500, 1000] lies under
    # async.h2d, so 1500 of 2500 ms are covered
    assert metric("idle_attributed_share", ctx(lo=500)) == pytest.approx(60.0)
    # phases that overlap each other are counted once
    rows = ROWS + [("/host:CPU", "hub-handler", "ps.send_weights", 2500 * MS, 500 * MS)]
    assert metric("idle_attributed_share", ctx(rows=rows)) == pytest.approx(50.0)
    # a program from before the phases: nothing to read, not 0
    old = [r for r in ROWS if not r[2].startswith(("async.", "ps.", "engine."))]
    assert metric("idle_attributed_share", ctx(rows=old)) is None
    assert metric("idle_attributed_share", {"trace": None}) is None
    # a device that was never idle in the stretch
    assert metric("idle_attributed_share", ctx(lo=1000, hi=2000)) is None


def test_readers_on_two_recorded_windows_of_the_async_cell():
    """The names, the plane and the lines of a real trace: both Python
    threads' lines are called ``python3``, the worker's and the hub's phases
    interleave, the runtime's own events (``shard_args``, ``XlaDelinearize``,
    ``np.asarray(jax.Array)``) lie beside them and are not counted."""
    c = {"trace": {"rows": [tuple(r) for r in RECORDED["rows"]],
                   "lo": RECORDED["lo"], "hi": RECORDED["hi"]}}
    want = {"async_pull_wait_ms": 0.01624, "async_h2d_ms": 90.29,
            "async_commit_d2h_ms": 523.4, "client_commit_drain_ms": 1891.2,
            "client_commit_pack_ms": 250.7, "client_commit_send_ms": 681.9,
            "hub_commit_recv_ms": 681.9, "hub_apply_ms": 2616.6,
            "hub_pull_send_ms": 784.0}
    for name, value in want.items():
        assert metric(name, c) == pytest.approx(value, rel=1e-3), name
    # two windows of 4.73 and 4.47 s: the worker's phases tile them, so
    # every idle microsecond of the chip lies under one
    assert metric("idle_attributed_share", c) == pytest.approx(100.0, abs=0.01)
    assert metric("engine_host_ms", c) is None          # no sync plane here
    # and the harness's own gap naming now finds the program's phase
    from benchmark.harness import trace
    gaps = trace.idle_gaps(c["trace"]["rows"], c["trace"]["lo"], c["trace"]["hi"])
    assert gaps[0][0] == "chip0:jit_window->jit_window:ps.commit_drain"
    assert gaps[0][1] == pytest.approx(3.652, abs=0.001)


def test_counter_over_counter_adds_label_sets_and_refuses_a_zero_denominator():
    counters = {"net_tx_bytes_total": 900.0, 'net_tx_bytes_total{shard="1"}': 100.0,
                "net_tx_frames_total": 7.0, "async_windows_total": 4.0}
    assert metric("wire_bytes_per_window", {"counters": counters}) == 250.0
    assert metric("wire_bytes_per_window",
                  {"counters": dict(counters, async_windows_total=0.0)}) is None
    assert metric("wire_bytes_per_window", {"counters": {}}) is None


def test_counter_reader_reads_the_programs_registry():
    from distkeras_tpu import observability as obs

    obs.reset()
    obs.enable()
    try:
        obs.counter("net_tx_bytes_total").inc(64)
        obs.counter("async_windows_total").inc(2)
        assert metric("wire_bytes_per_window", {}) == 32.0
    finally:
        obs.disable()
        obs.reset()


def metric_file(name):
    with open(os.path.join(ROOT, "benchmark", "metrics", name + ".json")) as f:
        return json.load(f)


NEW = [m for m in BENCH["per_layer"]
       if metric_file(m["name"])["reader"] in NEW_READERS]


def test_every_new_entry_has_its_files_and_lists_accepted_cells():
    assert [m["name"] for m in NEW] == [
        "async_pull_wait_ms", "async_h2d_ms", "async_commit_d2h_ms",
        "client_commit_drain_ms", "client_commit_pack_ms", "client_commit_send_ms",
        "hub_commit_recv_ms", "hub_apply_ms", "hub_pull_send_ms",
        "wire_bytes_per_window", "engine_host_ms", "feed_wait_ms_per_window",
        "idle_attributed_share"]
    # appended after the accepted entries, which stand as they were
    assert BENCH["per_layer"][-len(NEW):] == NEW
    accepted = {"lm590m_sync", "lm590m_async", "lm1b3_sync"}
    layers = {m["layer"] for m in BENCH["per_layer"][:-len(NEW)]}
    for m in NEW:
        assert set(m["workloads"]) <= accepted and m["workloads"]
        assert m["moves"] == "tokens_per_s_per_chip" and m["layer"] in layers
        assert m["source"] == ("program_counter" if m["name"] ==
                               "wire_bytes_per_window" else "program_span")
        body = metric_file(m["name"])
        assert body["name"] == m["name"] and body["what"]
        assert os.path.exists(os.path.join(ROOT, "benchmark", "readers",
                                           body["reader"] + ".py"))
        read, args = spec.load_reader(m["name"])
        assert read({"trace": None, "counters": {}}, **args) is None


def test_phase_names_the_metrics_read_are_registered():
    """A phase renamed in the program would silence its metric: every event
    name a metric file reads is a registered telemetry name."""
    from distkeras_tpu.analysis.telemetry_registry import TELEMETRY_NAMES

    for m in NEW:
        _, args = spec.load_reader(m["name"])
        names = args.get("names", [])
        for n in [names] if isinstance(names, str) else names:
            assert n in TELEMETRY_NAMES, (m["name"], n)
        for key in ("name", "over"):
            if key in args:
                assert args[key] in TELEMETRY_NAMES
