"""Issue-10 tests: telemetry-driven adaptive aggregation, staleness-scaled
rates, and hub backpressure.

Covers the Adasum merge rule itself (commutativity / order-invariance /
sparse-row composition), the flat-combining commit path, the
HealthMonitor subscription hook and the ``staleness_drift`` detector, the
event-driven per-worker rate controller, the reconnect-storm retry-after
protocol (including the bounded-accept-rate drill), the seeded
ChaosProxy slow-NIC mode, the wire-compat matrix (un-upgraded client vs
adaptive hub, byte-identical across plain / sharded / replicated
topologies), and the ``adaptive=False`` off-path guarantees (zero
adaptive machinery constructed, trajectories bit-equal).
"""

import threading
import time

import numpy as np
import pytest

from distkeras_tpu.observability import distributed as dtrace
from distkeras_tpu.observability import health as health_mod
from distkeras_tpu.observability.health import HealthCollector, HealthMonitor
from distkeras_tpu.runtime import networking as net
from distkeras_tpu.runtime.parameter_server import (
    ADAGParameterServer,
    AdaptiveRateController,
    DeltaParameterServer,
    DynSGDParameterServer,
    PSClient,
    ShardedParameterServer,
    ShardedPSClient,
    adasum_merge,
    adasum_pair,
    shard_plan,
)


@pytest.fixture
def fresh_health():
    """Clean process-default collector/monitor (the adaptive hub binds and
    subscribes to these at start())."""
    health_mod.reset_default()
    yield health_mod
    health_mod.reset_default()


def _weights():
    return [np.zeros((4, 4), np.float32), np.zeros((6,), np.float32)]


# -- the merge rule itself (satellite 3) ---------------------------------------

def test_adasum_orthogonal_sums_and_parallel_averages():
    a = [np.array([2.0, 0.0, 0.0], np.float32)]
    b = [np.array([0.0, 2.0, 0.0], np.float32)]
    np.testing.assert_allclose(adasum_pair(a, b)[0], [2.0, 2.0, 0.0])
    # parallel: adasum(g, g) = g (each side halves — one step, not two)
    np.testing.assert_allclose(adasum_pair(a, a)[0], [2.0, 0.0, 0.0])


def test_adasum_pair_is_commutative():
    rng = np.random.default_rng(3)
    a = [rng.normal(size=(4, 4)).astype(np.float32),
         rng.normal(size=(6,)).astype(np.float32)]
    b = [rng.normal(size=(4, 4)).astype(np.float32),
         rng.normal(size=(6,)).astype(np.float32)]
    ab, ba = adasum_pair(a, b), adasum_pair(b, a)
    for x, y in zip(ab, ba):
        np.testing.assert_allclose(x, y, rtol=1e-6)


def test_adasum_merge_order_invariance():
    """The order-invariance the rule actually guarantees: swapping the
    members WITHIN any tree pair changes nothing (pairwise commutativity
    lifted through the reduction), and a batch of mutually orthogonal
    commits merges to their plain sum under EVERY permutation (the
    reduction is only order-sensitive through the interference terms,
    which orthogonality zeroes)."""
    rng = np.random.default_rng(7)
    commits = [[rng.normal(size=(6,)).astype(np.float32)]
               for _ in range(4)]
    base = adasum_merge(commits)[0]
    swapped = adasum_merge([commits[1], commits[0],
                            commits[3], commits[2]])[0]
    np.testing.assert_allclose(swapped, base, rtol=1e-5, atol=1e-7)
    # orthogonal batch: permutation-invariant, exactly the sum
    ortho = [[np.eye(5, dtype=np.float32)[i] * (i + 1.0)] for i in range(4)]
    expected = np.sum([c[0] for c in ortho], axis=0)
    for perm in ([0, 1, 2, 3], [3, 1, 0, 2], [2, 3, 1, 0]):
        merged = adasum_merge([ortho[i] for i in perm])[0]
        np.testing.assert_allclose(merged, expected, rtol=1e-6)
    # and the magnitude never blows up past the triangle bound
    assert np.linalg.norm(base) <= sum(
        np.linalg.norm(c[0]) for c in commits) + 1e-5


def test_adasum_zero_norm_side_passes_other_through():
    a = [np.zeros(3, np.float32)]
    b = [np.array([1.0, 2.0, 3.0], np.float32)]
    np.testing.assert_array_equal(adasum_pair(a, b)[0], b[0])
    np.testing.assert_array_equal(adasum_pair(b, a)[0], b[0])


def test_adasum_sparse_matches_densified():
    """Sparse-row composition: merging two (ids, grads) commits on their
    row union equals merging their dense materializations — ONE rule for
    both commit forms."""
    rows, dim = 8, 3
    rng = np.random.default_rng(11)
    ids_a = np.array([1, 4, 6], np.int64)
    ids_b = np.array([2, 4, 7], np.int64)
    ga = rng.normal(size=(3, dim)).astype(np.float32)
    gb = rng.normal(size=(3, dim)).astype(np.float32)
    sparse = adasum_pair([(ids_a, ga)], [(ids_b, gb)])[0]
    da = np.zeros((rows, dim), np.float32)
    da[ids_a] = ga
    db = np.zeros((rows, dim), np.float32)
    db[ids_b] = gb
    dense = adasum_pair([da], [db])[0]
    ids, grads = sparse
    np.testing.assert_array_equal(ids, np.array([1, 2, 4, 6, 7], np.int64))
    full = np.zeros((rows, dim), np.float32)
    full[ids] = grads
    np.testing.assert_allclose(full, dense, rtol=1e-6)
    # untouched rows stay exactly zero in both forms
    np.testing.assert_array_equal(dense[[0, 3, 5]], 0.0)


def test_adasum_mixed_representation_refused():
    with pytest.raises(ValueError, match="densify"):
        adasum_pair([(np.array([0], np.int64),
                      np.ones((1, 2), np.float32))],
                    [np.ones((4, 2), np.float32)])


# -- the combiner (tentpole 1) -------------------------------------------------

def test_combiner_merges_queued_commits_one_batch(fresh_health):
    """Commits queued while another applies merge into ONE batch: clock
    and num_updates still advance by the commit count, and the combiner's
    counters record the fold."""
    ps = ADAGParameterServer([np.zeros(3, np.float32)], num_workers=4,
                             port=0, idle_timeout=None, adaptive=True)
    ps.start()
    try:
        comb = ps._combiner
        deltas = [np.eye(3, dtype=np.float32)[i % 3] * 4.0 for i in range(4)]
        comb._drain.acquire()  # park the drain: submitters must queue
        threads = [threading.Thread(target=ps.commit_direct, args=([d], 0))
                   for d in deltas]
        for t in threads:
            t.start()
        time.sleep(0.3)
        comb._drain.release()
        for t in threads:
            t.join(10)
        assert ps.num_updates == 4 and ps._clock == 4
        assert comb.max_batch == 4 and comb.merged_total == 3
        assert np.isfinite(ps.center[0]).all()
    finally:
        ps.stop()


def test_combiner_uncontended_matches_plain_hub_bitwise(fresh_health):
    """Serial (batch-of-one) adaptive applies are bit-identical to the
    plain hub across the scaling rules — the off-vs-on parity anchor at
    the center level."""
    for cls, kw in ((DeltaParameterServer, {}),
                    (ADAGParameterServer, {"num_workers": 3}),
                    (DynSGDParameterServer, {})):
        plain = cls([np.zeros((4, 4), np.float32)], port=0,
                    idle_timeout=None, **kw)
        adap = cls([np.zeros((4, 4), np.float32)], port=0,
                   idle_timeout=None, adaptive=True, **kw)
        plain.start()
        adap.start()
        try:
            rng = np.random.default_rng(5)
            for k in range(6):
                d = rng.normal(size=(4, 4)).astype(np.float32)
                # interleave pulls so DynSGD sees varied staleness
                clock_p = plain.pull_direct()[1] if k % 2 else 0
                clock_a = adap.pull_direct()[1] if k % 2 else 0
                plain.commit_direct([d], clock_p)
                adap.commit_direct([d], clock_a)
            np.testing.assert_array_equal(plain.center[0], adap.center[0])
        finally:
            plain.stop()
            adap.stop()


def test_combiner_sparse_commits_apply_and_replicate(fresh_health):
    """Sparse (ids, grads) commits ride the combiner natively, and a
    replicated adaptive primary streams the applied delta so the standby
    tracks bit for bit."""
    t = [np.zeros((8, 2), np.float32), np.zeros((3,), np.float32)]
    primary = DeltaParameterServer(t, port=0, idle_timeout=None,
                                   adaptive=True, sparse_leaves=(0,))
    primary.start()
    replica = DeltaParameterServer(t, idle_timeout=None,
                                   replica_of=("127.0.0.1", primary.port),
                                   sparse_leaves=(0,))
    replica.start()
    try:
        assert replica.wait_synced(timeout=10)
        ids = np.array([1, 5], np.int64)
        grads = np.ones((2, 2), np.float32)
        primary.commit_sparse_direct([(ids, grads),
                                      np.ones(3, np.float32)], 0)
        deadline = time.monotonic() + 10
        while replica._clock < 1 and time.monotonic() < deadline:
            time.sleep(0.01)
        np.testing.assert_array_equal(primary.center[0][ids], 1.0)
        np.testing.assert_array_equal(replica.center[0], primary.center[0])
        np.testing.assert_array_equal(replica.center[1], primary.center[1])
    finally:
        replica.stop()
        primary.stop()


def test_combiner_failed_batch_raises_everywhere_never_false_acks(
        fresh_health):
    """A batch whose apply raises must surface the error to EVERY
    submitter in it (their connections drop / their workers see it) —
    never a silent drop behind an ack — and must not corrupt the
    combiner for later commits."""
    ps = DeltaParameterServer([np.zeros(3, np.float32)], port=0,
                              idle_timeout=None, adaptive=True)
    ps.start()
    try:
        comb = ps._combiner
        results = {}

        def submit(key, parts):
            try:
                comb.commit(parts, 0)
                results[key] = None
            except Exception as e:  # noqa: BLE001 - recorded, asserted below
                results[key] = e

        comb._drain.acquire()  # both entries land in ONE batch
        threads = [
            threading.Thread(target=submit,
                             args=("bad", [np.ones(5, np.float32)])),
            threading.Thread(target=submit,
                             args=("good", [np.ones(3, np.float32)])),
        ]
        for t in threads:
            t.start()
        time.sleep(0.2)
        comb._drain.release()
        for t in threads:
            t.join(10)
        # the poisoned batch raised for BOTH members (the good commit was
        # not applied, so acking it would have been a lie)
        assert results["bad"] is not None and results["good"] is not None
        assert ps._clock == 0 and ps.num_updates == 0
        np.testing.assert_array_equal(ps.center[0], 0.0)
        # the combiner is intact: a fresh valid commit applies
        ps.commit_direct([np.ones(3, np.float32)], 0)
        assert ps._clock == 1
        np.testing.assert_array_equal(ps.center[0], 1.0)
    finally:
        ps.stop()


def test_admitted_hellos_are_not_storm_evidence(fresh_health):
    """A shed herd's paced returns (waits_taken > 0) must not re-arm the
    storm — otherwise the drain itself keeps shedding and a later lone
    reconnect gets punished on stale evidence."""
    ps = DeltaParameterServer(_weights(), port=0, idle_timeout=None,
                              adaptive=True)
    ps.start()
    try:
        # paced returns alone never start a storm
        for _ in range(5):
            assert ps._retry_after_ms(waits_taken=1) == 0
        assert len(ps._hello_times) == 0
        assert ps.backpressure_hints == 0
        # fresh arrivals still do
        hints = [ps._retry_after_ms(waits_taken=0) for _ in range(3)]
        assert hints[-1] > 0
    finally:
        ps.stop()


# -- subscription hook + drift detector (tentpole 2) ---------------------------

def test_monitor_subscribe_pushes_events_and_unsubscribes():
    c = HealthCollector()
    m = HealthMonitor(c, cooldown_s=0.0)
    seen = []
    bad_calls = []

    def bad(event):
        bad_calls.append(event)
        raise RuntimeError("broken subscriber")

    m.subscribe(bad)
    cb = m.subscribe(seen.append)
    ev = m.emit("straggler", worker="3", factor=2.5)
    assert ev is not None
    # the broken subscriber ran, raised, and neither blocked the emit nor
    # the other subscriber
    assert len(bad_calls) == 1 and len(seen) == 1
    assert seen[0].kind == "straggler" and seen[0].worker == "3"
    m.unsubscribe(cb)
    m.emit("straggler", worker="4", factor=2.0)
    assert len(seen) == 1
    # clear() keeps subscriptions (a run-boundary reset must not unhook a
    # live hub); the bad one is still attached and still harmless
    m.clear()
    m.emit("straggler", worker="5", factor=2.0)
    assert len(bad_calls) == 3


def test_staleness_drift_detector_is_fleet_relative():
    c = HealthCollector()
    m = HealthMonitor(c, cooldown_s=0.0, min_fleet=3, min_samples=3,
                      drift_factor=2.0, staleness_min=4.0)
    now = time.monotonic()
    for i in range(4):
        c.observe("0", "staleness", 1.0, ts=now - 4 + i)
        c.observe("1", "staleness", 2.0, ts=now - 4 + i)
        # worker 2 is ALWAYS behind — its own baseline is high, so the
        # spike detector never fires; drift must
        c.observe("2", "staleness", 9.0, ts=now - 4 + i)
    events = [e for e in m.check(now) if e.kind == "staleness_drift"]
    assert [e.worker for e in events] == ["2"]
    assert events[0].evidence["staleness_mean"] == 9.0
    assert events[0].evidence["fleet_median"] == 2.0
    # below the fleet floor nothing fires
    c2 = HealthCollector()
    m2 = HealthMonitor(c2, cooldown_s=0.0, min_fleet=3)
    for i in range(4):
        c2.observe("9", "staleness", 50.0, ts=now - 4 + i)
    assert m2.check(now) == []


def test_rate_controller_scales_and_expires():
    rc = AdaptiveRateController(floor=0.1, hold_s=0.2)

    class Ev:
        def __init__(self, kind, worker, **evidence):
            self.kind, self.worker, self.evidence = kind, worker, evidence

    rc.on_event(Ev("staleness_drift", "0", staleness_mean=9.0,
                   fleet_median=1.0))
    assert rc.scale_for("0") == pytest.approx(0.2)
    assert rc.scale_for(0) == pytest.approx(0.2)  # int/str key equivalence
    assert rc.scale_for("1") == 1.0 and rc.scale_for(None) == 1.0
    # a second, stricter verdict wins; a laxer one does not relax it
    rc.on_event(Ev("straggler", "0", factor=20.0))
    assert rc.scale_for("0") == pytest.approx(0.1)  # floored
    rc.on_event(Ev("staleness_spike", "0", staleness=1.0, baseline=1.0))
    assert rc.scale_for("0") == pytest.approx(0.1)
    time.sleep(0.25)
    assert rc.scale_for("0") == 1.0  # expired -> recovered
    assert rc.snapshot() == {}


def test_rate_controller_tracks_improving_evidence_per_kind():
    """A fresh event of one kind REPLACES that kind's verdict — a worker
    improving from severe to mild drift tracks down-penalty immediately
    instead of ratcheting at the historical minimum — while another
    kind's severe verdict keeps its own clock."""
    rc = AdaptiveRateController(floor=0.1, hold_s=0.3)

    class Ev:
        def __init__(self, kind, worker, **evidence):
            self.kind, self.worker, self.evidence = kind, worker, evidence

    rc.on_event(Ev("staleness_drift", "0", staleness_mean=39.0,
                   fleet_median=1.0))
    assert rc.scale_for("0") == pytest.approx(0.1)  # severe, floored
    rc.on_event(Ev("staleness_drift", "0", staleness_mean=3.0,
                   fleet_median=1.0))
    assert rc.scale_for("0") == pytest.approx(0.5)  # improved: tracked
    # a concurrent straggler verdict composes by min...
    rc.on_event(Ev("straggler", "0", factor=4.0))
    assert rc.scale_for("0") == pytest.approx(0.25)
    # ...and drift improving further does not erase the straggler verdict
    rc.on_event(Ev("staleness_drift", "0", staleness_mean=1.0,
                   fleet_median=1.0))
    assert rc.scale_for("0") == pytest.approx(0.25)
    time.sleep(0.35)
    assert rc.scale_for("0") == 1.0


def test_combiner_mixed_batch_applies_sequentially(fresh_health):
    """A batch mixing a full-delta (dense) commit with sparse-row commits
    applies in plain queue order — center equals the sum — instead of
    densifying the sparse sides under the lock to force a merge."""
    t = [np.zeros((6, 2), np.float32)]
    ps = DeltaParameterServer(t, port=0, idle_timeout=None, adaptive=True,
                              sparse_leaves=(0,))
    ps.start()
    try:
        comb = ps._combiner
        ids = np.array([1, 4], np.int64)
        comb._drain.acquire()  # both land in ONE batch
        threads = [
            threading.Thread(target=ps.commit_sparse_direct,
                             args=([(ids, np.ones((2, 2), np.float32))], 0)),
            threading.Thread(target=ps.commit_direct,
                             args=([np.full((6, 2), 2.0, np.float32)], 0)),
        ]
        for th in threads:
            th.start()
        time.sleep(0.2)
        comb._drain.release()
        for th in threads:
            th.join(10)
        assert comb.max_batch == 2 and ps.num_updates == 2
        expected = np.full((6, 2), 2.0, np.float32)
        expected[ids] += 1.0
        np.testing.assert_array_equal(ps.center[0], expected)
    finally:
        ps.stop()


def test_hub_reacts_to_staleness_event_without_polling(fresh_health):
    """The whole reaction chain: monitor event -> subscription -> rate
    controller -> scaled apply, with the committing worker named by its
    thread-local trace context (the inproc attribution path)."""
    ps = DeltaParameterServer([np.zeros(4, np.float32)], port=0,
                              idle_timeout=None, adaptive=True)
    ps.start()
    try:
        health_mod.monitor().emit("staleness_drift", worker="0",
                                  staleness_mean=9.0, fleet_median=1.0)
        dtrace.activate(dtrace.TraceContext(job_id="j", worker_id=0,
                                            span_id=dtrace.new_span_id()))
        try:
            ps.commit_direct([np.ones(4, np.float32)], 0)
        finally:
            dtrace.activate(None)
        np.testing.assert_allclose(ps.center[0], 0.2)
        # the applied scale joined the worker's live series (top/fleet
        # report read it from here)
        series = health_mod.collector().series("0", "adaptive_scale")
        assert series is not None and series.samples()[-1][1] == \
            pytest.approx(0.2)
    finally:
        ps.stop()


def test_fleet_report_adaptive_block(fresh_health):
    from distkeras_tpu.observability.distributed import fleet_report

    col = health_mod.collector()
    col.observe("0", "adaptive_scale", 0.25)
    col.observe("hub", "merge_queue_depth", 3.0)
    report = fleet_report(events=[], live=col)
    block = report["adaptive"]
    assert block["active"] is True
    assert block["worker_scales"]["0"]["last"] == 0.25
    assert block["merge_queue"]["hub"]["last"] == 3.0
    # no adaptive series -> no block (non-adaptive reports unchanged)
    health_mod.reset_default()
    col2 = health_mod.collector()
    col2.observe("0", "staleness", 1.0)
    assert "adaptive" not in fleet_report(events=[], live=col2)


def test_render_top_scale_and_mq_columns(fresh_health):
    from distkeras_tpu.observability.health import render_top

    c = health_mod.collector()
    c.observe("0", "adaptive_scale", 0.25)
    c.observe("hub", "merge_queue_depth", 3.0)
    frame = render_top({"fleet": c.snapshot(), "events": []})
    assert "SCALE" in frame and "MQ" in frame
    row0 = next(line for line in frame.splitlines()
                if line.strip().startswith("0 "))
    assert "0.25" in row0


# -- reconnect-storm backpressure (tentpole 3) ---------------------------------

def test_hub_answers_hello_zero_outside_storm(fresh_health):
    ps = DeltaParameterServer(_weights(), port=0, idle_timeout=None,
                              adaptive=True)
    ps.start()
    try:
        s = net.connect("127.0.0.1", ps.port)
        try:
            net.send_frame(s, net.encode_reconnect_payload(0))
            action, blobs = net.recv_tensors(s)
            assert action == net.ACTION_RETRY
            assert net.decode_retry_payload(blobs) == 0
        finally:
            s.close()
    finally:
        ps.stop()


def test_non_adaptive_hub_answers_hello_zero(fresh_health):
    """An adaptive client against a non-adaptive hub of this generation
    is admitted immediately — G is answered by every hub, hinted only by
    adaptive ones in a storm."""
    ps = DeltaParameterServer(_weights(), port=0, idle_timeout=None)
    ps.start()
    try:
        s = net.connect("127.0.0.1", ps.port)
        try:
            for _ in range(5):  # even a herd: never hinted
                net.send_frame(s, net.encode_reconnect_payload(0))
                action, blobs = net.recv_tensors(s)
                assert net.decode_retry_payload(blobs) == 0
        finally:
            s.close()
        assert ps.backpressure_hints == 0
    finally:
        ps.stop()


def test_storm_spreads_slots_and_admits_after_wait(fresh_health):
    ps = DeltaParameterServer(_weights(), port=0, idle_timeout=None,
                              adaptive=True)
    ps.RETRY_BASE_MS = 10
    ps.start()
    try:
        hints = []
        s = net.connect("127.0.0.1", ps.port)
        try:
            for _ in range(5):
                net.send_frame(s, net.encode_reconnect_payload(0))
                hints.append(net.decode_retry_payload(
                    net.recv_tensors(s)[1]))
            # a client announcing it already waited is admitted
            net.send_frame(s, net.encode_reconnect_payload(1))
            admitted = net.decode_retry_payload(net.recv_tensors(s)[1])
        finally:
            s.close()
        # first two hellos pre-storm (threshold 3), then increasing slots
        assert hints[:2] == [0, 0]
        assert hints[2:] == [10, 20, 30]
        assert admitted == 0
        assert ps.backpressure_hints == 3
        # the self-detected storm is an observable health event
        kinds = [e["kind"] for e in health_mod.monitor().events()]
        assert "reconnect_storm" in kinds
    finally:
        ps.stop()


def test_storm_event_from_monitor_arms_shedding(fresh_health):
    """A reconnect storm detected from worker health REPORTS (not from
    hellos) also sheds: the subscription closes the loop."""
    ps = DeltaParameterServer(_weights(), port=0, idle_timeout=None,
                              adaptive=True)
    ps.RETRY_BASE_MS = 10
    ps.start()
    try:
        health_mod.monitor().emit("reconnect_storm", "critical", worker="2",
                                  count=5)
        s = net.connect("127.0.0.1", ps.port)
        try:
            net.send_frame(s, net.encode_reconnect_payload(0))
            hint = net.decode_retry_payload(net.recv_tensors(s)[1])
        finally:
            s.close()
        assert hint == 10
    finally:
        ps.stop()


def test_reconnect_storm_drill_bounded_accept_zero_exceptions(fresh_health):
    """The acceptance drill: a 6-client herd severed at once reconnects
    against an adaptive hub — the hub paces the herd (increasing
    retry-after slots = bounded accept rate), every client recovers
    budget-neutrally, and no worker raises."""
    t = _weights()
    ps = DeltaParameterServer(t, port=0, idle_timeout=None, adaptive=True)
    ps.RETRY_BASE_MS = 20
    ps.start()
    errors, recovered = [], []

    def worker(i):
        try:
            c = PSClient("127.0.0.1", ps.port, templates=t, adaptive=True,
                         max_reconnects=4, reconnect_backoff=0.01)
            c.pull()
            c.commit([np.ones_like(x) for x in t])
            c.sock.shutdown(2)  # the blip: every client severed at once
            c.pull()
            c.commit([np.ones_like(x) for x in t])
            c.drain()
            recovered.append((i, c.backpressure_waits, c.reconnects_used))
            c.close()
        except Exception as e:  # noqa: BLE001 - the drill records, asserts below
            errors.append((i, e))

    threads = [threading.Thread(target=worker, args=(i,)) for i in range(6)]
    try:
        for th in threads:
            th.start()
        for th in threads:
            th.join(30)
        assert not any(th.is_alive() for th in threads)
    finally:
        ps.stop()
    assert errors == [], errors
    assert len(recovered) == 6
    # the herd was paced: hints were issued with increasing slots...
    assert ps.backpressure_hints >= 1
    # ...every commit landed (2 per client)...
    assert ps.num_updates == 12
    # ...and hub-paced waits were refunded: nobody burned more than the
    # one real fault's worth of budget
    assert all(used <= 2 for _, _, used in recovered), recovered
    kinds = [e["kind"] for e in health_mod.monitor().events()]
    assert "reconnect_storm" in kinds


# -- ChaosProxy slow-NIC mode (satellite 1) ------------------------------------

def test_chaos_throttle_deterministic_under_seed():
    from distkeras_tpu.runtime.faults import ChaosProxy

    p1 = ChaosProxy("127.0.0.1", 1, jitter_delay_s=(0.01, 0.02), seed=9,
                    bandwidth_bytes_per_s=1e6)
    p2 = ChaosProxy("127.0.0.1", 1, jitter_delay_s=(0.01, 0.02), seed=9,
                    bandwidth_bytes_per_s=1e6)

    def seq(proxy):
        rng = np.random.default_rng((proxy.seed, 0, 1))
        return [proxy._frame_delay(rng, nbytes)
                for nbytes in (13, 1024, 13, 65536)]

    s1, s2 = seq(p1), seq(p2)
    assert s1 == s2
    # bandwidth term: the big frame pays proportionally more
    assert s1[3] >= 65536 / 1e6 + 0.01 - 1e-9
    assert all(0.01 <= d - nb / 1e6 <= 0.02
               for d, nb in zip(s1, (13, 1024, 13, 65536)))
    with pytest.raises(ValueError, match="lo <= hi"):
        ChaosProxy("127.0.0.1", 1, jitter_delay_s=(0.5, 0.1))


def test_chaos_slow_conns_throttles_only_named_ordinals(fresh_health,
                                                        monkeypatch):
    from distkeras_tpu.runtime import faults as faults_mod
    from distkeras_tpu.runtime.faults import ChaosProxy

    sleeps = []
    real_sleep = time.sleep
    monkeypatch.setattr(faults_mod.time, "sleep",
                        lambda s: (sleeps.append(s), real_sleep(0))[1])
    t = _weights()
    ps = DeltaParameterServer(t, port=0, idle_timeout=None)
    ps.start()
    proxy = ChaosProxy("127.0.0.1", ps.port, jitter_delay_s=(0.01, 0.02),
                       seed=3, slow_conns={0}).start()
    try:
        def session():
            with PSClient("127.0.0.1", proxy.port, templates=t) as c:
                c.pull()
                c.commit([np.ones_like(x) for x in t])
                c.drain()

        def settled():
            # the pump threads may still be flushing the session's last
            # frames (BYE, trailing replies) after the client returned —
            # wait until the recorded-sleep count is quiescent
            n = -1
            deadline = time.monotonic() + 10
            while time.monotonic() < deadline:
                cur = len(sleeps)
                if cur == n:
                    return cur
                n = cur
                real_sleep(0.1)
            return len(sleeps)

        session()          # conn 0: throttled
        first = settled()
        assert first > 0
        assert all(0.01 <= s <= 0.02 for s in sleeps)
        session()          # conn 1: clean
        assert settled() == first
    finally:
        proxy.stop()
        ps.stop()


# -- wire-compat matrix (satellite 2) ------------------------------------------

class _RecordingSock:
    def __init__(self, sock):
        self._sock = sock
        self.tx = bytearray()

    def sendall(self, data):
        self.tx += bytes(data)
        return self._sock.sendall(data)

    def __getattr__(self, name):
        return getattr(self._sock, name)


def _assert_no_adaptive_frames(stream: bytes) -> None:
    i = 0
    while i < len(stream):
        n = int.from_bytes(stream[i:i + 8], "big")
        assert stream[i + 8:i + 9] not in (net.ACTION_RECONNECT,
                                           net.ACTION_RETRY)
        i += 8 + n


def _session_bytes(port, templates):
    with PSClient("127.0.0.1", port, templates=templates) as c:
        rec = _RecordingSock(c.sock)
        c.sock = rec
        c.pull()
        c.commit([np.full_like(t, 0.5) for t in templates])
        c.pull()
        c.drain()
    return bytes(rec.tx)


def test_plain_client_bytes_identical_against_adaptive_hub(fresh_health):
    t = _weights()
    plain = DeltaParameterServer(t, port=0, idle_timeout=None)
    adaptive = DeltaParameterServer(t, port=0, idle_timeout=None,
                                    adaptive=True)
    plain.start()
    adaptive.start()
    try:
        baseline = _session_bytes(plain.port, t)
        against_adaptive = _session_bytes(adaptive.port, t)
    finally:
        plain.stop()
        adaptive.stop()
    assert baseline == against_adaptive
    _assert_no_adaptive_frames(baseline)


def test_plain_striped_client_bytes_identical_on_adaptive_shards(
        fresh_health):
    t = [np.zeros((4, 4), np.float32), np.zeros((6,), np.float32),
         np.zeros((3,), np.float32)]
    plan = shard_plan(t, 2)

    def make(adaptive):
        ps = ShardedParameterServer(
            t, plan, lambda w, sid: DeltaParameterServer(
                w, shard_id=sid, idle_timeout=None, adaptive=adaptive))
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

    plain, adaptive = make(False), make(True)
    try:
        base_streams = session(plain)
        adap_streams = session(adaptive)
    finally:
        plain.stop()
        adaptive.stop()
    assert base_streams == adap_streams
    for s in base_streams:
        _assert_no_adaptive_frames(s)


def test_plain_client_bytes_identical_against_replicated_adaptive_primary(
        fresh_health):
    t = _weights()

    def make(adaptive):
        primary = DeltaParameterServer(t, port=0, idle_timeout=None,
                                       adaptive=adaptive)
        primary.start()
        replica = DeltaParameterServer(
            t, idle_timeout=None, replica_of=("127.0.0.1", primary.port))
        replica.start()
        assert replica.wait_synced(timeout=10)
        return primary, replica

    p1, r1 = make(False)
    p2, r2 = make(True)
    try:
        baseline = _session_bytes(p1.port, t)
        against_adaptive = _session_bytes(p2.port, t)
        # the adaptive primary replicated the applied (merged) delta
        deadline = time.monotonic() + 10
        while r2._clock < 1 and time.monotonic() < deadline:
            time.sleep(0.01)
        np.testing.assert_array_equal(r2.center[0], p2.center[0])
    finally:
        for hub in (r1, p1, r2, p2):
            hub.stop()
    assert baseline == against_adaptive
    _assert_no_adaptive_frames(baseline)


# -- off-path parity + trainer integration -------------------------------------

@pytest.mark.parametrize("trainer_name", [
    "AsyncADAG", "AsyncAEASGD", "AsyncDOWNPOUR", "AsyncDynSGD", "AsyncEAMSGD",
])
def test_adaptive_off_constructs_zero_adaptive_machinery(
        trainer_name, toy_dataset, monkeypatch):
    """adaptive=False (the default) never touches the adaptive stack —
    combiner and controller construction are made to raise, and all five
    Async* trainers still train exactly as at HEAD."""
    import distkeras_tpu as dk
    from distkeras_tpu.models.base import Model, ModelSpec
    from distkeras_tpu.runtime import parameter_server as ps_mod

    def boom(*a, **k):
        raise AssertionError("adaptive machinery constructed on the "
                             "adaptive=False path")

    monkeypatch.setattr(ps_mod._AdaptiveCombiner, "__init__", boom)
    monkeypatch.setattr(ps_mod.AdaptiveRateController, "__init__", boom)
    spec = ModelSpec(name="mlp",
                     config={"hidden_sizes": (16,), "num_outputs": 2},
                     input_shape=(8,))
    cls = getattr(dk, trainer_name)
    trainer = cls(Model.init(spec, seed=0),
                  loss="categorical_crossentropy", batch_size=16,
                  num_epoch=1, num_workers=2, communication_window=4,
                  learning_rate=0.05, seed=0)
    trainer.train(toy_dataset)
    assert trainer.history


def _native_mark():
    from distkeras_tpu.runtime.native import build_error, native_available

    return pytest.mark.skipif(not native_available(),
                              reason=f"native PS unavailable: {build_error()}")


# hub dimension (ISSUE 11): the C++ combiner's batch-of-one must equal
# the plain apply too
@pytest.mark.parametrize("trainer_name,pipeline,native", [
    ("AsyncADAG", False, False),
    ("AsyncDynSGD", True, False),  # pipelined: nonzero self-staleness scales
    pytest.param("AsyncDynSGD", True, True, marks=_native_mark()),
    pytest.param("AsyncADAG", False, True, marks=_native_mark()),
])
def test_adaptive_on_uncontended_trajectory_bit_equal(trainer_name, pipeline,
                                                      native, toy_dataset,
                                                      fresh_health):
    """One worker, no contention, no events: adaptive=True must be
    bit-identical to adaptive=False — the combiner's batch-of-one apply
    is the plain apply."""
    import jax

    import distkeras_tpu as dk
    from distkeras_tpu.models.base import Model, ModelSpec

    spec = ModelSpec(name="mlp",
                     config={"hidden_sizes": (16,), "num_outputs": 2},
                     input_shape=(8,))

    def run(adaptive):
        health_mod.reset_default()
        cls = getattr(dk, trainer_name)
        trainer = cls(Model.init(spec, seed=0),
                      loss="categorical_crossentropy", batch_size=16,
                      num_epoch=2, num_workers=1, communication_window=4,
                      learning_rate=0.05, seed=0, pipeline=pipeline,
                      adaptive=adaptive, native_ps=native)
        model = trainer.train(toy_dataset)
        return trainer.history, jax.tree.leaves(model.params)

    hist_off, params_off = run(False)
    hist_on, params_on = run(True)
    assert hist_off == hist_on
    for a, b in zip(params_off, params_on):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def test_adaptive_trainer_guards(toy_dataset):
    import distkeras_tpu as dk
    from distkeras_tpu.models.base import Model, ModelSpec
    from distkeras_tpu.runtime.launcher import start_parameter_server

    spec = ModelSpec(name="mlp",
                     config={"hidden_sizes": (16,), "num_outputs": 2},
                     input_shape=(8,))
    # adaptive + native is SERVED since ISSUE 11: the trainer constructs,
    # and a standalone native adaptive hub starts and stops cleanly
    dk.AsyncADAG(Model.init(spec, seed=0),
                 loss="categorical_crossentropy", batch_size=16,
                 num_epoch=1, adaptive=True, native_ps=True)
    from distkeras_tpu.runtime.native import native_available

    if native_available():
        ps = start_parameter_server(Model.init(spec, seed=0), native=True,
                                    adaptive=True, idle_timeout=None)
        try:
            assert ps.adaptive and ps.port > 0
        finally:
            ps.stop()


def test_adaptive_trainer_end_to_end(toy_dataset, fresh_health):
    """adaptive=True trains end to end over sockets with real worker
    concurrency: commits flow through the combiner (clock == windows),
    trace contexts exist without telemetry, and the run still learns."""
    import distkeras_tpu as dk
    from distkeras_tpu.models.base import Model, ModelSpec

    spec = ModelSpec(name="mlp",
                     config={"hidden_sizes": (16,), "num_outputs": 2},
                     input_shape=(8,))
    trainer = dk.AsyncADAG(Model.init(spec, seed=0),
                           loss="categorical_crossentropy", batch_size=16,
                           num_epoch=2, num_workers=2,
                           communication_window=4, learning_rate=0.05,
                           seed=0, adaptive=True, health_interval_s=0.1)
    trainer.train(toy_dataset)
    assert trainer.history
    assert trainer.worker_errors == []
    ps = trainer.parameter_server
    assert ps.num_updates == len(trainer.history)
    # the hub bound the health plane and folded per-worker staleness
    # (trace contexts exist even with telemetry off)
    workers = health_mod.collector().workers()
    assert any(w in ("0", "1") for w in workers), workers


def test_adaptive_inproc_trainer_end_to_end(toy_dataset, fresh_health):
    import distkeras_tpu as dk
    from distkeras_tpu.models.base import Model, ModelSpec

    spec = ModelSpec(name="mlp",
                     config={"hidden_sizes": (16,), "num_outputs": 2},
                     input_shape=(8,))
    trainer = dk.AsyncADAG(Model.init(spec, seed=0),
                           loss="categorical_crossentropy", batch_size=16,
                           num_epoch=1, num_workers=2,
                           communication_window=4, learning_rate=0.05,
                           seed=0, adaptive=True, transport="inproc")
    trainer.train(toy_dataset)
    assert trainer.history
    assert trainer.worker_errors == []


def test_distkeras_ps_adaptive_flag_composes_with_native():
    """--adaptive --native is no longer a parser error (ISSUE 11): the
    CLI reaches the model load (which fails on the nonexistent path,
    proving the flag combination passed validation)."""
    from distkeras_tpu.runtime.launcher import main

    with pytest.raises(FileNotFoundError):
        main(["--model", "/nonexistent", "--native", "--adaptive"])
