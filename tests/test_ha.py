"""Hub high availability (issue 7): per-shard primary->replica replication
(wire action R), standby promotion behind the clock fence, client failover
address lists, fleet-consistent snapshot sets, and the kill-primary drills.

Every drill is deterministic: kills are scheduled on the hub's commit clock
(:class:`~distkeras_tpu.runtime.faults.HubKillPlan`) or a seeded fault
plan, never on wall-clock sleeps alone.  Drills carry the ``chaos``
marker; the cheapest cell per trainer stays in tier-1, the rest of the
matrix is additionally slow-marked (the PR 6 convention)."""

import os
import signal
import socket
import subprocess
import sys
import threading
import time

import numpy as np
import pytest

from distkeras_tpu import observability as obs
from distkeras_tpu.runtime.faults import ChaosProxy, HubKillPlan
from distkeras_tpu.runtime.parameter_server import (
    ADAGParameterServer,
    DeltaParameterServer,
    DynSGDParameterServer,
    PSClient,
    ShardedParameterServer,
    ShardedPSClient,
    StripeLostError,
    shard_plan,
)


def _weights():
    return [np.zeros((2, 2), np.float32), np.zeros((3,), np.float32)]


def _ones():
    return [np.ones((2, 2), np.float32), np.ones((3,), np.float32)]


def _free_port() -> int:
    s = socket.socket()
    s.bind(("127.0.0.1", 0))
    port = s.getsockname()[1]
    s.close()
    return port


def _wait_until(pred, timeout=10.0, interval=0.01):
    deadline = time.monotonic() + timeout
    while time.monotonic() < deadline:
        if pred():
            return True
        time.sleep(interval)
    return False


def _replica_pair(hub_cls=DeltaParameterServer, retries=2, backoff=0.05,
                  **primary_kwargs):
    """A started (primary, replica) pair of Python hubs."""
    primary = hub_cls(_weights(), idle_timeout=None, **primary_kwargs)
    primary.start()
    replica = hub_cls(_weights(), idle_timeout=None,
                      replica_of=("127.0.0.1", primary.port),
                      replica_feed_retries=retries,
                      replica_feed_backoff=backoff, **primary_kwargs)
    replica.start()
    return primary, replica


# -- replication stream --------------------------------------------------------

def test_replica_full_syncs_then_tracks_deltas():
    """A standby attaching to a primary with history full-syncs (center +
    clock in one R frame), then applies every subsequent commit's scaled
    delta — its center equals the primary's bit for bit."""
    primary = DeltaParameterServer(_weights(), idle_timeout=None)
    primary.start()
    try:
        with PSClient("127.0.0.1", primary.port, templates=_weights()) as c:
            c.commit(_ones())  # pre-replica history -> exercises full sync
        replica = DeltaParameterServer(
            _weights(), idle_timeout=None,
            replica_of=("127.0.0.1", primary.port))
        replica.start()
        try:
            assert _wait_until(lambda: replica._clock == 1)
            assert replica.is_standby() and not replica.promoted
            with PSClient("127.0.0.1", primary.port,
                          templates=_weights()) as c:
                for _ in range(3):
                    c.commit(_ones())
            assert _wait_until(lambda: replica._clock == 4)
            for a, b in zip(primary.get_weights(), replica.get_weights()):
                np.testing.assert_array_equal(a, b)
            assert replica.num_updates == 4
        finally:
            replica.stop()
    finally:
        primary.stop()


def test_replication_streams_post_aggregation_deltas():
    """The feed carries the APPLIED delta (post scaling rule), not the raw
    commit: an ADAG primary with num_workers=4 streams delta/4, and the
    replica's center matches the primary's exactly — no scaling-rule
    knowledge needed on the replica."""
    primary = ADAGParameterServer(_weights(), num_workers=4,
                                  idle_timeout=None)
    primary.start()
    replica = ADAGParameterServer(_weights(), num_workers=4,
                                  idle_timeout=None,
                                  replica_of=("127.0.0.1", primary.port))
    replica.start()
    try:
        with PSClient("127.0.0.1", primary.port, templates=_weights()) as c:
            for _ in range(4):
                c.commit(_ones())
        assert _wait_until(lambda: replica._clock == 4)
        np.testing.assert_array_equal(replica.get_weights()[0],
                                      np.ones((2, 2), np.float32))
        for a, b in zip(primary.get_weights(), replica.get_weights()):
            np.testing.assert_array_equal(a, b)
    finally:
        replica.stop()
        primary.stop()


def test_replication_is_observationally_pure():
    """Acceptance: with a replica attached but no failure, the PRIMARY's
    center trajectory is bit-identical to an unreplicated run of the same
    commit sequence (x * float32(1.0) and the scale-then-add ordering are
    exact)."""
    rng = np.random.default_rng(7)
    deltas = [[rng.normal(size=w.shape).astype(np.float32) for w in _weights()]
              for _ in range(6)]

    def run(replicated):
        hub = DynSGDParameterServer(_weights(), idle_timeout=None)
        hub.start()
        replica = None
        if replicated:
            replica = DynSGDParameterServer(
                _weights(), idle_timeout=None,
                replica_of=("127.0.0.1", hub.port))
            replica.start()
            assert _wait_until(lambda: hub._feed is not None
                               and hub._feed.active(), timeout=5)
        try:
            with PSClient("127.0.0.1", hub.port, templates=_weights()) as c:
                for d in deltas:
                    c.commit([x.copy() for x in d])
            return [w.copy() for w in hub.get_weights()]
        finally:
            if replica is not None:
                replica.stop()
            hub.stop()

    plain = run(replicated=False)
    replicated = run(replicated=True)
    for a, b in zip(plain, replicated):
        np.testing.assert_array_equal(a, b)


def test_replica_lag_injection_feed_catches_up():
    """Replica-lag injection: the feed routed through a delay-everything
    ChaosProxy tracks the primary with measured lag, then converges."""
    primary = DeltaParameterServer(_weights(), idle_timeout=None)
    primary.start()
    try:
        with ChaosProxy("127.0.0.1", primary.port,
                        delay_all_s=0.05) as proxy:
            replica = DeltaParameterServer(
                _weights(), idle_timeout=None,
                replica_of=("127.0.0.1", proxy.port))
            replica.start()
            try:
                with PSClient("127.0.0.1", primary.port,
                              templates=_weights()) as c:
                    for _ in range(4):
                        c.commit(_ones())
                # commits ack without waiting for the delayed feed hop, so
                # the replica is BEHIND right after the burst...
                assert _wait_until(lambda: replica._clock == 4, timeout=10)
                # ...and converges to the exact primary center
                for a, b in zip(primary.get_weights(),
                                replica.get_weights()):
                    np.testing.assert_array_equal(a, b)
            finally:
                replica.stop()
    finally:
        primary.stop()


def test_publish_out_of_clock_order_loses_nothing():
    """Regression: concurrent commit handlers apply under the hub lock but
    publish under the feed lock, so deltas can reach the feed OUT of clock
    order.  A lower-clock delta arriving behind a higher one must still be
    streamed (deltas commute; only the attach-time sync may filter)."""
    primary, replica = _replica_pair()
    try:
        with PSClient("127.0.0.1", primary.port, templates=_weights()) as c:
            c.commit(_ones())  # ensures the replica is attached + synced
        assert _wait_until(lambda: replica._clock == 1)
        feed = primary._feed
        one = [np.ones_like(t) for t in _weights()]
        # simulate the inversion: clock 3 beats clock 2 to the feed
        feed.publish(3, one)
        feed.publish(2, one)
        assert _wait_until(lambda: replica.num_updates == 3)
        # both deltas landed: center = 3 units, not 2
        np.testing.assert_array_equal(replica.get_weights()[0],
                                      np.full((2, 2), 3, np.float32))
        assert replica._clock == 3
    finally:
        replica.stop()
        primary.stop()


def test_feed_socket_blocks_without_recv_timeout():
    """Regression: the feed's connect timeout must not linger as a recv
    timeout — an idle primary (no commits for 30 s) must not read as feed
    loss and trigger a full-resync loop."""
    primary, replica = _replica_pair()
    try:
        assert _wait_until(lambda: replica._replica_sock is not None)
        assert replica._replica_sock.gettimeout() is None
    finally:
        replica.stop()
        primary.stop()


# -- promotion + fence ---------------------------------------------------------

@pytest.mark.chaos
def test_feed_loss_promotes_behind_clock_fence():
    primary, replica = _replica_pair(retries=2, backoff=0.02)
    try:
        with PSClient("127.0.0.1", primary.port, templates=_weights()) as c:
            for _ in range(3):
                c.commit(_ones())
        assert _wait_until(lambda: replica._clock == 3)
        primary.kill()
        assert _wait_until(lambda: replica.promoted, timeout=10)
        assert not replica.is_standby()
        assert replica._clock_fence == replica._clock == 3
    finally:
        replica.stop()


@pytest.mark.chaos
def test_commit_to_standby_promotes_first():
    """A failed-over worker's commit must not wait for the feed-loss
    detector: committing into a standby promotes it immediately (fence
    armed BEFORE the commit's staleness is computed)."""
    primary, replica = _replica_pair(retries=50, backoff=1.0)  # detector slow
    try:
        with PSClient("127.0.0.1", primary.port, templates=_weights()) as c:
            c.commit(_ones())
        assert _wait_until(lambda: replica._clock == 1)
        primary.kill()
        # the feed notices the death (EOF) almost instantly; a commit
        # arriving even earlier would be refused once as a split-brain
        # probe — wait for the deterministic precondition
        assert _wait_until(lambda: replica._replica_sock is None)
        with PSClient("127.0.0.1", replica.port, templates=_weights()) as c:
            c.commit(_ones())
        assert replica.promoted
        assert replica._clock_fence == 1
        assert replica.num_updates == 2
    finally:
        replica.stop()


@pytest.mark.chaos
def test_promotion_fences_pre_promotion_socket_connections():
    """Regression: a connection born on the STANDBY before promotion
    carries last_pull_clock = the pre-promotion fence (0).  When the hub
    promotes underneath it, its next commit must be re-based at the new
    fence — otherwise DynSGD sees the full replicated clock as staleness
    and near-zeroes the delta."""
    primary, replica = _replica_pair(hub_cls=DynSGDParameterServer,
                                     retries=50, backoff=1.0)
    try:
        with PSClient("127.0.0.1", primary.port, templates=_weights()) as c:
            for _ in range(9):
                c.pull()
                c.commit(_ones())  # staleness 0 each -> center += 1 each
        assert _wait_until(lambda: replica._clock == 9)
        # connection born on the standby BEFORE promotion, never pulls
        early = PSClient("127.0.0.1", replica.port, templates=_weights())
        try:
            primary.kill()
            assert _wait_until(lambda: replica._replica_sock is None)
            # another client's commit promotes (fence = 9, clock -> 10)
            with PSClient("127.0.0.1", replica.port,
                          templates=_weights()) as trigger:
                trigger.commit(_ones())
            assert replica.promoted and replica._clock_fence == 9
            before = replica.get_weights()[0][0, 0]
            early.commit(_ones())  # no pull: stale clock from birth
            after = replica.get_weights()[0][0, 0]
            # fenced: staleness = 10 - 9 = 1 -> scale 1/2.  Unfenced it
            # would be 10 - 0 = 10 -> scale 1/11 (near-zeroed work)
            np.testing.assert_allclose(after - before, 0.5, rtol=1e-6)
        finally:
            early.close()
    finally:
        replica.stop()


@pytest.mark.chaos
def test_commit_with_live_feed_refuses_and_reverifies_no_split_brain():
    """Split-brain guard: one misdirected worker committing into a SYNCED
    standby whose primary is alive must not promote it.  The commit is
    refused and the feed is severed as a probe; the feed reconnects to
    the live primary, the standby stays standby, and the primary keeps
    serving."""
    primary, replica = _replica_pair(retries=5, backoff=0.02)
    try:
        with PSClient("127.0.0.1", primary.port, templates=_weights()) as c:
            c.commit(_ones())
        assert _wait_until(lambda: replica._clock == 1)
        # pulls from a synced standby are fine (read-only)
        with PSClient("127.0.0.1", replica.port, templates=_weights()) as c:
            assert float(c.pull()[0][0, 0]) == 1.0
        # a stray commit while the feed is live: refused, not promoted
        with pytest.raises(ConnectionError):
            with PSClient("127.0.0.1", replica.port,
                          templates=_weights()) as stray:
                stray.commit(_ones())
        assert not replica.promoted
        # the probe severed the feed; it re-verifies the LIVE primary and
        # resyncs — still standby, still tracking
        assert _wait_until(lambda: replica._replica_sock is not None,
                           timeout=10)
        with PSClient("127.0.0.1", primary.port, templates=_weights()) as c:
            c.commit(_ones())
        assert _wait_until(lambda: replica._clock == 2)
        assert replica.is_standby() and not replica.promoted
        for a, b in zip(primary.get_weights(), replica.get_weights()):
            np.testing.assert_array_equal(a, b)
    finally:
        replica.stop()
        primary.stop()


def test_clean_teardown_never_promotes():
    """stop()/kill() of the replica itself is not a failover: the standby
    exits standby-side without promoting."""
    primary, replica = _replica_pair()
    replica.stop()
    assert not replica.promoted
    primary.stop()


# -- client failover -----------------------------------------------------------

@pytest.mark.chaos
def test_client_failover_zero_acked_commit_loss():
    """The acceptance property at the client level: every commit the
    client saw ACKED before the primary's death is present in the
    promoted replica's center (send-to-replica happens before the ack
    leaves); the in-flight unacked commit may drop (PR-4 semantics)."""
    primary, replica = _replica_pair(retries=2, backoff=0.02)
    try:
        # same deterministic gate as the telemetry drill below: the kill
        # must not race the standby's initial attach+sync
        assert replica.wait_synced(timeout=10)
        with PSClient("127.0.0.1", primary.port, templates=_weights(),
                      failover=[("127.0.0.1", replica.port)],
                      max_reconnects=6, reconnect_backoff=0.02) as c:
            acked = 0
            for _ in range(5):
                c.commit(_ones())  # blocking: returns only once acked
                acked += 1
            primary.kill()
            for _ in range(3):
                c.commit(_ones())
            final = [w.copy() for w in c.pull()]
        assert (c.host, c.port) == ("127.0.0.1", replica.port)
        assert replica.promoted
        # zero ACKED loss, judged at PROMOTION time so post-failover
        # commits can't mask a lossy feed: every acked commit replicated
        assert replica.promoted_at_clock >= acked
        # and whatever landed did so exactly once (delta hub: center is an
        # integer multiple of the unit delta)
        assert float(final[0][0, 0]) == replica.num_updates
        assert replica.num_updates <= acked + 3
    finally:
        replica.stop()


@pytest.mark.chaos
def test_failover_telemetry_and_fleet_report():
    """ps.failovers / ps.failover_ms land on a failover (and NOT on a
    same-address reconnect), promotion is counted hub-side, and
    fleet_report surfaces both."""
    primary, replica = _replica_pair(retries=2, backoff=0.02)
    obs.enable()
    obs.reset()
    try:
        # deterministic promotion gate (the PR 8 drill-ordering rule):
        # kill ONLY once the standby has (a) applied its full sync and
        # (b) seen the first commit replicate.  Killing earlier races the
        # replica's initial attach — under full-suite load the standby
        # could still be dialing a primary that is already dead, never
        # sync, and (correctly) refuse to promote forever, so the whole
        # drill came down to thread-scheduling luck (~1-in-10 timeouts)
        assert replica.wait_synced(timeout=10)
        with PSClient("127.0.0.1", primary.port, templates=_weights(),
                      failover=[("127.0.0.1", replica.port)],
                      max_reconnects=6, reconnect_backoff=0.02) as c:
            c.commit(_ones())
            assert _wait_until(lambda: replica._clock >= 1)
            primary.kill()
            c.commit(_ones())
            c.commit(_ones())
        assert _wait_until(lambda: replica.promoted, timeout=10)
        snap = obs.snapshot()
        assert snap["counters"].get("ps.failovers") == 1.0
        hist = snap["histograms"].get("ps.failover_ms")
        assert hist and hist["count"] == 1
        assert snap["counters"].get("ps_promotions_total") == 1.0
        from distkeras_tpu.observability.distributed import fleet_report

        report = fleet_report(events=obs.TRACER.events())
        assert report["failovers_total"] == 1
        assert report["failover_ms_mean"] is not None
        assert len(report["promotions"]) == 1
    finally:
        obs.reset()
        obs.disable()
        replica.stop()


def test_initial_connect_walks_failover_list():
    """A worker (re)started AFTER the failover finds the promoted standby:
    the constructor tries the dead primary, then the failover address."""
    dead_port = _free_port()
    hub = DeltaParameterServer(_weights(), idle_timeout=None)
    hub.start()
    try:
        with PSClient("127.0.0.1", dead_port, templates=_weights(),
                      failover=[("127.0.0.1", hub.port)]) as c:
            assert (c.host, c.port) == ("127.0.0.1", hub.port)
            c.commit(_ones())
        assert hub.num_updates == 1
    finally:
        hub.stop()
    # every address dead -> the primary's error surfaces
    with pytest.raises(OSError):
        PSClient("127.0.0.1", dead_port, templates=_weights(),
                 failover=[("127.0.0.1", _free_port())], timeout=2.0)


# -- heartbeat vs close/failover races (satellite) -----------------------------

@pytest.mark.chaos
def test_heartbeat_racing_reconnect_burns_no_extra_budget():
    """Satellite pin: an aggressive heartbeat riding through a real fault +
    reconnect costs the caller EXACTLY the real fault's budget — the ping
    can neither fire into a half-swapped socket (io-lock serialized) nor
    poison the fresh connection (last_io reset on swap)."""
    from distkeras_tpu.runtime.faults import Fault, FaultPlan

    ps = DeltaParameterServer(_weights(), idle_timeout=None)
    ps.start()
    plan = FaultPlan([Fault(conn=0, direction="s2c", frame=2, kind="sever")])
    try:
        with ChaosProxy("127.0.0.1", ps.port, plan) as proxy:
            with PSClient("127.0.0.1", proxy.port, templates=_weights(),
                          max_reconnects=5, reconnect_backoff=0.02,
                          heartbeat_interval=0.02) as c:
                for _ in range(4):
                    c.pull()
                    c.commit(_ones())
                # idle long enough for many heartbeat rounds on the
                # post-reconnect socket, then keep exchanging
                time.sleep(0.3)
                for _ in range(2):
                    c.pull()
                    c.commit(_ones())
            assert len(proxy.faults_fired) == 1
            assert c.reconnects_used == 1  # the sever, nothing else
    finally:
        ps.stop()


def test_close_during_active_heartbeat_is_clean():
    """close() serializes with the heartbeat under the io lock: repeated
    open/exchange/close cycles with a hot heartbeat never deadlock, leak,
    or consume reconnect budget."""
    ps = DeltaParameterServer(_weights(), idle_timeout=None)
    ps.start()
    try:
        for _ in range(10):
            c = PSClient("127.0.0.1", ps.port, templates=_weights(),
                         max_reconnects=3, reconnect_backoff=0.02,
                         heartbeat_interval=0.01)
            c.pull()
            c.commit(_ones())
            time.sleep(0.02)  # let a ping round trip get going
            c.close()
            assert c.reconnects_used == 0
            assert c._hb_thread is None
    finally:
        ps.stop()


# -- sharded stripes: typed partial failure + per-shard failover ---------------

def _templates():
    return [np.zeros((4, 4), np.float32), np.zeros((8,), np.float32),
            np.zeros((2, 3), np.float32)]


@pytest.mark.chaos
def test_stripe_lost_error_names_the_shard():
    t = _templates()
    plan = shard_plan(t, 2)
    hubs = [DeltaParameterServer(
        [t[i] for i in plan.assignments[sid]], idle_timeout=None,
        shard_id=sid) for sid in range(2)]
    for hub in hubs:
        hub.start()
    obs.enable()
    obs.reset()
    try:
        client = ShardedPSClient(
            [("127.0.0.1", h.port) for h in hubs], t, plan,
            max_reconnects=1, reconnect_backoff=0.02)
        with client:
            client.commit([np.full(a.shape, 0.5, np.float32) for a in t])
            hubs[1].kill()
            with pytest.raises(StripeLostError) as ei:
                for _ in range(3):
                    client.commit([np.full(a.shape, 0.5, np.float32)
                                   for a in t])
        err = ei.value
        assert err.shard_index == 1
        assert f"{err.host}:{err.port}" in str(err)
        assert "shard 1" in str(err)
        assert isinstance(err, ConnectionError)  # old handlers still catch
        spans = [s for s in obs.TRACER.events()
                 if s["name"] == "ps.stripe_lost"]
        assert spans and spans[0]["attrs"]["shard"] == 1
        from distkeras_tpu.observability.distributed import fleet_report

        report = fleet_report(events=obs.TRACER.events())
        assert report["stripes_lost"] and \
            report["stripes_lost"][0]["shard"] == 1
    finally:
        obs.reset()
        obs.disable()
        for hub in hubs:
            hub.stop()


def test_stripe_lost_covers_fail_fast_timeout_and_desync():
    """Regression: with max_reconnects=0 the ORIGINAL fault propagates —
    a recv timeout (socket.timeout, not a ConnectionError) and a desynced
    stream (ProtocolError, a ValueError) must still surface as the typed
    StripeLostError naming the shard."""
    t = _templates()
    plan = shard_plan(t, 2)
    hubs = [DeltaParameterServer(
        [t[i] for i in plan.assignments[sid]], idle_timeout=None,
        shard_id=sid) for sid in range(2)]
    for hub in hubs:
        hub.start()
    try:
        # recv timeout on shard 1: commit, then wait for an ack that a
        # wedged hub never sends (simulated by a tiny client timeout
        # against a hub that DID ack — consume the real ack first via a
        # plain pull... simplest deterministic wedge: point shard 1 at a
        # listener that never replies)
        silent = socket.socket()
        silent.bind(("127.0.0.1", 0))
        silent.listen(8)
        try:
            client = ShardedPSClient(
                [("127.0.0.1", hubs[0].port),
                 ("127.0.0.1", silent.getsockname()[1])],
                t, plan, timeout=0.3, max_reconnects=0)
            with client:
                with pytest.raises(StripeLostError) as ei:
                    client.pull()
            assert ei.value.shard_index == 1
        finally:
            silent.close()
    finally:
        for hub in hubs:
            hub.stop()


@pytest.mark.chaos
def test_sharded_failover_per_stripe():
    """Each shard primary has its own standby; killing ONE shard primary
    fails only that stripe over, and no acked striped commit is lost."""
    t = _templates()
    plan = shard_plan(t, 2)

    def make(sid, replica_of=None):
        hub = DeltaParameterServer(
            [t[i] for i in plan.assignments[sid]], idle_timeout=None,
            shard_id=sid, replica_of=replica_of,
            replica_feed_retries=2, replica_feed_backoff=0.02)
        hub.start()
        return hub

    primaries = [make(sid) for sid in range(2)]
    replicas = [make(sid, replica_of=("127.0.0.1", primaries[sid].port))
                for sid in range(2)]
    try:
        client = ShardedPSClient(
            [("127.0.0.1", h.port) for h in primaries], t, plan,
            max_reconnects=6, reconnect_backoff=0.02,
            failover=[("127.0.0.1", replicas[0].port),
                      ("127.0.0.1", replicas[1].port)])
        with client:
            acked = 0
            for _ in range(4):
                client.commit([np.full(a.shape, 1.0, np.float32) for a in t])
                acked += 1
            assert _wait_until(lambda: all(r._clock == acked
                                           for r in replicas))
            primaries[1].kill()
            for _ in range(3):
                client.commit([np.full(a.shape, 1.0, np.float32) for a in t])
            final = [w.copy() for w in client.pull()]
        assert replicas[1].promoted
        assert not replicas[0].promoted          # stripe 0 never failed over
        assert client.shards[0].reconnects_used == 0
        assert (client.shards[1].host, client.shards[1].port) == \
            ("127.0.0.1", replicas[1].port)
        # shard 0 (untouched primary) saw all 7; shard 1's standby holds
        # at least every acked striped commit
        assert primaries[0].num_updates == 7
        assert replicas[1].num_updates >= acked
        for i in plan.assignments[1]:
            assert float(np.ravel(final[i])[0]) == replicas[1].num_updates
    finally:
        for hub in replicas + primaries:
            try:
                hub.stop()
            except Exception:
                pass


# -- coordinated snapshot sets -------------------------------------------------

def _facade(tmp_path, hub_cls=DeltaParameterServer, native=False, **kw):
    t = _templates()
    plan = shard_plan(t, 2)
    if native:
        from distkeras_tpu.runtime.native import (MODE_DELTA,
                                                  NativeParameterServer)

        def factory(w, sid):
            return NativeParameterServer(w, mode=MODE_DELTA,
                                         idle_timeout=None, shard_id=sid)
    else:
        def factory(w, sid):
            return hub_cls(w, idle_timeout=None, shard_id=sid)
    ps = ShardedParameterServer(t, plan, factory,
                                snapshot_dir=str(tmp_path), **kw)
    return ps, plan, t


@pytest.mark.parametrize("hub_kind", ["python", "native"])
def test_snapshot_set_saves_one_causal_cut_and_restores(tmp_path, hub_kind):
    if hub_kind == "native":
        from distkeras_tpu.runtime.native import native_available
        if not native_available():
            pytest.skip("no C++ toolchain for the native hub")
    ps, plan, t = _facade(tmp_path, native=(hub_kind == "native"),
                          snapshot_interval=3600.0)
    ps.start()
    try:
        for hub in ps.shards:
            assert getattr(hub, "snapshotter", None) is None
        ps.commit_direct([np.full(a.shape, 0.5, np.float32) for a in t], 0)
        ps.coordinator.save_set()
        expected = [w.copy() for w in ps.get_weights()]
        # set metadata: same set id + clock vector everywhere
        metas = [cp.metadata()["metadata"] for cp in ps.coordinator.checkpointers]
        assert len({m["snapshot_set"] for m in metas}) == 1
        assert all(m["set_clocks"] == [1, 1] for m in metas)
    finally:
        ps.kill()  # crash semantics: recovery comes from the snapshot set

    fresh, _, _ = _facade(tmp_path, native=(hub_kind == "native"),
                          snapshot_interval=3600.0, restore=True)
    fresh.start()
    try:
        for a, b in zip(expected, fresh.get_weights()):
            np.testing.assert_array_equal(a, b)
        if hub_kind == "python":
            for hub in fresh.shards:
                assert hub._clock_fence == hub._clock == 1
    finally:
        fresh.stop()


@pytest.mark.parametrize("hub_kind", ["python", "native"])
def test_torn_snapshot_set_detected_and_refused(tmp_path, hub_kind):
    """Satellite: a multi-shard restore across mismatched sets must be
    detected — fall back to the newest COMPLETE set when one exists,
    refuse when none does.  Covers both hubs."""
    if hub_kind == "native":
        from distkeras_tpu.runtime.native import native_available
        if not native_available():
            pytest.skip("no C++ toolchain for the native hub")
    ps, plan, t = _facade(tmp_path, native=(hub_kind == "native"),
                          snapshot_interval=3600.0)
    ps.start()
    try:
        ps.commit_direct([np.full(a.shape, 0.5, np.float32) for a in t], 0)
        ps.coordinator.save_set()          # step 1: complete
        set1 = [w.copy() for w in ps.get_weights()]
        ps.commit_direct([np.full(a.shape, 0.5, np.float32) for a in t], 0)
        ps.coordinator.save_set()          # step 2: will be torn below
    finally:
        ps.kill()

    # tear step 2: shard 1's copy vanishes (crash between per-shard saves)
    ps.coordinator.checkpointers[1].delete_step(2)

    fresh, _, _ = _facade(tmp_path, native=(hub_kind == "native"),
                          snapshot_interval=3600.0, restore=True)
    with pytest.warns(UserWarning, match="torn"):
        fresh.start()  # falls back to the newest COMPLETE set (step 1)
    try:
        for a, b in zip(set1, fresh.get_weights()):
            np.testing.assert_array_equal(a, b)
    finally:
        fresh.kill()

    # mismatched-clock tear: shard 1's step-1 snapshot replaced by one
    # from a DIFFERENT history (wrong set id + wrong clock) -> with no
    # complete set left anywhere, restore must refuse
    rogue = DeltaParameterServer([t[i] for i in plan.assignments[1]],
                                 idle_timeout=None)
    center, state = rogue.snapshot_state()
    ps.coordinator.checkpointers[1].delete_step(1)
    ps.coordinator.checkpointers[1].save(
        1, {"center": center}, metadata={"kind": "ps-hub-snapshot", **state})
    last, _, _ = _facade(tmp_path, native=(hub_kind == "native"),
                         snapshot_interval=3600.0, restore=True)
    with pytest.warns(UserWarning):
        with pytest.raises(RuntimeError, match="complete and clock-consistent"):
            last.start()


def test_legacy_per_shard_snapshots_restore_with_torn_warning(tmp_path):
    """Back-compat: shard-NN/ snapshots written by PR-6's independent
    per-shard snapshotters carry no snapshot_set id.  The coordinated
    restore path must still load them (warning about the uncoordinated
    cut) instead of stranding the job behind the torn-set refusal."""
    t = _templates()
    plan = shard_plan(t, 2)
    # write PR-6-style snapshots: per-hub snapshotters, no coordination
    hubs = [DeltaParameterServer(
        [t[i] for i in plan.assignments[sid]], idle_timeout=None,
        shard_id=sid, snapshot_dir=os.path.join(str(tmp_path),
                                                f"shard-{sid:02d}"),
        snapshot_interval=3600.0) for sid in range(2)]
    legacy = ShardedParameterServer(t, plan, lambda w, sid: hubs[sid])
    legacy.start()
    try:
        legacy.commit_direct([np.full(a.shape, 0.5, np.float32)
                              for a in t], 0)
        for hub in legacy.shards:
            hub.snapshotter.save_now()
        expected = [w.copy() for w in legacy.get_weights()]
    finally:
        legacy.kill()

    fresh, _, _ = _facade(tmp_path, snapshot_interval=3600.0, restore=True)
    with pytest.warns(UserWarning, match="predates coordinated sets"):
        fresh.start()
    try:
        for a, b in zip(expected, fresh.get_weights()):
            np.testing.assert_array_equal(a, b)
        for hub in fresh.shards:
            assert hub._clock_fence == hub._clock == 1
    finally:
        fresh.stop()


def test_snapshot_set_gc_prunes_all_shards_in_lockstep(tmp_path):
    """Satellite: keep-N retention applies to the SET — after every save,
    all shard-NN/ directories hold exactly the same step numbers."""
    ps, plan, t = _facade(tmp_path, snapshot_interval=3600.0,
                          snapshot_keep=2)
    ps.start()
    try:
        for _ in range(4):
            ps.commit_direct([np.full(a.shape, 0.5, np.float32)
                              for a in t], 0)
            ps.coordinator.save_set()
        step_sets = [cp.all_steps() for cp in ps.coordinator.checkpointers]
        assert step_sets[0] == step_sets[1] == [3, 4]
    finally:
        ps.kill()


def test_launcher_facade_uses_coordinated_snapshots(tmp_path):
    """start_parameter_server's all-shards-in-one-process path snapshots
    through the coordinator (per-hub snapshotters stay off), and a
    relaunch with restore=True resumes the set."""
    from distkeras_tpu.models.base import Model, ModelSpec
    from distkeras_tpu.runtime.launcher import start_parameter_server

    spec = ModelSpec(name="mlp", config={"hidden_sizes": (16,),
                                         "num_outputs": 2},
                     input_shape=(8,))
    model = Model.init(spec, seed=0)
    snap = str(tmp_path / "sets")
    ps = start_parameter_server(model, mode="delta", num_shards=2,
                                idle_timeout=None, snapshot_dir=snap,
                                snapshot_interval=3600.0)
    try:
        assert ps.coordinator is not None
        assert all(getattr(h, "snapshotter", None) is None
                   for h in ps.shards)
        ps.commit_direct([np.ones(w.shape, np.float32)
                          for w in ps.get_weights()], 0)
    finally:
        ps.stop()  # writes the final coordinated set
    expected_first = None
    ps2 = start_parameter_server(model, mode="delta", num_shards=2,
                                 idle_timeout=None, snapshot_dir=snap,
                                 snapshot_interval=3600.0, restore=True)
    try:
        got = ps2.get_weights()
        expected_first = float(np.ravel(got[0])[0])
        assert ps2.num_updates == 1
    finally:
        ps2.stop()
    assert expected_first is not None


# -- launcher / trainer replica plumbing ---------------------------------------

def test_launcher_replica_of_starts_a_tracking_standby():
    from distkeras_tpu.models.base import Model, ModelSpec
    from distkeras_tpu.runtime.launcher import start_parameter_server

    spec = ModelSpec(name="mlp", config={"hidden_sizes": (16,),
                                         "num_outputs": 2},
                     input_shape=(8,))
    model = Model.init(spec, seed=0)
    primary = start_parameter_server(model, mode="delta", idle_timeout=None)
    replica = start_parameter_server(model, mode="delta", idle_timeout=None,
                                     replica_of=("127.0.0.1", primary.port))
    try:
        assert replica.is_standby()
        primary.commit_direct([np.ones(w.shape, np.float32)
                               for w in primary.get_weights()], 0)
        assert _wait_until(lambda: replica._clock == 1)
        for a, b in zip(primary.get_weights(), replica.get_weights()):
            np.testing.assert_array_equal(a, b)
    finally:
        replica.stop()
        primary.stop()
    # native hubs run the replication feed too since ISSUE 11 (both
    # sides); the cross-implementation drills live in test_native_ps.py


def test_native_hub_accepts_replica_of():
    """replica_of on the C++ hub constructs a standby (ISSUE 11) — the
    live feed/promotion drills ride tests/test_native_ps.py."""
    from distkeras_tpu.runtime.native import (MODE_DELTA,
                                              NativeParameterServer,
                                              native_available)

    if not native_available():
        pytest.skip("no C++ toolchain for the native hub")
    ps = NativeParameterServer(_weights(), mode=MODE_DELTA,
                               replica_of=("127.0.0.1", 1))
    assert ps.is_standby() and not ps.promoted


def test_trainer_replica_knob_validation():
    import distkeras_tpu as dk
    from distkeras_tpu.models.base import ModelSpec

    spec = ModelSpec(name="mlp", config={"hidden_sizes": (16,),
                                         "num_outputs": 2},
                     input_shape=(8,))
    with pytest.raises(ValueError, match="worker-only"):
        dk.AsyncADAG(spec, ps_address=("h", 1), replica_of=("h", 2))
    with pytest.raises(ValueError, match="num_shards"):
        dk.AsyncADAG(spec, num_shards=2, replica_of=("h", 2))
    with pytest.raises(ValueError, match="per shard"):
        dk.AsyncADAG(spec, ps_address=[("h", 1), ("h", 2)],
                     ps_failover=[("h", 3)])
    # a bare pair with num_shards=2 has the RIGHT length by accident and
    # must still be rejected, not sliced into per-shard garbage
    with pytest.raises(ValueError, match="single \\(host, port\\) pair"):
        dk.AsyncADAG(spec, ps_address=[("h", 1), ("h", 2)],
                     ps_failover=("127.0.0.1", 6000))
    tr = dk.AsyncADAG(spec, ps_address=("h", 1), ps_failover=("h", 2))
    assert tr._ps_failover == [[("h", 2)]]


@pytest.mark.chaos
def test_trainer_replica_of_takes_over_primary_state():
    """A trainer whose own hub is a replica_of standby must WAIT for the
    primary's full sync before its workers run: training continues from
    the primary's center (here: far from init), never silently from
    seed."""
    import distkeras_tpu as dk
    from distkeras_tpu.models.base import Model
    from distkeras_tpu.runtime.launcher import start_parameter_server

    model0 = Model.init(_mlp_spec(), seed=0)
    primary = start_parameter_server(model0, mode="adag", num_workers=2,
                                     idle_timeout=None)
    # move the primary's center somewhere unmistakable (the adag hub
    # halves the delta at num_workers=2 — read back what actually landed)
    primary.commit_direct([np.full(w.shape, 7.25, np.float32) - w
                           for w in primary.get_weights()], 0)
    marker = [w.copy() for w in primary.get_weights()]
    assert not np.allclose(marker[0], 0.0)
    trainer = dk.AsyncADAG(Model.init(_mlp_spec(), seed=0),
                           loss="categorical_crossentropy", batch_size=16,
                           num_epoch=1, num_workers=2,
                           communication_window=2, learning_rate=0.0,
                           seed=0, replica_of=("127.0.0.1", primary.port))
    try:
        model = trainer.train(_tiny_dataset())
    finally:
        primary.stop()
    hub = trainer.parameter_server
    assert hub.promoted  # the first worker commit took the job over
    # lr=0 -> every commit delta is zero: the final center IS the synced
    # primary center, proving workers trained from it, not from seed
    from distkeras_tpu.utils import flatten_weights

    final, _ = flatten_weights(model.params)
    for f, m in zip(final, marker):
        np.testing.assert_allclose(np.asarray(f), m, atol=1e-6)


def test_trainer_replica_of_unreachable_primary_fails_loudly():
    """replica_of pointing at a dead address must raise, not silently
    train from fresh weights (and a never-synced standby never promotes
    itself meanwhile)."""
    import distkeras_tpu as dk
    from distkeras_tpu.models.base import Model

    dead = _free_port()
    trainer = dk.AsyncADAG(Model.init(_mlp_spec(), seed=0),
                           loss="categorical_crossentropy", batch_size=16,
                           num_epoch=1, num_workers=1,
                           communication_window=2, learning_rate=0.05,
                           seed=0, replica_of=("127.0.0.1", dead),
                           replica_sync_timeout=1.0)
    with pytest.raises(RuntimeError, match="no full sync"):
        trainer.train(_tiny_dataset())


def test_commit_into_never_synced_standby_is_refused():
    """A standby whose sync never arrived holds fresh init weights, not
    the job's state: a commit into it (a worker failing over too eagerly)
    must be refused — the connection drops and the standby stays
    unpromoted — instead of promoting seed weights into 'the job'."""
    dead = _free_port()
    replica = DeltaParameterServer(_weights(), idle_timeout=None,
                                   replica_of=("127.0.0.1", dead),
                                   replica_feed_retries=1000,
                                   replica_feed_backoff=0.05)
    replica.start()
    try:
        with pytest.raises(ConnectionError):
            with PSClient("127.0.0.1", replica.port,
                          templates=_weights()) as c:
                c.commit(_ones())
        assert not replica.promoted
        assert replica.is_standby()
        assert replica.num_updates == 0
        # pulls are refused too: seed weights must never be served as if
        # they were the job's state (a failed-over worker would train a
        # whole window on them)
        with pytest.raises(ConnectionError):
            with PSClient("127.0.0.1", replica.port,
                          templates=_weights()) as c:
                c.pull()
        # inproc paths refuse too, with guidance
        with pytest.raises(RuntimeError, match="never-synced standby"):
            replica.commit_direct(_ones(), 0)
        with pytest.raises(RuntimeError, match="never-synced standby"):
            replica.pull_direct()
    finally:
        replica.stop()


def test_never_synced_standby_does_not_promote():
    """A standby that never reached its primary keeps retrying (one
    warning, capped backoff) instead of promoting — it has nothing to
    take over, and serving fresh init weights as the job's state would be
    silent data loss."""
    dead = _free_port()
    replica = DeltaParameterServer(_weights(), idle_timeout=None,
                                   replica_of=("127.0.0.1", dead),
                                   replica_feed_retries=1,
                                   replica_feed_backoff=0.02)
    with pytest.warns(UserWarning, match="never-synced standby"):
        replica.start()
        # well past the retry budget: still standby, still unpromoted
        time.sleep(0.5)
        assert replica.is_standby() and not replica.promoted
        replica.stop()


# -- kill-primary-mid-run drills (the acceptance matrix) -----------------------

_TRAINER_MODES = {
    "AsyncDOWNPOUR": "delta",
    "AsyncADAG": "adag",
    "AsyncDynSGD": "dynsgd",
    "AsyncAEASGD": "delta",
    "AsyncEAMSGD": "delta",
}


def _tiny_dataset(n=256, seed=0):
    from distkeras_tpu.data.dataset import Dataset

    rng = np.random.default_rng(seed)
    half = n // 2
    x = np.concatenate([
        rng.normal(loc=-2.0, scale=1.0, size=(half, 8)),
        rng.normal(loc=+2.0, scale=1.0, size=(half, 8))]).astype(np.float32)
    y = np.concatenate([np.zeros(half, np.int64), np.ones(half, np.int64)])
    perm = rng.permutation(n)
    return Dataset({"features": x[perm],
                    "label": np.eye(2, dtype=np.float32)[y[perm]]})


def _mlp_spec():
    from distkeras_tpu.models.base import ModelSpec

    return ModelSpec(name="mlp", config={"hidden_sizes": (16,),
                                         "num_outputs": 2},
                     input_shape=(8,))


def _kill_primary_drill(trainer_name, pipeline=True, after_commits=8):
    """One kill-primary drill: external primary + hot standby, a trainer
    in worker-only mode with the standby as its failover address, the
    primary crashed on its commit clock mid-run."""
    import distkeras_tpu as dk
    from distkeras_tpu.models.base import Model
    from distkeras_tpu.runtime.launcher import start_parameter_server

    model0 = Model.init(_mlp_spec(), seed=0)
    mode = _TRAINER_MODES[trainer_name]
    primary = start_parameter_server(model0, mode=mode, num_workers=2,
                                     idle_timeout=None)
    replica = start_parameter_server(model0, mode=mode, num_workers=2,
                                     idle_timeout=None,
                                     replica_of=("127.0.0.1", primary.port))
    kill_plan = HubKillPlan(after_commits=after_commits)
    try:
        kwargs = dict(loss="categorical_crossentropy", batch_size=16,
                      num_epoch=2, num_workers=2, communication_window=2,
                      learning_rate=0.05, seed=0, pipeline=pipeline,
                      ps_address=("127.0.0.1", primary.port),
                      ps_failover=("127.0.0.1", replica.port),
                      max_reconnects=8, reconnect_backoff=0.02)
        if trainer_name in ("AsyncAEASGD", "AsyncEAMSGD"):
            kwargs["rho"] = 2.0
        trainer = getattr(dk, trainer_name)(Model.init(_mlp_spec(), seed=0),
                                            **kwargs)
        kill_plan.start(primary)
        model = trainer.train(_tiny_dataset())
        kill_plan.join(timeout=30)
        assert kill_plan.fired.is_set(), "primary was never killed"
        assert replica.promoted, "standby never promoted"
        assert trainer.worker_errors == []
        assert len(trainer.history) > 0
        assert np.isfinite(trainer.history).all()
        # zero ACKED loss, judged at PROMOTION time (end-of-run counts are
        # inflated by post-failover commits): at the kill, at most
        # num_workers * max_inflight_commits commits were
        # applied-but-unacked; every acked one must have replicated
        slack = trainer.num_workers * trainer.max_inflight_commits
        assert replica.promoted_at_clock is not None
        assert replica.promoted_at_clock >= kill_plan.fired_at_clock - slack
        # post-failover progress actually landed on the standby
        assert replica.num_updates > replica.promoted_at_clock
        assert model.predict(_tiny_dataset()["features"][:4]).shape == (4, 2)
        return trainer
    finally:
        kill_plan.cancel()
        replica.stop()
        try:
            primary.stop()
        except Exception:
            pass


@pytest.mark.chaos
@pytest.mark.parametrize("pipeline", [True, False])
def test_kill_primary_mid_run_failover_adag(pipeline):
    """Tier-1 drill cell (cheapest trainer config, both exchange modes):
    workers fail over to the standby within the reconnect budget and the
    run completes with zero acked-commit loss."""
    _kill_primary_drill("AsyncADAG", pipeline=pipeline)


@pytest.mark.chaos
@pytest.mark.parametrize("trainer_name",
                         ["AsyncDOWNPOUR", "AsyncDynSGD", "AsyncAEASGD",
                          "AsyncEAMSGD"])
def test_kill_primary_mid_run_failover_matrix(trainer_name):
    """The rest of the trainer matrix (slow-marked, PR-6 convention)."""
    _kill_primary_drill(trainer_name)


@pytest.mark.chaos
def test_kill_primary_sigkill_subprocess(tmp_path):
    """The deployment-shaped drill: a REAL distkeras-ps primary process
    SIGKILLed mid-run, a distkeras-ps --replica-of standby in-process
    promoting, workers failing over.  Slow-marked: subprocess startup
    pays full import twice."""
    import distkeras_tpu as dk
    from distkeras_tpu.models.base import Model
    from distkeras_tpu.runtime.launcher import start_parameter_server

    model0 = Model.init(_mlp_spec(), seed=0)
    model_path = str(tmp_path / "model.bin")
    with open(model_path, "wb") as f:
        f.write(model0.serialize())
    port = _free_port()
    repo_root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    proc = subprocess.Popen(
        [sys.executable, "-m", "distkeras_tpu.runtime.launcher",
         "--model", model_path, "--mode", "adag", "--num-workers", "2",
         "--port", str(port), "--idle-timeout", "0"],
        stdout=subprocess.PIPE, stderr=subprocess.STDOUT, text=True,
        cwd=repo_root,
        env=dict(os.environ, PYTHONPATH=repo_root))
    line = ""
    for _ in range(200):
        line = proc.stdout.readline()
        if not line or "listening" in line:
            break
    assert "listening" in line, f"primary never came up: {line!r}"
    replica = start_parameter_server(model0, mode="adag", num_workers=2,
                                     idle_timeout=None,
                                     replica_of=("127.0.0.1", port))
    result = {}

    def run_trainer():
        trainer = dk.AsyncADAG(
            Model.init(_mlp_spec(), seed=0),
            loss="categorical_crossentropy", batch_size=16, num_epoch=3,
            num_workers=2, communication_window=2, learning_rate=0.05,
            seed=0, ps_address=("127.0.0.1", port),
            ps_failover=("127.0.0.1", replica.port),
            max_reconnects=20, reconnect_backoff=0.05)
        trainer.train(_tiny_dataset())
        result["history"] = trainer.history

    t = threading.Thread(target=run_trainer)
    t.start()
    try:
        assert _wait_until(lambda: replica._clock >= 4, timeout=120.0)
        proc.send_signal(signal.SIGKILL)
        proc.wait(timeout=30)
        t.join(timeout=300)
        assert not t.is_alive(), "trainer did not finish after failover"
        assert len(result.get("history", [])) > 0
        assert replica.promoted
    finally:
        replica.stop()
        if proc.poll() is None:
            proc.kill()
