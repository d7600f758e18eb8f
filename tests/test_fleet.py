"""ISSUE-19 tests: the self-scaling fleet — hub-side FleetController,
graceful preemption drain, and multi-job admission control.

Covers the controller's decision rules (spawn cooldown/cap, drift-strike
retirement above the ``min_fleet`` floor, advisory mode, the preemption
respawn authorization), the :class:`SpotPreemptionPlan` drill itself,
job-namespace isolation in both directions, admission control (slot and
byte budgets, re-attach, rejected-session refusal, sparse refusal), the
hub-flavor ``commit_scale`` applied inside a job namespace, the two-job
concurrent isolation drill with the ``fleet_report`` fairness block, the
un-upgraded-client wire-compat matrix (byte-identical across plain /
sharded / replicated hubs that are actively serving other jobs), the
2-of-6 planned-preemption recovery drill (zero acked-commit loss, no
restart budget burned), the ``autoscale=False`` off-path guarantees, and
the ``distkeras-ps`` SIGTERM drain (clean daemon exit + the standby's
replication stream surviving a SIGTERM'd primary untorn).
"""

import os
import signal
import subprocess
import sys
import threading
import time

import numpy as np
import pytest

from distkeras_tpu import observability as obs
from distkeras_tpu.observability import distributed as dtrace
from distkeras_tpu.observability import health as health_mod
from distkeras_tpu.observability.distributed import fleet_report
from distkeras_tpu.observability.health import HealthCollector, HealthMonitor
from distkeras_tpu.runtime import networking as net
from distkeras_tpu.runtime.faults import SpotPreemptionPlan, WorkerPreempted
from distkeras_tpu.runtime.fleet_controller import FleetController
from distkeras_tpu.runtime.parameter_server import (
    ADAGParameterServer,
    DeltaParameterServer,
    DynSGDParameterServer,
    JobAdmissionError,
    PSClient,
    ShardedParameterServer,
    ShardedPSClient,
    shard_plan,
)

_TESTS_DIR = os.path.dirname(os.path.abspath(__file__))
_REPO_ROOT = os.path.dirname(_TESTS_DIR)


@pytest.fixture
def fresh_health():
    """Clean process-default collector/monitor (hubs and autoscale
    trainers bind and subscribe to these at start())."""
    health_mod.reset_default()
    yield health_mod
    health_mod.reset_default()


def _weights():
    return [np.zeros((4, 4), np.float32), np.zeros((6,), np.float32)]


def _monitor(cooldown_s=0.0):
    return HealthMonitor(HealthCollector(), cooldown_s=cooldown_s)


def _wait_until(pred, timeout=10.0, interval=0.01):
    deadline = time.monotonic() + timeout
    while time.monotonic() < deadline:
        if pred():
            return True
        time.sleep(interval)
    return bool(pred())


# -- the controller's decision rules -------------------------------------------

def test_controller_spawns_on_regression_with_cooldown_and_cap(monkeypatch):
    import types

    from distkeras_tpu.runtime import fleet_controller

    # the controller's clock, one second after its origin: time.monotonic()
    # counts from boot on Linux, and the first spawn must not wait out a
    # cooldown since then (on a machine up for under an hour it did)
    clock = [1.0]
    monkeypatch.setattr(
        fleet_controller, "time",
        types.SimpleNamespace(monotonic=lambda: clock[0], time=time.time))
    mon = _monitor()
    spawned = []
    fc = FleetController(mon, spawn_fn=spawned.append,
                         cooldown_s=3600.0, max_spawns=8)
    try:
        mon.emit("throughput_regression", dedup="a", ratio=0.5)
        clock[0] += 3599.0
        mon.emit("throughput_regression", dedup="b", ratio=0.4)
        # the second firing lands inside the spawn cooldown: one spawn
        assert spawned == [None]
        assert fc.stats()["spawns"] == 1
        fc.cooldown_s = 0.0
        for i in range(20):
            mon.emit("throughput_regression", dedup=f"c{i}", ratio=0.3)
        # lifetime cap: a regression spawning cannot fix must not fork-bomb
        assert len(spawned) == 8
        assert fc.stats()["spawns"] == 8
        acts = [d["action"] for d in fc.decisions()]
        assert acts == ["spawn"] * 8
        assert all(d["reason"] == "throughput_regression"
                   for d in fc.decisions())
    finally:
        fc.stop()


def test_controller_retires_after_strikes_never_below_min_fleet():
    mon = _monitor()
    retired = []
    fc = FleetController(mon, retire_fn=retired.append,
                         drift_strikes=2, min_fleet=1)
    try:
        for w in ("0", "1"):
            fc.notify_worker_started(w)
        mon.emit("staleness_drift", worker="0", dedup="s1", z=4.0)
        assert retired == []  # one firing can be a scheduling hiccup
        mon.emit("staleness_drift", worker="0", dedup="s2", z=4.2)
        assert retired == ["0"]
        assert fc.stats()["retires"] == 1
        # worker 1 is the last one above the floor: strikes accrue but
        # the retire is refused
        mon.emit("staleness_drift", worker="1", dedup="s3", z=5.0)
        mon.emit("staleness_drift", worker="1", dedup="s4", z=5.1)
        mon.emit("staleness_drift", worker="1", dedup="s5", z=5.2)
        assert retired == ["0"]
        assert fc.stats()["retires"] == 1
    finally:
        fc.stop()


def test_controller_advisory_mode_records_without_acting():
    """No spawn_fn/retire_fn (the launcher shape): decisions are recorded
    and counted, nothing is called, nothing raises."""
    mon = _monitor()
    fc = FleetController(mon, cooldown_s=0.0, drift_strikes=1)
    try:
        fc.notify_worker_started("0")
        fc.notify_worker_started("1")
        mon.emit("throughput_regression", dedup="r", ratio=0.6)
        mon.emit("staleness_drift", worker="1", dedup="d", z=9.0)
        acts = [(d["action"], d["worker"]) for d in fc.decisions()]
        assert ("spawn", None) in acts
        assert ("retire", "1") in acts
        st = fc.stats()
        assert st["spawns"] == 1 and st["retires"] == 1
        assert st["retiring"] == 1
    finally:
        fc.stop()


def test_controller_preemption_authorizes_respawn_until_stopped():
    mon = _monitor()
    fc = FleetController(mon)
    fc.notify_worker_started("3")
    assert fc.notify_preempted("3", deadline_s=5.0) is True
    fc.notify_drained("3", clean=True)
    assert fc.fleet_size() == 0
    acts = [d["action"] for d in fc.decisions()]
    assert acts == ["respawn", "drained"]
    assert fc.decisions()[0]["evidence"] == {"deadline_s": 5.0}
    assert fc.stats()["preemptions"] == 1
    fc.stop()
    # stopped controller authorizes nothing and the subscription is gone
    assert fc.notify_preempted("4") is False
    mon.emit("throughput_regression", dedup="late", ratio=0.1)
    assert fc.stats()["spawns"] == 0


def test_controller_broken_spawn_fn_never_breaks_the_health_plane():
    mon = _monitor()

    def boom(_):
        raise RuntimeError("spawn backend down")

    fc = FleetController(mon, spawn_fn=boom, cooldown_s=0.0)
    try:
        # the emit path must survive the subscriber's callback failing
        ev = mon.emit("throughput_regression", dedup="x", ratio=0.5)
        assert ev is not None
        assert fc.stats()["spawns"] == 1  # decision recorded regardless
    finally:
        fc.stop()


def test_spot_preemption_plan_fires_once_per_pair():
    plan = SpotPreemptionPlan([(1, 2)], deadline_s=3.0)
    plan.hook(0, 2)  # unplanned worker: no notice
    with pytest.raises(WorkerPreempted) as ei:
        plan.hook(1, 2)
    assert (ei.value.worker, ei.value.window) == (1, 2)
    assert ei.value.deadline_s == 3.0
    plan.hook(1, 2)  # the respawned replacement replays the window freely
    assert plan.fired == [(1, 2)]


# -- multi-job admission + namespace isolation ---------------------------------

def test_job_namespace_isolated_both_directions():
    t = _weights()
    ps = DeltaParameterServer(t, port=0, idle_timeout=None)
    ps.start()
    try:
        with PSClient("127.0.0.1", ps.port, templates=t) as c0, \
                PSClient("127.0.0.1", ps.port, templates=t,
                         job="expA") as cj:
            c0.pull()
            cj.pull()
            cj.commit([np.ones_like(x) for x in t])
            # the job's commit never lands on the default center
            got0 = c0.pull()
            assert all(float(np.abs(g).sum()) == 0.0 for g in got0)
            c0.commit([np.full_like(x, 2.0) for x in t])
            # ...and the default commit never lands on the job's center
            gotj = cj.pull()
            for g in gotj:
                np.testing.assert_array_equal(g, np.ones_like(g))
        info = ps.fleet_info()
        assert info["jobs"] == {"expA": {"clock": 1, "num_updates": 1}}
        assert info["jobs_admitted"] == 1 and info["jobs_rejected"] == 0
        assert info["num_updates"] == 1  # the default-namespace commit
    finally:
        ps.stop()


def test_job_center_seeds_from_current_center_and_reattaches():
    t = _weights()
    ps = DeltaParameterServer(t, port=0, idle_timeout=None)
    ps.start()
    try:
        with PSClient("127.0.0.1", ps.port, templates=t) as c0:
            c0.pull()
            c0.commit([np.full_like(x, 3.0) for x in t])
        # a job admitted NOW snapshots the current default center
        with PSClient("127.0.0.1", ps.port, templates=t, job="expB") as cj:
            for g in cj.pull():
                np.testing.assert_array_equal(g, np.full_like(g, 3.0))
            cj.commit([np.ones_like(x) for x in t])
        # re-announcing the same job re-attaches to the existing namespace
        with PSClient("127.0.0.1", ps.port, templates=t, job="expB") as cj2:
            for g in cj2.pull():
                np.testing.assert_array_equal(g, np.full_like(g, 4.0))
        assert ps.fleet_info()["jobs_admitted"] == 1  # one namespace, not two
    finally:
        ps.stop()


def test_admission_default_budget_admits_four_then_slots_exhausted():
    """Defaults: job_budget_bytes = 4x center and max_jobs = 4 admit
    exactly four namespaces; the fifth announce is refused with the slot
    reason and the client surfaces it as JobAdmissionError."""
    t = _weights()
    ps = DeltaParameterServer(t, port=0, idle_timeout=None)
    ps.start()
    try:
        for i in range(4):
            with PSClient("127.0.0.1", ps.port, templates=t,
                          job=f"job{i}") as c:
                c.pull()
        with pytest.raises(JobAdmissionError, match=r"job slots exhausted "
                                                    r"\(4/4\)"):
            PSClient("127.0.0.1", ps.port, templates=t, job="job4",
                     max_reconnects=0)
        info = ps.fleet_info()
        assert sorted(info["jobs"]) == ["job0", "job1", "job2", "job3"]
        assert info["jobs_admitted"] == 4 and info["jobs_rejected"] == 1
    finally:
        ps.stop()


def test_admission_tight_byte_budget_rejects_with_projection():
    t = _weights()
    ps = DeltaParameterServer(t, port=0, idle_timeout=None,
                              job_budget_bytes=1)
    ps.start()
    try:
        with pytest.raises(JobAdmissionError,
                           match="shard memory budget exceeded"):
            PSClient("127.0.0.1", ps.port, templates=t, job="heavy",
                     max_reconnects=0)
        assert ps.fleet_info()["jobs_rejected"] == 1
    finally:
        ps.stop()


def test_admission_disabled_hub_rejects_every_job():
    t = _weights()
    ps = DeltaParameterServer(t, port=0, idle_timeout=None, max_jobs=0)
    ps.start()
    try:
        with pytest.raises(JobAdmissionError,
                           match="multi-job serving is disabled"):
            PSClient("127.0.0.1", ps.port, templates=t, job="any",
                     max_reconnects=0)
    finally:
        ps.stop()


def test_job_session_refuses_sparse_actions():
    """Row-sparse exchange is default-namespace only: a job session that
    sends a sparse pull is severed with a protocol error, never silently
    served from the wrong center."""
    t = [np.zeros((8, 4), np.float32), np.zeros((3,), np.float32)]
    ps = DeltaParameterServer(t, port=0, idle_timeout=None,
                              sparse_leaves=(0,))
    ps.start()
    try:
        c = PSClient("127.0.0.1", ps.port, templates=t, job="sparsejob",
                     sparse_leaves=(0,), max_reconnects=0)
        try:
            with pytest.raises((net.ProtocolError, ConnectionError, OSError)):
                c.pull_nowait(sparse_rows=[np.array([0, 1], np.int64)])
                c.wait_weights()
        finally:
            c.close()
    finally:
        ps.stop()


def test_job_commits_scale_by_hub_flavor_staleness():
    """DynSGD's 1/(s+1) staleness rule applies inside a job namespace
    exactly as on the default center."""
    t = _weights()
    ps = DynSGDParameterServer(t, port=0, idle_timeout=None)
    ps.start()
    try:
        with PSClient("127.0.0.1", ps.port, templates=t, job="dj") as c1, \
                PSClient("127.0.0.1", ps.port, templates=t, job="dj") as c2:
            c1.pull()
            c2.pull()
            c1.commit([np.ones_like(x) for x in t])  # staleness 0: full
            c1.drain()
            c2.commit([np.ones_like(x) for x in t])  # staleness 1: half
            c2.drain()
            with PSClient("127.0.0.1", ps.port, templates=t,
                          job="dj") as c3:
                for g in c3.pull():
                    np.testing.assert_allclose(g, np.full_like(g, 1.5))
    finally:
        ps.stop()


def test_two_job_isolation_drill_and_fairness_report(fresh_health):
    """Two jobs hammer one hub concurrently (plus a default-namespace
    bystander): every namespace lands exactly its own commits, and the
    fleet_report gains the per-job fairness block — which a single-job
    run must NOT grow (report-shape compatibility)."""
    t = _weights()
    obs.reset()
    obs.enable()
    ps = ADAGParameterServer(t, num_workers=4, port=0, idle_timeout=None,
                             elastic=True)
    ps.start()
    commits_per_worker = 6
    errors = []

    def run(job, worker_id, delta_val):
        try:
            ctx = dtrace.TraceContext(job_id=job, worker_id=worker_id,
                                      span_id=dtrace.new_span_id())
            with PSClient("127.0.0.1", ps.port, templates=t, job=job,
                          trace_context=ctx) as c:
                for _ in range(commits_per_worker):
                    c.pull()
                    c.commit([np.full_like(x, delta_val) for x in t])
                c.drain()
        except Exception as e:  # pragma: no cover - surfaced by assert
            errors.append(e)

    try:
        threads = [threading.Thread(target=run, args=("jobA", i, 1.0))
                   for i in range(2)]
        threads += [threading.Thread(target=run, args=("jobB", 2 + i, 2.0))
                    for i in range(2)]
        for th in threads:
            th.start()
        for th in threads:
            th.join(timeout=60)
        assert not errors, errors
        info = ps.fleet_info()
        assert info["jobs"]["jobA"]["num_updates"] == 2 * commits_per_worker
        assert info["jobs"]["jobB"]["num_updates"] == 2 * commits_per_worker
        assert info["num_updates"] == 0  # the default center never moved
        assert all(float(np.abs(c).sum()) == 0.0 for c in ps.center)

        # a handler records its ps.handle_commit span on leaving it, AFTER
        # the ack its client's drain() returned on: wait for the last one
        assert _wait_until(lambda: sum(
            e.get("name") == "ps.handle_commit"
            for e in obs.TRACER.events()) >= 4 * commits_per_worker)
        report = fleet_report(events=obs.TRACER.events())
        jobs = report["jobs"]
        assert sorted(jobs["per_job"]) == ["jobA", "jobB"]
        for j in ("jobA", "jobB"):
            assert jobs["per_job"][j]["commits"] == 2 * commits_per_worker
            assert jobs["per_job"][j]["share"] == 0.5
        assert jobs["max_share"] == jobs["min_share"] == 0.5
        assert set(jobs["ranked"]) == {"jobA", "jobB"}

        # single-job span set: the report keeps its exact prior shape
        single = [e for e in obs.TRACER.events()
                  if e.get("attrs", {}).get("job") == "jobA"]
        assert "jobs" not in fleet_report(events=single)
    finally:
        ps.stop()
        obs.disable()
        obs.reset()


def test_fleet_info_is_json_safe_and_complete():
    import json

    t = _weights()
    ps = DeltaParameterServer(t, port=0, idle_timeout=None)
    ps.start()
    try:
        with PSClient("127.0.0.1", ps.port, templates=t, job="j") as c:
            c.pull()
            c.commit([np.ones_like(x) for x in t])  # membership joins here
            c.drain()
            info = ps.fleet_info()
            assert set(info) == {"live_workers", "jobs", "clock",
                                 "num_updates", "jobs_admitted",
                                 "jobs_rejected"}
            assert info["live_workers"] == 1
            json.dumps(info)  # the launcher/distkeras-top contract
    finally:
        ps.stop()


# -- wire-compat matrix: un-upgraded client vs multi-job hub -------------------

class _RecordingSock:
    def __init__(self, sock):
        self._sock = sock
        self.tx = bytearray()

    def sendall(self, data):
        self.tx += bytes(data)
        return self._sock.sendall(data)

    def __getattr__(self, name):
        return getattr(self._sock, name)


def _assert_no_job_frames(stream: bytes) -> None:
    """A job-unaware client sends no trace/admission announces at all —
    walk the frames and refuse any T (the announce jobs ride on)."""
    i = 0
    while i < len(stream):
        n = int.from_bytes(stream[i:i + 8], "big")
        assert stream[i + 8:i + 9] != net.ACTION_TRACE
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


def test_plain_client_bytes_identical_against_multijob_hub(fresh_health):
    t = _weights()
    plain = DeltaParameterServer(t, port=0, idle_timeout=None)
    busy = DeltaParameterServer(t, port=0, idle_timeout=None)
    plain.start()
    busy.start()
    try:
        # make the second hub genuinely multi-tenant before the probe
        with PSClient("127.0.0.1", busy.port, templates=t,
                      job="tenant") as cj:
            cj.pull()
            cj.commit([np.ones_like(x) for x in t])
            cj.drain()
            baseline = _session_bytes(plain.port, t)
            against_busy = _session_bytes(busy.port, t)
    finally:
        plain.stop()
        busy.stop()
    assert baseline == against_busy
    _assert_no_job_frames(baseline)


def test_plain_striped_client_bytes_identical_on_multijob_shards(
        fresh_health):
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

    quiet, busy = make(), make()
    try:
        # per-shard tenants: each shard hub of the busy facade is
        # actively serving a job namespace while the probe runs
        tenants = [PSClient("127.0.0.1", port,
                            templates=[t[i] for i in plan.assignments[sid]],
                            job="tenant")
                   for sid, port in enumerate(busy.ports)]
        for tc in tenants:
            tc.pull()
        base_streams = session(quiet)
        busy_streams = session(busy)
        for tc in tenants:
            tc.close()
    finally:
        quiet.stop()
        busy.stop()
    assert base_streams == busy_streams
    for s in base_streams:
        _assert_no_job_frames(s)


def test_plain_client_bytes_identical_against_replicated_multijob_primary(
        fresh_health):
    t = _weights()

    def make():
        primary = DeltaParameterServer(t, port=0, idle_timeout=None)
        primary.start()
        replica = DeltaParameterServer(
            t, idle_timeout=None, replica_of=("127.0.0.1", primary.port))
        replica.start()
        assert replica.wait_synced(timeout=10)
        return primary, replica

    p1, r1 = make()
    p2, r2 = make()
    try:
        with PSClient("127.0.0.1", p2.port, templates=t, job="tenant") as cj:
            cj.pull()
            cj.commit([np.ones_like(x) for x in t])
            cj.drain()
            baseline = _session_bytes(p1.port, t)
            against_busy = _session_bytes(p2.port, t)
        # the default-namespace commit replicated; the job commit did NOT
        # move the replicated (default) center
        assert _wait_until(lambda: r2._clock >= 1)
        np.testing.assert_array_equal(r2.center[0], p2.center[0])
        np.testing.assert_allclose(r2.center[0], np.full_like(t[0], 0.5))
    finally:
        for hub in (r1, p1, r2, p2):
            hub.stop()
    assert baseline == against_busy
    _assert_no_job_frames(baseline)


# -- trainer integration: autoscale, preemption drain, respawn -----------------

def _mlp_spec():
    from distkeras_tpu.models.base import ModelSpec

    return ModelSpec(name="mlp", config={"hidden_sizes": (16,),
                                         "num_outputs": 2},
                     input_shape=(8,))


def test_autoscale_requires_trainer_owned_hub():
    import distkeras_tpu as dk
    from distkeras_tpu.models.base import Model

    with pytest.raises(ValueError, match="autoscale"):
        dk.AsyncADAG(Model.init(_mlp_spec(), seed=0), autoscale=True,
                     ps_address=("127.0.0.1", 1))


def test_autoscale_off_constructs_no_controller_and_matches(
        toy_dataset, fresh_health):
    """autoscale=False (the default) builds no FleetController, and
    autoscale=True with zero fleet events trains the bit-identical
    uncontended trajectory — the knob is observationally free until
    something fires (the test_adaptive single-worker parity shape)."""
    import jax

    import distkeras_tpu as dk
    from distkeras_tpu.models.base import Model

    def run(autoscale):
        health_mod.reset_default()
        trainer = dk.AsyncADAG(Model.init(_mlp_spec(), seed=0),
                               loss="categorical_crossentropy",
                               batch_size=16, num_epoch=1, num_workers=1,
                               communication_window=4, learning_rate=0.05,
                               seed=0, autoscale=autoscale)
        model = trainer.train(toy_dataset)
        return trainer, trainer.history, jax.tree.leaves(model.params)

    off, hist_off, params_off = run(False)
    assert off.fleet_controller is None
    assert off.worker_preemptions == []
    on, hist_on, params_on = run(True)
    assert on.fleet_controller is not None
    assert on.fleet_controller.stats()["preemptions"] == 0
    assert hist_off == hist_on
    for a, b in zip(params_off, params_on):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    health_mod.reset_default()


def test_preemption_recovery_drill_two_of_six(toy_dataset, fresh_health):
    """The ISSUE-19 acceptance drill, tier-1 sized: preempt 2 of 6
    workers mid-run; both drain cleanly (every in-flight commit acked,
    zero outstanding), both are respawned WITHOUT burning restart
    budget, and the run finishes with no worker errors."""
    import distkeras_tpu as dk
    from distkeras_tpu.models.base import Model

    plan = SpotPreemptionPlan([(4, 1), (5, 1)], deadline_s=5.0)
    trainer = dk.AsyncADAG(
        Model.init(_mlp_spec(), seed=0), loss="categorical_crossentropy",
        batch_size=16, num_epoch=2, num_workers=6, communication_window=2,
        learning_rate=0.05, seed=0, elastic=True, autoscale=True,
        on_worker_failure="restart", max_worker_restarts=1,
        fault_hook=plan.hook)
    trainer.train(toy_dataset)

    assert sorted(plan.fired) == [(4, 1), (5, 1)]
    assert len(trainer.worker_preemptions) == 2
    for p in trainer.worker_preemptions:
        assert p["drained_clean"] is True
        assert p["outstanding_after_drain"] == 0
    st = trainer.fleet_controller.stats()
    assert st["preemptions"] == 2
    # planned capacity loss is not a crash: the full restart budget is
    # intact and nothing errored
    assert trainer.worker_restarts == 0
    assert trainer.worker_errors == []
    acts = [d["action"] for d in trainer.fleet_controller.decisions()]
    assert acts.count("respawn") == 2
    assert acts.count("drained") == 2


# -- distkeras-ps SIGTERM drain ------------------------------------------------

def test_sigterm_primary_never_tears_standby_stream(fresh_health):
    """A SIGTERM'd primary (the launcher path calls ps.stop()) must end
    the replication feed cleanly: the standby holds every replicated
    commit, promotes on the feed loss, and serves the untorn center."""
    t = _weights()
    primary = DeltaParameterServer(t, port=0, idle_timeout=None)
    primary.start()
    replica = DeltaParameterServer(
        t, port=0, idle_timeout=None, replica_feed_retries=0,
        replica_of=("127.0.0.1", primary.port))
    replica.start()
    try:
        assert replica.wait_synced(timeout=10)
        with PSClient("127.0.0.1", primary.port, templates=t) as c:
            for _ in range(3):
                c.pull()
                c.commit([np.ones_like(x) for x in t])
            c.drain()
        assert _wait_until(lambda: replica._clock >= 3)
        primary.stop()  # the SIGTERM handler's drain
        assert _wait_until(lambda: replica.promoted, timeout=15), \
            "standby never promoted after the primary's clean shutdown"
        # the stream was not torn: the standby holds exactly the acked
        # commits and still serves them
        with PSClient("127.0.0.1", replica.port, templates=t) as c2:
            for g in c2.pull():
                np.testing.assert_allclose(g, np.full_like(g, 3.0))
    finally:
        replica.stop()
        primary.stop()


def test_launcher_sigterm_drains_daemon_cleanly(tmp_path):
    """A real `distkeras-ps` process handles SIGTERM as a graceful drain:
    prints the drain banner, writes --save-final, exits 0."""
    from distkeras_tpu.models.base import Model

    model0 = Model.init(_mlp_spec(), seed=0)
    model_path = str(tmp_path / "model.bin")
    with open(model_path, "wb") as f:
        f.write(model0.serialize())
    final_path = str(tmp_path / "final.bin")
    proc = subprocess.Popen(
        [sys.executable, "-m", "distkeras_tpu.runtime.launcher",
         "--model", model_path, "--port", "0", "--autoscale",
         "--save-final", final_path],
        stdout=subprocess.PIPE, stderr=subprocess.STDOUT, text=True,
        cwd=_REPO_ROOT,
        env=dict(os.environ, PYTHONPATH=_REPO_ROOT))
    try:
        line = ""
        for _ in range(200):
            line = proc.stdout.readline()
            if not line or "listening" in line:
                break
        assert "listening" in line, f"hub never came up: {line!r}"
        proc.send_signal(signal.SIGTERM)
        out = proc.stdout.read()
        rc = proc.wait(timeout=60)
    finally:
        if proc.poll() is None:
            proc.kill()
    assert rc == 0, out
    assert "SIGTERM: draining hub" in out
    assert os.path.exists(final_path), out
    # the drained final model round-trips
    with open(final_path, "rb") as f:
        Model.deserialize(f.read())
