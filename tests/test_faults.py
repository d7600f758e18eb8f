"""Fault-tolerance tests (issue 4): deterministic chaos harness, PSClient
reconnect/backoff, hub snapshots + clock fence, idle eviction + heartbeat,
elastic membership, worker supervision, and the end-to-end
kill-hub-and-recover acceptance run.

Every injected fault is SCHEDULED (runtime/faults.py), so a failure here
replays bit-identically from its seed/plan."""

import os
import signal
import socket
import subprocess
import sys
import threading
import time

import numpy as np
import pytest

from distkeras_tpu.runtime import networking as net
from distkeras_tpu.runtime.faults import (
    ChaosProxy,
    Fault,
    FaultPlan,
    InjectedWorkerFault,
    WorkerKillPlan,
)
from distkeras_tpu.runtime.parameter_server import (
    ADAGParameterServer,
    DeltaParameterServer,
    DynSGDParameterServer,
    PSClient,
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


def _wait_until(pred, timeout=5.0, interval=0.01):
    deadline = time.monotonic() + timeout
    while time.monotonic() < deadline:
        if pred():
            return True
        time.sleep(interval)
    return False


# -- fault plans ---------------------------------------------------------------

def test_fault_plan_seeded_determinism_and_lookup():
    a = FaultPlan.random(seed=7, conns=4, frames=10, n_faults=3,
                         kinds=("sever", "delay", "truncate"))
    b = FaultPlan.random(seed=7, conns=4, frames=10, n_faults=3,
                         kinds=("sever", "delay", "truncate"))
    assert a.faults == b.faults  # same seed -> identical schedule
    c = FaultPlan.random(seed=8, conns=4, frames=10, n_faults=3,
                         kinds=("sever", "delay", "truncate"))
    assert a.faults != c.faults
    f = a.faults[0]
    assert a.lookup(f.conn, f.direction, f.frame) is f
    assert a.lookup(f.conn, f.direction, f.frame + 10**6) is None
    with pytest.raises(ValueError, match="kind"):
        Fault(conn=0, frame=1, kind="meteor")


def test_worker_kill_plan_fires_once_per_pair():
    plan = WorkerKillPlan([(1, 2)], seed=0)
    plan.hook(0, 2)  # other worker: no-op
    plan.hook(1, 1)
    with pytest.raises(InjectedWorkerFault, match="worker 1 dies at window 2"):
        plan.hook(1, 2)
    plan.hook(1, 2)  # replay after restart: fires at most once
    assert plan.fired == [(1, 2)]


# -- chaos proxy ---------------------------------------------------------------

def test_chaos_proxy_passthrough_is_transparent():
    """An empty plan must forward frames byte-exactly: the full PS exchange
    works through the proxy with an unchanged trajectory."""
    ps = DeltaParameterServer(_weights())
    ps.start()
    try:
        with ChaosProxy("127.0.0.1", ps.port) as proxy:
            with PSClient("127.0.0.1", proxy.port, templates=_weights()) as c:
                assert all(np.all(w == 0) for w in c.pull())
                c.commit(_ones())
                w = c.pull()
                np.testing.assert_allclose(w[0], np.ones((2, 2)))
        assert ps.num_updates == 1
        assert proxy.faults_fired == []
    finally:
        ps.stop()


def test_chaos_sever_client_reconnects_and_recovers():
    """A severed weights reply mid-pipeline: the client reconnects (through
    the proxy, as a fresh conn ordinal the plan leaves alone), re-pulls,
    and every subsequent exchange lands — the hub's center never skips."""
    ps = DeltaParameterServer(_weights())
    ps.start()
    plan = FaultPlan([Fault(conn=0, direction="s2c", frame=2, kind="sever")])
    try:
        with ChaosProxy("127.0.0.1", ps.port, plan) as proxy:
            with PSClient("127.0.0.1", proxy.port, templates=_weights(),
                          max_reconnects=5, reconnect_backoff=0.02) as c:
                for _ in range(4):
                    c.pull()
                    c.commit(_ones())
                w = c.pull()
            assert len(proxy.faults_fired) == 1
        assert c.reconnects_used >= 1
        # commits may be dropped across the fault (never half-applied, never
        # doubled): the center is an exact integer multiple of the delta
        applied = float(w[0][0, 0])
        assert applied == ps.num_updates
        assert 1 <= ps.num_updates <= 4
    finally:
        ps.stop()


def test_chaos_truncate_desyncs_then_recovers():
    """A frame truncated mid-payload (crashed peer shape) must not hang
    either end: the hub drops the connection, the client reconnects and
    finishes its exchanges."""
    ps = DeltaParameterServer(_weights())
    ps.start()
    plan = FaultPlan([Fault(conn=0, direction="c2s", frame=3,
                            kind="truncate", keep_bytes=6)])
    try:
        with ChaosProxy("127.0.0.1", ps.port, plan) as proxy:
            with PSClient("127.0.0.1", proxy.port, templates=_weights(),
                          max_reconnects=5, reconnect_backoff=0.02,
                          timeout=10.0) as c:
                for _ in range(4):
                    c.pull()
                    c.commit(_ones())
            assert len(proxy.faults_fired) == 1
        assert c.reconnects_used >= 1
        assert ps.num_updates >= 1
    finally:
        ps.stop()


@pytest.mark.parametrize("kind,keep_bytes", [("sever", 0),
                                             ("truncate", 40_000)])
def test_fault_mid_land_restores_the_pull_and_reissues_it(kind, keep_bytes):
    """The prefetched reply dies while ``land_weights()`` receives it (before
    its first byte, or 40 kB into a 256 kB frame): the pending entry and the
    landing-buffer flip are restored, the client reconnects INSIDE the call
    and re-issues the pull, and the landed weights — the hub's current
    center — go into the buffer the previous ``wait_weights()`` did not
    hand out."""
    n = 1 << 16
    tmpl = [np.zeros((n,), np.float32)]
    ps = DeltaParameterServer(tmpl)
    ps.start()
    # s2c frames: 0 the seed pull's reply, 1 the commit's ack, 2 the prefetch
    plan = FaultPlan([Fault(conn=0, direction="s2c", frame=2, kind=kind,
                            keep_bytes=keep_bytes)])
    try:
        with ChaosProxy("127.0.0.1", ps.port, plan) as proxy:
            with PSClient("127.0.0.1", proxy.port, templates=tmpl,
                          max_reconnects=5, reconnect_backoff=0.02,
                          timeout=10.0) as c:
                held = c.pull()
                c.commit_nowait([np.ones((n,), np.float32)])
                c.pull_nowait()
                flip = c._flip
                c.land_weights()
                assert len(proxy.faults_fired) == 1
                assert c.reconnects_used == 1
                assert c._flip == flip ^ 1 and len(c._ready) == 1
                assert not c._pending
                np.testing.assert_array_equal(held[0], 0.0)
                landed = c.wait_weights()
                assert landed[0] is not held[0]
                # the ack crossed before the fault: the commit is in the
                # center the re-issued pull observed
                np.testing.assert_array_equal(landed[0], 1.0)
                c.commit([np.ones((n,), np.float32)])
                np.testing.assert_array_equal(c.pull()[0], 2.0)
        assert ps.num_updates == 2
    finally:
        ps.stop()


@pytest.mark.parametrize("leaves", ["numpy", "device"])
def test_cut_mid_streamed_commit_is_retried_and_applied_once(leaves):
    """A dense commit leaves leaf by leaf with no packed frame.  The
    connection dies 5 MB into a 24 MB commit — inside the second of three
    leaves, far past what the kernel buffers — so the client's send fails
    MID-STREAM: it reconnects and sends the whole commit again from the same
    leaves (a landed device array keeps its host value), the hub never
    applies the cut one (its last byte never came), and the center moves by
    the commit exactly once."""
    import jax

    from distkeras_tpu import observability as obs

    n = 1 << 21
    tmpl = [np.zeros((n,), np.float32) for _ in range(3)]
    rng = np.random.default_rng(4)
    delta = [rng.standard_normal(n).astype(np.float32) for _ in tmpl]
    ps = DeltaParameterServer(tmpl)
    ps.start()
    # c2s frames: 0 the pull's request, 1 the commit
    plan = FaultPlan([Fault(conn=0, direction="c2s", frame=1,
                            kind="truncate", keep_bytes=5 << 20)])
    obs.reset()
    obs.enable()
    try:
        with ChaosProxy("127.0.0.1", ps.port, plan) as proxy:
            # the severed proxy socket answers the bytes still coming with a
            # reset, or (unread bytes queued at the sever) stops taking them:
            # then the send's timeout is the fault, as for any wedged peer
            with PSClient("127.0.0.1", proxy.port, templates=tmpl,
                          max_reconnects=5, reconnect_backoff=0.02,
                          timeout=2.0) as c:
                assert all(np.all(w == 0) for w in c.pull())
                payload = delta
                if leaves == "device":
                    payload = [jax.device_put(d) for d in delta]
                    for leaf in payload:
                        leaf.copy_to_host_async()
                c.commit(payload)
                assert len(proxy.faults_fired) == 1
                assert c.reconnects_used == 1
                for w, d in zip(c.pull(), delta):
                    np.testing.assert_array_equal(w, d)
        assert ps.num_updates == 1
        # the cut attempt is not counted: one commit went out whole
        assert obs.snapshot()["counters"]["ps_commits_streamed_total"] == 1
    finally:
        obs.disable()
        obs.reset()
        ps.stop()


# -- reconnect/backoff bounds --------------------------------------------------

def test_reconnect_storm_bounded_by_budget_and_backoff():
    """A hub that never comes back: attempts stop at max_reconnects, total
    backoff stays within the exponential schedule's [0.5x, 1x] jitter
    envelope, and the surfaced error is a clean ConnectionError."""
    ps = DeltaParameterServer(_weights())
    ps.start()
    c = PSClient("127.0.0.1", ps.port, templates=_weights(),
                 max_reconnects=3, reconnect_backoff=0.05,
                 reconnect_backoff_max=0.2)
    c.pull()  # known-good connection
    ps.stop()
    t0 = time.monotonic()
    with pytest.raises(ConnectionError, match="reconnect budget"):
        for _ in range(100):
            c.pull()
    elapsed = time.monotonic() - t0
    assert c.reconnects_used == 3
    # schedule: 0.05, 0.1, 0.2 -> jittered total in [0.175, 0.35] plus
    # small connect-refused overheads; the bound that matters is "no
    # unbounded storm, no premature give-up"
    assert 0.17 <= elapsed < 5.0
    c.sock.close()


def test_default_client_faults_exactly_as_before():
    """max_reconnects=0 (the default) must preserve the pre-resilience
    contract: the first fault surfaces immediately, no retries."""
    ps = DeltaParameterServer(_weights())
    ps.start()
    c = PSClient("127.0.0.1", ps.port, templates=_weights())
    c.pull()
    ps.stop()
    with pytest.raises((ConnectionError, OSError, ValueError)):
        for _ in range(100):
            c.pull()
    assert c.reconnects_used == 0
    c.sock.close()


# -- idle eviction + heartbeat -------------------------------------------------

def test_hub_evicts_half_open_connection():
    """Satellite: a peer that goes silent (half-open) must not park its
    handler forever — the idle timeout evicts it and frees the slot."""
    ps = DeltaParameterServer(_weights(), idle_timeout=0.3)
    ps.start()
    try:
        c = PSClient("127.0.0.1", ps.port, templates=_weights())
        c.pull()
        c.commit(_ones())  # join membership: a real worker going silent
        assert _wait_until(lambda: ps.live_workers() == 1)
        # silence > idle_timeout: handler times out, membership drops
        assert _wait_until(lambda: ps.live_workers() == 0, timeout=5.0), \
            "idle worker was not evicted"
        assert _wait_until(lambda: not any(t.is_alive() for t in ps._handlers))
        c.sock.close()
        # the hub still serves fresh connections after the eviction
        with PSClient("127.0.0.1", ps.port, templates=_weights()) as c2:
            np.testing.assert_allclose(c2.pull()[0], np.ones((2, 2)))
    finally:
        ps.stop()


def test_heartbeat_keeps_idle_worker_alive():
    """A slow-but-alive worker (long window, no traffic) heartbeats through
    the idle window: no eviction, membership retained, next exchange
    proceeds on the SAME connection (no reconnect consumed)."""
    ps = DeltaParameterServer(_weights(), idle_timeout=0.6)
    ps.start()
    try:
        with PSClient("127.0.0.1", ps.port, templates=_weights(),
                      max_reconnects=2, heartbeat_interval=0.15) as c:
            c.pull()
            c.commit(_ones())
            time.sleep(1.5)  # >> idle_timeout: only heartbeats cross
            assert ps.live_workers() == 1
            c.commit(_ones())
            np.testing.assert_allclose(c.pull()[0], np.full((2, 2), 2.0))
            assert c.reconnects_used == 0
    finally:
        ps.stop()


# -- elastic membership --------------------------------------------------------

def test_adag_elastic_live_count_scaling():
    """The acceptance assertion on ADAG's denominator: with elastic=True the
    scale follows LIVE membership — 1/1 while one worker has committed,
    1/2 with two, back to 1/1 after a worker leaves — clamped so it never
    exceeds the configured cohort."""
    ps = ADAGParameterServer(_weights(), num_workers=4, elastic=True,
                             idle_timeout=30.0)
    ps.start()
    try:
        a = PSClient("127.0.0.1", ps.port, templates=_weights())
        b = PSClient("127.0.0.1", ps.port, templates=_weights())
        a.pull()
        b.pull()
        a.commit(_ones())           # members: {a} -> scaled 1/1
        assert _wait_until(lambda: ps.live_workers() == 1)
        np.testing.assert_allclose(ps.get_weights()[0], np.ones((2, 2)))
        b.commit(_ones())           # members: {a, b} -> scaled 1/2
        np.testing.assert_allclose(ps.get_weights()[0], np.full((2, 2), 1.5))
        b.close()                   # b departs: denominator falls back to 1
        assert _wait_until(lambda: ps.live_workers() == 1), \
            "membership did not drop after disconnect"
        a.commit(_ones())
        np.testing.assert_allclose(ps.get_weights()[0], np.full((2, 2), 2.5))
        a.close()
    finally:
        ps.stop()


def test_adag_elastic_inproc_commits_use_static_denominator():
    """commit_direct bypasses connection membership (inproc transport), so
    elastic hubs must fall back to the STATIC denominator there — never
    to 1/1, which would over-apply every inproc delta num_workers-fold."""
    ps = ADAGParameterServer(_weights(), num_workers=4, elastic=True)
    ps.start()
    try:
        assert ps.live_workers() == 0
        ps.commit_direct([np.full((2, 2), 4.0, np.float32),
                          np.full((3,), 4.0, np.float32)], 0)
        np.testing.assert_allclose(ps.get_weights()[0], np.ones((2, 2)))
    finally:
        ps.stop()


def test_adag_static_denominator_unchanged_by_default():
    ps = ADAGParameterServer(_weights(), num_workers=4)
    ps.start()
    try:
        with PSClient("127.0.0.1", ps.port, templates=_weights()) as c:
            c.commit([np.full((2, 2), 4.0, np.float32),
                      np.full((3,), 4.0, np.float32)])
            np.testing.assert_allclose(c.pull()[0], np.ones((2, 2)))
    finally:
        ps.stop()


# -- hub snapshots + clock fence -----------------------------------------------

@pytest.mark.parametrize("hub_kind", ["python", "native"])
def test_hub_kill_and_restore_from_snapshot(tmp_path, hub_kind):
    """Kill a hub (no final snapshot — crash semantics) and restart a
    replacement from the last periodic snapshot on the SAME port: center,
    clock and update count resume; a reconnecting client continues
    committing against the restored center."""
    if hub_kind == "native":
        from distkeras_tpu.runtime.native import native_available
        if not native_available():
            pytest.skip("no C++ toolchain for the native hub")

    snap_dir = str(tmp_path / f"hub-snap-{hub_kind}")
    port = _free_port()

    def make_hub(restore):
        if hub_kind == "native":
            from distkeras_tpu.runtime.native import MODE_DELTA, NativeParameterServer
            return NativeParameterServer(_weights(), mode=MODE_DELTA, port=port,
                                         snapshot_dir=snap_dir,
                                         snapshot_interval=60.0, restore=restore)
        return DeltaParameterServer(_weights(), port=port, snapshot_dir=snap_dir,
                                    snapshot_interval=60.0, restore=restore)

    ps1 = make_hub(restore=False)
    ps1.start()
    with PSClient("127.0.0.1", port, templates=_weights(),
                  max_reconnects=20, reconnect_backoff=0.05) as c:
        c.pull()
        c.commit(_ones())
        c.commit(_ones())
        ps1.snapshotter.save_now()   # the "periodic" snapshot the crash eats up to
        c.commit(_ones())            # post-snapshot commit: lost by the crash
        ps1.kill()
        ps2 = make_hub(restore=True)
        ps2.start()                  # same port, restored center
        try:
            w = c.pull()             # client reconnects via backoff
            np.testing.assert_allclose(w[0], np.full((2, 2), 2.0))
            assert c.reconnects_used >= 1
            assert ps2.num_updates == 2  # update count resumed from snapshot
            c.commit(_ones())        # training continues against the restoree
            np.testing.assert_allclose(c.pull()[0], np.full((2, 2), 3.0))
        finally:
            ps2.stop()


def test_clock_fence_rejects_pre_restart_stale_clocks(tmp_path):
    """DynSGD makes the fence observable: a client presenting a
    pre-restart pull clock (0) to a hub restored at clock 50 must be
    scaled as if it pulled AT the restart (staleness 0 -> full delta), not
    as 50 commits stale (-> delta/51)."""
    ps1 = DynSGDParameterServer(_weights(), snapshot_dir=str(tmp_path / "s"),
                                snapshot_interval=60.0)
    ps1.start()
    for _ in range(50):
        ps1.commit_direct(_ones(), last_pull_clock=ps1._clock)
    ps1.snapshotter.save_now()
    ps1.kill()

    ps2 = DynSGDParameterServer(_weights(), snapshot_dir=str(tmp_path / "s"),
                                snapshot_interval=60.0, restore=True)
    ps2.start()
    try:
        assert ps2._clock == 50 and ps2.num_updates == 50
        before = ps2.get_weights()[0].copy()
        ps2.commit_direct(_ones(), last_pull_clock=0)  # pre-restart clock
        after = ps2.get_weights()[0]
        # fenced to staleness 0: the FULL delta landed (not 1/51 of it)
        np.testing.assert_allclose(after - before, np.ones((2, 2)), rtol=1e-6)
    finally:
        ps2.stop()


def test_hub_snapshot_skips_corrupt_latest(tmp_path):
    """A torn latest snapshot (disk truncation) is skipped with a warning;
    the hub restores from the previous good one."""
    snap_dir = str(tmp_path / "snaps")
    ps1 = DeltaParameterServer(_weights(), snapshot_dir=snap_dir,
                               snapshot_interval=60.0)
    ps1.start()
    ps1.commit_direct(_ones(), 0)
    ps1.snapshotter.save_now()       # good snapshot: center == 1
    ps1.commit_direct(_ones(), 0)
    ps1.snapshotter.save_now()       # snapshot to corrupt: center == 2
    ps1.kill()
    latest = sorted(os.listdir(snap_dir))[-1]
    npz = [f for f in os.listdir(os.path.join(snap_dir, latest))
           if f.endswith(".npz")][0]
    with open(os.path.join(snap_dir, latest, npz), "wb") as f:
        f.write(b"not a zipfile")

    ps2 = DeltaParameterServer(_weights(), snapshot_dir=snap_dir,
                               snapshot_interval=60.0, restore=True)
    with pytest.warns(UserWarning, match="skipping unreadable PS snapshot"):
        ps2.start()
    try:
        np.testing.assert_allclose(ps2.get_weights()[0], np.ones((2, 2)))
    finally:
        ps2.stop()


def test_restore_racing_save_loop_never_loses_a_step(tmp_path):
    """Guarded-by regression (ISSUE 14): ``restore_latest`` advances
    ``_next_step`` under the save lock, so a restore racing the periodic
    save loop cannot lose-update the step counter — every concurrent
    save_now lands on a distinct step directory."""
    import threading

    snap_dir = str(tmp_path / "snaps")
    ps = DeltaParameterServer(_weights(), snapshot_dir=snap_dir,
                              snapshot_interval=60.0)
    ps.start()
    ps.commit_direct(_ones(), 0)
    ps.snapshotter.save_now()
    stop = threading.Event()
    errors = []

    def saver():
        try:
            while not stop.is_set():
                ps.snapshotter.save_now()
        except Exception as e:  # pragma: no cover - the regression
            errors.append(e)

    t = threading.Thread(target=saver)
    t.start()
    try:
        for _ in range(20):
            assert ps.snapshotter.restore_latest()
    finally:
        stop.set()
        t.join()
        ps.kill()
    assert not errors, errors
    steps = sorted(int(d.split("_")[-1]) for d in os.listdir(snap_dir))
    assert steps and ps.snapshotter._next_step > max(steps)


def test_restore_refuses_when_snapshots_exist_but_none_readable(tmp_path):
    """Progress on disk that cannot be read must stop the hub, not let it
    silently serve fresh weights; an EMPTY dir (first boot under a
    restart-with-restore supervisor) only warns."""
    snap_dir = str(tmp_path / "snaps")
    ps1 = DeltaParameterServer(_weights(), snapshot_dir=snap_dir,
                               snapshot_interval=60.0)
    ps1.start()
    ps1.commit_direct(_ones(), 0)
    ps1.snapshotter.save_now()
    ps1.kill()
    for step in os.listdir(snap_dir):
        npz = [f for f in os.listdir(os.path.join(snap_dir, step))
               if f.endswith(".npz")][0]
        with open(os.path.join(snap_dir, step, npz), "wb") as f:
            f.write(b"torn")
    ps2 = DeltaParameterServer(_weights(), snapshot_dir=snap_dir,
                               snapshot_interval=60.0, restore=True)
    with pytest.warns(UserWarning):
        with pytest.raises(RuntimeError, match="none is readable"):
            ps2.start()
    # restore without any snapshot dir at all is a constructor error
    with pytest.raises(ValueError, match="requires snapshot_dir"):
        DeltaParameterServer(_weights(), restore=True)
    # first boot: empty dir warns and serves initial weights
    ps3 = DeltaParameterServer(_weights(), snapshot_dir=str(tmp_path / "new"),
                               snapshot_interval=60.0, restore=True)
    with pytest.warns(UserWarning, match="no snapshot exists yet"):
        ps3.start()
    ps3.stop()


# -- trainer-level supervision matrix ------------------------------------------

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

    return ModelSpec(name="mlp", config={"hidden_sizes": (16,), "num_outputs": 2},
                     input_shape=(8,))


_ALL_TRAINERS = ["AsyncDOWNPOUR", "AsyncADAG", "AsyncDynSGD", "AsyncAEASGD",
                 "AsyncEAMSGD"]


def _make_trainer(trainer_name, hub, transport, **extra):
    import distkeras_tpu as dk
    from distkeras_tpu.models.base import Model

    cls = getattr(dk, trainer_name)
    kwargs = dict(loss="categorical_crossentropy", batch_size=16, num_epoch=1,
                  num_workers=2, communication_window=2, learning_rate=0.05,
                  seed=0, native_ps=(hub == "native"), transport=transport)
    if trainer_name in ("AsyncAEASGD", "AsyncEAMSGD"):
        kwargs["rho"] = 2.0
    kwargs.update(extra)
    return cls(Model.init(_mlp_spec(), seed=0), **kwargs)


@pytest.mark.parametrize("trainer_name", _ALL_TRAINERS)
@pytest.mark.parametrize("hub", ["python", "native"])
@pytest.mark.parametrize("transport", ["socket", "inproc"])
def test_worker_killed_mid_window_is_restarted(trainer_name, hub, transport):
    """The satellite fault-injection matrix: all five Async* trainers x
    {socket, inproc} x {python, native} hubs — a worker killed mid-window
    by a seeded plan is restarted by the supervisor from the hub's current
    center, the run completes with no recorded error, and the hub applied
    commits from both workers."""
    if hub == "native":
        from distkeras_tpu.runtime.native import native_available
        if not native_available():
            pytest.skip("no C++ toolchain for the native hub")

    plan = WorkerKillPlan([(1, 1)], seed=4)
    trainer = _make_trainer(trainer_name, hub, transport,
                            on_worker_failure="restart", max_worker_restarts=2,
                            fault_hook=plan.hook,
                            max_reconnects=3, reconnect_backoff=0.02)
    trainer.train(_tiny_dataset())
    assert plan.fired == [(1, 1)]
    assert trainer.worker_restarts == 1
    assert trainer.worker_errors == []
    assert trainer.parameter_server.num_updates > 4  # both workers committed
    assert len(trainer.history) > 0


def test_restart_budget_exhaustion_degrades_to_continue():
    """A worker that dies on EVERY attempt exhausts max_worker_restarts;
    the error is recorded, survivors finish, and the run returns a model
    (restart degrades to continue, never to a hang)."""
    def always_kill_worker_1(idx, window):
        if idx == 1:
            raise InjectedWorkerFault("worker 1 always dies")

    trainer = _make_trainer("AsyncADAG", "python", "socket",
                            on_worker_failure="restart", max_worker_restarts=2,
                            fault_hook=always_kill_worker_1)
    model = trainer.train(_tiny_dataset())
    assert trainer.worker_restarts == 2          # budget fully used
    assert len(trainer.worker_errors) == 1       # then recorded, not raised
    assert isinstance(trainer.worker_errors[0], InjectedWorkerFault)
    assert model.predict(_tiny_dataset()["features"][:4]).shape == (4, 2)


def test_elastic_trainer_survives_permanent_worker_death(toy_dataset):
    """Degraded-but-correct: elastic ADAG + a permanently dead worker —
    the survivors' commits stop being diluted by the ghost's 1/num_workers
    share and the run still learns the toy task."""
    from distkeras_tpu.data.transformers import LabelIndexTransformer
    from distkeras_tpu.evaluators import AccuracyEvaluator
    from distkeras_tpu.predictors import ModelPredictor

    plan = WorkerKillPlan([(1, 1)], seed=0)
    trainer = _make_trainer("AsyncADAG", "python", "socket",
                            num_epoch=2, elastic=True,
                            on_worker_failure="continue", fault_hook=plan.hook)
    model = trainer.train(toy_dataset)
    assert len(trainer.worker_errors) == 1
    assert trainer.parameter_server.elastic
    ds = ModelPredictor(model, features_col="features").predict(toy_dataset)
    ds = LabelIndexTransformer().transform(ds)
    acc = AccuracyEvaluator(prediction_col="prediction_index",
                            label_col="label_index").evaluate(ds)
    assert acc > 0.9, f"elastic degraded run underperformed: {acc}"


# -- end-to-end kill-and-recover (the issue-4 acceptance run) ------------------

def test_hub_kill_restart_recovery_end_to_end(toy_dataset, tmp_path):
    """The acceptance criterion, end to end: the hub dies abruptly mid-run
    (crash semantics — no final snapshot), a replacement restores the last
    periodic snapshot on the same port, workers reconnect via backoff and
    finish training; the final trajectory lands within tolerance of the
    fault-free run and the recovered model still solves the task."""
    from distkeras_tpu.data.transformers import LabelIndexTransformer
    from distkeras_tpu.evaluators import AccuracyEvaluator
    from distkeras_tpu.models.base import Model
    from distkeras_tpu.predictors import ModelPredictor
    from distkeras_tpu.runtime.launcher import start_parameter_server

    common = dict(loss="categorical_crossentropy", batch_size=16, num_epoch=3,
                  num_workers=2, communication_window=2, learning_rate=0.05,
                  seed=0)

    # fault-free reference trajectory
    import distkeras_tpu as dk

    ref = dk.AsyncADAG(Model.init(_mlp_spec(), seed=0), **common)
    ref.train(toy_dataset)
    ref_loss = float(np.mean(ref.history[-8:]))

    # chaos run: external hub with periodic snapshots, killed mid-run
    snap_dir = str(tmp_path / "hub-snaps")
    port = _free_port()
    model0 = Model.init(_mlp_spec(), seed=0)
    hub_kwargs = dict(mode="adag", num_workers=2, port=port,
                      snapshot_dir=snap_dir, snapshot_interval=0.1,
                      idle_timeout=30.0)
    ps1 = start_parameter_server(model0, **hub_kwargs)
    state = {"ps2": None, "killed_at": None}

    def killer():
        # wait until training is genuinely mid-run AND a periodic snapshot
        # exists, then crash the hub and restart it from the snapshot
        _wait_until(lambda: ps1.num_updates >= 8
                    and ps1.snapshotter.checkpointer.latest_step() is not None,
                    timeout=120.0)
        state["killed_at"] = ps1.num_updates
        ps1.kill()
        ps2 = start_parameter_server(model0, restore=True, **hub_kwargs)
        state["ps2"] = ps2

    kthread = threading.Thread(target=killer)
    kthread.start()
    trainer = dk.AsyncADAG(Model.init(_mlp_spec(), seed=0),
                           ps_address=("127.0.0.1", port),
                           max_reconnects=40, reconnect_backoff=0.05,
                           **common)
    try:
        model = trainer.train(toy_dataset)
    finally:
        kthread.join(timeout=120)
    ps2 = state["ps2"]
    assert ps2 is not None, "hub was never killed/restarted (run too fast?)"
    try:
        assert state["killed_at"] >= 8
        assert ps2.num_updates > 0  # post-restart commits landed
        # recovery quality: the final trajectory is within tolerance of the
        # fault-free one, and the model still solves the task
        final_loss = float(np.mean(trainer.history[-8:]))
        assert abs(final_loss - ref_loss) < 0.5, \
            f"post-recovery loss {final_loss} vs fault-free {ref_loss}"
        ds = ModelPredictor(model, features_col="features").predict(toy_dataset)
        ds = LabelIndexTransformer().transform(ds)
        acc = AccuracyEvaluator(prediction_col="prediction_index",
                                label_col="label_index").evaluate(ds)
        assert acc > 0.85, f"recovered model accuracy {acc}"
    finally:
        ps2.stop()


def test_hub_sigkill_subprocess_soak(toy_dataset, tmp_path):
    """Soak: a REAL `distkeras-ps` process SIGKILLed mid-run and relaunched
    with --restore — the full deployment shape (process death, not an
    in-process stand-in).  Slow-marked: subprocess startup pays full
    import+jax init twice."""
    from distkeras_tpu.models.base import Model

    import distkeras_tpu as dk

    model0 = Model.init(_mlp_spec(), seed=0)
    model_path = str(tmp_path / "model.bin")
    with open(model_path, "wb") as f:
        f.write(model0.serialize())
    snap_dir = str(tmp_path / "snaps")
    port = _free_port()

    def launch(restore):
        args = [sys.executable, "-m", "distkeras_tpu.runtime.launcher",
                "--model", model_path, "--mode", "adag", "--num-workers", "2",
                "--port", str(port), "--snapshot-dir", snap_dir,
                "--snapshot-interval", "0.2"]
        if restore:
            args.append("--restore")
        repo_root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
        proc = subprocess.Popen(
            args, stdout=subprocess.PIPE, stderr=subprocess.STDOUT, text=True,
            cwd=repo_root,
            env=dict(os.environ, PYTHONPATH=repo_root))
        for _ in range(200):  # warnings may precede the banner
            line = proc.stdout.readline()
            if not line or "listening" in line:
                break
        assert "listening" in line, f"hub never came up: {line!r}"
        return proc

    proc1 = launch(restore=False)
    result = {}

    def run_trainer():
        trainer = dk.AsyncADAG(
            Model.init(_mlp_spec(), seed=0), loss="categorical_crossentropy",
            batch_size=16, num_epoch=3, num_workers=2, communication_window=2,
            learning_rate=0.05, seed=0, ps_address=("127.0.0.1", port),
            max_reconnects=60, reconnect_backoff=0.1)
        trainer.train(toy_dataset)
        result["history"] = trainer.history

    t = threading.Thread(target=run_trainer)
    t.start()
    # let training make progress past at least one snapshot, then SIGKILL
    assert _wait_until(
        lambda: os.path.isdir(snap_dir) and
        any(n.startswith("step_") for n in os.listdir(snap_dir)),
        timeout=120.0)
    time.sleep(0.5)
    proc1.send_signal(signal.SIGKILL)
    proc1.wait(timeout=30)
    proc2 = launch(restore=True)
    try:
        t.join(timeout=300)
        assert not t.is_alive(), "trainer did not finish after hub restart"
        assert len(result.get("history", [])) > 0
    finally:
        proc2.terminate()
        proc2.wait(timeout=30)


# -- frame-header sanity (satellite) -------------------------------------------

def test_garbage_length_prefix_is_typed_and_bounded():
    """A garbage 8-byte prefix declaring an absurd frame must raise
    ProtocolError BEFORE allocating, and a hub receiving one must drop the
    connection and keep serving."""
    import struct

    a, b = socket.socketpair()
    try:
        a.sendall(struct.pack(">Q", 1 << 40))
        buf = bytearray(64)
        with pytest.raises(net.ProtocolError, match="exceeds limit"):
            net.recv_frame_into(b, buf, limit=1024)
        assert len(buf) == 64  # nothing was grown toward the declared size
    finally:
        a.close()
        b.close()

    assert issubclass(net.ProtocolError, ValueError)  # except ValueError holds

    ps = DeltaParameterServer(_weights())
    ps.start()
    try:
        raw = socket.create_connection(("127.0.0.1", ps.port))
        raw.sendall(struct.pack(">Q", 1 << 40) + b"junk")
        # hub rejects and closes promptly (no hang): EOF, or RST when our
        # unread junk was still in the hub's receive buffer at close
        raw.settimeout(5.0)
        try:
            assert raw.recv(1) == b""
        except ConnectionResetError:
            pass
        raw.close()
        # and the hub still serves a well-behaved client afterwards
        with PSClient("127.0.0.1", ps.port, templates=_weights()) as c:
            c.commit(_ones())
            np.testing.assert_allclose(c.pull()[0], np.ones((2, 2)))
    finally:
        ps.stop()
