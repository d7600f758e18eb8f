"""bench.py contract: exactly one parseable JSON line on stdout, always —
and a NON-ZERO exit unless every leg ran on the TPU without an error.

The line keeps the headline metric keys on failure (zeroed, with an
``error`` note) so a failed run is still a record; what changed with the
chip bring-up is that a failure can no longer end in exit 0: no CPU
fallback in ``_init_backend``, no ``None`` peak for an unknown
``device_kind``, and a leg's ``{"error": ...}`` fails the run.
"""

import json

import pytest

import bench

# every secondary leg main() runs after the headline; stubbed so main()'s
# own contract can be driven on the CPU
_LEGS = ("_bench_lm", "_bench_attn", "_bench_ring", "_bench_decode",
         "_bench_feed", "_bench_moe", "_bench_pipeline", "_bench_async",
         "_bench_async_recovery", "_bench_observability", "_bench_health",
         "_bench_embedding")


def _parse_single_json_line(capsys):
    out = capsys.readouterr().out.strip().splitlines()
    assert len(out) == 1, f"expected exactly one stdout line, got {out}"
    return json.loads(out[0])


def _stub_chip(monkeypatch, tmp_path):
    """Make main() believe it is on the chip with instant legs."""
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
    monkeypatch.setattr(bench, "_init_backend", lambda: "tpu")
    monkeypatch.setattr(bench, "_bench_mnist_cnn",
                        lambda **kw: (123.4, bench._METHODOLOGY))
    for name in _LEGS:
        monkeypatch.setattr(bench, name, lambda *a, **kw: {})


def test_main_emits_metric_line_and_exits_zero(capsys, monkeypatch, tmp_path):
    _stub_chip(monkeypatch, tmp_path)
    bench.main()  # no SystemExit: exit code 0
    rec = _parse_single_json_line(capsys)
    assert rec["metric"] == "mnist_cnn_train_samples_per_sec_per_chip"
    assert rec["value"] == 123.4
    assert rec["unit"] == "samples/sec/chip"
    assert isinstance(rec["vs_baseline"], float)
    assert rec["platform"] == "tpu"
    assert rec["compile_cache"] == str(tmp_path)  # the env's, untouched
    assert "error" not in rec


def test_main_refuses_the_cpu_platform(capsys, monkeypatch, tmp_path):
    """No accelerator -> the line names the platform found and the exit
    code is non-zero; nothing is measured on the CPU (conftest pins it)."""
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
    monkeypatch.setattr(bench, "_bench_mnist_cnn",
                        lambda **kw: pytest.fail("measured on the CPU"))
    with pytest.raises(SystemExit) as exc:
        bench.main()
    assert exc.value.code != 0
    rec = _parse_single_json_line(capsys)
    assert rec["value"] == 0.0 and "platform" not in rec
    assert "no TPU" in rec["error"] and "'cpu'" in rec["error"]


def test_main_fatal_failure_is_nonzero(capsys, monkeypatch, tmp_path):
    _stub_chip(monkeypatch, tmp_path)

    def boom(**kw):
        raise RuntimeError("synthetic backend meltdown")

    monkeypatch.setattr(bench, "_bench_mnist_cnn", boom)
    with pytest.raises(SystemExit) as exc:
        bench.main()
    assert exc.value.code != 0
    rec = _parse_single_json_line(capsys)
    assert rec["value"] == 0.0 and rec["vs_baseline"] == 0.0
    assert "synthetic backend meltdown" in rec["error"]
    assert rec["metric"] == "mnist_cnn_train_samples_per_sec_per_chip"


def test_main_leg_failure_is_recorded_and_nonzero(capsys, monkeypatch, tmp_path):
    """One leg's exception stays that leg's {"error": ...} (the other legs
    still run) but the run can no longer exit 0."""
    _stub_chip(monkeypatch, tmp_path)

    def boom(*a, **kw):
        raise ValueError("leg fell over")

    monkeypatch.setattr(bench, "_bench_moe", boom)
    with pytest.raises(SystemExit) as exc:
        bench.main()
    assert exc.value.code != 0
    rec = _parse_single_json_line(capsys)
    assert rec["value"] == 123.4 and "error" not in rec
    assert "leg fell over" in rec["moe"]["error"]
    assert rec["pipeline"] == {}  # later legs still ran


@pytest.mark.slow  # tier-1 budget fix (PR 11): heaviest cells ride the full suite
def test_mnist_bench_runs_on_cpu():
    sps, method = bench._bench_mnist_cnn(batch_size=8, num_batches=2, reps=1)
    assert sps > 0
    # the profiler trace has no device module events on CPU: the tag must
    # say WALL so the ratio logic refuses a device-keyed baseline
    assert method == bench._METHODOLOGY_WALL


def test_peak_flops_lookup():
    assert bench._peak_flops("TPU v5 lite") == 197e12
    with pytest.raises(ValueError, match="Quantum Abacus 9000"):
        bench._peak_flops("Quantum Abacus 9000")


@pytest.mark.slow  # tier-1 budget fix (PR 11): heaviest cells ride the full suite
def test_decode_bench_runs_tiny_on_cpu():
    """The decode section (incl. the TRAINED speculative leg) at toy scale:
    every leg present, spread recorded, acceptance_rate a real fraction."""
    out = bench._bench_decode(batch=2, prompt_len=8, new_tokens=16,
                              model_dim=32, num_heads=2, num_layers=2,
                              vocab=64, reps=2, train_steps=8)
    for mode in ("fp", "int8", "fp_b1", "fp_b1_trained", "speculative_b1",
                 "speculative_batched"):
        assert out[mode]["tokens_per_sec"] > 0, mode
        assert "wall_spread" in out[mode], mode
    for sp in (out["speculative_b1"], out["speculative_batched"]):
        assert sp["trained"] is True
        assert 0.0 <= sp["acceptance_rate"] <= 1.0
    assert out["speculative_speedup_vs_fp_batched"] > 0
    # CPU trace may or may not yield module events; the tag must say which
    assert out["timing"] in ("device-median-of-2", "wall-median-of-2")
    assert out["speculative_speedup_vs_fp_b1"] > 0


def test_ring_bench_runs_tiny_on_cpu():
    leg = bench._bench_ring(256, batch=1, heads=2, head_dim=64, steps=1)
    assert leg["l_local"] == 256
    assert leg["flash_ms"] > 0 and leg["dense_ms"] > 0
    assert leg["auto_selects"] == "dense"
    assert leg["timing"] in ("device", "wall")


def test_lm_leg_baseline_keys_include_heads():
    """A heads change must break the baseline match (no bogus ratio)."""
    out = {"lm": [{"seq_len": 2048, "batch": 8, "model_dim": 512,
                   "num_heads": 4, "timing": "device",
                   "tokens_per_sec": 100.0}]}
    baseline = {"legs": {"lm:2048x8:d512h8": {"tokens_per_sec": 50.0}}}
    bench._apply_leg_baselines(out, baseline)
    assert "vs_baseline" not in out["lm"][0]
    out["lm"][0]["num_heads"] = 8
    bench._apply_leg_baselines(out, baseline)
    assert out["lm"][0]["vs_baseline"] == 2.0


def test_ring_baseline_ratio_inverted():
    leg = {"l_local": 2048, "batch": 1, "heads": 8, "head_dim": 64,
           "flash_ms": 2.0, "timing": "device"}
    out = {"ring": [dict(leg)]}
    baseline = {"legs": {"ring:2048:b1h8d64:device": {"flash_ms": 4.0}}}
    bench._apply_leg_baselines(out, baseline)
    assert out["ring"][0]["vs_baseline"] == 2.0  # faster than recorded best

    # a wall-fallback leg must NOT ratio against the device record
    wall = {"ring": [dict(leg, timing="wall")]}
    bench._apply_leg_baselines(wall, baseline)
    assert "vs_baseline" not in wall["ring"][0]

    # a config change (different heads) must break the match
    other = {"ring": [dict(leg, heads=4)]}
    bench._apply_leg_baselines(other, baseline)
    assert "vs_baseline" not in other["ring"][0]


def test_lm_wall_fallback_skips_baseline():
    out = {"lm": [{"seq_len": 2048, "batch": 8, "model_dim": 512,
                   "num_heads": 8, "timing": "wall", "tokens_per_sec": 100.0}]}
    baseline = {"legs": {"lm:2048x8:d512h8": {"tokens_per_sec": 50.0}}}
    bench._apply_leg_baselines(out, baseline)
    assert "vs_baseline" not in out["lm"][0]


@pytest.mark.slow  # ~10-70s of bench machinery; the full suite runs it
def test_feed_bench_sweep_and_decomposition_tiny_on_cpu():
    """The feed leg's round-6 shape: a chunk-size sweep whose best config
    is promoted to the headline comparison, plus a per-chunk IO/wire/step
    decomposition — all at toy scale."""
    out = bench._bench_feed(batch=16, total_batches=8, reps=1,
                            sweep_batches_per_chunk=(2, 4), sweep_reps=1)
    assert len(out["sweep"]) == 2
    assert {"batches_per_chunk", "chunk_mb", "prefetch_ms",
            "samples_per_sec"} <= set(out["sweep"][0])
    assert out["best_chunk_mb"] in {s["chunk_mb"] for s in out["sweep"]}
    # the headline comparison ran AT the promoted best size
    assert out["chunk_mb"] == out["best_chunk_mb"]
    dec = out["decomposition"]
    for k in ("io_ms_per_chunk", "wire_ms_per_chunk",
              "step_wall_ms_per_chunk", "device_ms_per_chunk"):
        assert dec[k] >= 0.0, k
    assert out["compute_only_ms"] > 0 and out["prefetch_ms"] > 0


@pytest.mark.slow  # ~10-70s of bench machinery; the full suite runs it
def test_moe_capacity_sweep_tiny_on_cpu():
    """Trained-router capacity sweep machinery at toy scale: drops are
    recorded untrained AND trained per factor, and training reduces them
    at generous capacity (the aux loss is in the objective)."""
    sweep = bench._bench_moe_capacity_sweep(
        model_dim=16, num_heads=2, vocab=64, experts=4, batch=2, seq_len=16,
        num_layers=1, steps=40, factors=(1.0, 2.0))
    import numpy as np

    assert [s["capacity_factor"] for s in sweep] == [1.0, 2.0]
    for s in sweep:
        assert 0.0 <= s["dropped_fraction_trained"] <= 1.0
        assert 0.0 <= s["dropped_fraction_untrained"] <= 1.0
        assert s["capacity"] >= 1 and np.isfinite(s["final_loss"])


def test_moe_baseline_keys_cover_dispatch_legs():
    """top1 (sorted, default) and top1_dense ratio against SEPARATE
    baseline records; a wall-fallback leg must not ratio at all."""
    moe = {"batch": 4, "seq_len": 512, "experts": 8,
           "top1": {"timing": "device", "tokens_per_sec": 400.0},
           "top1_dense": {"timing": "device", "tokens_per_sec": 250.0},
           "top2": {"timing": "wall", "tokens_per_sec": 300.0}}
    baseline = {"legs": {
        "moe:top1:b4s512e8:device": {"tokens_per_sec": 253.2},
        "moe:top1_dense:b4s512e8:device": {"tokens_per_sec": 250.0}}}
    out = {"moe": moe}
    bench._apply_leg_baselines(out, baseline)
    assert moe["top1"]["vs_baseline"] == round(400.0 / 253.2, 4)
    assert moe["top1_dense"]["vs_baseline"] == 1.0
    assert "vs_baseline" not in moe["top2"]  # wall fallback


def test_async_baseline_keys_cover_new_legs():
    asy = {"workers": 2, "window": 8, "batch": 256,
           "async_adag_native": {"per_window_device_ms": 2.0},
           "async_adag_int8": {"per_window_device_ms": 4.0},
           "async_adag_inproc": {"per_window_device_ms": 3.0}}
    baseline = {"legs": {
        "async:async_adag_native:w2x8b256:device-window":
            {"per_window_device_ms": 4.0},
        "async:async_adag_inproc:w2x8b256:device-window":
            {"per_window_device_ms": 6.0}}}
    out = {"async": asy}
    bench._apply_leg_baselines(out, baseline)
    assert asy["async_adag_native"]["vs_baseline"] == 2.0  # ms inverted
    assert asy["async_adag_inproc"]["vs_baseline"] == 2.0  # ms inverted
    assert "vs_baseline" not in asy["async_adag_int8"]  # no record yet


def test_async_acceptance_block_tripwires():
    """The issue-3 acceptance block: vs-sync ratios + r05 speedup + final-
    loss parity, with None (not a crash) wherever a leg errored out."""
    out = {
        "async_adag": {"samples_per_sec": 9000.0, "per_window_wall_ms": 42.0,
                       "final_loss": 0.51},
        "async_adag_inproc": {"samples_per_sec": 9500.0},
        "async_adag_serial": {"samples_per_sec": 4800.0, "final_loss": 0.52},
        "sync_adag": {"samples_per_sec": 10000.0},
    }
    bench._async_acceptance(out)
    acc = out["acceptance"]
    assert out["adag_vs_sync"] == 0.9 and acc["adag_vs_sync_ok"] is True
    assert out["adag_inproc_vs_sync"] == 0.95 and acc["inproc_vs_sync_ok"] is True
    assert acc["per_window_speedup_vs_r05"] == round(421.15 / 42.0, 2)
    assert acc["per_window_speedup_ok"] is True
    assert acc["final_loss_parity"]["abs_diff"] == 0.01

    # a dead sync denominator degrades to None tripwires, not a KeyError
    out2 = {"async_adag": {"samples_per_sec": 9000.0,
                           "per_window_wall_ms": 500.0, "final_loss": 0.5},
            "sync_adag": {"error": "AttributeError: no shard_map"}}
    bench._async_acceptance(out2)
    acc2 = out2["acceptance"]
    assert "adag_vs_sync" not in out2
    assert acc2["adag_vs_sync_ok"] is None and acc2["inproc_vs_sync_ok"] is None
    assert acc2["per_window_speedup_ok"] is False  # 500ms > 421.15/5
    assert acc2["final_loss_parity"] is None


def test_async_transport_acceptance_tripwires():
    """The ISSUE-18 zero-copy tripwires: shm-ring per-window wall must
    beat the inproc direct pair, and the recv_batch hub must have served
    more than one frame per blocking fill — None-degrading like every
    other acceptance boolean."""
    out = {
        "async_adag_inproc": {"per_window_wall_ms": 40.0},
        "shm_ring": {"per_window_wall_ms": 38.0},
        "recv_batch": {"per_window_wall_ms": 41.0, "decomposition": {
            "recv_batch_depth": {"count": 6, "mean": 2.5, "max": 4}}},
    }
    bench._async_acceptance(out)
    acc = out["acceptance"]
    assert acc["shm_vs_inproc_per_window"] == 0.95
    assert acc["shm_beats_inproc_direct_ok"] is True
    assert acc["batch_syscalls_ok"] is True

    # a slower ring trips the wire; a depth that never batched trips too
    out2 = {
        "async_adag_inproc": {"per_window_wall_ms": 40.0},
        "shm_ring": {"per_window_wall_ms": 44.0},
        "recv_batch": {"per_window_wall_ms": 41.0, "decomposition": {
            "recv_batch_depth": {"count": 6, "mean": 1.0, "max": 1}}},
    }
    bench._async_acceptance(out2)
    assert out2["acceptance"]["shm_beats_inproc_direct_ok"] is False
    assert out2["acceptance"]["batch_syscalls_ok"] is False

    # dead/missing legs degrade to None, not a KeyError
    out3 = {"shm_ring": {"error": "OSError: /dev/shm full"},
            "recv_batch": {"per_window_wall_ms": 41.0}}
    bench._async_acceptance(out3)
    assert out3["acceptance"]["shm_vs_inproc_per_window"] is None
    assert out3["acceptance"]["shm_beats_inproc_direct_ok"] is None
    assert out3["acceptance"]["batch_syscalls_ok"] is None


@pytest.mark.slow  # trains real (tiny) models; the full suite runs it
def test_bench_async_transport_legs_tiny_e2e():
    """The evidence sources the shm_ring/recv_batch bench legs consume,
    end to end at toy scale: an shm run moves frames over the rings
    (ps.shm_frames_total), and a batched hub records its frames-per-fill
    histogram (ps_recv_batch_depth) — the batch tripwire's input."""
    import numpy as np

    from distkeras_tpu import observability as obs
    from distkeras_tpu.data.dataset import Dataset
    from distkeras_tpu.models.base import Model, ModelSpec
    from distkeras_tpu.runtime.async_trainer import AsyncADAG

    spec = ModelSpec(name="mlp",
                     config={"hidden_sizes": (8,), "num_outputs": 2},
                     input_shape=(4,))
    rng = np.random.default_rng(0)
    x = rng.normal(size=(64, 4)).astype(np.float32)
    y = rng.integers(0, 2, size=64)
    ds = Dataset({"features": x, "label": np.eye(2, dtype=np.float32)[y]})
    kwargs = dict(loss="categorical_crossentropy", batch_size=16,
                  num_epoch=1, num_workers=2, communication_window=2,
                  learning_rate=0.05, seed=0)
    obs.reset()
    obs.enable()
    try:
        AsyncADAG(Model.init(spec, seed=0), transport="shm",
                  **kwargs).train(ds)
        snap = obs.snapshot()
        assert snap["counters"].get("ps.shm_frames_total", 0) > 0
        obs.reset()
        AsyncADAG(Model.init(spec, seed=0), recv_batch_depth=8,
                  **kwargs).train(ds)
        hist = obs.snapshot()["histograms"].get("ps_recv_batch_depth")
        assert hist is not None and hist["count"] >= 1
    finally:
        obs.disable()
        obs.reset()


def test_async_shard_acceptance_block_tripwires():
    """The ISSUE-6 shard-scaling tripwire: >= 3x aggregate commit
    throughput at 4 shards vs 1, None-degrading (the PR-3 convention)
    when either leg is missing or errored."""
    out = {"1": {"commits_per_sec": 100.0}, "4": {"commits_per_sec": 320.0}}
    bench._async_shard_acceptance(out)
    acc = out["acceptance"]
    assert acc["shard_scaling_target"] == 3.0
    assert acc["scaling_x_4_vs_1"] == 3.2
    assert acc["shard_scaling_ok"] is True

    out2 = {"1": {"commits_per_sec": 100.0}, "4": {"commits_per_sec": 250.0}}
    bench._async_shard_acceptance(out2)
    assert out2["acceptance"]["shard_scaling_ok"] is False

    # a dead leg degrades to None tripwires, not a KeyError/ZeroDivision
    out3 = {"1": {"error": "ConnectionError: hub process died"},
            "4": {"commits_per_sec": 250.0}}
    bench._async_shard_acceptance(out3)
    assert out3["acceptance"]["scaling_x_4_vs_1"] is None
    assert out3["acceptance"]["shard_scaling_ok"] is None

    out4 = {"1": {"commits_per_sec": 0.0}, "4": {"commits_per_sec": 250.0}}
    bench._async_shard_acceptance(out4)
    assert out4["acceptance"]["shard_scaling_ok"] is None  # zero denominator

    out5 = {}  # both legs missing entirely
    bench._async_shard_acceptance(out5)
    assert out5["acceptance"]["shard_scaling_ok"] is None


@pytest.mark.slow  # spawns ~6 processes; the full suite runs it
def test_async_shard_bench_runs_tiny():
    """The shard-scaling leg end to end at toy scale: both legs produce
    throughput figures, the per-shard decomposition covers every shard,
    and every shard applied every logical commit."""
    out = bench._bench_async_shards(shard_counts=(1, 2), workers=2,
                                    leaves=4, leaf_elems=256,
                                    commits_per_worker=8)
    for key, shards in (("1", 1), ("2", 2)):
        leg = out[key]
        assert leg["commits_per_sec"] > 0
        assert set(leg["per_shard"]) == {str(s) for s in range(shards)}
        for sb in leg["per_shard"].values():
            assert sb["commits"] == leg["logical_commits"]
            assert sb["wire_mb"] > 0
    # acceptance needs the 1 and 4 legs; a (1, 2) run degrades to None
    assert out["acceptance"]["shard_scaling_ok"] is None


def test_async_recovery_acceptance_block_tripwires():
    """The issue-4 recovery acceptance block: recovered/parity booleans,
    with None (not a crash) wherever a denominator leg errored out."""
    out = {
        "fault_free": {"wall_s": 10.0, "final_loss": 2.0},
        "sever": {"wall_s": 14.0, "final_loss": 2.1, "faults_fired": 2,
                  "reconnects": 2.0, "recovery_ms": {"count": 2}},
        "worker_restart": {"wall_s": 13.0, "final_loss": 2.05,
                           "kills_fired": 1, "restarts": 1,
                           "worker_errors": 0},
    }
    bench._async_recovery_acceptance(out)
    acc = out["acceptance"]
    assert acc["sever_recovered_ok"] is True
    assert acc["sever_loss_abs_diff"] == 0.1
    assert acc["sever_loss_tol"] == 0.3  # max(0.05, 0.15 * 2.0)
    assert acc["sever_loss_parity_ok"] is True
    assert acc["worker_restart_ok"] is True
    assert acc["restart_loss_parity_ok"] is True

    # a dead fault-free denominator degrades parity to None, and a dead
    # chaos leg degrades its own tripwires — nothing raises
    out2 = {
        "fault_free": {"error": "RuntimeError: device fell over"},
        "sever": {"error": "ConnectionError: proxy died"},
        "worker_restart": {"wall_s": 13.0, "final_loss": 2.05,
                           "kills_fired": 1, "restarts": 1,
                           "worker_errors": 0},
    }
    bench._async_recovery_acceptance(out2)
    acc2 = out2["acceptance"]
    assert acc2["sever_recovered_ok"] is None
    assert acc2["sever_loss_parity_ok"] is None
    assert acc2["worker_restart_ok"] is True
    assert acc2["restart_loss_parity_ok"] is None
    # legs absent entirely (issue-7 failover + barrier): None, not a crash
    assert acc2["failover_recovered_ok"] is None
    assert acc2["failover_ms_recorded"] is None
    assert acc2["failover_loss_parity_ok"] is None
    assert acc2["snapshot_barrier_ok"] is None


def test_failover_acceptance_block_tripwires():
    """The issue-7 failover/barrier tripwires: recovered means the kill
    fired, workers failed over, the standby promoted and its clock AT
    PROMOTION respects the zero-ACKED-loss bound (kill clock minus the
    in-flight slack — end-of-run counts are inflated by post-failover
    commits and prove nothing); the barrier tripwire pins <5%
    commit-throughput overhead.  All None-degrading."""
    out = {
        "fault_free": {"wall_s": 10.0, "final_loss": 2.0},
        "sever": {"error": "skipped"},
        "worker_restart": {"error": "skipped"},
        "failover": {"wall_s": 15.0, "final_loss": 2.08,
                     "killed_at_clock": 16, "promoted_at_clock": 14,
                     "replica_commits": 40,
                     "acked_loss_slack": 4, "promoted": True,
                     "failovers": 2.0,
                     "failover_ms": {"count": 2, "mean": 180.0, "max": 300.0}},
        "snapshot_barrier": {"overhead_pct": 2.4},
    }
    bench._async_recovery_acceptance(out)
    acc = out["acceptance"]
    assert acc["failover_recovered_ok"] is True
    assert acc["failover_ms_recorded"] is True
    assert acc["failover_loss_abs_diff"] == 0.08
    assert acc["failover_loss_parity_ok"] is True
    assert acc["snapshot_barrier_overhead_pct"] == 2.4
    assert acc["snapshot_barrier_ok"] is True

    # acked-commit loss beyond the in-flight slack flips recovered to
    # False — judged at PROMOTION time, so a post-failover-inflated
    # replica_commits (40 here) cannot mask it
    out["failover"]["promoted_at_clock"] = 11
    bench._async_recovery_acceptance(out)
    assert out["acceptance"]["failover_recovered_ok"] is False
    # a heavy barrier flips its tripwire
    out["snapshot_barrier"] = {"overhead_pct": 9.0}
    bench._async_recovery_acceptance(out)
    assert out["acceptance"]["snapshot_barrier_ok"] is False
    # an errored barrier leg degrades, never crashes
    out["snapshot_barrier"] = {"error": "OSError: disk full"}
    bench._async_recovery_acceptance(out)
    assert out["acceptance"]["snapshot_barrier_ok"] is None


def test_adaptive_acceptance_block_tripwires():
    """The issue-10 adaptive tripwires: adaptive beats plain final loss
    at comparable wall (ratio <= 1.25), and the control loop visibly
    reacted (merged or rate-scaled >= 1 commit) — None-degrading when
    either leg errored or the whole sub-leg is missing."""
    out = {
        "fault_free": {"wall_s": 10.0, "final_loss": 2.0},
        "sever": {"error": "skipped"},
        "worker_restart": {"error": "skipped"},
        "adaptive": {
            "plain": {"wall_s": 10.0, "final_loss": 2.30,
                      "merged_commits": 0.0, "rate_scaled_commits": 0.0},
            "adaptive": {"wall_s": 11.0, "final_loss": 2.10,
                         "merged_commits": 5.0,
                         "rate_scaled_commits": 3.0},
        },
    }
    bench._async_recovery_acceptance(out)
    acc = out["acceptance"]
    assert acc["adaptive_plain_final_loss"] == 2.30
    assert acc["adaptive_final_loss"] == 2.10
    assert acc["adaptive_wall_ratio"] == 1.1
    assert acc["adaptive_beats_plain_ok"] is True
    assert acc["adaptive_reacted_ok"] is True

    # adaptive landing WORSE than plain flips the tripwire
    out["adaptive"]["adaptive"]["final_loss"] = 2.50
    bench._async_recovery_acceptance(out)
    assert out["acceptance"]["adaptive_beats_plain_ok"] is False
    # equal-work walls drifting apart invalidates the comparison too
    out["adaptive"]["adaptive"]["final_loss"] = 2.10
    out["adaptive"]["adaptive"]["wall_s"] = 20.0
    bench._async_recovery_acceptance(out)
    assert out["acceptance"]["adaptive_beats_plain_ok"] is False
    # a control loop that never reacted is its own failure
    out["adaptive"]["adaptive"]["wall_s"] = 11.0
    out["adaptive"]["adaptive"]["merged_commits"] = 0.0
    out["adaptive"]["adaptive"]["rate_scaled_commits"] = 0.0
    bench._async_recovery_acceptance(out)
    assert out["acceptance"]["adaptive_reacted_ok"] is False

    # an errored plain leg degrades the comparison (not the reaction
    # check); a missing sub-leg degrades everything — never a crash
    out["adaptive"]["adaptive"]["merged_commits"] = 5.0
    out["adaptive"]["plain"] = {"error": "ConnectionError: proxy died"}
    bench._async_recovery_acceptance(out)
    assert out["acceptance"]["adaptive_beats_plain_ok"] is None
    assert out["acceptance"]["adaptive_reacted_ok"] is True
    del out["adaptive"]
    bench._async_recovery_acceptance(out)
    assert out["acceptance"]["adaptive_beats_plain_ok"] is None
    assert out["acceptance"]["adaptive_reacted_ok"] is None
    assert out["acceptance"]["adaptive_wall_ratio"] is None


def test_spot_preemption_acceptance_block_tripwires():
    """The ISSUE-19 tripwires: preemption_recovered_ok pins every planned
    notice fired + respawned with zero operator input and >= 90% of the
    pre-preemption windows/s restored; drain_zero_loss_ok separately pins
    that every drain completed clean with nothing outstanding.  Both
    None-degrade when the leg errored or never measured a rate."""
    sp = {
        "workers": 6, "preempt": 2, "preemptions_fired": 2,
        "drains": [{"worker": 4, "drained_clean": True,
                    "outstanding_after_drain": 0},
                   {"worker": 5, "drained_clean": True,
                    "outstanding_after_drain": 0}],
        "drains_clean": True, "outstanding_after_drain": 0,
        "respawns": 2, "pre_rate_windows_s": 100.0,
        "post_rate_windows_s": 95.0, "restarts": 0, "worker_errors": 0,
    }
    out = {
        "fault_free": {"wall_s": 10.0, "final_loss": 2.0},
        "sever": {"error": "skipped"},
        "worker_restart": {"error": "skipped"},
        "spot_preemption": dict(sp),
    }
    bench._async_recovery_acceptance(out)
    acc = out["acceptance"]
    assert acc["preemption_pre_rate_windows_s"] == 100.0
    assert acc["preemption_post_rate_windows_s"] == 95.0
    assert acc["preemption_recovered_ok"] is True
    assert acc["drain_zero_loss_ok"] is True

    # < 90% throughput restored flips recovered (the acceptance floor)
    out["spot_preemption"] = dict(sp, post_rate_windows_s=80.0)
    bench._async_recovery_acceptance(out)
    assert out["acceptance"]["preemption_recovered_ok"] is False
    assert out["acceptance"]["drain_zero_loss_ok"] is True
    # a missing respawn (operator input needed) flips recovered
    out["spot_preemption"] = dict(sp, respawns=1)
    bench._async_recovery_acceptance(out)
    assert out["acceptance"]["preemption_recovered_ok"] is False
    # an unclean drain or leftover in-flight commit flips zero-loss
    out["spot_preemption"] = dict(sp, drains_clean=False)
    bench._async_recovery_acceptance(out)
    assert out["acceptance"]["drain_zero_loss_ok"] is False
    out["spot_preemption"] = dict(sp, outstanding_after_drain=1)
    bench._async_recovery_acceptance(out)
    assert out["acceptance"]["drain_zero_loss_ok"] is False
    # a drain that never fired its notice count flips zero-loss too
    out["spot_preemption"] = dict(sp, drains=sp["drains"][:1])
    bench._async_recovery_acceptance(out)
    assert out["acceptance"]["drain_zero_loss_ok"] is False

    # no rate measured -> recovered degrades to None; an errored or
    # absent leg degrades everything — never a crash
    out["spot_preemption"] = dict(sp, pre_rate_windows_s=None)
    bench._async_recovery_acceptance(out)
    assert out["acceptance"]["preemption_recovered_ok"] is None
    out["spot_preemption"] = {"error": "RuntimeError: hub fell over"}
    bench._async_recovery_acceptance(out)
    assert out["acceptance"]["preemption_recovered_ok"] is None
    assert out["acceptance"]["drain_zero_loss_ok"] is None
    assert out["acceptance"]["preemption_pre_rate_windows_s"] is None
    del out["spot_preemption"]
    bench._async_recovery_acceptance(out)
    assert out["acceptance"]["preemption_recovered_ok"] is None
    assert out["acceptance"]["drain_zero_loss_ok"] is None


@pytest.mark.slow
def test_bench_async_spot_preemption_tiny_e2e():
    """The spot-preemption bench leg end to end at a CI-scale shape:
    notices fire, drains complete clean, respawns are budget-neutral."""
    out = bench._bench_async_spot_preemption(workers=4, preempt=1,
                                             window=2, batch=16,
                                             windows_per_epoch=4, epochs=2)
    assert "error" not in out, out
    assert out["preemptions_fired"] == 1
    assert out["drains_clean"] is True
    assert out["outstanding_after_drain"] == 0
    assert out["respawns"] >= 1
    assert out["restarts"] == 0
    assert out["worker_errors"] == 0


@pytest.mark.slow
def test_bench_async_adaptive_tiny_e2e():
    """The adaptive bench leg end to end at a CI-scale shape: both legs
    run, record losses/walls, and the adaptive leg's counters exist."""
    out = bench._bench_async_adaptive(workers=2, window=2, batch=16,
                                      windows_per_epoch=2, epochs=1,
                                      jitter_s=(0.001, 0.002))
    for name in ("plain", "adaptive"):
        leg = out[name]
        assert "error" not in leg, leg
        assert leg["final_loss"] is not None
        assert leg["wall_s"] > 0
    assert out["adaptive"]["merged_commits"] >= 0.0


def test_observability_acceptance_block_tripwires():
    """The issue-5 tripwire block: tracing overhead under the 3% target,
    >=95% commit-context coverage, straggler ranking present — with None
    (not a crash) wherever a leg is missing."""
    out = {
        "overhead_pct": 1.4,
        "fleet": {"commit_context_coverage": 0.99, "total_commits": 96,
                  "top_straggler": "1", "workers_seen": 2},
    }
    bench._observability_acceptance(out)
    acc = out["acceptance"]
    assert acc["overhead_ok"] is True and acc["overhead_pct_target"] == 3.0
    assert acc["coverage_ok"] is True and acc["coverage_target"] == 0.95
    assert acc["straggler_ranked"] is True

    out2 = {"overhead_pct": 5.2,
            "fleet": {"commit_context_coverage": 0.5, "top_straggler": None}}
    bench._observability_acceptance(out2)
    acc2 = out2["acceptance"]
    assert acc2["overhead_ok"] is False
    assert acc2["coverage_ok"] is False
    assert acc2["straggler_ranked"] is False

    out3 = {}  # the whole leg errored before measuring anything
    bench._observability_acceptance(out3)
    acc3 = out3["acceptance"]
    assert acc3["overhead_ok"] is None
    assert acc3["coverage_ok"] is None
    assert acc3["straggler_ranked"] is None


def test_health_acceptance_block_tripwires():
    """The issue-8 tripwire block: the fully-on health plane (tracking +
    streaming collector + detectors) under the 3% wall-overhead target,
    fleet coverage (every worker reported), reports actually ingested —
    with None (not a crash) wherever a leg is missing."""
    out = {
        "workers": 2,
        "overhead_pct": 1.1,
        "collector": {"workers_seen": 2, "reports_ingested": 6,
                      "tracked_series": 4, "events": 0},
    }
    bench._health_acceptance(out)
    acc = out["acceptance"]
    assert acc["overhead_ok"] is True and acc["overhead_pct_target"] == 3.0
    assert acc["fleet_covered"] is True
    assert acc["reports_ok"] is True

    out2 = {"workers": 4, "overhead_pct": 4.9,
            "collector": {"workers_seen": 2, "reports_ingested": 0}}
    bench._health_acceptance(out2)
    acc2 = out2["acceptance"]
    assert acc2["overhead_ok"] is False
    assert acc2["fleet_covered"] is False
    assert acc2["reports_ok"] is False

    out3 = {}  # the whole leg errored before measuring anything
    bench._health_acceptance(out3)
    acc3 = out3["acceptance"]
    assert acc3["overhead_ok"] is None
    assert acc3["fleet_covered"] is None
    assert acc3["reports_ok"] is None


def test_embedding_acceptance_block_tripwires():
    """The issue-9 tripwire block: sparse exchange bytes under
    1.1 x touched-row fraction of the dense leg, rows/s recorded — with
    None (not a crash) wherever a leg is missing (PR-3 convention)."""
    out = {
        "dense": {"wall_s": 1.0, "wire_bytes": 110_000_000,
                  "exchange_bytes": 100_000_000},
        "sparse": {"wall_s": 0.5, "wire_bytes": 2_000_000,
                   "exchange_bytes": 1_000_000, "rows_per_s": 5000.0,
                   "touched_row_fraction": 0.01},
    }
    bench._embedding_acceptance(out)
    acc = out["acceptance"]
    assert acc["wire_ratio"] == 0.01
    assert acc["wire_ratio_bound"] == 0.011
    assert acc["sparse_wire_ok"] is True
    assert acc["rows_per_s_recorded"] is True

    out2 = {
        "dense": {"exchange_bytes": 100_000_000},
        "sparse": {"exchange_bytes": 2_000_000, "rows_per_s": 5000.0,
                   "touched_row_fraction": 0.01},
    }
    bench._embedding_acceptance(out2)
    assert out2["acceptance"]["sparse_wire_ok"] is False  # 0.02 > 0.011

    out3 = {"dense": {"error": "boom"}}  # sparse leg never ran
    bench._embedding_acceptance(out3)
    acc3 = out3["acceptance"]
    assert acc3["sparse_wire_ok"] is None
    assert acc3["wire_ratio"] is None
    assert acc3["rows_per_s_recorded"] is None

    out4 = {}  # the whole leg errored before measuring anything
    bench._embedding_acceptance(out4)
    assert out4["acceptance"]["sparse_wire_ok"] is None


def test_embedding_hot_tier_acceptance_tripwires():
    """The issue-15 tripwire block: replication bytes under 1.1 x the
    touched-row fraction of the dense-R equivalent, client cache memory
    scaling with the hot fraction, warm hit rate recorded — with None
    (not a crash) wherever the hot leg is missing (PR-3 convention)."""
    out = {
        "dense": {"exchange_bytes": 100_000_000},
        "sparse": {"exchange_bytes": 1_000_000, "rows_per_s": 5000.0,
                   "touched_row_fraction": 0.01},
        "hot": {"repl_sparse_bytes": 1_000_000,
                "repl_dense_equiv_bytes": 100_000_000,
                "touched_row_fraction": 0.01,
                "cache_memory_ratio": 0.02, "hot_fraction": 0.01,
                "cache_hit_rate": 0.8},
    }
    bench._embedding_acceptance(out)
    acc = out["acceptance"]
    assert acc["repl_ratio"] == 0.01
    assert acc["repl_ratio_bound"] == 0.011
    assert acc["repl_sparse_ok"] is True
    assert acc["cache_memory_ok"] is True  # 0.02 <= 4 x 0.01
    assert acc["cache_hit_ok"] is True

    out2 = dict(out)
    out2["hot"] = {"repl_sparse_bytes": 2_000_000,
                   "repl_dense_equiv_bytes": 100_000_000,
                   "touched_row_fraction": 0.01,
                   "cache_memory_ratio": 0.2, "hot_fraction": 0.01,
                   "cache_hit_rate": 0.1}
    bench._embedding_acceptance(out2)
    acc2 = out2["acceptance"]
    assert acc2["repl_sparse_ok"] is False  # 0.02 > 0.011
    assert acc2["cache_memory_ok"] is False  # 0.2 > 0.04
    assert acc2["cache_hit_ok"] is False

    out3 = {"dense": {"exchange_bytes": 1},
            "sparse": {"exchange_bytes": 1},
            "hot": {"error": "boom"}}  # hot leg degraded, PR-9 legs live
    bench._embedding_acceptance(out3)
    acc3 = out3["acceptance"]
    assert acc3["repl_sparse_ok"] is None
    assert acc3["cache_memory_ok"] is None
    assert acc3["cache_hit_ok"] is None

    out4 = {}
    bench._embedding_acceptance(out4)
    assert out4["acceptance"]["repl_sparse_ok"] is None


@pytest.mark.slow  # ~60-200s of real bench machinery on CPU
def test_embedding_bench_runs_tiny():
    """End-to-end smoke of the issue-9 leg at toy scale: both legs run,
    the tripwire block attaches, the sparse leg actually moved fewer
    exchange bytes than the dense leg and counted its rows.  (The toy
    shape's dense head is NOT negligible next to the toy table, so the
    1.1x bound itself is asserted only at the real bench shape.)"""
    out = bench._bench_embedding(rows=2048, dim=32, fields=2, batch=8,
                                 window=2, windows_per_epoch=2, epochs=1,
                                 workers=1, reps=1)
    assert "acceptance" in out
    assert out["dense"]["exchange_bytes"] > 0
    assert out["sparse"]["exchange_bytes"] > 0
    assert out["sparse"]["exchange_bytes"] < out["dense"]["exchange_bytes"]
    assert out["sparse"]["rows_committed"] > 0
    assert out["acceptance"]["rows_per_s_recorded"] is True
    assert out["acceptance"]["wire_ratio"] is not None
    # issue-15 hot leg: the standby saw row-delta frames, the bounded
    # cache was smaller than the table, hits landed (the 1.1x bounds are
    # asserted at the real shape only — the toy head is not negligible)
    hot = out["hot"]
    assert hot["repl_sparse_bytes"] > 0
    assert hot["repl_sparse_bytes"] < hot["repl_dense_equiv_bytes"]
    assert hot["cache_bytes"] < hot["full_cache_bytes"]
    assert hot["cache_hits"] > 0
    assert out["acceptance"]["cache_memory_ok"] is True
    assert out["acceptance"]["repl_ratio"] is not None


@pytest.mark.slow  # ~60-200s of real bench machinery on CPU
def test_health_bench_runs_tiny():
    """End-to-end smoke of the issue-8 leg at toy scale: both sub-legs
    run, the tripwire block attaches, and the on-leg's collector actually
    saw every worker's reports."""
    out = bench._bench_health(workers=2, window=2, batch=8,
                              windows_per_epoch=2, epochs=1, reps=1,
                              health_interval_s=0.05)
    assert "acceptance" in out
    assert out["health_off"]["wall_s"] > 0
    assert out["health_on"]["wall_s"] > 0
    assert out["collector"]["workers_seen"] == 2
    assert out["collector"]["reports_ingested"] >= 2
    assert out["collector"]["tracked_series"] >= 1
    assert out["acceptance"]["fleet_covered"] is True
    assert out["acceptance"]["reports_ok"] is True


@pytest.mark.slow  # ~10-70s of bench machinery; the full suite runs it
def test_moe_acceptance_block_shape(monkeypatch):
    """The issue-2 tripwire block: booleans with the targets recorded next
    to them, derived from top1 + the sweep.  The leg computes an MFU, and
    the CPU has no row in the peaks table — stub the lookup the way the
    chip would answer it (the shape of the block is the subject here)."""
    import numpy as _np
    monkeypatch.setattr(bench, "_peak_flops", lambda kind: 197e12)
    out = bench._bench_moe(batch=1, seq_len=16, model_dim=16, num_heads=2,
                           num_layers=1, vocab=64, experts=4, reps=1,
                           sweep_layers=1, sweep_steps=8,
                           capacity_factors=(2.0,))
    acc = out["acceptance"]
    assert acc["mfu_target"] == 0.45 and acc["dispatch_pct_target"] == 20.0
    assert acc["trained_drop_target"] == 0.05
    assert acc["dispatch_pct_ok"] is True  # sorted path: 0% dispatch FLOPs
    assert out["top1"]["dispatch_impl"] == "sorted"
    assert out["top1_dense"]["dispatch_impl"] == "dense"
    assert out["top1_dense"]["dispatch_flops_pct"] > 0
    assert _np.isfinite(out["sorted_vs_dense_top1"])


def test_native_features_acceptance_block_tripwires():
    """The ISSUE-11 per-leg tripwires: native per-window wall must be
    at-or-under the Python hub's, None-degrading (the PR-3 convention)
    when either leg is missing, errored, or zero."""
    out = {
        "sparse_python": {"per_window_wall_ms": 40.0},
        "sparse_native": {"per_window_wall_ms": 30.0},
        "adaptive_python": {"per_window_wall_ms": 50.0},
        "adaptive_native": {"per_window_wall_ms": 55.0},
        "sparse_adaptive_python": {"error": "RuntimeError: boom"},
        "sparse_adaptive_native": {"per_window_wall_ms": 30.0},
    }
    bench._native_features_acceptance(out)
    acc = out["acceptance"]
    assert acc["sparse_native_vs_python"] == 0.75
    assert acc["sparse_native_beats_python_ok"] is True
    assert acc["adaptive_native_vs_python"] == 1.1
    assert acc["adaptive_native_beats_python_ok"] is False
    assert acc["sparse_adaptive_native_vs_python"] is None
    assert acc["sparse_adaptive_native_beats_python_ok"] is None

    # zero / missing denominators degrade to None, never ZeroDivision
    out2 = {"sparse_python": {"per_window_wall_ms": 0.0},
            "sparse_native": {"per_window_wall_ms": 1.0}}
    bench._native_features_acceptance(out2)
    assert out2["acceptance"]["sparse_native_beats_python_ok"] is None
    out3 = {}
    bench._native_features_acceptance(out3)
    assert out3["acceptance"]["adaptive_native_beats_python_ok"] is None


@pytest.mark.slow  # ~60-200s of real bench machinery on CPU
def test_bench_async_native_features_tiny_e2e():
    """The ISSUE-11 legs run end to end tiny: every feature combination
    lands a wall number on BOTH hubs (or a recorded error, never a
    crash), and the acceptance block carries one tripwire per leg."""
    from distkeras_tpu.runtime.native import native_available

    out = bench._bench_async_native_features(
        workers=2, window=2, batch=8, windows_per_epoch=2, epochs=1,
        rows=32, dim=4, fields=2)
    acc = out["acceptance"]
    for leg in ("sparse", "adaptive", "sparse_adaptive"):
        for hub in ("python", "native"):
            rec = out[f"{leg}_{hub}"]
            assert isinstance(rec, dict)
            assert "per_window_wall_ms" in rec or "error" in rec
        assert f"{leg}_native_beats_python_ok" in acc
        if native_available():
            # tiny-shape wall is noisy — the tripwire may be False here
            # (the real bench runs production shapes), but it must EXIST
            assert acc[f"{leg}_native_vs_python"] is not None
