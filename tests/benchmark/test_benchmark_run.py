"""A whole run of the harness on the CPU with the look for a chip skipped:
the last line's keys, and ``correct`` coming out false when the timed path
is broken underneath."""

import json
import os
import sys

import pytest

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))
from bench_fixtures import tiny_root  # noqa: E402

from benchmark import run as bench  # noqa: E402

SEED = 3_000_000_011


@pytest.fixture(scope="module")
def root(tmp_path_factory):
    return tiny_root(str(tmp_path_factory.mktemp("bench") / "root"))


def _run(capsys, root, cell, trace=0, seconds=0.3):
    rc = bench.main(["--workload", cell, "--seed", str(SEED), "--seconds",
                     str(seconds), "--trace", str(trace)],
                    skip_device_check=True, root=root)
    out, err = capsys.readouterr()
    assert rc == 0
    return json.loads(out.strip().splitlines()[-1]), err


def test_last_line_of_an_untraced_run(capsys, root):
    line, err = _run(capsys, root, "tiny_sync")
    assert list(line) == ["correct", "attempted", "failed", "metrics", "device", "compared"]
    assert line["correct"] is True and line["failed"] == 0 and line["attempted"] >= 1
    assert set(line["metrics"]) == {"tokens_per_s_per_chip", "loss_at_tokens", "setup_s"}
    assert all(set(m) == {"value", "unit"} and m["value"] > 0
               for m in line["metrics"].values())
    assert {"platform", "kind", "count", "memory_peak_bytes"} <= set(line["device"])
    assert set(line["compared"]) == {"loss_first", "first_gap", "change_gap", "rare_gap"}
    # each number compared stands beside its limit at the end of stderr
    tail = err.strip().splitlines()[-4:]
    assert all(t.startswith("compared ") and "limit=" in t for t in tail)


def test_traced_async_run_reports_the_layers_it_can_read(capsys, root):
    line, _ = _run(capsys, root, "tiny_async", trace=1)
    assert line["correct"] is True
    got = set(line["metrics"])
    # host spans and hub histograms exist on the CPU; device-trace readers
    # find nothing to read there and return nothing (never a 0)
    assert {"compile_s", "train_call_fixed_s", "feed_load_ms_per_window",
            "async_exchange_share", "hub_commit_ms", "hub_pull_ms"} <= got
    assert not got & {"step_mfu", "flash_fwd_roofline", "device_idle_share",
                      "engine_epoch_ms"}
    assert 0 < line["metrics"]["async_exchange_share"]["value"] < 100


def _broken_step(kind):
    import jax
    import optax

    def make(apply_fn, loss, optimizer, with_rng=False):
        def loss_of(params, batch):
            x, y = batch[0], batch[1]
            if kind == "half_batch":          # half left out, mean over the rest
                x, y = x[: x.shape[0] // 2], y[: y.shape[0] // 2]
            return loss(apply_fn(params, x), y)

        def step(carry, batch):
            params, opt_state = carry
            loss_val, grads = jax.value_and_grad(loss_of)(params, batch)
            if kind == "state_unchanged":     # the step hands its state back
                return (params, opt_state), loss_val
            updates, opt_state = optimizer.update(grads, opt_state, params)
            return (optax.apply_updates(params, updates), opt_state), loss_val

        return step
    return make


@pytest.mark.parametrize("kind,number", [("state_unchanged", "first_gap"),
                                         ("half_batch", "rare_gap")])
def test_a_broken_timed_path_reads_not_correct(capsys, root, monkeypatch, kind, number):
    from distkeras_tpu.parallel import engine

    monkeypatch.setattr(engine, "make_minibatch_step", _broken_step(kind))
    line, _ = _run(capsys, root, "tiny_sync")
    assert line["correct"] is False
    c = line["compared"][number]
    assert c["value"] > c["limit"]
    if kind == "state_unchanged":
        assert c["value"] == pytest.approx(1.0, abs=1e-4)


def test_no_accelerator_means_no_result(capsys, root):
    with pytest.raises(SystemExit) as e:
        bench.main(["--workload", "tiny_sync", "--seed", "1", "--seconds", "1",
                    "--trace", "0"], root=root)
    assert e.value.code not in (0, None)
    assert capsys.readouterr().out == ""
