"""Whole runs of the harness on the small DeepSeek-V3 configuration
(``data/deepseek_v3_small/config.json``, see ``test_benchmark_deepseek_v3.py``)
on the CPU: a traced run reads ``correct``, and a step with a fault planted
in it does not."""

import json
import os
import shutil
import sys

import pytest

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))
from bench_fixtures import ROOT  # noqa: E402

from benchmark import run as bench  # noqa: E402
from benchmark.harness import spec  # noqa: E402

HERE = os.path.dirname(os.path.abspath(__file__))
with open(os.path.join(HERE, "data", "deepseek_v3_small", "config.json")) as f:
    SMALL = json.load(f)


@pytest.fixture(scope="module")
def small_root(tmp_path_factory):
    """The benchmark's data tree with the small configuration as a cell."""
    dst = str(tmp_path_factory.mktemp("deepseek_v3") / "root")
    shutil.copytree(os.path.join(ROOT, "benchmark"), os.path.join(dst, "benchmark"),
                    ignore=shutil.ignore_patterns("__pycache__"))
    b = spec.load_benchmark(ROOT)
    with open(os.path.join(dst, "benchmark", "configs", "deepseek-v3-small.json"), "w") as f:
        json.dump(SMALL, f)
    b["configs"].append({"name": "deepseek-v3-small", "source": "test", "reduced": [],
                         "file": "benchmark/configs/deepseek-v3-small.json", "why": "test"})
    b["workloads"].append({"name": "deepseek_v3_small", "config": "deepseek-v3-small",
                           "traffic": "sync_adag", "chips": 1, "why": "test"})
    for m in b["per_layer"] + b["end_to_end"]:
        if "kanana_sync8k" in m.get("workloads", ()):
            m["workloads"].append("deepseek_v3_small")
    with open(os.path.join(dst, "benchmark", "workloads", "deepseek_v3_small.json"), "w") as f:
        json.dump({"name": "deepseek_v3_small", "config": "deepseek-v3-small",
                   "traffic": "sync_adag", "chips": 1, "why": "test", "windows_per_second": 20,
                   "loss_at_tokens": {"mark_windows": 2, "average_windows": 3},
                   "check": {"calls": [1, 2], "rare_min_rank": 40,
                             "limits": {"loss_first": 0.01, "first_gap": 0.01,
                                        "change_gap": 0.01, "rare_gap": 0.05}},
                   "trace": {"max_seconds": 5}}, f)
    with open(os.path.join(dst, "BENCHMARK.json"), "w") as f:
        json.dump(b, f)
    return dst


def test_whole_traced_run_of_the_harness(capsys, small_root):
    rc = bench.main(["--workload", "deepseek_v3_small", "--seed", "3400000011", "--seconds",
                     "0.2", "--trace", "1"], skip_device_check=True, root=small_root)
    out, _ = capsys.readouterr()
    assert rc == 0
    line = json.loads(out.strip().splitlines()[-1])
    assert line["correct"] is True and line["failed"] == 0
    got = line["metrics"]
    # the counters exist on the CPU; the device-trace readers find no device
    # plane there and report nothing (never a 0)
    assert {"moe_held_share", "moe_expert_load_max_over_mean", "compile_s"} <= set(got)
    assert not set(got) & {"mla_device_share", "mla_latent_device_share", "attn_device_share",
                           "step_mfu", "flash_fwd_roofline"}
    assert 0 < got["moe_held_share"]["value"] < 100
    assert got["moe_expert_load_max_over_mean"]["value"] >= 1.0


def _broken(kind, real):
    """The program's own step builder with a fault planted in what it builds."""
    def make(*args, **kwargs):
        step = real(*args, **kwargs)

        def broken(carry, batch):
            if kind == "half_batch":          # half left out, mean over the rest
                batch = tuple(b[: b.shape[0] // 2] for b in batch)
            new, out = step(carry, batch)
            # state_unchanged: the step hands its state back
            return (carry if kind == "state_unchanged" else new), out

        return broken
    return make


@pytest.mark.parametrize("kind,number", [("state_unchanged", "first_gap"),
                                         ("half_batch", "rare_gap")])
def test_a_broken_hooked_step_reads_not_correct(capsys, small_root, monkeypatch, kind, number):
    from distkeras_tpu.parallel import engine

    monkeypatch.setattr(engine, "make_minibatch_step",
                        _broken(kind, engine.make_minibatch_step))
    rc = bench.main(["--workload", "deepseek_v3_small", "--seed", "3400000011", "--seconds",
                     "0.2", "--trace", "0"], skip_device_check=True, root=small_root)
    out, _ = capsys.readouterr()
    assert rc == 0
    line = json.loads(out.strip().splitlines()[-1])
    assert line["correct"] is False
    c = line["compared"][number]
    assert c["value"] > c["limit"]
    if kind == "state_unchanged":
        assert c["value"] == pytest.approx(1.0, abs=1e-4)
