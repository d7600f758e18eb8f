"""BENCHMARK.json against its contract, and the harness finding a cell, a
configuration, a traffic mix and a metric that were added as new files."""

import json
import os
import re
import sys

import pytest

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))
from bench_fixtures import ROOT, tiny_root  # noqa: E402

from benchmark.harness import spec  # noqa: E402

NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")
BENCH = spec.load_benchmark()
WIDTH_WORDS = ("hidden", "intermediate", "n_embd", "n_inner", "head_dim", "n_head")


def test_top_level_keys_and_sizes():
    assert set(BENCH) == {"command", "paths", "run_seconds", "configs",
                          "workloads", "end_to_end", "per_layer"}
    assert BENCH["command"] == ["python3", "benchmark/run.py"]
    assert BENCH["paths"] == ["benchmark", "tests/benchmark"]
    assert 1 <= BENCH["run_seconds"] <= 51 and isinstance(BENCH["run_seconds"], int)
    assert os.path.getsize(os.path.join(ROOT, "BENCHMARK.json")) <= 64 * 1024
    # a full check at the full 24 cells has to fit
    assert (2 + 14 * 24) * (BENCH["run_seconds"] + 60) + 24 * 180 + 1200 <= 43200


@pytest.mark.parametrize("cfg", BENCH["configs"], ids=lambda c: c["name"])
def test_config_entry_and_file(cfg):
    assert set(cfg) == {"name", "source", "file", "reduced", "why"}
    assert NAME.match(cfg["name"]) and cfg["file"].startswith("benchmark/")
    with open(os.path.join(ROOT, cfg["file"])) as f:
        body = json.load(f)
    assert body["reduced"] == cfg["reduced"]
    assert not [k for k in cfg["reduced"] if k in WIDTH_WORDS or k.endswith(("_dim", "_rank"))]
    # published widths of the family: head size 128, d_ffn = 4 d_model, no cut
    assert body["n_embd"] // body["n_head"] == 128
    assert body["n_inner"] == 4 * body["n_embd"]
    assert (body["vocab_size"], body["n_positions"]) == (50257, 2048)
    for key in cfg["reduced"]:
        assert body["published"][key] != body[key]
    assert any(w["config"] == cfg["name"] for w in BENCH["workloads"])


@pytest.mark.parametrize("cell", BENCH["workloads"], ids=lambda c: c["name"])
def test_workload_entry_loads(cell):
    assert set(cell) == {"name", "config", "traffic", "chips", "why"}
    assert NAME.match(cell["name"]) and NAME.match(cell["traffic"])
    assert cell["chips"] in (1, 4) and 1 <= len(cell["why"]) <= 200
    loaded = spec.load_cell(cell["name"])
    names = {m["name"] for m in loaded["end_to_end"]}
    assert "setup_s" in names and len(names) >= 2 and loaded["per_layer"]
    assert set(loaded["check"]["limits"]) == {"loss_first", "first_gap", "change_gap", "rare_gap"}
    assert loaded["traffic_file"]["trainer"] in ("ADAG", "AsyncADAG")
    # only what the traffic file names reaches the constructor
    assert set(loaded["traffic_file"]["constructor"]) <= {
        "num_workers", "batch_size", "communication_window", "learning_rate",
        "seed", "loss", "chunk_windows"}


@pytest.mark.parametrize("metric", BENCH["end_to_end"] + BENCH["per_layer"],
                         ids=lambda m: m["name"])
def test_metric_entry(metric):
    e2e = metric in BENCH["end_to_end"]
    want = {"name", "unit", "better", "source"} | (
        {"bound"} if e2e else {"layer", "moves"})
    assert want <= set(metric) <= want | {"workloads"}
    assert NAME.match(metric["name"]) and UNIT.match(metric["unit"])
    assert metric["better"] in ("lower", "higher")
    if e2e:
        assert metric["source"] in ("host_clock", "device_trace")
        assert 0.01 <= metric["bound"] <= 0.1
    else:
        assert metric["source"] in ("device_trace", "program_span",
                                    "program_counter", "host_clock")
        assert metric["moves"] in {m["name"] for m in BENCH["end_to_end"]}
        read, args = spec.load_reader(metric["name"])
        assert callable(read) and isinstance(args, dict)
    cells = {w["name"] for w in BENCH["workloads"]}
    assert set(metric.get("workloads", cells)) <= cells


def test_new_cell_config_traffic_and_metric_are_only_new_files(tmp_path):
    """A later PR adds entries and files; no existing file is edited."""
    root = tiny_root(str(tmp_path / "root"))
    base = os.path.join(root, "benchmark")
    with open(os.path.join(root, "BENCHMARK.json")) as f:
        bench = json.load(f)
    # a new traffic mix (another trainer class by name), a metric with a
    # reader of its own, and a cell that uses them
    with open(os.path.join(base, "traffic", "sync_adag.json")) as f:
        traffic = dict(json.load(f), name="sync_downpour", trainer="DOWNPOUR")
    with open(os.path.join(base, "traffic", "sync_downpour.json"), "w") as f:
        json.dump(traffic, f)
    with open(os.path.join(base, "readers", "answer.py"), "w") as f:
        f.write("def read(ctx, value):\n    return value * ctx['scale']\n")
    with open(os.path.join(base, "metrics", "answer_ms.json"), "w") as f:
        json.dump({"name": "answer_ms", "reader": "answer", "args": {"value": 21}}, f)
    with open(os.path.join(base, "workloads", "tiny_downpour.json"), "w") as f:
        json.dump({"name": "tiny_downpour", "config": "tiny", "chips": 1,
                   "traffic": "sync_downpour", "why": "test",
                   "loss_at_tokens": {"mark_windows": 1},
                   "check": {"calls": [1], "limits": {}}}, f)
    bench["workloads"].append({"name": "tiny_downpour", "config": "tiny",
                               "traffic": "sync_downpour", "chips": 1, "why": "t"})
    bench["per_layer"].append({"name": "answer_ms", "unit": "ms", "better": "lower",
                               "source": "program_counter", "layer": "input",
                               "moves": "setup_s", "workloads": ["tiny_downpour"]})
    with open(os.path.join(root, "BENCHMARK.json"), "w") as f:
        json.dump(bench, f)
    cell = spec.load_cell("tiny_downpour", root)
    assert cell["traffic_file"]["trainer"] == "DOWNPOUR"
    assert cell["config_file"]["n_embd"] == 64
    assert [m["name"] for m in cell["per_layer"]] == ["answer_ms"]
    read, args = spec.load_reader("answer_ms", root)
    assert read({"scale": 2}, **args) == 42
    from benchmark.harness import program
    assert program.trainer_class(cell["traffic_file"]).__name__ == "DOWNPOUR"
    # the cells that were there are found as before
    assert spec.load_cell("tiny_sync", root)["traffic_file"]["trainer"] == "ADAG"
    with pytest.raises(KeyError):
        spec.load_cell("no_such_cell", root)
