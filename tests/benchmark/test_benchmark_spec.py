"""BENCHMARK.json against its contract, and the harness finding a cell, a
configuration, a traffic mix and a metric that were added as new files."""

import glob
import json
import os
import re
import shutil
import sys

import pytest

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))
from bench_fixtures import ROOT, TINY_LIMITS, tiny_root  # noqa: E402

from benchmark import run as bench  # noqa: E402
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


OTHER = {"family": "other_lm", "name": "other", "vocab_size": 512, "hidden_size": 64,
         "num_attention_heads": 2, "num_key_value_heads": 1, "num_hidden_layers": 2,
         "intermediate_size": 256, "max_position_embeddings": 64, "reduced": []}


def _files(root):
    out = {}
    for path in glob.glob(os.path.join(root, "**", "*"), recursive=True):
        if os.path.isfile(path) and "__pycache__" not in path:
            with open(path, "rb") as f:
                out[os.path.relpath(path, root)] = f.read()
    return out


def test_new_cell_config_traffic_and_metric_are_only_new_files(tmp_path, capsys):
    """A later PR adds entries and files; no existing file is edited.  That
    holds for a second model FAMILY too: a reference and a family file of
    its own (another block — rotary positions, grouped key/value heads —
    under other ``config.json`` keys), a configuration, a traffic mix whose
    sequence length is under the configuration's positions, a cell; the
    whole harness then runs on it to ``correct``."""
    root = tiny_root(str(tmp_path / "root"))
    base = os.path.join(root, "benchmark")
    before = _files(root)
    with open(os.path.join(root, "BENCHMARK.json")) as f:
        bench_json = json.load(f)
    held = json.loads(json.dumps(bench_json))
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
    bench_json["workloads"].append({"name": "tiny_downpour", "config": "tiny",
                                    "traffic": "sync_downpour", "chips": 1, "why": "t"})
    bench_json["per_layer"].append({"name": "answer_ms", "unit": "ms", "better": "lower",
                                    "source": "program_counter", "layer": "input",
                                    "moves": "setup_s", "workloads": ["tiny_downpour"]})
    # the second family: its two files, a configuration, a traffic mix, a cell
    data = os.path.join(os.path.dirname(os.path.abspath(__file__)), "data", "other_lm")
    shutil.copy(os.path.join(data, "reference.py"),
                os.path.join(base, "reference", "other_lm.py"))
    shutil.copy(os.path.join(data, "family.py"), os.path.join(base, "families", "other_lm.py"))
    with open(os.path.join(base, "configs", "other.json"), "w") as f:
        json.dump(OTHER, f)
    traffic["name"], traffic["trainer"] = "sync_adag_32", "ADAG"
    traffic["data"] = dict(traffic["data"], seq_len=32)
    with open(os.path.join(base, "traffic", "sync_adag_32.json"), "w") as f:
        json.dump(traffic, f)
    with open(os.path.join(base, "workloads", "other_sync.json"), "w") as f:
        json.dump({"name": "other_sync", "config": "other", "traffic": "sync_adag_32",
                   "chips": 1, "why": "test", "windows_per_second": 20,
                   "loss_at_tokens": {"mark_windows": 2, "average_windows": 3},
                   "check": {"calls": [1, 2], "rare_min_rank": 40, "limits": TINY_LIMITS}}, f)
    bench_json["configs"].append({"name": "other", "source": "test", "reduced": [],
                                  "file": "benchmark/configs/other.json", "why": "test"})
    bench_json["workloads"].append({"name": "other_sync", "config": "other",
                                    "traffic": "sync_adag_32", "chips": 1, "why": "t"})
    for m in bench_json["per_layer"] + bench_json["end_to_end"]:
        if "tiny_sync" in m.get("workloads", ()):
            m["workloads"].append("other_sync")
    with open(os.path.join(root, "BENCHMARK.json"), "w") as f:
        json.dump(bench_json, f)
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
    # the new family's cell: the sequence length is the traffic file's, and a
    # whole run of the harness on it comes out correct
    other = spec.load_cell("other_sync", root)
    assert bench.shapes(other)["seq_len"] == 32 < other["config_file"]["max_position_embeddings"]
    fam = spec.load_family(other["config_file"], root)
    assert fam.model_spec(other["config_file"]).config["positional"] == "rope"
    assert bench.main(["--workload", "other_sync", "--seed", "3000000017", "--seconds", "0.3",
                       "--trace", "1"], skip_device_check=True, root=root) == 0
    line = json.loads(capsys.readouterr().out.strip().splitlines()[-1])
    assert line["correct"] is True and line["failed"] == 0, line["compared"]
    assert "compile_s" in line["metrics"] and "flash_fwd_roofline" not in line["metrics"]
    # only additions: every file that was there holds the bytes it held, and
    # every entry BENCHMARK.json had stands where it stood (a metric's list
    # of cells grew at its end)
    after = _files(root)
    assert {k: after[k] for k in before if k != "BENCHMARK.json"} == {
        k: v for k, v in before.items() if k != "BENCHMARK.json"}
    assert set(after) - set(before) == {os.path.join("benchmark", *p) for p in (
        ("traffic", "sync_downpour.json"), ("readers", "answer.py"),
        ("metrics", "answer_ms.json"), ("workloads", "tiny_downpour.json"),
        ("reference", "other_lm.py"), ("families", "other_lm.py"),
        ("configs", "other.json"), ("traffic", "sync_adag_32.json"),
        ("workloads", "other_sync.json"))}
    def but_cells(entry):
        return {k: v for k, v in entry.items() if k != "workloads"}

    for key in ("configs", "workloads", "end_to_end", "per_layer"):
        for old, new in zip(held[key], bench_json[key]):
            cells = old.get("workloads", [])
            assert but_cells(new) == but_cells(old)
            assert new.get("workloads", [])[:len(cells)] == cells


def test_a_sequence_length_over_the_configurations_positions_raises(tmp_path):
    root = tiny_root(str(tmp_path / "root"))
    cell = spec.load_cell("tiny_sync", root)
    assert bench.shapes(cell)["seq_len"] == 64          # the configuration's own
    cell["traffic_file"]["data"]["seq_len"] = 48
    assert bench.shapes(cell)["seq_len"] == 48          # the traffic file's
    cell["traffic_file"]["data"]["seq_len"] = 65
    with pytest.raises(ValueError, match="65 is over the configuration's 64"):
        bench.shapes(cell)


HARNESS_FILES = [os.path.join(ROOT, "benchmark", "run.py")] + sorted(
    glob.glob(os.path.join(ROOT, "benchmark", "harness", "*.py"))
    + glob.glob(os.path.join(ROOT, "benchmark", "readers", "*.py")))


@pytest.mark.parametrize("path", HARNESS_FILES, ids=lambda p: os.path.relpath(
    p, os.path.join(ROOT, "benchmark")))
def test_no_harness_file_names_a_key_of_a_gpt2_config(path):
    """Only ``families/gpt_lm.py`` and ``reference/gpt_lm.py`` read a GPT-2
    ``config.json``'s keys (``peaks.py``'s helpers take ``n_layer`` as an
    argument's name, which is no key)."""
    with open(path) as f:
        text = f.read()
    assert [k for k in ("n_embd", "n_head", "n_layer", "n_inner", "n_positions")
            if f'"{k}"' in text or f"'{k}'" in text] == []
