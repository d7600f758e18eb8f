"""Shared helpers of the benchmark's CPU tests (not a test file)."""

import json
import os
import shutil

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

TINY = {"vocab_size": 512, "n_positions": 64, "n_embd": 64, "n_head": 2,
        "n_layer": 2, "n_inner": 256}
# readings on the CPU at this size (PR 24): the program against the reference
# reads up to 0.002 on either gap, the fp8 control 0.010 to 0.016
TINY_LIMITS = {"loss_first": 0.1, "first_gap": 0.005, "change_gap": 0.005, "rare_gap": 0.05}


def tiny_root(dst: str) -> str:
    """A copy of the benchmark's data tree with one tiny configuration and
    two tiny cells in place of the real ones — made only by ADDING files
    and entries beside copies of the committed ones."""
    os.makedirs(os.path.join(dst, "benchmark"))
    for d in ("readers", "reference", "families", "metrics", "traffic", "configs",
              "workloads"):
        shutil.copytree(os.path.join(ROOT, "benchmark", d),
                        os.path.join(dst, "benchmark", d))
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        bench = json.load(f)
    with open(os.path.join(ROOT, "benchmark", "configs", "cerebras-gpt-590m.json")) as f:
        cfg = dict(json.load(f), name="tiny", **TINY)
    with open(os.path.join(dst, "benchmark", "configs", "tiny.json"), "w") as f:
        json.dump(cfg, f)
    bench["configs"].append({"name": "tiny", "source": "test",
                             "file": "benchmark/configs/tiny.json",
                             "reduced": [], "why": "test"})
    for name, traffic, like in (("tiny_sync", "sync_adag", "lm590m_sync"),
                                ("tiny_async", "async_adag_1", "lm590m_async")):
        bench["workloads"].append({"name": name, "config": "tiny",
                                   "traffic": traffic, "chips": 1, "why": "test"})
        for m in bench["per_layer"] + bench["end_to_end"]:
            if like in m.get("workloads", ()):
                m["workloads"].append(name)
        with open(os.path.join(dst, "benchmark", "workloads", name + ".json"), "w") as f:
            json.dump({"name": name, "config": "tiny", "traffic": traffic,
                       "chips": 1, "why": "test", "windows_per_second": 20,
                       "loss_at_tokens": {"mark_windows": 2, "average_windows": 3},
                       "check": {"calls": [1, 2], "rare_min_rank": 40, "limits": TINY_LIMITS},
                       "trace": {"max_seconds": 5}}, f)
    with open(os.path.join(dst, "BENCHMARK.json"), "w") as f:
        json.dump(bench, f)
    return dst
