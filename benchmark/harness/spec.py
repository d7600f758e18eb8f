"""Find a cell and everything it names, by name, as data.

``BENCHMARK.json`` lists cells, configurations and metrics; what belongs to
one of them sits in a file of its own under ``benchmark/``:
``workloads/<cell>.json``, ``configs/<config>.json`` (or the ``file`` the
entry gives), ``traffic/<traffic>.json``, ``metrics/<metric>.json`` and
``readers/<reader>.py``.  What belongs to a model FAMILY, the ``"family"``
a configuration file names, sits in two files: ``reference/<family>.py``,
the plain reference, and ``families/<family>.py``, the only code that reads
that family's ``config.json`` keys on the harness's side:

``model_spec(cfg)``, ``to_program_tree(ref_leaves, cfg)`` /
``from_program_tree(tree, cfg)`` (the program's model and pure, traceable
indexing between the reference's leaves and the program's tree),
``shapes(cfg, traffic)`` -> ``{"seq_len", "vocab"}`` (the traffic file's
``data.seq_len`` where it gives one, never over the configuration's
positions: ``job_seq_len`` below), ``train_flops_per_token(cfg, seq_len)`` -> ``{"dense",
"attention", "total"}`` (what a trained token requires) and
``kernel_work(cfg, kernel, batch, seq_len)`` -> ``{"flops", "bytes"}`` (one
mean call of the kernel of that HLO name).  ``families/gpt_lm.py`` states
the contract in full.

A later PR adds files and entries; no file here needs an edit for them.  A
new family is ``configs/<name>.json`` with ``"family": "<f>"``,
``reference/<f>.py``, ``families/<f>.py``, its traffic, cell and metric
files (a kernel's roofline is a metric file ``{"reader": "trace_kernel",
"args": {"kernel": "<name>"}}`` and a branch of the family's
``kernel_work``), and the entries in ``BENCHMARK.json``.
"""

from __future__ import annotations

import importlib.util
import json
import os
from typing import Any, Dict, List

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def _load(path: str) -> Dict[str, Any]:
    with open(path) as f:
        return json.load(f)


def load_benchmark(root: str = ROOT) -> Dict[str, Any]:
    return _load(os.path.join(root, "BENCHMARK.json"))


def _entry(entries: List[Dict[str, Any]], name: str, kind: str) -> Dict[str, Any]:
    for e in entries:
        if e["name"] == name:
            return e
    raise KeyError(f"BENCHMARK.json has no {kind} named {name!r}; it has "
                   f"{[e['name'] for e in entries]}")


def load_cell(name: str, root: str = ROOT) -> Dict[str, Any]:
    """The cell with its configuration, traffic mix and metric lists."""
    bench = load_benchmark(root)
    entry = _entry(bench["workloads"], name, "workload")
    base = os.path.join(root, "benchmark")
    cell = dict(_load(os.path.join(base, "workloads", name + ".json")))
    for key in ("config", "traffic", "chips"):
        if cell.get(key, entry[key]) != entry[key]:
            raise ValueError(f"{name}: workloads/{name}.json says {key}="
                             f"{cell[key]!r}, BENCHMARK.json {entry[key]!r}")
        cell[key] = entry[key]
    cfg_entry = _entry(bench["configs"], entry["config"], "config")
    cell["name"], cell["root"] = name, root
    cell["config_file"] = _load(os.path.join(root, cfg_entry["file"]))
    cell["traffic_file"] = _load(
        os.path.join(base, "traffic", entry["traffic"] + ".json"))

    def mine(metric: Dict[str, Any]) -> bool:
        return "workloads" not in metric or name in metric["workloads"]

    cell["end_to_end"] = [m for m in bench["end_to_end"] if mine(m)]
    cell["per_layer"] = [m for m in bench["per_layer"] if mine(m)]
    return cell


def load_reference(config_file: Dict[str, Any], root: str = ROOT):
    """The configuration's plain reference: ``reference/<family>.py``."""
    return _import(os.path.join(root, "benchmark", "reference",
                                config_file["family"] + ".py"),
                   "benchmark_reference_" + config_file["family"])


def load_family(config_file: Dict[str, Any], root: str = ROOT):
    """The configuration's glue to the program and its required-work
    counts: ``families/<family>.py`` (contract: this module's docstring)."""
    return _import(os.path.join(root, "benchmark", "families",
                                config_file["family"] + ".py"),
                   "benchmark_family_" + config_file["family"])


def job_seq_len(traffic: Dict[str, Any], positions: int) -> int:
    """The job's sequence length, for a family's ``shapes``: the traffic
    file's ``data.seq_len`` where it gives one, else the configuration's own
    positions; a length over what the configuration declares raises."""
    seq_len = int(traffic.get("data", {}).get("seq_len", positions))
    if seq_len > positions:
        raise ValueError(f"the traffic's seq_len {seq_len} is over the "
                         f"configuration's {positions} positions")
    return seq_len


def load_reader(metric_name: str, root: str = ROOT):
    """(read function, its arguments) for a per-layer metric's own file."""
    base = os.path.join(root, "benchmark")
    m = _load(os.path.join(base, "metrics", metric_name + ".json"))
    mod = _import(os.path.join(base, "readers", m["reader"] + ".py"),
                  "benchmark_reader_" + m["reader"])
    return mod.read, dict(m.get("args", {}))


def _import(path: str, name: str):
    spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod
