#!/usr/bin/env python3
"""The benchmark's one command.

    python3 benchmark/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

A new process each time.  It demands the chips the cell names (no CPU
fallback: off the TPU, on an unknown ``device_kind`` or with too few chips
it exits non-zero and prints no result), makes the weights on the device
from ``--seed``, drives the cell's trainer through its public entry
``Trainer(...).train(Dataset)``, and prints one JSON object as the last line
of standard output.  Everything about a cell is data under ``benchmark/``
(see ``harness/spec.py``).

Order of a run: set-up (weights, the trainer, its first ``train()`` calls —
which compile, calibrate one window's wall time and are what the plain
reference later follows); the timed ``train()`` call over the smallest
whole number of windows whose predicted wall reaches ``--seconds``; peak
memory; the program's state freed; the reference; the result.
"""

from __future__ import annotations

import time

T_START = time.perf_counter()  # before the heavy imports: set-up counts them

import argparse
import gc
import json
import math
import os
import shutil
import sys
import tempfile
import threading

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from benchmark.harness import check, peaks, spec, tokens, trace, window  # noqa: E402


def log(msg: str) -> None:
    print(f"[{time.perf_counter() - T_START:7.1f}s] {msg}", file=sys.stderr, flush=True)


def require_chips(chips: int):
    """The devices, which must be TPUs of a kind in the peaks table, and at
    least as many as the cell asks for.  ``skip_device_check`` exists for
    the tests under ``tests/benchmark`` only (see ``main``'s argument)."""
    import jax

    devices = jax.devices()
    if devices[0].platform != "tpu":
        raise SystemExit(f"no TPU: JAX came up on {devices[0].platform!r}; "
                         f"this benchmark runs on the chip only")
    if len(devices) < chips:
        raise SystemExit(f"the cell needs {chips} chips, JAX sees {len(devices)}")
    peaks.device_peaks(devices[0].device_kind)
    return devices


def place_compile_cache() -> str:
    """JAX's persistent compilation cache: where ``JAX_COMPILATION_CACHE_DIR``
    says, else at one fixed path inside the checkout."""
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    import jax

    path = os.path.join(ROOT, ".jax_cache")
    jax.config.update("jax_compilation_cache_dir", path)
    return path


def histogram_sums(snapshot) -> dict:
    """{name: {"count", "sum"}} with every label set of a name added up."""
    out: dict = {}
    for key, h in snapshot.get("histograms", {}).items():
        name = key.split("{", 1)[0]
        acc = out.setdefault(name, {"count": 0, "sum": 0.0})
        acc["count"] += h["count"]
        acc["sum"] += h["sum"]
    return out


def to_host(model):
    """The same model on host arrays: between calls no parameter-sized
    device array is kept (an asynchronous ``train()`` otherwise holds the
    previous call's center on the device beside its four trees)."""
    import jax
    import numpy as np

    from distkeras_tpu.models.base import Model

    return Model(spec=model.spec, params=jax.tree.map(np.array, model.params))


def shapes(cell: dict) -> dict:
    """The sizes a cell's calls have, from its traffic and, for what only
    the model's family can say (sequence length, vocabulary), its family."""
    cfg, traffic = cell["config_file"], cell["traffic_file"]
    c = traffic["constructor"]
    job = spec.load_family(cfg, cell["root"]).shapes(cfg, traffic)
    chips = int(cell["chips"])
    workers = chips if c["num_workers"] == "chips" else int(c["num_workers"])
    steps, batch = int(c["communication_window"]), int(c["batch_size"])
    return {"chips": chips, "workers": workers, "steps": steps, "batch": batch,
            "seq_len": int(job["seq_len"]), "vocab": int(job["vocab"]),
            "rows_per_window": steps * batch * workers,
            "data_kw": {k: v for k, v in traffic["data"].items()
                        if k in ("zipf_exponent", "repeat_prob", "repeat_span")}}


def rare_rows(cell: dict):
    """Rows of ``wte`` of the tokens of Zipf rank ``check.rare_min_rank`` and
    beyond (``None`` where the cell's file names no such rank)."""
    rank = cell["check"].get("rare_min_rank")
    if rank is None:
        return None
    return tokens.rare_token_ids(shapes(cell)["vocab"], int(rank))


def drive_setup(cell: dict, seed: int, reference) -> dict:
    """Set-up's part with the program: the model on the seed's weights, ONE
    trainer object, and its first ``train()`` calls (the cell's
    ``check.calls`` windows each) on rows that all differ.  Returns the
    trainer — the very object the timed call then drives — what each call
    produced (window losses, per-leaf norms of the center's change), the
    rows each call saw and the host-clock record of each call."""
    from benchmark.harness import program

    cfg, traffic, sh = cell["config_file"], cell["traffic_file"], shapes(cell)
    family = spec.load_family(cfg, cell["root"])
    model = program.build_model(cfg, family, reference, seed)
    trainer = program.make_trainer(traffic, model, sh["chips"])
    del model
    log("weights made, trainer built")
    calls = [int(k) for k in cell["check"]["calls"]]
    rows = tokens.make_rows(sum(calls) * sh["rows_per_window"], sh["seq_len"],
                            sh["vocab"], seed, **sh["data_kw"])
    rare = rare_rows(cell)
    followed, call_data, records, at = [], [], [], 0
    for k in calls:
        part = {n: v[at * sh["rows_per_window"]:(at + k) * sh["rows_per_window"]]
                for n, v in rows.items()}
        at += k
        rec = program.run_call(trainer, traffic, part)
        followed.append({"losses": [float(x) for x in rec["losses"]],
                         "norms": program.change_norms(
                             cfg, family, reference, rec.pop("model").params, seed, rare)})
        trainer.model = to_host(trainer.model)
        call_data.append(tuple(
            part[n].reshape(k, sh["steps"], sh["batch"] * sh["workers"], sh["seq_len"])
            for n in ("features", "label")))
        records.append(rec)
        log(f"set-up call of {k} window(s): {rec['t_return'] - rec['t_call']:.2f} s, "
            f"window {rec['t_close'] - rec['t_open']:.2f} s, losses {followed[-1]['losses']}")
    return {"trainer": trainer, "followed": followed, "call_data": call_data,
            "records": records, "calls": calls}


def follow_reference(cell: dict, seed: int, reference, call_data, **kw):
    """The plain reference over the set-up calls' rows (``kw``: the
    control's ``precision`` or a planted fault's ``rows``)."""
    c, ex = cell["traffic_file"]["constructor"], cell["traffic_file"]["exchange"]
    return reference.follow(cell["config_file"], seed, call_data,
                            lr=float(c["learning_rate"]),
                            num_workers=shapes(cell)["workers"],
                            self_staleness=int(ex["self_staleness"]),
                            rare_rows=rare_rows(cell), **kw)


def start_tracing(cell: dict, fixed_s: float) -> dict:
    """Telemetry on and the profiler started (Python tracer off).  A timer
    stops the profiler ``trace.max_seconds`` into the window, so that a long
    window's trace stays small; ``stop`` ends it earlier."""
    import jax

    from distkeras_tpu import observability as obs

    obs.enable()
    state = {"before": histogram_sums(obs.snapshot()),
             "dir": tempfile.mkdtemp(prefix="bench_trace_")}
    options = jax.profiler.ProfileOptions()
    options.python_tracer_level = 0
    jax.profiler.start_trace(state["dir"], profiler_options=options)
    stop_lock, stopped = threading.Lock(), []

    def stop_trace():
        # the timer or the end of the call, whichever comes first
        with stop_lock:
            if not stopped:
                stopped.append(True)
                jax.profiler.stop_trace()

    timer = threading.Timer(float(cell.get("trace", {}).get("max_seconds", 20))
                            + fixed_s, stop_trace)
    timer.start()
    state["stop"] = lambda: (timer.cancel(), stop_trace())
    state["t_mark"] = time.perf_counter()
    with jax.profiler.TraceAnnotation("bench.mark"):
        pass                        # ties the host clock to the trace's clock
    return state


def layer_metrics(ctx: dict, tracing: dict, rec: dict, device: dict, result: dict) -> dict:
    """The cell's per-layer metrics, each by its own reader; also fills the
    contract's ``busy_s`` / ``window_s`` and the ``breakdown``."""
    from distkeras_tpu import observability as obs

    before = tracing["before"]
    ctx["histograms"] = {
        k: {"count": v["count"] - before.get(k, {}).get("count", 0),
            "sum": v["sum"] - before.get(k, {}).get("sum", 0.0)}
        for k, v in histogram_sums(obs.snapshot()).items()}
    rows = trace.load_events(tracing["dir"])
    shutil.rmtree(tracing["dir"], ignore_errors=True)
    ctx["trace"] = None
    mark = trace.annotation(rows, "bench.mark")
    if mark is not None and trace.device_planes(rows):
        # the traced stretch of the window, on the trace's clock
        lo = mark[0] + 1e9 * (rec["t_open"] - tracing["t_mark"])
        close = mark[0] + 1e9 * (rec["t_close"] - tracing["t_mark"])
        end = max(r[3] + r[4] for r in rows)
        hi = min(close, end)
        # cut: the profiler stopped inside the window (trace.max_seconds)
        ctx["trace"] = {"rows": rows, "lo": lo, "hi": hi, "cut": end < close}
        bw = trace.busy_and_window(rows, lo, hi)
        device["busy_s"], device["window_s"] = bw["busy_s"], bw["window_s"]
        result["breakdown"] = {"device_ops": trace.top_device_ops(rows),
                               "idle_gaps": trace.idle_gaps(rows, lo, hi)}
    out = {}
    for m in ctx["cell"]["per_layer"]:
        read, args = spec.load_reader(m["name"], ctx["cell"]["root"])
        value = read(ctx, **args)
        if value is not None:
            out[m["name"]] = {"value": float(value), "unit": m["unit"]}
    for note in ctx["notes"]:
        log(note)
    return out


def run(cell: dict, seed: int, seconds: float, traced: bool, devices) -> dict:
    from benchmark.harness import program

    cfg, traffic, sh = cell["config_file"], cell["traffic_file"], shapes(cell)
    chips, workers, seq_len, batch = sh["chips"], sh["workers"], sh["seq_len"], sh["batch"]
    reference = spec.load_reference(cfg, cell["root"])
    family = spec.load_family(cfg, cell["root"])
    rows_per_window = sh["rows_per_window"]
    tok_per_window = window.tokens_per_window(traffic, seq_len, workers)

    # -- set-up ---------------------------------------------------------------
    su = drive_setup(cell, seed, reference)
    trainer, followed, call_data = su["trainer"], su["followed"], su["call_data"]
    records, calls = su["records"], su["calls"]
    steady = records[-1]                       # every program is compiled by now
    wall_per_window = (steady["t_close"] - steady["t_open"]) / calls[-1]
    fixed_s = (steady["t_return"] - steady["t_call"]) - (steady["t_close"] - steady["t_open"])
    first = records[0]
    compile_s = (first["t_return"] - first["t_call"]) - (fixed_s + calls[0] * wall_per_window)
    n_windows = window.windows_for(seconds, 1.0 / float(cell["windows_per_second"]))
    timed_rows = tokens.make_rows(n_windows * rows_per_window, seq_len,
                                  sh["vocab"], seed + 1, **sh["data_kw"])
    log(f"set-up's steady call: {wall_per_window:.3f} s a window, {fixed_s:.2f} s fixed "
        f"a call; timed call gets {n_windows} windows")

    # -- the timed call -------------------------------------------------------
    tracing = start_tracing(cell, fixed_s) if traced else None
    rec = program.run_call(trainer, traffic, timed_rows)
    if tracing:
        tracing["stop"]()
    setup_s = rec["t_open"] - T_START
    if rec["window_starts"]:
        log("windows began at " + " ".join(
            f"{t - rec['t_open']:.2f}" for t in rec["window_starts"])
            + f" s; last ended at {rec['t_close'] - rec['t_open']:.2f} s")
    losses = [float(x) for x in rec["losses"]]
    mark = cell["loss_at_tokens"]
    loss_mark = window.loss_at_mark(losses, int(mark["mark_windows"]),
                                    int(mark.get("average_windows", 3)))
    failed = window.failed_windows(losses, n_windows)
    if not math.isfinite(loss_mark):
        failed += 1
    if rec["hub_updates"] is not None and rec["hub_updates"] != n_windows * workers:
        failed += abs(n_windows * workers - int(rec["hub_updates"]))
    metrics = {
        "tokens_per_s_per_chip": window.rate(n_windows * tok_per_window,
                                             rec["t_open"], rec["t_close"], chips),
        "loss_at_tokens": loss_mark,
        "setup_s": setup_s,
    }
    log(f"timed call: {n_windows} windows in {rec['t_close'] - rec['t_open']:.3f} s "
        f"(call {rec['t_return'] - rec['t_call']:.3f} s); "
        f"{metrics['tokens_per_s_per_chip']:.1f} tokens/s/chip")
    stats = [d.memory_stats() or {} for d in devices[:chips]]
    device = {"platform": devices[0].platform, "kind": devices[0].device_kind,
              "count": len(devices),
              "memory_peak_bytes": max(int(s.get("peak_bytes_in_use", 0)) for s in stats)}
    result = {"attempted": n_windows, "failed": failed}
    if traced:
        ctx = {"cell": cell, "cfg": cfg, "family": family, "traffic": traffic,
               "chips": chips, "batch": batch, "seq_len": seq_len,
               "peaks": peaks.device_peaks(devices[0].device_kind), "notes": [],
               "steps_per_program": int(traffic["steps_per_program"]),
               "spans": {"compile_s": compile_s, "train_call_fixed_s": fixed_s,
                         "feed_load_ms_per_window": 1e3 * rec["feed_s"] / n_windows,
                         "loss_at_mark": loss_mark if math.isfinite(loss_mark) else None},
               "flops_per_step": batch * seq_len * family.train_flops_per_token(
                   cfg, seq_len)["total"]}
        result["metrics"] = layer_metrics(ctx, tracing, rec, device, result)
    else:
        units = {m["name"]: m["unit"] for m in cell["end_to_end"]}
        result["metrics"] = {k: {"value": float(v), "unit": units[k]}
                             for k, v in metrics.items() if k in units}
    result["device"] = device

    # -- the program's state freed, then the plain reference ------------------
    del trainer, rec, timed_rows, su
    gc.collect()
    t_ref = time.perf_counter()
    ref = follow_reference(cell, seed, reference, call_data)
    numbers = check.compare(followed, ref)
    ok, compared = check.verdict(numbers, cell["check"]["limits"])
    log(f"reference followed {sum(calls)} windows in {time.perf_counter() - t_ref:.1f} s; "
        f"worst leaves {numbers['first_leaf']} / {numbers['change_leaf']}")
    result["correct"] = bool(ok and failed == 0)
    result["compared"] = compared
    return result


def main(argv=None, skip_device_check: bool = False, root: str = spec.ROOT) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    cell = spec.load_cell(args.workload, root)
    if skip_device_check:
        import jax

        devices = jax.devices()
        peaks.DEVICE_PEAKS.setdefault(devices[0].device_kind,
                                      peaks.DEVICE_PEAKS["TPU v5 lite"])
    else:
        devices = require_chips(int(cell["chips"]))
        place_compile_cache()
    result = run(cell, args.seed, args.seconds, bool(args.trace), devices)
    for name, c in result["compared"].items():
        print(f"compared {name} value={c['value']:.6g} limit={c['limit']:.6g}",
              file=sys.stderr, flush=True)
    ordered = {k: result[k] for k in ("correct", "attempted", "failed", "metrics",
                                      "device", "breakdown", "compared") if k in result}
    print(json.dumps(ordered), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
