"""The trace reduction on one small trace recorded on a v5e (the first
window program of a three-window ADAG call at cerebras-gpt-590m, PR 24:
module and container events, every Pallas call, ops over 0.2 ms, host
events over 0.05 ms)."""

import json
import os

import pytest

from benchmark.harness import peaks, trace

with open(os.path.join(os.path.dirname(os.path.abspath(__file__)),
                       "data", "trace_small.json")) as _f:
    ROWS = [tuple(r) for r in json.load(_f)]


def test_module_time():
    runs = trace.module_runs(ROWS, "jit_shard_fn")
    assert len(runs) == 2 and trace.device_planes(ROWS) == ["/device:TPU:0"]
    # one window program = 5 steps: 1.0738 s -> 214.8 ms a step
    assert runs[0][2] / 5 / 1e6 == pytest.approx(214.77, abs=0.01)
    assert trace.module_runs(ROWS, "jit_window") == []


def test_kernel_time_by_operand_count():
    calls = trace.mosaic_calls(ROWS)
    fwd = [d for n, d in calls if n == 3]
    bwd = [d for n, d in calls if n >= 5]
    assert len(fwd) >= 90 and len(bwd) >= 90 and len(fwd) + len(bwd) == len(calls)
    # 18 layers x 5 steps of the first window: 0.594 ms forward, 1.35 ms backward
    assert sum(fwd[:90]) / 90 / 1e6 == pytest.approx(0.594, abs=0.005)
    assert sum(bwd[:90]) / 90 / 1e6 == pytest.approx(1.35, abs=0.02)
    c = peaks.flash_counts("fwd", 4, 12, 2048, 128)
    share = peaks.roofline_share(c["flops"], c["bytes"], sum(fwd[:90]) / 90 / 1e9,
                                 peaks.device_peaks("TPU v5 lite"))
    assert share["bound"] == "compute" and 40 < share["share"] < 50


def test_idle_share_and_gap_naming():
    bw = trace.busy_and_window(ROWS)
    assert bw["planes"] == 1 and bw["busy_s"] < bw["window_s"]
    # one 204 ms gap between two window programs
    assert bw["idle_share"] == pytest.approx(
        100 * (1 - bw["busy_s"] / bw["window_s"]))
    gaps = trace.idle_gaps(ROWS, bw["lo"], bw["hi"])
    assert gaps[0][0].startswith("chip0:jit_shard_fn->jit_shard_fn:")
    assert gaps[0][1] == pytest.approx(0.2038, abs=0.001)
    # a window that opens before the first device op: the lead-in is a gap too
    early = trace.idle_gaps(ROWS, bw["lo"] - 5e8, bw["hi"])
    assert any(name.startswith("chip0:window_start->jit_shard_fn") and
               secs == pytest.approx(0.5, abs=0.01) for name, secs in early)
    assert trace.busy_and_window(ROWS, bw["lo"] - 5e8, bw["hi"])["idle_share"] > bw["idle_share"]


def test_top_ops_leave_containers_out_and_label_kernels():
    top = dict(trace.top_device_ops(ROWS))
    assert "while" not in top and "mosaic:block" in "".join(top)
    assert trace.op_label('%fusion.2844 = (bf16[4]) fusion(...)') == "fusion"
    assert trace.union([(0, 2), (1, 3), (5, 6)]) == [(0, 3), (5, 6)]
    assert trace.busy_and_window([])["idle_share"] is None
