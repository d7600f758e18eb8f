"""The trace reduction on one small trace recorded on a v5e (the first
window program of a three-window ADAG call at cerebras-gpt-590m, PR 24:
module and container events, every Pallas call, ops over 0.2 ms, host
events over 0.05 ms), and the kernels by name on the first training step of
a traced ``lm590m_sync`` run (``data/trace_kernels_small.json``, PR 27: the
trace has carried the ``pallas_call``s' names since PR 25)."""

import json
import os

import pytest

from benchmark.harness import peaks, spec, trace

DATA = os.path.join(os.path.dirname(os.path.abspath(__file__)), "data")
with open(os.path.join(DATA, "trace_small.json")) as _f:
    ROWS = [tuple(r) for r in json.load(_f)]
with open(os.path.join(DATA, "trace_kernels_small.json")) as _f:
    STEP = [tuple(r) for r in json.load(_f)["rows"]]
with open(os.path.join(spec.ROOT, "benchmark", "configs", "cerebras-gpt-590m.json")) as _f:
    CFG = json.load(_f)
V5E = peaks.device_peaks("TPU v5 lite")


def kernel_ctx(rows, **more):
    """What ``run.py`` hands a reader, for a batch of 4 rows of 2,048."""
    return dict({"trace": {"rows": rows}, "cfg": CFG, "family": spec.load_family(CFG),
                 "batch": 4, "seq_len": 2048, "peaks": V5E, "notes": []}, **more)


def mosaic_row(name, operands, ns, start=0.0):
    text = (f"%{name} = bf16[4,12,2048,128] custom-call("
            + ", ".join(f"bf16[4,12,2048,128] %p.{i}" for i in range(operands))
            + '), custom_call_target="tpu_custom_call"')
    return ("/device:TPU:0", "XLA Ops", text, start, ns)


def test_module_time():
    runs = trace.module_runs(ROWS, "jit_shard_fn")
    assert len(runs) == 2 and trace.device_planes(ROWS) == ["/device:TPU:0"]
    # one window program = 5 steps: 1.0738 s -> 214.8 ms a step
    assert runs[0][2] / 5 / 1e6 == pytest.approx(214.77, abs=0.01)
    assert trace.module_runs(ROWS, "jit_window") == []


def test_kernel_time_by_name():
    calls = trace.mosaic_calls(STEP)
    fwd = [d for n, d in calls if n.startswith("_fwd_kernel.")]
    bwd = [d for n, d in calls if n.startswith("_bwd_fused_kernel.")]
    # one step at 18 layers: 18 + 18 calls, 0.594 ms forward, 1.35 ms backward
    assert len(fwd) == 18 and len(bwd) == 18 and len(calls) == 36
    assert sum(fwd) / 18 / 1e6 == pytest.approx(0.594, abs=0.005)
    assert sum(bwd) / 18 / 1e6 == pytest.approx(1.35, abs=0.02)
    # the trace recorded before the kernels had names holds none of them
    assert len(trace.mosaic_calls(ROWS)) == 180
    assert not [n for n, _ in trace.mosaic_calls(ROWS) if "kernel" in n]
    # the readers, through the metric files, on the named step: the shares
    # the chip's runs read (ledger, PR 26: 44.0 and 48.6)
    shares = {}
    for metric in ("flash_fwd_roofline", "flash_bwd_roofline"):
        read, args = spec.load_reader(metric)
        c = kernel_ctx(STEP)
        shares[metric] = read(c, **args)
        assert c["notes"] == [f"{args['kernel']}: 18 calls, compute-bound"]
        assert read(kernel_ctx(ROWS), **args) is None        # nothing to read
        assert read({"trace": None}, **args) is None
    assert shares["flash_fwd_roofline"] == pytest.approx(44.0, abs=0.2)
    assert shares["flash_bwd_roofline"] == pytest.approx(48.6, abs=0.3)


def test_kernel_reader_goes_by_the_name_not_the_operand_count():
    """A 3-operand Mosaic call of another name (a grouped matmul: x, weights,
    group sizes) is not the flash forward; a kernel the family does not know
    raises; ``%<kernel>`` and ``%<kernel>.<n>`` are the kernel, a longer
    name that starts alike is not."""
    read, args = spec.load_reader("flash_fwd_roofline")
    rows = [mosaic_row("_fwd_kernel.7", 3, 6e5), mosaic_row("_fwd_kernel", 3, 4e5),
            mosaic_row("_bwd_fused_kernel.9", 6, 14e5),
            mosaic_row("_grouped_matmul.3", 3, 9e5), mosaic_row("_fwd_kernel_v2.1", 3, 9e5),
            ("/device:TPU:0", "XLA Ops", "%fusion.7 = bf16[4] fusion(bf16[4] %p.0)", 0.0, 5e5)]
    assert sorted(trace.mosaic_calls(rows)) == [
        ("_bwd_fused_kernel.9", 14e5), ("_fwd_kernel", 4e5), ("_fwd_kernel.7", 6e5),
        ("_fwd_kernel_v2.1", 9e5), ("_grouped_matmul.3", 9e5)]
    work = peaks.flash_counts("fwd", 4, 12, 2048, 128)
    want = peaks.roofline_share(2 * work["flops"], 2 * work["bytes"], 1e-3, V5E)["share"]
    assert read(kernel_ctx(rows), **args) == pytest.approx(want)
    bwd, bwd_args = spec.load_reader("flash_bwd_roofline")
    work = peaks.flash_counts("bwd", 4, 12, 2048, 128)
    assert bwd(kernel_ctx(rows), **bwd_args) == pytest.approx(
        peaks.roofline_share(work["flops"], work["bytes"], 14e-4, V5E)["share"])
    with pytest.raises(KeyError, match="_grouped_matmul"):
        read(kernel_ctx(rows), kernel="_grouped_matmul")
    assert read(kernel_ctx(rows), kernel="_no_such_kernel") is None   # no call, no work


def test_a_program_the_profilers_stop_cut_is_left_out_of_the_step_time():
    """Five steps a program, 1,000 ms each run; the last run of a cut trace
    reads 400 ms and would pull the step from 200 to 176 ms."""
    read, args = spec.load_reader("step_device_ms")
    mfu, mfu_args = spec.load_reader("step_mfu")
    module = ("/device:TPU:0", "XLA Modules", "jit_shard_fn(1)")
    rows = [module + (1100e6 * i, 1000e6) for i in range(4)] + [
        module + (4400e6, 400e6), ("/device:TPU:0", "XLA Modules", "jit_other(2)", 0.0, 1e6)]
    c = {"trace": {"rows": rows, "cut": True}, "traffic": {"window_program": "jit_shard_fn"},
         "steps_per_program": 5, "flops_per_step": 19.7e12, "peaks": V5E}
    assert read(c, **args) == pytest.approx(200.0)
    assert mfu(c, **mfu_args) == pytest.approx(50.0)          # 19.7 TFLOP in 0.2 s of 197
    whole = dict(c, trace={"rows": rows, "cut": False})       # a trace that was not cut
    assert read(whole, **args) == pytest.approx(4400 / 25)
    only = dict(c, trace={"rows": rows[4:], "cut": True})     # nothing but the cut run
    assert read(only, **args) is None and mfu(only, **mfu_args) is None


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
