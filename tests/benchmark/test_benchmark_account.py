"""``benchmark/readers/trace_account.py`` on the small recorded v5e trace
(``data/trace_small.json``: two runs of ``lm590m_sync``'s window program,
PR 24) with a hand-made account of its instructions, on hand-made rows, and
through ``obs.device_account`` on a hand-written module; and the seven metric
files that read through it."""

import json
import os
import sys

import pytest

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))
from bench_fixtures import ROOT  # noqa: E402

from benchmark.harness import spec, trace  # noqa: E402
from distkeras_tpu import observability as obs  # noqa: E402
from distkeras_tpu.observability.account import DeviceAccount  # noqa: E402

DATA = os.path.join(os.path.dirname(os.path.abspath(__file__)), "data")
with open(os.path.join(DATA, "trace_small.json")) as _f:
    ROWS = [tuple(r) for r in json.load(_f)]
with open(os.path.join(ROOT, "BENCHMARK.json")) as _f:
    BENCH = json.load(_f)
READER = spec._import(os.path.join(ROOT, "benchmark", "readers", "trace_account.py"),
                      "benchmark_reader_trace_account_under_test")
SYNC = ["lm590m_sync", "lm1b3_sync", "trinity_sync8k", "olmohybrid_sync8k", "kanana_sync8k"]
METRICS = {"fwd_device_share": ({"passes": ["forward"]}, SYNC),
           "remat_device_share": ({"passes": ["recompute"]}, SYNC[2:]),
           "bwd_device_share": ({"passes": ["backward"]}, SYNC),
           "ffn_device_share": ({"parts": ["ffn.dense"]}, SYNC),
           "vocab_device_share": ({"parts": ["lm.embed", "lm.head", "step.loss"]}, SYNC),
           "update_commit_device_share": ({"parts": ["step.update", "step.commit"]}, SYNC),
           "unscoped_device_share": ({"parts": ["none", "block.other"]}, SYNC)}
# what an op label of the recorded trace is taken to be, for the test's account
BY_LABEL = {"multiply_add_fusion": ("ffn.dense", "backward"),
            "multiply_reduce_fusion": ("attn.full", "backward"),
            "convert_reduce_fusion": ("attn.full", "forward"),
            "fusion": ("ffn.dense", "forward"),
            "mosaic:block_": ("attn.full", "recompute"),
            "subtract_add_fusion": ("step.commit", "other"),
            "convert_element_type": ("lm.head", "forward"),
            "copy_bitcast_fusion": ("step.update", "other"),
            "broadcast_in_dim": ("block.other", "forward")}


def names(rows):
    return {r[2].split(" = ", 1)[0].lstrip("%"): r[2] for r in rows if r[1] == "XLA Ops"}


def hand_account(rows, mixed=(), containers=("while.458",), lacking=()):
    table = {}
    for name, text in names(rows).items():
        cell = BY_LABEL.get(trace.op_label(text))
        if cell and name not in lacking and name not in containers:
            table[name] = cell
    return DeviceAccount(table, {n: (("ffn.dense", "step.update"), ("backward",), True) for n in mixed},
                         frozenset(containers), 12345)


def ctx_of(rows, cut=False):
    device = [r for r in rows if r[0].startswith("/device:")]
    lo, hi = min(r[3] for r in device), max(r[3] + r[4] for r in device)
    return {"trace": {"rows": rows, "lo": lo, "hi": hi, "cut": cut},
            "traffic": {"window_program": "jit_shard_fn"}, "steps_per_program": 5, "notes": []}


@pytest.fixture
def account(monkeypatch):
    """Stands in for the program's account; ``use(acct)`` sets what it says."""
    said = {}
    monkeypatch.setattr(obs, "device_account", lambda name: said.get(name), raising=False)

    def use(acct, name="jit_shard_fn"):
        said[name] = acct
        return acct

    return use


def leaf_ns(rows, leave_out=("while.458",)):
    return sum(r[4] for r in rows if r[1] == "XLA Ops"
               and r[2].split(" = ", 1)[0].lstrip("%") not in leave_out)


@pytest.mark.parametrize("partition", [
    [{"parts": ["attn.full"]}, {"parts": ["ffn.dense"]}, {"parts": ["lm.embed", "lm.head", "step.loss"]},
     {"parts": ["step.update", "step.commit"]}, {"parts": ["none", "block.other"]}],
    [{"passes": [p]} for p in ("forward", "recompute", "backward", "other")],
    [{"parts": ["ffn.dense"], "passes": ["forward"]}, {"parts": ["ffn.dense"], "passes": ["backward"]},
     {"parts": ["attn.full", "lm.head", "step.update", "step.commit", "none", "block.other"]}],
], ids=["parts", "passes", "both"])
def test_shares_of_a_partition_sum_to_100(account, partition):
    account(hand_account(ROWS))
    c = ctx_of(ROWS)
    shares = [READER.read(c, **args) for args in partition]
    assert all(s is not None and 0 < s < 100 for s in shares)
    assert sum(shares) == pytest.approx(100.0, abs=1e-9)
    assert READER.read(c) == pytest.approx(100.0)              # any part, any pass


def test_the_share_is_the_parts_leaf_time_over_all_leaf_time(account):
    account(hand_account(ROWS))
    ffn = sum(r[4] for r in ROWS if r[1] == "XLA Ops"
              and trace.op_label(r[2]) in ("multiply_add_fusion", "fusion"))
    got = READER.read(ctx_of(ROWS), parts=["ffn.dense"])
    # the %while (1.06 s a run, the whole program) is no leaf: counted, the
    # share would read under half of this
    assert got == pytest.approx(100.0 * ffn / leaf_ns(ROWS), rel=1e-12)
    assert 100.0 * ffn / (leaf_ns(ROWS, ()) ) < 0.5 * got


def row(text, start, ns, line="XLA Ops", plane="/device:TPU:0"):
    return (plane, line, text, float(start), float(ns))


def test_a_conditional_is_left_out_by_its_opcode_and_its_body_counts(account):
    """``lax.cond`` makes ``%cond.<n>``, which ``trace.CONTAINERS`` does not
    know by name: the event covers its body's events."""
    rows = [row("jit_shard_fn(1)", 0, 1000, line="XLA Modules"),
            row("%cond.3 = (f32[8]{0}, s32[]) conditional(s32[] %p, f32[8]{0} %a), "
                "branch_computations={%b0, %b1}", 100, 600),
            row("%fusion.5 = f32[8]{0} fusion(f32[8]{0} %a), kind=kLoop", 150, 400),
            row("%fusion.6 = f32[8]{0} fusion(f32[8]{0} %b), kind=kLoop", 700, 200),
            row("%call.2 = f32[8]{0} call(f32[8]{0} %a), to_apply=%f", 900, 50),
            row("%copy.9 = f32[8]{0} copy(f32[8]{0} %a)", 900, 50)]
    account(DeviceAccount({"fusion.5": ("moe.experts", "forward"), "fusion.6": ("ffn.dense", "forward"),
                           "cond.3": ("moe.dispatch", "forward"), "copy.9": ("none", "other")},
                          {}, frozenset(), 1))
    c = ctx_of(rows)
    assert not rows[1][2].startswith(trace.CONTAINERS)         # the old base counts it
    assert READER.read(c, parts=["moe.experts"]) == pytest.approx(100.0 * 400 / 650)
    assert READER.read(c, parts=["moe.dispatch"]) == 0.0
    assert READER.read(c, parts=["none"]) == pytest.approx(100.0 * 50 / 650)


def test_an_event_text_cut_short_of_its_opcode_goes_by_the_programs_word(account):
    """The recorded ``%while.458``'s text ends inside its result's shape."""
    cut_short = [r for r in ROWS if r[2].startswith("%while.458")]
    assert cut_short and all(" while(" not in r[2] for r in cut_short)
    account(hand_account(ROWS, containers=("while.458",)))
    with_word = READER.read(ctx_of(ROWS), parts=["ffn.dense"])
    account(hand_account(ROWS, containers=()))
    without = READER.read(ctx_of(ROWS), parts=["ffn.dense"])
    assert without < 0.5 * with_word


def test_an_instruction_the_table_lacks_is_none_and_the_note_says_how_much(account):
    lacking = {n for n, t in names(ROWS).items() if trace.op_label(t) == "convert_reduce_fusion"}
    ns = sum(r[4] for r in ROWS if r[1] == "XLA Ops"
             and r[2].split(" = ", 1)[0].lstrip("%") in lacking)
    account(hand_account(ROWS))
    before = READER.read(ctx_of(ROWS), parts=["none"])
    account(hand_account(ROWS, lacking=lacking))
    c = ctx_of(ROWS)
    after = READER.read(c, parts=["none"], passes=["other"])
    share = 100.0 * ns / leaf_ns(ROWS)
    assert share > 5 and after - before == pytest.approx(share)
    assert "instructions the table lacks %.2f%%" % share in c["notes"][-1]


def test_no_account_reads_none(account, monkeypatch):
    c = ctx_of(ROWS)
    assert READER.read(c, parts=["ffn.dense"]) is None           # nothing noted
    assert READER.read(dict(c, trace=None), parts=["ffn.dense"]) is None
    monkeypatch.delattr(obs, "device_account")                   # the parent of PR 36
    assert READER.read(c, passes=["forward"]) is None and c["notes"] == []
    account(hand_account(ROWS))                                  # setattr again
    none_in_stretch = dict(c, trace=dict(c["trace"], lo=-2.0, hi=-1.0))
    assert READER.read(none_in_stretch, passes=["forward"]) is None


def test_the_table_goes_to_the_notes_once_a_run(account):
    mixed = [n for n, t in names(ROWS).items() if trace.op_label(t) == "multiply_add_fusion"]
    account(hand_account(ROWS, mixed=mixed))
    c = ctx_of(ROWS)
    for args in METRICS.values():
        READER.read(c, **args[0])
    assert len(c["notes"]) == 2 and c["notes"][0].startswith("device_account: ")
    assert "12345 bytes" in c["notes"][0]
    note = c["notes"][1]
    lines = note.splitlines()
    assert lines[0] == "device account, ms a step over 10.00 steps (part x pass):"
    head = lines[1].split()
    assert head == ["part", "forward", "recompute", "backward", "other", "all", "%"]
    rows = {ln.split()[0]: [float(x) for x in ln.split()[1:]] for ln in lines[2:]
            if len(ln.split()) == 7 and not ln.lstrip().startswith(("kernel", "in ", "instr"))}
    assert set(rows) == {"attn.full", "ffn.dense", "lm.head", "step.commit", "step.update",
                         "block.other", "all"}
    # the cells are ms a step: they sum to the leaf time of the two runs over ten steps
    assert rows["all"][4] == pytest.approx(leaf_ns(ROWS) / 1e6 / 10, abs=0.01)
    assert rows["all"][5] == pytest.approx(100.0)
    assert rows["ffn.dense"][1] == 0.0 and rows["ffn.dense"][0] > 0 and rows["ffn.dense"][2] > 0
    ma = sum(r[4] for r in ROWS if trace.op_label(r[2]) == "multiply_add_fusion")
    share = 100.0 * ma / leaf_ns(ROWS)
    assert ("of more than one part %.2f%% (%.2f%% counted as their matmul, 0.00%% as their root)"
            % (share, share)) in note
    assert "of more than one pass 0.00%" in note
    assert "largest unscoped (none, block.other): broadcast_in_dim" in note


def test_steps_in_a_cut_stretch_count_the_part_of_the_program_that_is_there():
    c = ctx_of(ROWS)
    assert READER.steps_in_stretch(c) == pytest.approx(10.0)
    runs = sorted(trace.module_runs(ROWS, "jit_shard_fn"), key=lambda r: r[1])
    # the profiler stopped 40% into the second run: its event is cut short
    cut_rows = [r if not (r[1] == "XLA Modules" and r[3] == runs[1][1])
                else (r[0], r[1], r[2], r[3], 0.4 * r[4]) for r in ROWS]
    c = ctx_of(cut_rows, cut=True)
    c["trace"]["hi"] = runs[1][1] + 0.4 * runs[1][2]
    assert READER.steps_in_stretch(c) == pytest.approx(5 + 0.4 * 5 * runs[1][2] / runs[0][2])
    assert READER.steps_in_stretch(dict(c, traffic={"window_program": "jit_window"})) == 0.0


def test_through_the_programs_own_account_of_a_noted_module():
    """No stand-in: ``obs.note_program`` -> ``obs.device_account`` -> the reader."""
    step = "jit(shard_fn)/while/body/closed_call/while/body/closed_call/"
    meta = lambda op: 'metadata={op_type="x" op_name="%s"}' % op
    text = "\n".join([
        "ENTRY %main (a: f32[8]) -> f32[8] {",
        "  %a = f32[8]{0} parameter(0)",
        "  %fusion.5 = f32[8]{0} fusion(%a), kind=kLoop, calls=%f5, "
        + meta(step + "jvp(TransformerLM)/TransformerLM._trunk/block_0.<lambda>/block_0/ffn.dense/up"),
        "  %fusion.6 = f32[8]{0} fusion(%fusion.5), kind=kLoop, calls=%f6, "
        + meta(step + "transpose(jvp(TransformerLM))/TransformerLM._trunk/jvp(TransformerLM)/"
               "TransformerLM._trunk/checkpoint/rematted_computation/block_0.<lambda>/block_0/"
               "ffn.dense/up"),
        "  %fusion.7 = f32[8]{0} fusion(%fusion.6), kind=kLoop, calls=%f7, "
        + meta(step + "step.update/add"),
        "  ROOT %while.1 = (f32[8]{0}) while(%fusion.7), condition=%c, body=%b",
        "}"])
    rows = [row("jit_shard_fn(1)", 0, 1000, line="XLA Modules"),
            row("%while.1 = (f32[8]{0}) while((f32[8]{0}) %t), condition=%c, body=%b", 0, 1000),
            row("%fusion.5 = f32[8]{0} fusion(f32[8]{0} %a), kind=kLoop", 0, 500),
            row("%fusion.6 = f32[8]{0} fusion(f32[8]{0} %a), kind=kLoop", 500, 300),
            row("%fusion.7 = f32[8]{0} fusion(f32[8]{0} %a), kind=kLoop", 800, 100),
            row("%fusion.8 = f32[8]{0} fusion(f32[8]{0} %a), kind=kLoop", 900, 100)]
    obs.note_program("jit_shard_fn", text)
    try:
        c = ctx_of(rows)
        got = {m: READER.read(c, **args) for m, (args, _) in METRICS.items()}
    finally:
        obs._PROGRAMS.pop("jit_shard_fn", None)
        obs._ACCOUNTS.pop("jit_shard_fn", None)
    assert got == {"fwd_device_share": 50.0, "remat_device_share": 30.0, "bwd_device_share": 0.0,
                   "ffn_device_share": 80.0, "vocab_device_share": 0.0,
                   "update_commit_device_share": 10.0, "unscoped_device_share": 10.0}


@pytest.mark.parametrize("metric", sorted(METRICS))
def test_metric_file_loads_and_its_entry_lists_accepted_cells(metric):
    args, cells = METRICS[metric]
    with open(os.path.join(ROOT, "benchmark", "metrics", metric + ".json")) as f:
        body = json.load(f)
    assert body["name"] == metric and body["reader"] == "trace_account" and body["args"] == args
    assert "step_device_ms" in body["what"]
    if metric in ("fwd_device_share", "bwd_device_share"):
        assert "direction means nothing alone" in body["what"]
    read, read_args = spec.load_reader(metric, ROOT)
    assert read_args == args and read({"trace": None}, **read_args) is None
    entry = [m for m in BENCH["per_layer"] if m["name"] == metric]
    assert entry == [{"name": metric, "unit": "%", "better": "lower", "source": "device_trace",
                      "layer": "model step", "moves": "tokens_per_s_per_chip", "workloads": cells}]
    accepted = [w["name"] for w in BENCH["workloads"]]
    assert set(cells) <= set(accepted) and "lm590m_async" not in cells
    # the cell's own list, as run.py loads it, holds the metric
    for cell in cells:
        assert metric in [m["name"] for m in spec.load_cell(cell, ROOT)["per_layer"]]


def test_the_seven_entries_were_appended_and_nothing_else_moved():
    names_now = [m["name"] for m in BENCH["per_layer"]]
    assert names_now[-7:] == list(METRICS)
    assert names_now[-8] == "async_commit_streamed_share"        # PR 35's, where it was
    assert len(names_now) == len(set(names_now))
