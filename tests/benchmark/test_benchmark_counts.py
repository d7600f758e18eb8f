"""The table of peaks and the FLOP and byte counts, against hand-worked
numbers, and the whole-window arithmetic on a fake trainer."""

import json
import math
import os

import pytest

from benchmark.harness import peaks, spec, window


def _cfg(name):
    with open(os.path.join(spec.ROOT, "benchmark", "configs", name + ".json")) as f:
        return json.load(f)


def test_flops_per_token_of_cerebras_gpt_590m():
    cfg = _cfg("cerebras-gpt-590m")
    ref, fam = spec.load_reference(cfg), spec.load_family(cfg)
    # by hand: 18 layers x 12 d^2 (d = 1536) + the tied unembedding 50257 x d
    by_hand = 18 * 12 * 1536 ** 2 + 50257 * 1536
    assert ref.matmul_params(cfg) == by_hand == 586_802_688
    f = fam.train_flops_per_token(cfg, 2048)
    assert f == peaks.train_flops_per_token(by_hand, 18, 2048, 1536)
    assert f["dense"] / 1e9 == pytest.approx(3.52, abs=0.005)
    assert f["attention"] / 1e9 == pytest.approx(0.34, abs=0.005)
    assert f["total"] == f["dense"] + f["attention"]
    assert f["total"] / 1e9 == pytest.approx(3.86, abs=0.005)
    # a shorter job needs fewer score pairs a token, the same parameters
    short = fam.train_flops_per_token(cfg, 1024)
    assert short["dense"] == f["dense"] and short["attention"] == f["attention"] / 2


def test_flops_per_token_of_the_1b3_cut():
    cfg = _cfg("cerebras-gpt-1.3b")
    ref, fam = spec.load_reference(cfg), spec.load_family(cfg)
    assert ref.matmul_params(cfg) == cfg["n_layer"] * 12 * 2048 ** 2 + 50257 * 2048
    assert cfg["published"]["n_layer"] == 24 and cfg["reduced"] == ["n_layer"]
    assert fam.train_flops_per_token(cfg, 2048)["total"] / 1e9 == pytest.approx(3.89, abs=0.005)


@pytest.mark.parametrize("config,heads", [("cerebras-gpt-590m", 12), ("cerebras-gpt-1.3b", 16)])
def test_family_kernel_work_and_shapes_of_the_gpt_configurations(config, heads):
    cfg = _cfg(config)
    fam = spec.load_family(cfg)
    for kernel, direction in (("_fwd_kernel", "fwd"), ("_bwd_fused_kernel", "bwd")):
        assert fam.kernel_work(cfg, kernel, 4, 2048) == peaks.flash_counts(
            direction, 4, heads, 2048, 128)
    with pytest.raises(KeyError, match="_grouped_matmul"):
        fam.kernel_work(cfg, "_grouped_matmul", 4, 2048)
    # no traffic file of the benchmark names a length: the configuration's own
    assert fam.shapes(cfg, {"data": {}}) == {"seq_len": 2048, "vocab": 50257}
    assert fam.shapes(cfg, {"data": {"seq_len": 512}})["seq_len"] == 512
    with pytest.raises(ValueError):
        fam.shapes(cfg, {"data": {"seq_len": 4096}})
    spec_ = fam.model_spec(cfg)
    assert spec_.config["num_heads"] == heads and spec_.config["positional"] == "learned"
    with pytest.raises(ValueError, match="4 \\* d_model"):
        fam.model_spec(dict(cfg, n_inner=3 * cfg["n_embd"]))


@pytest.mark.parametrize("direction,flops,nbytes", [
    # B 4, H 12, L 2048, D 128: one head's QK^T is 2*L*L*D = 1.0737 GFLOP
    ("fwd", 4 * 12 * 2 * 2 * 2048 ** 2 * 128 / 2, 4 * 4 * 12 * 2048 * 128 * 2),
    ("bwd", 2.5 * 4 * 12 * 2 * 2 * 2048 ** 2 * 128 / 2, 8 * 4 * 12 * 2048 * 128 * 2),
])
def test_flash_counts(direction, flops, nbytes):
    c = peaks.flash_counts(direction, 4, 12, 2048, 128)
    assert c["flops"] == flops and c["bytes"] == nbytes


def test_roofline_share_names_its_bound_and_unknown_kind_raises():
    p = peaks.device_peaks("TPU v5 lite")
    assert p == {"bf16_flops": 197e12, "hbm_bytes_per_s": 819e9, "hbm_bytes": 16e9}
    r = peaks.roofline_share(197e12, 1.0, 2.0, p)      # 1 s of FLOPs in 2 s
    assert r == {"share": 50.0, "bound": "compute"}
    assert peaks.roofline_share(1.0, 819e9, 4.0, p) == {"share": 25.0, "bound": "memory"}
    with pytest.raises(ValueError):
        peaks.device_peaks("TPU v9 imaginary")


class FakeClockTrainer:
    """Windows of 9 s of fake clock; 3.5 s before the first and 2 s after
    the last, once a call."""

    def __init__(self):
        self.now = 100.0

    def train(self, n_windows):
        t_call = self.now
        self.now += 3.5
        t_open = self.now
        self.now += 9.0 * n_windows
        t_close = self.now
        self.now += 2.0
        return {"t_call": t_call, "t_open": t_open, "t_close": t_close,
                "t_return": self.now}


@pytest.mark.parametrize("seconds,want", [(10, 2), (45, 5), (9, 1), (46, 6), (0.5, 1)])
def test_whole_windows_cannot_be_quantised_by_the_clock(seconds, want):
    fake = FakeClockTrainer()
    cal = fake.train(2)
    wall = (cal["t_close"] - cal["t_open"]) / 2
    n = window.windows_for(seconds, wall)
    assert n == want
    rec = fake.train(n)
    tokens = n * 40960
    # all tokens over all the window's wall time: the same for any --seconds
    assert window.rate(tokens, rec["t_open"], rec["t_close"], 1) == pytest.approx(40960 / 9)
    assert window.rate(tokens, rec["t_open"], rec["t_close"], 4) == pytest.approx(40960 / 36)
    # what a call does before its first and after its last window is outside
    assert (rec["t_return"] - rec["t_call"]) - (rec["t_close"] - rec["t_open"]) == pytest.approx(5.5)


def test_loss_at_mark_and_failed_windows():
    losses = [9.8125, 8.5625, 8.3125, 8.125, 7.84375]
    assert window.loss_at_mark(losses, 2, 3) == pytest.approx((9.8125 + 8.5625 + 8.3125) / 3)
    assert window.loss_at_mark(losses, 1, 3) == pytest.approx((9.8125 + 8.5625) / 2)
    assert window.loss_at_mark(losses, 5, 1) == 7.84375
    assert math.isnan(window.loss_at_mark(losses, 6, 3))     # mark not reached
    assert window.failed_windows(losses, 5) == 0
    assert window.failed_windows(losses + [float("nan")], 7) == 2
    assert window.tokens_per_window(
        {"constructor": {"communication_window": 5, "batch_size": 4}}, 2048, 1) == 40960
