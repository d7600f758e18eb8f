"""Whole-window arithmetic: how many windows the timed call gets, its
rate, and the loss at the cell's token mark.  Pure Python."""

from __future__ import annotations

import math
from typing import Any, Dict, Sequence


def windows_for(seconds: float, window_wall_s: float) -> int:
    """The smallest whole number of windows whose stated wall reaches
    ``seconds`` (at least one).  ``window_wall_s`` is the cell's own figure
    (``1 / windows_per_second`` in its file, measured once on the chip), not
    a reading of this run: a run-to-run reading would let the count flip
    between runs, and with it the share of a call's lead-in in the rate."""
    if window_wall_s <= 0:
        raise ValueError(f"window wall {window_wall_s} s")
    return max(1, math.ceil(seconds / window_wall_s - 1e-9))


def tokens_per_window(traffic: Dict[str, Any], seq_len: int, workers: int) -> int:
    c = traffic["constructor"]
    return int(c["communication_window"]) * int(c["batch_size"]) * seq_len * workers


def rate(tokens: int, t_open: float, t_close: float, chips: int) -> float:
    """All tokens of the call's windows over all the wall time from its
    first window's start to its last commit applied, over chips."""
    return tokens / (t_close - t_open) / chips


def loss_at_mark(losses: Sequence[float], mark_windows: int,
                 average_windows: int = 3) -> float:
    """The training loss when the call has trained ``mark_windows`` windows:
    the float64 mean of the recorded window losses around the mark (the
    program hands back bfloat16-rounded values, 0.06 nats apart at 8).
    ``nan`` where the call never reached the mark."""
    if len(losses) < mark_windows:
        return math.nan
    half = average_windows // 2
    lo = max(mark_windows - 1 - half, 0)
    hi = min(mark_windows - 1 + half + 1, len(losses))
    vals = [float(x) for x in losses[lo:hi]]
    return sum(vals) / len(vals)


def failed_windows(losses: Sequence[float], attempted: int) -> int:
    """Windows that did not come back, or came back with a loss that is
    not finite."""
    good = sum(1 for x in losses[:attempted] if math.isfinite(float(x)))
    return attempted - good
