"""The arithmetic from logs to end-to-end numbers. No JAX, no program code.

Two logs meet here. The load generator (its own process) reports, per
batch: when it was due, sent and seen resolved at the client, and the
tick the leader committed it at. The leader's completion probe reports,
per dispatched window: the ticks it covers and the host time at which
the device finished it. Both clocks are CLOCK_MONOTONIC on one machine.

A batch is *done* at the later of its ack at the client and the device
completion of the window that holds it. An acknowledgement alone is a
durability point: nothing on the ack path waits for the device, so a
latency or a rate that ended there would leave the chip out.
"""

from __future__ import annotations

import bisect
import math
from typing import Dict, List, Optional, Sequence

__all__ = ["percentile", "join", "done_inside", "completion_rate",
           "freshness_ms", "Joined"]


def percentile(xs: Sequence[float], q: float) -> float:
    """Linear-interpolated percentile (numpy's default rule), written
    out so the yardstick does not move with a library. Empty input is an
    error: a metric over nothing is not a number."""
    s = sorted(float(x) for x in xs)
    if not s:
        raise ValueError("percentile of an empty sample")
    if len(s) == 1:
        return s[0]
    pos = (len(s) - 1) * (q / 100.0)
    lo = math.floor(pos)
    hi = min(lo + 1, len(s) - 1)
    return s[lo] + (s[hi] - s[lo]) * (pos - lo)


class Joined:
    """The two logs joined: per batch its window and done time, per
    window its rows and done time."""

    def __init__(self, batches: List[dict], windows: List[dict]):
        self.batches = batches
        self.windows = windows


def join(batches: Sequence[dict], windows: Sequence[dict]) -> Joined:
    """Attach each acked batch to the window whose ticks cover its
    committed tick: ``tick_lo < tick <= tick_hi``. Adds ``window`` and
    ``done`` to the batch and ``rows`` / ``n_batches`` / ``done`` to the
    window. A batch without a tick (never applied) or whose window never
    completed gets ``done = None``: it counts as failed, never as fast.
    """
    wins = sorted((dict(w) for w in windows), key=lambda w: w["tick_hi"])
    for w in wins:
        w["rows"] = 0
        w["n_batches"] = 0
        w["last_ack"] = None
    his = [w["tick_hi"] for w in wins]
    out = []
    for b in batches:
        b = dict(b)
        b["window"] = None
        b["done"] = None
        tick = b.get("tick")
        if tick is not None and b.get("ack") is not None:
            lo = bisect.bisect_left(his, tick)   # first tick_hi >= tick
            if lo < len(wins) and wins[lo]["tick_lo"] < tick:
                w = wins[lo]
                b["window"] = w["ix"]
                w["rows"] += int(b["rows"])
                w["n_batches"] += 1
                w["last_ack"] = (b["ack"] if w["last_ack"] is None
                                 else max(w["last_ack"], b["ack"]))
                if w.get("ready") is not None:
                    b["done"] = max(b["ack"], w["ready"])
        out.append(b)
    for w in wins:
        w["done"] = (None if w.get("ready") is None or w["last_ack"] is None
                     else max(w["ready"], w["last_ack"]))
    return Joined(out, wins)


def done_inside(j: Joined, t_open: float, t_close: float) -> List[dict]:
    """The windows that hold batches and completed (device and acks)
    inside ``[t_open, t_close]``: what a rate, or a count of what the
    window drained, is taken over."""
    return [w for w in j.windows if w["done"] is not None
            and w["n_batches"] and t_open <= w["done"] <= t_close]


def completion_rate(j: Joined, t_open: float, t_close: float
                    ) -> Optional[dict]:
    """Rows per second between the first and the last window completion
    inside ``[t_open, t_close]``: the rows of the windows done in
    ``(t_first, t_last]`` over ``t_last - t_first``. Taken between
    completions so that a window of seconds does not quantise the run
    into a handful of steps; the first completion only starts the clock.
    What lies outside the two completions does not move the rate, so the
    caller holds ``edge_s``, the longer of the two stretches between a
    completion and the window's nearer end, to ``median_gap_s``, the
    run's own cadence. None when fewer than two windows completed
    inside."""
    done = sorted((w["done"], w["rows"], w["ix"])
                  for w in done_inside(j, t_open, t_close))
    if len(done) < 2:
        return None
    t_first, t_last = done[0][0], done[-1][0]
    if t_last <= t_first:
        return None
    rows = sum(r for t, r, _ in done if t > t_first)
    gaps = [b[0] - a[0] for a, b in zip(done, done[1:])]
    return {"rows_per_s": rows / (t_last - t_first), "rows": rows,
            "span_s": t_last - t_first, "windows": len(done) - 1,
            "t_first": t_first, "t_last": t_last,
            "median_gap_s": percentile(gaps, 50),
            "edge_s": max(t_first - t_open, t_close - t_last)}


def freshness_ms(j: Joined, t_open: float, t_close: float) -> List[float]:
    """Due-to-done latency, in ms, of every batch due inside the window
    that was applied. Timed from the *due* time, so a stall is charged
    to every request that had to wait behind it."""
    return [1e3 * (b["done"] - b["due"]) for b in j.batches
            if b["done"] is not None and t_open <= b["due"] < t_close]
