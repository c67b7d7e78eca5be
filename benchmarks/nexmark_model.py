"""The NEXmark joins' arithmetic, for their per-layer readers: what the
program's own counters (``reflow_tpu.executors.lowerings.OP_COUNTERS``,
read from the ``window_device`` spans) say the two joins and the
maximum did between two windows, how many rows the join lowering in
force touches a tick, and the bytes a tick cannot avoid. Peaks come from
the table ``knn_model`` holds. No JAX outside ``knn_model.device_kind``.

Every function that reads a run returns ``None`` on a program whose
spans carry no such counters, as the parent of PR 31 has none: the
reader then leaves its metric out of the line.
"""

from __future__ import annotations

from typing import Dict, Optional

import knn_model
import pump_spans as ps
from common import bucket_capacity

#: the graph's counting nodes (``reflow_tpu/workloads/nexmark.py``) and
#: their counters, in the order the spans carry them
JOINS = ("q3_join", "q4_join")
MAXIMUM = "q4_max"
JOIN_COUNTERS = ("pairs", "late_pairs", "arena_rows", "index_rebuilds",
                 "compactions", "probe_steps")
MAX_COUNTERS = ("touched", "evicted")

#: bytes of one row as the device holds it
ROW_BYTES = 25 * 4 + 4 + 4           # int32[25] + int32 key + int32 weight
PAIR_BYTES = 3 * 4 + 4               # the matched table or arena row + weight
ARENA_ROW_BYTES = 4 + 2 * 4 + 4 + 2 * 4   # key, value, weight, index entry


def _counted(run):
    """``(done, ticks, {node: [counters]})`` of every ``window_device``
    span that carries the joins' counters, in order of completion."""
    out = []
    for s in run.spans:
        c = s["args"].get("counters") if s["name"] == "window_device" \
            else None
        if c and all(n in c for n in JOINS + (MAXIMUM,)):
            out.append((s["t1"], int(s["args"].get("ticks", 0)), c))
    return sorted(out, key=lambda x: x[0])


def moved(run, t0: float, t1: float) -> Optional[Dict[str, float]]:
    """By how much the counters moved between the last window the device
    finished by ``t0`` and the last it finished by ``t1``: the joins'
    summed, the maximum's, the ticks of the windows between, and the two
    times. Counters are int32 and wrap: differences modulo 2^32.
    ``arena_rows`` is a level, so its difference is the rows appended."""
    seen = _counted(run)
    lo = [x for x in seen if x[0] <= t0]
    hi = [x for x in seen if x[0] <= t1]
    if not lo or not hi or hi[-1][0] <= lo[-1][0]:
        return None
    (ta, _, a), (tb, _, b) = lo[-1], hi[-1]
    out = {"t0": ta, "t1": tb,
           "ticks": sum(k for t, k, _ in seen if ta < t <= tb)}
    for i, name in enumerate(JOIN_COUNTERS):
        out[name] = float(sum((b[n][i] - a[n][i]) % (1 << 32)
                              for n in JOINS))
    for i, name in enumerate(MAX_COUNTERS):
        out[name] = float((b[MAXIMUM][i] - a[MAXIMUM][i]) % (1 << 32))
    return out


_ONCE: Dict[tuple, object] = {}


def in_window(run) -> Optional[Dict[str, float]]:
    """``moved`` over the whole window, computed (and said) once a run."""
    key = ("in_window", id(run))
    if key not in _ONCE:
        m = moved(run, run.t_open, run.t_close)
        if m is not None and m["ticks"] > 0:
            ps.say("nexmark: " + ", ".join(
                f"{k} {m[k]:.0f}" for k in ("ticks",) + JOIN_COUNTERS
                + MAX_COUNTERS) + f" in {m['t1'] - m['t0']:.3f} s")
        _ONCE[key] = m if m is not None and m["ticks"] > 0 else None
    return _ONCE[key]


def tick_ms(run) -> Optional[float]:
    """Device busy time per tick over the traced stretch (the last 40 %
    of the window): the trace's busy share of its span, over the ticks a
    second of the windows the device finished in the same stretch."""
    if run.trace is None:
        return None
    m = moved(run, run.t_open + 0.6 * (run.t_close - run.t_open),
              run.t_close)
    if m is None or m["ticks"] <= 0:
        return None
    per_s = m["ticks"] / (m["t1"] - m["t0"])
    return 1e3 * run.trace["busy_s"] / run.trace["window_s"] / per_s


def swept_rows(cfg: dict, traffic: dict, m: Dict[str, float]) -> float:
    """Rows the two joins touched between the two windows ``m`` was read
    from, by what their counters say they did: every tick each join
    looks up its ``C`` left delta rows' degrees and its ``C`` right
    delta rows' left-table rows (``C`` the tick's static capacity, dead
    rows included: a lookup is paid for every slot), every trip of a
    probe's chain walk (``probe_steps``) passes over that join's
    ``product_slack x C`` pair slots, and the appended rows are written
    (``arena_rows`` moved by them). A join whose δA product swept its
    arena would touch ``2 x arena_capacity`` rows a tick instead, and
    counts nothing: it has no counters and the metric is left out."""
    c = bucket_capacity(traffic["coalesce"]["max_rows"])
    return (2.0 * 2 * c * m["ticks"]
            + cfg["product_slack"] * c * m["probe_steps"]
            + m["arena_rows"])


def floor_bytes_per_tick(cfg: dict, m: Dict[str, float]) -> float:
    """What one tick must move between HBM and the cores, from what the
    counters say it did: its rows in, opaque words and all; for every pair the matched
    left-table or arena row gathered; every touched auction's candidate
    buffer read and written; the appended arena rows and their index
    entries written. Left out, so that the share reads low and never
    high: every sort's passes, the filters' and re-keys' intermediate
    rows, the served tables' emissions, the pair slots that stay
    empty."""
    t = m["ticks"]
    buffer_bytes = cfg["candidates"] * (2 * 4 + 4)
    rows = cfg["batch_events"] // 50 * 63      # 63 rows of 100 bytes to 50 events
    return (ROW_BYTES * rows
            + PAIR_BYTES * m["pairs"] / t
            + 2 * buffer_bytes * m["touched"] / t
            + ARENA_ROW_BYTES * m["arena_rows"] / t)


def floor_s(run, m: Dict[str, float]) -> float:
    p = knn_model.peaks(knn_model.device_kind(run))
    return floor_bytes_per_tick(run.cfg, m) / p["hbm_bytes_per_s"]
