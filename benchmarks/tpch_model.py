"""The TPC-H Q3 cell's arithmetic, for its per-layer readers: what the
program's own counters (``reflow_tpu.executors.lowerings.OP_COUNTERS``,
read from the ``window_device`` spans) say the two joins did between two
windows, the executor's ``join_reindex`` spans laid onto the device
trace, and the bytes a tick and a reindex cannot avoid. Peaks come from
the table ``knn_model`` holds. No JAX outside ``knn_model.device_kind``
and ``xplane._load``.

Every function that reads a run returns ``None`` on a program whose
spans carry no such counters or spans, as the parent of PR 43 has none:
the reader then leaves its metric out of the line.
"""

from __future__ import annotations

from typing import Dict, List, Optional, Tuple

import knn_model
import pump_spans as ps
import xplane

#: the graph's joins (``reflow_tpu/workloads/tpch.py``): customers with
#: orders, orders with lineitems; and their counters by position in the
#: spans' vectors (new names are appended: positions stay)
JOINS = ("q3_orders", "q3_join")
COUNTERS = {"pairs": 0, "late_pairs": 1, "arena_rows": 2,
            "index_rebuilds": 3, "compactions": 4, "probe_steps": 5,
            "retracted": 9}
REINDEX = "join_reindex"

#: bytes of one row as the device holds it
ROW_BYTES = 28 * 4 + 4 + 4           # int32[28] + int32 key + int32 weight
#: an arena row and its index entry (``seg_len``, ``seg_prev``): key,
#: value columns, weight, two index words
ARENA_ROW_BYTES = {"q3_orders": 4 + 3 * 4 + 4 + 2 * 4,
                   "q3_join": 4 + 2 * 4 + 4 + 2 * 4}
KEY_BYTES = 2 * 4                    # ``head`` and ``deg`` of one key
PAIR_BYTES = 3 * 4 + 4               # the matched table or arena row + weight
SUM_SLOT_BYTES = 4 + 4 + 4 + 1       # wsum, wcnt, emitted, emitted_has


def _signed(x: int) -> int:
    """A difference of two int32 counters, wrapped, as a signed number."""
    x %= 1 << 32
    return x - (1 << 32) if x >= 1 << 31 else x


def _counted(run):
    """``(done, ticks, {node: [counters]})`` of every ``window_device``
    span that carries both joins' counters, ``retracted`` among them, in
    order of completion."""
    out = []
    for s in run.spans:
        c = s["args"].get("counters") if s["name"] == "window_device" \
            else None
        if c and all(n in c and len(c[n]) > COUNTERS["retracted"]
                     for n in JOINS):
            out.append((s["t1"], int(s["args"].get("ticks", 0)), c))
    return sorted(out, key=lambda x: x[0])


def reindexes(run, t0: float, t1: float) -> List[dict]:
    """The executor's ``join_reindex`` spans that lie inside ``[t0,
    t1]``: dispatch to the arena's row count read behind it, which is
    when the device finished the program."""
    return sorted((s for s in run.spans if s["name"] == REINDEX
                   and s["t0"] >= t0 and s["t1"] <= t1),
                  key=lambda s: s["t0"])


def moved(run, t0: float, t1: float) -> Optional[Dict[str, float]]:
    """By how much each join's counters moved between the last window
    the device finished by ``t0`` and the last it finished by ``t1``
    (``<counter>`` summed over the joins, ``<node>.<counter>`` each),
    the ticks of the windows between, the two times, and ``appended``:
    the rows the arenas took in, which is the move of their level plus
    what the reindexes between took out (their spans' ``rows_before`` -
    ``rows_after``)."""
    seen = _counted(run)
    lo = [x for x in seen if x[0] <= t0]
    hi = [x for x in seen if x[0] <= t1]
    if not lo or not hi or hi[-1][0] <= lo[-1][0]:
        return None
    (ta, _, a), (tb, _, b) = lo[-1], hi[-1]
    out = {"t0": ta, "t1": tb,
           "ticks": sum(k for t, k, _ in seen if ta < t <= tb)}
    for name, i in COUNTERS.items():
        for n in JOINS:
            out[f"{n}.{name}"] = float(_signed(b[n][i] - a[n][i]))
        out[name] = sum(out[f"{n}.{name}"] for n in JOINS)
    for n in JOINS:
        out[f"{n}.appended"] = out[f"{n}.arena_rows"] + sum(
            s["args"]["rows_before"] - s["args"]["rows_after"]
            for s in reindexes(run, ta, tb) if s["args"]["node"] == n)
    out["appended"] = sum(out[f"{n}.appended"] for n in JOINS)
    return out


_ONCE: Dict[tuple, object] = {}


def _once(fn):
    """Several readers share each quantity: computed (and said) once a
    run."""
    def cached(run):
        key = (fn.__name__, id(run))
        if key not in _ONCE:
            _ONCE[key] = fn(run)
        return _ONCE[key]
    return cached


@_once
def in_window(run) -> Optional[Dict[str, float]]:
    """``moved`` over the whole window."""
    m = moved(run, run.t_open, run.t_close)
    if m is None or m["ticks"] <= 0:
        return None
    ps.say("tpch: " + ", ".join(
        f"{k} {m[k]:.0f}" for k in ("ticks", "pairs", "late_pairs",
                                    "appended", "retracted", "probe_steps")
    ) + "; reindexes " + ", ".join(
        f"{n} {m[n + '.index_rebuilds']:.0f}" for n in JOINS)
        + f" in {m['t1'] - m['t0']:.3f} s")
    return m


def _traced(run) -> Tuple[float, float]:
    """The traced stretch on the spans' clock: the window's last 40 %."""
    return run.t_open + 0.6 * (run.t_close - run.t_open), run.t_close


@_once
def reindex_device(run) -> Optional[dict]:
    """The reindex programs in the device trace: for every
    ``join_reindex`` span that lies whole inside the traced stretch, the
    device's busy seconds inside it (the pump dispatches nothing else
    between a reindex and the count read that ends its span), the spans
    mapped onto the trace's clock by the ``reflow.clock`` anchors.
    ``busy_s``: all of them together; ``by_node``: seconds and calls a
    join."""
    if run.trace is None:
        return None
    path = ps.own_trace_path()
    if path is None:
        return None
    anchors, busy, (lo, hi) = ps.read_trace(path)
    off = ps.clock_offset(anchors)
    if off is None or not busy:
        return None
    d = off["median_s"]
    spans = [s for s in run.spans if s["name"] == REINDEX
             and s["t0"] + d >= lo and s["t1"] + d <= hi]
    by: Dict[str, List[float]] = {}
    for s in spans:
        iv = [(s["t0"] + d, s["t1"] + d)]
        secs = sum(sum(b - a for a, b in xplane.intersect(ivs, iv))
                   for ivs in busy.values()) / len(busy)
        by.setdefault(s["args"]["node"], []).append(secs)
    total = sum(sum(v) for v in by.values())
    ps.say("tpch: reindex programs in the traced stretch: " + (", ".join(
        f"{n} {len(v)} x {sum(v) / len(v):.4f} s" for n, v in by.items())
        or "none") + f"; {total:.4f} s of {run.trace['busy_s']:.4f} s busy")
    return {"busy_s": total, "by_node": by}


@_once
def tick_ms(run) -> Optional[float]:
    """Device busy time per tick over the traced stretch, the reindex
    programs' time taken out: what is left of the trace's busy seconds
    over the ticks of the windows the device finished in the same
    stretch."""
    if run.trace is None:
        return None
    m = moved(run, *_traced(run))
    re = reindex_device(run)
    if m is None or m["ticks"] <= 0 or re is None:
        return None
    share = (run.trace["busy_s"] - re["busy_s"]) / run.trace["window_s"]
    return 1e3 * share * (m["t1"] - m["t0"]) / m["ticks"]


def rows_per_tick(run) -> Optional[float]:
    wins = [w for w in run.windows_inside() if w["n_batches"]]
    ticks = sum(w["k"] for w in wins)
    return sum(w["rows"] for w in wins) / ticks if ticks else None


def tick_floor_bytes(run, m: Dict[str, float]) -> Optional[float]:
    """What one tick must move between HBM and the cores, from what the
    counters say it did: its rows in, opaque words and all; the arena
    rows appended with their index entries and their keys' chain heads
    and degrees; for every pair the matched table or arena row gathered;
    and the sum's touched slots read and written, at most one a pair of
    the lineitem join. Left out, so that the share reads low and never
    high: every dense pass over a per-key table, every sort's passes,
    the filters' and re-keys' intermediate rows, the pair slots that
    stay empty."""
    rows = rows_per_tick(run)
    if rows is None:
        return None
    t = m["ticks"]
    appended = sum((ARENA_ROW_BYTES[n] + KEY_BYTES) * m[f"{n}.appended"]
                   for n in JOINS)
    return (ROW_BYTES * rows
            + appended / t
            + PAIR_BYTES * m["pairs"] / t
            + 2 * SUM_SLOT_BYTES * m["q3_join.pairs"] / t)


def reindex_bytes(cfg: dict, node: str) -> float:
    """One read and one write of a join's arena and its index: the row
    columns over the arena's capacity, ``head`` and ``deg`` over the
    left side's key space."""
    rows, keys = {"q3_orders": (cfg["orders_arena"], cfg["customers"] + 1),
                  "q3_join": (cfg["lineitem_arena"], cfg["order_keys"])
                  }[node]
    return 2.0 * (ARENA_ROW_BYTES[node] * rows + KEY_BYTES * keys)


def hbm_bytes_per_s(run) -> float:
    return knn_model.peaks(knn_model.device_kind(run))["hbm_bytes_per_s"]
