"""The SSSP fixpoint's arithmetic, for its per-layer readers: what the
program's own counters (``reflow_tpu.executors.lowerings.OP_COUNTERS``,
read from the ``window_device`` spans) say the row fixpoint program, its
swept join and its minimum did between two windows, and the bytes a
pass cannot avoid. Peaks come from the table ``knn_model`` holds. No JAX
outside ``knn_model.device_kind``.

Every function that reads a run returns ``None`` on a program whose
spans carry no such counters, as the parent of PR 41 has none (its row
program's token is the tick's ``converged`` flag): the reader then
leaves its metric out of the line.
"""

from __future__ import annotations

from typing import Dict, Optional

import knn_model
import pump_spans as ps

#: the graph's counting nodes (``reflow_tpu/workloads/sssp.py``) and the
#: places of their counters in the vectors the spans carry
LOOP, JOIN, MINIMUM = "dist", "relax", "best"
PLACES = {"passes": (LOOP, 0), "ticks": (LOOP, 1), "unquiesced": (LOOP, 2),
          "pairs": (JOIN, 0), "late_pairs": (JOIN, 1), "sweeps": (JOIN, 6),
          "swept_rows": (JOIN, 7), "left_rows": (JOIN, 8),
          "touched": (MINIMUM, 0), "evicted": (MINIMUM, 1),
          "blocks": (MINIMUM, 2)}
_WIDTH = {LOOP: 3, JOIN: 9, MINIMUM: 3}

#: bytes of one arena row as the device holds it (int32 key, float32
#: ``[dst, w]``, int32 weight) and of one distance (int32 key, float32)
ARENA_ROW_BYTES = 4 + 2 * 4 + 4
DIST_BYTES = 4 + 4


def _counted(run):
    """``(done, {node: [counters]})`` of every ``window_device`` span
    that carries the three nodes' counters whole, in order."""
    out = []
    for s in run.spans:
        c = s["args"].get("counters") if s["name"] == "window_device" \
            else None
        if c and all(len(c.get(n, ())) >= w for n, w in _WIDTH.items()):
            out.append((s["t1"], c))
    return sorted(out, key=lambda x: x[0])


def moved(run, t0: float, t1: float) -> Optional[Dict[str, float]]:
    """By how much the counters moved between the last window the device
    finished by ``t0`` and the last it finished by ``t1``, and the two
    times. Counters are int32 and wrap, ``swept_rows`` within a few
    hundred passes, so the differences are taken window by window,
    modulo 2^32, and summed."""
    seen = _counted(run)
    lo = [i for i, x in enumerate(seen) if x[0] <= t0]
    hi = [i for i, x in enumerate(seen) if x[0] <= t1]
    if not lo or not hi or hi[-1] <= lo[-1]:
        return None
    out = {"t0": seen[lo[-1]][0], "t1": seen[hi[-1]][0]}
    for name, (node, i) in PLACES.items():
        out[name] = float(sum(
            (b[node][i] - a[node][i]) % (1 << 32)
            for (_, a), (_, b) in zip(seen[lo[-1]:hi[-1]],
                                      seen[lo[-1] + 1:hi[-1] + 1])))
    return out


#: two readers share each quantity: computed (and said) once a run
_once = knn_model._once


@_once
def in_window(run) -> Optional[Dict[str, float]]:
    """``moved`` over the whole window, computed (and said) once a run."""
    m = moved(run, run.t_open, run.t_close)
    if m is None or m["ticks"] <= 0 or m["passes"] <= 0:
        return None
    ps.say("sssp: " + ", ".join(f"{k} {m[k]:.0f}" for k in PLACES)
           + f" in {m['t1'] - m['t0']:.3f} s")
    return m


@_once
def traced(run) -> Optional[Dict[str, float]]:
    """``moved`` over the traced stretch (the last 40 % of the window),
    with ``pass_ms``: the trace's busy share of its span over the passes
    a second of the windows the device finished in the same stretch."""
    if run.trace is None:
        return None
    m = moved(run, run.t_open + 0.6 * (run.t_close - run.t_open),
              run.t_close)
    if m is None or m["passes"] <= 0:
        return None
    busy = run.trace["busy_s"] / run.trace["window_s"]
    m["pass_ms"] = 1e3 * busy * (m["t1"] - m["t0"]) / m["passes"]
    ps.say(f"sssp: {m['passes']:.0f} passes of {m['ticks']:.0f} ticks in "
           f"the traced {m['t1'] - m['t0']:.3f} s, device busy "
           f"{100 * busy:.3f} %: {m['pass_ms']:.3f} ms a pass, floor "
           f"{floor_bytes_per_pass(m):.0f} bytes a pass")
    return m


def floor_bytes_per_pass(m: Dict[str, float]) -> float:
    """What one pass must move between HBM and the cores whatever the
    implementation, from what the counters say the passes did: one read
    of the arena row of every edge relaxed, and every improved distance
    written. Both are counted from below. A relaxation is a live row of
    the sweep's insert half; the counter ``late_pairs`` holds both
    halves, and a vertex reached for the first time has no retraction
    half, so the insert half is at least half of it. An improved
    distance is an insert row of the join's left delta, at least half of
    ``left_rows`` by the same argument. Left out, so that the share
    reads low and never high: the rest of the arena a sweep passes over,
    every sort, the candidate buffers, the rows between the operators.
    Not a dense-pass count: a join that followed its frontier would do
    these reads and no others."""
    return (ARENA_ROW_BYTES * 0.5 * m["late_pairs"]
            + DIST_BYTES * 0.5 * m["left_rows"]) / m["passes"]


def roofline_pct(run) -> Optional[float]:
    m = traced(run)
    if m is None:
        return None
    p = knn_model.peaks(knn_model.device_kind(run))
    return (100.0 * 1e3 * floor_bytes_per_pass(m) / p["hbm_bytes_per_s"]
            / m["pass_ms"])
