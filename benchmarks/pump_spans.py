"""Shared by the readers of the program's own pump and device spans
(PR 24): which track is the pump's, which of its spans are outermost,
and the join of the span clock with a profiler trace through the
``reflow.clock[<perf_counter_ns>]`` annotations the program enters at
every traced window dispatch. No JAX outside ``xplane._load``.

Every function returns nothing (``None`` / an empty list) on a program
that records no such span, as the parent of PR 24 does not: the reader
then leaves its metric out of the line.
"""

from __future__ import annotations

import glob
import os
import re
from typing import Dict, List, Optional, Tuple

from measure import percentile
import xplane

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

#: covers all of ``_run_window`` and so overlaps everything else the
#: pump records: counting it would call every second explained
UMBRELLA = "window"
ANCHOR = re.compile(r"^reflow\.clock\[(\d+)\]$")


def say(msg: str) -> None:
    print(f"bench: {msg}", flush=True)


def pump_spans(run) -> List[dict]:
    """The spans of the pump thread that began inside the window, but
    for the umbrella: the track that recorded the ``window_stage``
    spans."""
    tracks: Dict[str, int] = {}
    for s in run.spans_named("window_stage"):
        tracks[s["track"]] = tracks.get(s["track"], 0) + 1
    if not tracks:
        return []
    track = max(tracks, key=tracks.get)
    return sorted((s for s in run.spans
                   if s["track"] == track and s["name"] != UMBRELLA
                   and run.t_open <= s["t0"] <= run.t_close),
                  key=lambda s: (s["t0"], -s["t1"]))


def outermost(spans: List[dict]) -> List[dict]:
    """Of spans sorted by (start, longest first): those inside no
    other."""
    out, edge = [], float("-inf")
    for s in spans:
        if s["t1"] > edge:
            out.append(s)
            edge = s["t1"]
    return out


def own_trace_path() -> Optional[str]:
    """The ``.xplane.pb`` of this process's own run: ``run.py`` keeps it
    under ``<root>/.bench_runs/<cell>-<seed>-<pid>/profile`` until the
    readers are done."""
    dirs = glob.glob(os.path.join(
        ROOT, ".bench_runs", f"*-{os.getpid()}", "profile"))
    for d in dirs:
        try:
            return xplane.find_trace(d)
        except RuntimeError:
            continue
    return None


def read_trace(path: str) -> Tuple[List[Tuple[int, float]],
                                   Dict[str, List[xplane.Interval]],
                                   Tuple[float, float]]:
    """``(anchors, device busy intervals, traced stretch)`` of one
    trace: anchors as ``(ns in the name, start on the trace's clock in
    seconds)``; the operations and the stretch as ``xplane.reduce_trace``
    takes them."""
    pd = xplane._load(path)
    anchors, notes = [], []
    device: Dict[str, List[xplane.Interval]] = {}
    cpu: List[xplane.Interval] = []
    for plane in pd.planes:
        is_dev = plane.name.startswith("/device:")
        has_ops = any(ln.name == "XLA Ops" for ln in plane.lines)
        for line in plane.lines:
            for e in line.events:
                if e.duration_ns <= 0:
                    continue
                iv = (e.start_ns * 1e-9,
                      (e.start_ns + e.duration_ns) * 1e-9)
                if is_dev:
                    if (line.name == "XLA Ops") if has_ops else not any(
                            d in line.name for d in xplane._DERIVED):
                        device.setdefault(plane.name, []).append(iv)
                elif line.name.startswith("tf_XLA"):
                    cpu.append(iv)
                elif e.name.startswith(("reflow.", "bench.")):
                    notes.append(iv)
                    m = ANCHOR.match(e.name)
                    if m:
                        anchors.append((int(m.group(1)), iv[0]))
    if not device and cpu:
        device = {"/host:CPU (XLA threads)": cpu}
    every = [iv for ivs in device.values() for iv in ivs] + notes
    if not device or not every:
        return anchors, {}, (0.0, 0.0)
    stretch = (min(s for s, _ in every), max(e for _, e in every))
    return anchors, {k: xplane.union(v) for k, v in device.items()}, stretch


def clock_offset(anchors) -> Optional[dict]:
    """``trace clock - span clock`` in seconds: the median over the
    anchors, and the p10-p90 spread that says how fine a label may be
    trusted. None with fewer than two anchors."""
    if len(anchors) < 2:
        return None
    offs = [t - ns * 1e-9 for ns, t in anchors]
    return {"n": len(offs), "median_s": percentile(offs, 50),
            "spread_s": percentile(offs, 90) - percentile(offs, 10)}


def idle_by_span(run, path: str) -> Optional[dict]:
    """The device's idle seconds in the traced stretch, split by the
    pump span that covers them (innermost first: a gap inside
    ``queue_write`` inside ``window_stage`` is the former's), the pump's
    spans mapped onto the trace's clock by the anchors' median offset.
    ``unexplained`` is what no pump span covers."""
    anchors, busy, (lo, hi) = read_trace(path)
    off = clock_offset(anchors)
    spans = [s for s in run.spans if s["name"] != UMBRELLA]
    tracks = {s["track"] for s in spans if s["name"] == "window_stage"}
    spans = [s for s in spans if s["track"] in tracks]
    if off is None or not busy or not spans:
        return None
    d = off["median_s"]
    by_len: Dict[str, List[float]] = {}
    cover: Dict[str, List[xplane.Interval]] = {}
    for s in spans:
        by_len.setdefault(s["name"], []).append(s["t1"] - s["t0"])
        cover.setdefault(s["name"], []).append((s["t0"] + d, s["t1"] + d))
    # innermost first: a name whose spans are shorter nests inside
    order = sorted(cover, key=lambda n: percentile(by_len[n], 50))
    cover = {n: xplane.union(cover[n]) for n in order}
    by: Dict[str, float] = {}
    idle_s = 0.0
    for ivs in busy.values():
        edges = [lo] + [x for iv in ivs for x in iv] + [hi]
        idle = [(a, b) for a, b in zip(edges[0::2], edges[1::2]) if b > a]
        idle_s += sum(b - a for a, b in idle)
        for name in order:
            hit = xplane.intersect(idle, cover[name])
            if hit:
                by[name] = by.get(name, 0.0) + sum(b - a for a, b in hit)
                idle = xplane.subtract(idle, cover[name])
        by["unexplained"] = by.get("unexplained", 0.0) + sum(
            b - a for a, b in idle)
    n = len(busy)
    return {"idle_s": idle_s / n, "stretch_s": hi - lo, "offset": off,
            "by_span": {k: v / n for k, v in sorted(
                by.items(), key=lambda kv: -kv[1])}}
