"""Reduce a ``jax.profiler`` trace (``.xplane.pb``) to device numbers.

Read with ``jax.profiler.ProfileData`` and nothing else. Per device:
busy = the union of the intervals in which an operation ran; idle share
= 1 - busy / traced span. ``device_ops`` = the operations with most
*self* time (a ``while`` that only wraps its body's operations has almost
none), under the names XLA printed. ``idle_gaps`` = the device's idle
seconds split by what the host was doing meanwhile: the host annotations
(``reflow.window[K]`` from the program's dispatch, ``bench.*`` from the
benchmark's wrappers) that overlap each gap, and ``unannotated`` for the
rest (the pump waiting for input, or resolving tickets).

On a TPU the device planes are ``/device:TPU:<n>`` and the operations
are the ``XLA Ops`` line. The CPU backend (the ``--tiny`` rehearsal) has
no device plane: its operations run on the ``tf_XLA*`` host threads,
which stand in for the device there and only there.
"""

from __future__ import annotations

import glob
import gzip
import os
import shutil
import tempfile
from typing import Dict, List, Tuple

Interval = Tuple[float, float]

#: lines of a device plane that repeat or group the operations of
#: another line; counting them would count the same time twice
_DERIVED = ("XLA Modules", "Steps", "XLA TraceMe", "Framework",
            "Source", "Name Scope")


def find_trace(log_dir: str) -> str:
    paths = sorted(glob.glob(os.path.join(
        log_dir, "plugins", "profile", "*", "*.xplane.pb")))
    if not paths:
        raise RuntimeError(f"the profiler wrote no .xplane.pb under "
                           f"{log_dir}")
    return paths[-1]


def _load(path: str):
    from jax.profiler import ProfileData

    if path.endswith(".gz"):
        with tempfile.NamedTemporaryFile(suffix=".xplane.pb") as tmp:
            with gzip.open(path, "rb") as f:
                shutil.copyfileobj(f, tmp)
            tmp.flush()
            return ProfileData.from_file(tmp.name)
    return ProfileData.from_file(path)


def union(intervals: List[Interval]) -> List[Interval]:
    out: List[Interval] = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            if e > out[-1][1]:
                out[-1] = (out[-1][0], e)
        else:
            out.append((s, e))
    return out


def self_times(events: List[Tuple[str, float, float]]) -> Dict[str, float]:
    """Self time per name on one line: an event's duration minus the
    events nested inside it."""
    out: Dict[str, float] = {}
    stack: List[list] = []          # [name, end, self]

    def close(upto: float) -> None:
        while stack and stack[-1][1] <= upto:
            name, _, own = stack.pop()
            out[name] = out.get(name, 0.0) + max(own, 0.0)

    for name, s, e in sorted(events, key=lambda x: (x[1], -x[2])):
        close(s)
        if stack:
            stack[-1][2] -= min(e, stack[-1][1]) - s
        stack.append([name, e, e - s])
    close(float("inf"))
    return out


def intersect(a: List[Interval], b: List[Interval]) -> List[Interval]:
    """Of two sorted lists of disjoint intervals."""
    out, i, j = [], 0, 0
    while i < len(a) and j < len(b):
        s, e = max(a[i][0], b[j][0]), min(a[i][1], b[j][1])
        if e > s:
            out.append((s, e))
        if a[i][1] < b[j][1]:
            i += 1
        else:
            j += 1
    return out


def subtract(a: List[Interval], b: List[Interval]) -> List[Interval]:
    """``a`` minus ``b``, both sorted lists of disjoint intervals."""
    out, j = [], 0
    for s, e in a:
        while j < len(b) and b[j][1] <= s:
            j += 1
        k = j
        while k < len(b) and b[k][0] < e:
            if b[k][0] > s:
                out.append((s, b[k][0]))
            s = max(s, b[k][1])
            k += 1
        if s < e:
            out.append((s, e))
    return out


def reduce_trace(path: str) -> dict:
    pd = _load(path)
    device_lines: Dict[str, List[Tuple[str, float, float]]] = {}
    host: List[Tuple[str, float, float]] = []
    cpu_ops: List[Tuple[str, float, float]] = []
    for plane in pd.planes:
        is_dev = plane.name.startswith("/device:")
        has_ops = any(ln.name == "XLA Ops" for ln in plane.lines)
        for line in plane.lines:
            evs = [(e.name, e.start_ns * 1e-9,
                    (e.start_ns + e.duration_ns) * 1e-9)
                   for e in line.events if e.duration_ns > 0]
            if is_dev:
                if has_ops:
                    if line.name != "XLA Ops":
                        continue
                elif any(d in line.name for d in _DERIVED):
                    continue
                device_lines.setdefault(plane.name, []).extend(evs)
            elif line.name.startswith("tf_XLA"):
                cpu_ops.extend(evs)
            else:
                host.extend(evs)
    on_cpu = not device_lines
    if on_cpu and cpu_ops:
        device_lines = {"/host:CPU (XLA threads)": cpu_ops}
    if not device_lines:
        raise RuntimeError("the trace holds no device operation")

    notes = [(n, s, e) for n, s, e in host
             if n.startswith("reflow.") or n.startswith("bench.")]
    every = [iv for evs in device_lines.values() for iv in evs] + notes
    span0 = min(s for _, s, _ in every)
    span1 = max(e for _, _, e in every)
    window_s = span1 - span0

    # the pump thread's annotations, innermost first: a gap is charged
    # to the first of them that covers it. ``bench.await_device`` (the
    # watcher, always waiting while a window is in flight) is no host
    # work and labels nothing.
    order = ("reflow.window", "bench.stage_window", "bench.retire_staged",
             "bench.dispatch_staged")
    cover = {lab: union([(s, e) for n, s, e in notes
                         if n.split("[")[0] == lab]) for lab in order}
    per_device, ops_total, gaps_by = [], {}, {}
    for name, evs in sorted(device_lines.items()):
        busy_iv = union([(s, e) for _, s, e in evs])
        busy = sum(e - s for s, e in busy_iv)
        per_device.append({"device": name, "busy_s": busy,
                           "idle_pct": 100.0 * (1.0 - busy / window_s),
                           "ops": len(evs)})
        for op, t in self_times(evs).items():
            ops_total[op] = ops_total.get(op, 0.0) + t
        edges = [span0] + [x for iv in busy_iv for x in iv] + [span1]
        idle = [(g0, g1) for g0, g1 in zip(edges[0::2], edges[1::2])
                if g1 > g0]
        for lab in order:
            hit = intersect(idle, cover[lab])
            if hit:
                gaps_by[lab] = gaps_by.get(lab, 0.0) + sum(
                    e - s for s, e in hit)
                idle = subtract(idle, cover[lab])
        left = sum(e - s for s, e in idle)
        if left > 0:
            gaps_by["unannotated"] = gaps_by.get("unannotated", 0.0) + left
    n_dev = len(per_device)
    counts: Dict[str, int] = {}
    for n, _, _ in notes:
        label = n.split("[")[0]
        counts[label] = counts.get(label, 0) + 1
    # XLA prints an operation with its operands: keep the head of it
    top = lambda d: [[k[:160], v] for k, v in sorted(     # noqa: E731
        d.items(), key=lambda kv: -kv[1])[:10]]
    return {
        "on_cpu": on_cpu,
        "busy_s": sum(d["busy_s"] for d in per_device) / n_dev,
        "window_s": window_s,
        "per_device": per_device,
        "device_ops": top({k: v / n_dev for k, v in ops_total.items()}),
        "idle_gaps": top({k: v / n_dev for k, v in gaps_by.items()}),
        "annotation_counts": counts,
    }
