"""Shared by the readers of the program's thread ledger (PR 39): the
``thread_ledger`` events the device watcher records at most twice a
second (track ``proc``; args = ``reflow_tpu.obs.threads.ledger()``:
cumulative ``cpu_s`` by thread role, ``native`` for what the Python
threads leave of ``process_cpu_s``) and the ``rpc_ops`` events of the
ingest server's handler threads (each its own cumulative table
``op -> [n, busy_s, cpu_s, n_cpu]``: every request counted and timed,
the CPU clock read for ``n_cpu`` of them). Both are counters on the
spans' clock, so every reader differences two events: the first after
``t_open`` and the last before ``t_close``.

Every function returns ``None`` on a program that records no such
event, as the parent of PR 39 does not: the reader then leaves its
metric out of the line.
"""

from __future__ import annotations

from typing import Dict, List, Optional

import pump_spans as ps

PUMP = "reflow-ingest-pump"
HANDLERS = "rpc-serve"
NATIVE = "native"
KEYS = ("cpu_s", "runq_s", "vol", "invol")


def _once(fn):
    """Several readers share each difference: taken (and said) once a
    run, and kept on the run itself."""
    def cached(run):
        kept = run.__dict__.setdefault("_thread_ledger", {})
        if fn.__name__ not in kept:
            kept[fn.__name__] = fn(run)
        return kept[fn.__name__]
    return cached


@_once
def moved(run) -> Optional[dict]:
    """What every role's counters moved by between the first and the
    last ``thread_ledger`` event inside the window: ``{"t0", "t1",
    "wall_s", "process_cpu_s", "roles": {role: {key: difference}}}``. A
    role that began in between counts from zero; a key the ledger does
    not hold (``runq_s`` and the switch counts where the kernel keeps
    no ``schedstat``: gVisor, on the TPU machines) is missing from the
    role. None with fewer than two events. Says every role's share of a
    core, the pump role's CPU against what the pump's own spans'
    ``cpu_s`` sum to over the same stretch (one measurement a thread,
    one a span), and where they are there the pump's run-queue wait
    and switches a window."""
    evs = sorted(run.spans_named("thread_ledger"), key=lambda s: s["t0"])
    if len(evs) < 2 or evs[-1]["t0"] <= evs[0]["t0"]:
        return None
    a, b = evs[0]["args"], evs[-1]["args"]
    roles = {}
    for role, row in b["roles"].items():
        was = a["roles"].get(role, {})
        roles[role] = {k: row[k] - was.get(k, 0) for k in KEYS if k in row}
    m = {"t0": evs[0]["t0"], "t1": evs[-1]["t0"],
         "wall_s": evs[-1]["t0"] - evs[0]["t0"],
         "process_cpu_s": b["process_cpu_s"] - a["process_cpu_s"],
         "roles": roles, "events": len(evs)}
    reads = [e["args"].get("read_s", 0.0) for e in evs]
    ps.say(f"thread ledger: {len(evs)} events over {m['wall_s']:.3f} s, "
           f"a read {1e3 * sum(reads) / len(reads):.3f} ms (longest "
           f"{1e3 * max(reads):.3f}); {b['cores']} cores, switch interval "
           f"{1e3 * b['switch_interval_s']:.1f} ms; process "
           f"{m['process_cpu_s']:.3f} s of CPU = "
           f"{100 * m['process_cpu_s'] / m['wall_s']:.1f} % of one core; "
           f"Python threads " + ", ".join(
               f"{role} {row['n']}" for role, row in b["roles"].items()
               if "n" in row))
    ps.say("cpu_s by role, % of one core: " + ", ".join(
        f"{role} {v:.3f}" for v, role in sorted(
            ((100 * r["cpu_s"] / m["wall_s"], role)
             for role, r in roles.items()), reverse=True)))
    pump = roles.get(PUMP)
    spans = [s for s in ps.outermost(ps.pump_spans(run))
             if s["name"] != "pump_wait" and "cpu_s" in s["args"]
             and m["t0"] <= s["t0"] < m["t1"]]
    by_span = sum(s["args"]["cpu_s"] for s in spans)
    if pump is not None and by_span > 0:
        ps.say(f"pump CPU by the ledger {pump['cpu_s']:.4f} s, by its "
               f"outermost spans {by_span:.4f} s "
               f"({100 * (pump['cpu_s'] / by_span - 1):+.2f} %)")
    if pump is not None and "runq_s" in pump and spans:
        wall = sum(s["t1"] - s["t0"] for s in spans)
        n = sum(1 for s in spans if s["name"] == "window_stage")
        ps.say(f"pump's working {wall:.3f} s: on the CPU "
               f"{pump['cpu_s']:.3f}, runnable and waiting for a core "
               f"{pump['runq_s']:.3f}, blocked "
               f"{wall - pump['cpu_s'] - pump['runq_s']:.3f}; "
               f"{pump['vol'] / max(n, 1):.2f} voluntary and "
               f"{pump['invol'] / max(n, 1):.2f} involuntary switches a "
               f"window over {n} windows")
    return m


@_once
def ops_moved(run) -> Optional[Dict[str, List[float]]]:
    """``op -> [n, busy_s, cpu_s, n_cpu]`` summed over the handler
    threads' tracks: on each, the last ``rpc_ops`` table inside the
    window less the table as the window opened — the last event before
    ``t_open`` (a handler records at a request's end, so its table
    stands still while its lane waits for the window), else the first
    inside it, or nothing, where the table itself began inside the
    window (``since``: a handler born after a link reset). None when no
    track has a difference to give."""
    by_track: Dict[str, List[dict]] = {}
    for s in run.spans:
        if s["name"] == "rpc_ops" and s["t0"] <= run.t_close:
            by_track.setdefault(s["track"], []).append(s)
    total: Dict[str, List[float]] = {}
    used = 0
    for evs in by_track.values():
        evs.sort(key=lambda s: s["t0"])
        before = [s for s in evs if s["t0"] < run.t_open]
        inside = evs[len(before):]
        if not inside:
            continue
        last = inside[-1]["args"]
        if last["since"] >= run.t_open:
            first = {}
        elif before:
            first = before[-1]["args"]["ops"]
        elif len(inside) >= 2:
            first = inside[0]["args"]["ops"]
        else:
            continue
        used += 1
        for op, row in last["ops"].items():
            was = first.get(op, [0, 0.0, 0.0, 0])
            t = total.setdefault(op, [0, 0.0, 0.0, 0])
            for i in range(4):
                t[i] += row[i] - was[i]
    if not used:
        return None
    ps.say(f"rpc ops over {used} handler tracks, requests / busy s / "
           f"us of CPU a request (clock read for): " + ", ".join(
               f"{op} {n} / {busy:.3f} / "
               f"{1e6 * cpu / k if k else 0.0:.1f} ({k})"
               for op, (n, busy, cpu, k) in sorted(total.items())))
    return total


def ops_cpu_s(ops: Dict[str, List[float]]) -> Dict[str, float]:
    """The CPU seconds each operation cost the handlers: its requests
    times the CPU a request of those whose clock was read."""
    return {op: n * cpu / k for op, (n, _busy, cpu, k) in ops.items()
            if k > 0}
