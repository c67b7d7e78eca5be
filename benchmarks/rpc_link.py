"""Shared by the two readers of the ingest link's counters (PR 40): the
``link`` table beside ``ops`` in the ``rpc_ops`` events of the ingest
server's handler threads (``serve/rpc.py``; one handler a connection,
each its own cumulative ``{"sock_calls", "frames_in", "fates_on_ack",
"fates_by_resolve"}``: the system calls the handler's end of the
connection made on its socket, the request frames it received, and the
fates of tickets it had acked ``pending`` that went out on a later
submit's ack or by a ``resolve``). Counters on the spans' clock, so a
reader differences two events a track, as ``thread_ledger.ops_moved``
does for ``ops``: the last inside the window less the table as the
window opened — the last event before ``t_open``, else the first inside
it, or nothing where the table itself began inside the window
(``since``: a handler born after a link reset).

``None`` on a program whose events have no such table, as the parent of
PR 40 has not: the reader then leaves its metric out of the line.
"""

from __future__ import annotations

from typing import Dict, List, Optional

import pump_spans as ps
import thread_ledger as tl

KEYS = ("sock_calls", "frames_in", "fates_on_ack", "fates_by_resolve")


@tl._once
def link_moved(run) -> Optional[Dict[str, int]]:
    """``counter -> difference`` summed over the handler tracks; taken
    (and said) once a run."""
    by_track: Dict[str, List[dict]] = {}
    for s in run.spans:
        if s["name"] == "rpc_ops" and s["t0"] <= run.t_close \
                and "link" in s["args"]:
            by_track.setdefault(s["track"], []).append(s)
    total = dict.fromkeys(KEYS, 0)
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
            first = before[-1]["args"]["link"]
        elif len(inside) >= 2:
            first = inside[0]["args"]["link"]
        else:
            continue
        used += 1
        for k in KEYS:
            total[k] += last["link"].get(k, 0) - first.get(k, 0)
    if not used:
        return None
    fates = total["fates_on_ack"] + total["fates_by_resolve"]
    ps.say(f"rpc link over {used} handler tracks: {total['sock_calls']} "
           f"socket calls for {total['frames_in']} requests; {fates} "
           f"fates of pending tickets, {total['fates_on_ack']} on a later "
           f"submit's ack and {total['fates_by_resolve']} by resolve")
    return total
