"""Share of the loop join's products that followed the frontier, inside
the window: of the passes in which the distances had any delta, those
whose keys' arena rows fit the join's slot budget and were enumerated
through its key-sorted view of the arena (its eleventh device counter, ``probes``)
over those and the ones that swept the arena instead (``sweeps``),
between the same two ``window_device`` spans the other counters are read
from. 100 would be no sweep at all; a hub's improvement, whose fan-out
passes the budget, sweeps. ``probes`` is int32 like the rest: the
difference is taken span by span, modulo 2^32, as ``sssp_model.moved``
takes the others. Counts only. None on a program whose join keeps ten
counters (it sweeps in every such pass)."""

import sssp_model

PROBES = 10


def read(run):
    m = sssp_model.in_window(run)
    if m is None:
        return None
    seen = [c[sssp_model.JOIN] for t, c in sssp_model._counted(run)
            if m["t0"] <= t <= m["t1"]]
    if any(len(c) <= PROBES for c in seen):
        return None
    probes = float(sum((b[PROBES] - a[PROBES]) % (1 << 32)
                       for a, b in zip(seen, seen[1:])))
    if probes + m["sweeps"] <= 0:
        return None
    return 100.0 * probes / (probes + m["sweeps"])
