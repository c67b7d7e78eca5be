"""Slots of the join's delta the minimum's merge ran over, a pass,
inside the window: by how much the ``best`` node's fourth device counter
(``merged_slots``: the rung of the delta's capacity each merge took,
the smallest of ``K``, ``4 K``, ``16 K``, ... slots that holds the
pass's live rows, the whole capacity past the last) moved between the
same two ``window_device`` spans the other counters are read from, over
the fixpoint program's passes (one merge a pass). 65 536 would be every
pass on the first rung at this deployment's sizes, 4 194 304 a minimum
that merges the whole delta whatever it holds. The counter is int32 and
wraps (a thousand whole-delta merges): the difference is taken span by
span, modulo 2^32, as ``sssp_model.moved`` takes the others. Counts
only. None on a program whose minimum keeps three counters."""

import sssp_model

MERGED_SLOTS = 3


def read(run):
    m = sssp_model.in_window(run)
    if m is None:
        return None
    seen = [c[sssp_model.MINIMUM] for t, c in sssp_model._counted(run)
            if m["t0"] <= t <= m["t1"]]
    if any(len(c) <= MERGED_SLOTS for c in seen):
        return None
    return float(sum((b[MERGED_SLOTS] - a[MERGED_SLOTS]) % (1 << 32)
                     for a, b in zip(seen, seen[1:]))) / m["passes"]
