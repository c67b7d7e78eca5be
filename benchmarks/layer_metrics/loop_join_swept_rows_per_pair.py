"""Arena slots the loop's join read per live row it emitted, inside the
window: its device counters ``swept_rows`` (``2 x arena_capacity`` for
every pass in which the distances had any delta) over ``pairs``. 1 would
be a join that follows its frontier; a swept join at this deployment's
sizes reads tens of thousands. Counts only."""

import sssp_model


def read(run):
    m = sssp_model.in_window(run)
    if m is None or m["pairs"] <= 0:
        return None
    return m["swept_rows"] / m["pairs"]
