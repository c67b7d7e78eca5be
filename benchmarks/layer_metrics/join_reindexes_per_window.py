"""Arena index rebuilds of both joins inside the window, per 40 s (the
joins' ``index_rebuilds`` counters between the first and the last
window the device finished in it): each is a compaction's re-sort of a
whole arena, run between two windows because the log, which keeps a
retraction and its insert until then, was about to fill."""

import tpch_model


def read(run):
    m = tpch_model.in_window(run)
    if m is None:
        return None
    return 40.0 * m["index_rebuilds"] / (m["t1"] - m["t0"])
