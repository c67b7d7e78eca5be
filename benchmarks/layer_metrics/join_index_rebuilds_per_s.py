"""Arena index rebuilds a second inside the window (the joins'
``index_rebuilds`` counters): each is a compaction's re-sort of a whole
arena. 0 in a cell whose state is sized at build for everything the mix
can send; anything else is seconds of device time nobody planned."""

import nexmark_model


def read(run):
    m = nexmark_model.in_window(run)
    return None if m is None else \
        m["index_rebuilds"] / (m["t1"] - m["t0"])
