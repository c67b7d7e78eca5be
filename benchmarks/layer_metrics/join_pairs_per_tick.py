"""Live pairs the two joins emitted per tick inside the window (their
``pairs`` device counters, summed, between the last window the device
finished before the window opened and the last inside it, over those
windows' ticks): about one a bid or category-10 auction whose other side
has arrived. Counts only: the CPU rehearsal reads the same."""

import nexmark_model


def read(run):
    m = nexmark_model.in_window(run)
    return None if m is None else m["pairs"] / m["ticks"]
