"""Share of the rows the two arenas took in inside the window that
carried a negative weight (the joins' ``retracted`` counters over the
rows appended: the move of the arenas' level plus what the reindexes
took out): half under a refresh stream whose deletes equal its inserts,
0 in an insert-only mix."""

import tpch_model


def read(run):
    m = tpch_model.in_window(run)
    if m is None or m["appended"] <= 0:
        return None
    return 100.0 * m["retracted"] / m["appended"]
