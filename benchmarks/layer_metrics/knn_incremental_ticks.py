"""Ticks inside the window that did NOT rescan the corpus (the KnnIndex
node's device counter of incremental merges, between the last window the
device finished before the window opened and the last it finished inside
it). 0 in a cell whose every batch carries retractions: the guard that
the mix is what it says."""

import knn_model


def read(run):
    moved = knn_model.counters_between(run, run.t_open, run.t_close)
    return None if moved is None else float(moved["incremental"])
