"""Passes a tick of the row fixpoint program inside the window: by how
much its device counter ``passes`` (phase A, every trip of the
``while_loop``, the exit pass where there is one: ``TickResult.passes``)
moved over its ``ticks``, between the last window the device finished
before the window opened and the last it finished inside it. Counts
only. The dataset fixes it: a seed deals labels, not structure."""

import sssp_model


def read(run):
    m = sssp_model.in_window(run)
    return None if m is None else m["passes"] / m["ticks"]
