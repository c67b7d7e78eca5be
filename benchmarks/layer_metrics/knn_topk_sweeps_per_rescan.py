"""Chunk sweeps the top-k fold ran per corpus rescan inside the window:
by how much the KnnIndex node's fourth device counter (``sweeps``: one
per 8-row block and sweep of the fold kernel over a score chunk; a block
is swept as often as the most columns any of its rows has that beat its
carry's k-th score, at most k times) moved, over by how much ``rescans``
did, between the same two ``window_device`` spans
``knn_incremental_ticks`` reads. How often the kernel's gate lets a
sweep through: k a block and chunk (65 536 a rescan at 256 queries, k =
16 and 128 chunks) is a kernel that sweeps whatever the chunk holds. The
counter is int32 and wraps, so the difference is taken modulo 2^32.
Counts only: the program's XLA body counts by the same rule, so the CPU
rehearsal reads the same. None on a program without the fourth
counter."""

import knn_model


def read(run):
    moved = knn_model.counters_between(run, run.t_open, run.t_close)
    if moved is None or moved["rescans"] <= 0:
        return None
    at = {t: c for t, c in knn_model._counted(run)}
    a, b = at[moved["t0"]], at[moved["t1"]]
    if len(a) < 4:
        return None
    return float((b[3] - a[3]) % (1 << 32)) / moved["rescans"]
