"""Seconds the committer spent in fsync inside the window (``wal_fsync``
spans) per window dispatched inside it."""


def read(run):
    n = len(run.windows_inside())
    spans = run.spans_named("wal_fsync")
    if not n or not spans:
        return None
    return 1e3 * sum(s["t1"] - s["t0"] for s in spans) / n
