"""The ticket stage ``execute``: the host wall of the window's dispatch,
median. Dispatch wall on the host, not a device time."""

from measure import percentile


def read(run):
    ms = run.stage_ms("execute")
    return percentile(ms, 50) if ms else None
