"""The exposed durability wait: the ticket stage ``fsync`` (dispatch
returned -> the window's LSN durable), median."""

from measure import percentile


def read(run):
    ms = run.stage_ms("fsync")
    return percentile(ms, 50) if ms else None
