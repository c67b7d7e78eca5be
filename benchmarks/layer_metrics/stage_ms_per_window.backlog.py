"""Host time to stage a window (slot writes + WAL append):
``frontend.stage_s_total / windows_staged``, over the window."""


def read(run):
    a, b = run.snap_open, run.snap_close
    n = b["windows_staged"] - a["windows_staged"]
    if n <= 0:
        return None
    return 1e3 * (b["stage_s_total"] - a["stage_s_total"]) / n
