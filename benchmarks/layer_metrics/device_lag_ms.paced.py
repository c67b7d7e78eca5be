"""From the dispatch returning on the host to the device finishing the
window (the completion probe's watcher), median over the windows
dispatched inside the window."""

from measure import percentile


def read(run):
    ms = [1e3 * (w["ready"] - w["dispatch1"]) for w in run.windows_inside()
          if w.get("ready") is not None]
    return percentile(ms, 50) if ms else None
