"""The program's own device-completion span (``window_device``, PR 24):
from the later of the window's launch returning and the previous
window's completion to this window's completion, median over the windows
that began inside the window. The inside twin of
``device_ms_per_window.backlog`` (trace) and ``device_lag_ms.paced``
(the benchmark's probe); ``dur + queued_s`` is the latter's quantity."""

from measure import percentile
import pump_spans as ps


def read(run):
    spans = run.spans_named("window_device")
    if not spans:
        return None
    ms = [1e3 * (s["t1"] - s["t0"]) for s in spans]
    lag = [1e3 * (s["t1"] - s["t0"] + s["args"].get("queued_s", 0.0))
           for s in spans]
    upper = [1e3 * (s["t1"] - s["t0"] + s["args"].get("launch_s", 0.0))
             for s in spans if not s["args"].get("queued_s")]
    ps.say(f"window_device over {len(ms)} windows: median "
           f"{percentile(ms, 50):.3f} ms, sum {sum(ms) / 1e3:.3f} s of "
           f"{run.t_close - run.t_open:.1f} s; launch returned -> done "
           f"(dur + queued_s) median {percentile(lag, 50):.3f} ms; launch "
           f"entered -> done (dur + launch_s, windows with nothing queued "
           f"ahead) median "
           + (f"{percentile(upper, 50):.3f} ms" if upper else "-"))
    return percentile(ms, 50)
