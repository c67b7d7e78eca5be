"""Host wall of ``dispatch_staged`` per window (``pump_execute`` spans
inside the window, mean). Dispatch wall on the host, not a device time."""


def read(run):
    spans = run.spans_named("pump_execute")
    if not spans:
        return None
    return 1e3 * sum(s["t1"] - s["t0"] for s in spans) / len(spans)
