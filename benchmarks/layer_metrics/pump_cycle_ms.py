"""The pump's cycle: the gap between the starts of consecutive
``window_stage`` spans (the program's own, PR 24), median over the
window. What ``rows_per_window / rows_per_s`` says from outside."""

from measure import percentile


def read(run):
    t = sorted(s["t0"] for s in run.spans_named("window_stage"))
    gaps = [1e3 * (b - a) for a, b in zip(t, t[1:])]
    return percentile(gaps, 50) if gaps else None
