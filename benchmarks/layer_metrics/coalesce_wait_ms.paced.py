"""Admitted to picked up by a window: the ticket stages ``coalesce`` +
``sched_delay``, median."""

from measure import percentile


def read(run):
    by = {}
    for s in run.spans:
        if s["name"] in ("coalesce", "sched_delay") \
                and s["track"].startswith("ticket/"):
            by[s["track"]] = by.get(s["track"], 0.0) + s["t1"] - s["t0"]
    ids = {"ticket/" + b["id"] for b in run.joined.batches
           if run.t_open <= b["due"] < run.t_close}
    ms = [1e3 * v for k, v in by.items() if k in ids]
    return percentile(ms, 50) if ms else None
