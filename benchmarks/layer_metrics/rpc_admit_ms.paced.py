"""From the child's send to the batch admitted in the frontend: frame,
pickle, the server thread, the admission lock. The child's ``sent``
stamp to the end of the ticket's ``admission`` stage, median."""

from measure import percentile


def read(run):
    sent = {b["id"]: b["sent"] for b in run.joined.batches
            if run.t_open <= b["due"] < run.t_close}
    ms = [1e3 * (s["t1"] - sent[s["track"][7:]]) for s in run.spans
          if s["name"] == "admission" and s["track"][7:] in sent
          and s["track"].startswith("ticket/")]
    return percentile(ms, 50) if ms else None
