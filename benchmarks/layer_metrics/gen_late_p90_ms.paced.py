"""How late the generator ran: sent - due on the child's clock, 90th
percentile over the batches due inside the window. A starved generator
must not be read as a fast server."""

from measure import percentile


def read(run):
    late = [1e3 * (b["sent"] - b["due"]) for b in run.joined.batches
            if run.t_open <= b["due"] < run.t_close]
    return percentile(late, 90) if late else None
