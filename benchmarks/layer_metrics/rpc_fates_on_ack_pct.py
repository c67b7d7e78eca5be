"""Of the fates the ingest server reported for tickets it had acked
``pending``, the share that rode a later submit's ack on the same link
and cost no request of their own; the rest went by ``resolve`` (the
handlers' ``fates_on_ack`` and ``fates_by_resolve``: ``rpc_ops`` events,
table ``link``, PR 40), differenced over the window and summed over the
handler tracks. High where a lane keeps submitting (a closed loop);
low, and rightly, where a lane is quiet between submits and polls (a
paced lane: the poll is there before the next submit). Counts only."""

import rpc_link


def read(run):
    m = rpc_link.link_moved(run)
    if m is None:
        return None
    fates = m["fates_on_ack"] + m["fates_by_resolve"]
    if fates <= 0:
        return None
    return 100.0 * m["fates_on_ack"] / fates
