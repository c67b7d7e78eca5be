"""System calls a request costs the ingest server's handler thread on
its socket: the handlers' ``sock_calls`` over their ``frames_in``
(``rpc_ops`` events, table ``link``, PR 40), differenced over the window
and summed over the handler tracks. Every call gives the interpreter
lock up, and beside the leader's other threads the lock is about a
millisecond away, so this is what a request costs in hand-overs before
any Python runs. A request that arrives whole and a reply that fits the
socket's buffer: one wait, one read, one write = 3; the connection
before PR 40 made 9 and counted none. A batch of hundreds of kilobytes
arrives in pieces and costs a read a piece; a handler's idle 0.2 s poll
slices count too. Counts only."""

import rpc_link


def read(run):
    m = rpc_link.link_moved(run)
    if m is None or m["frames_in"] <= 0:
        return None
    return m["sock_calls"] / m["frames_in"]
