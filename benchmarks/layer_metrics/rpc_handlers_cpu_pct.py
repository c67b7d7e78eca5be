"""How much of one core the ingest server's handler threads used: the
CPU seconds of the ``rpc-serve`` role of the thread ledger
(``thread_ledger`` events, PR 39; a handler that exited with a link
reset keeps what it had used) between the first and the last event
inside the window, over the wall between them. 100 = one core. Where
the ``rpc_ops`` tables are there too, says how much of it lies inside
requests (dispatch and reply) and how much outside (the socket read,
the unpickle, the poll slices)."""

import pump_spans as ps
import thread_ledger as tl


def read(run):
    m = tl.moved(run)
    if m is None or tl.HANDLERS not in m["roles"]:
        return None
    cpu_s = m["roles"][tl.HANDLERS]["cpu_s"]
    ops = tl.ops_moved(run)
    if ops is not None:
        ps.say(f"handlers' CPU: {cpu_s:.3f} s by the ledger, "
               f"{sum(tl.ops_cpu_s(ops).values()):.3f} s inside requests "
               f"by their rpc_ops tables")
    return 100.0 * cpu_s / m["wall_s"]
