"""The ``resolve`` long-poll's share of the CPU the ingest server's
handler threads spend inside requests: the ``resolve`` row's CPU
seconds over all rows' in the handlers' cumulative ``rpc_ops`` tables
(PR 39; a row's CPU is its requests times the CPU a request of those
whose clock was read), differenced over the window and summed over the
handler tracks. Every lane polls for its tickets every ``ack_poll_s``
and a poll that finds them undecided wakes in slices under the
interpreter lock: a large share says the ack path, not admission, is
what the handlers cost the pump."""

import thread_ledger as tl


def read(run):
    ops = tl.ops_moved(run)
    if ops is None:
        return None
    cpu = tl.ops_cpu_s(ops)
    if sum(cpu.values()) <= 0:
        return None
    return 100.0 * cpu.get("resolve", 0.0) / sum(cpu.values())
