"""How much of one core the leader's Python threads used together:
the CPU seconds of every role of the thread ledger but ``native``
(``thread_ledger`` events, PR 39: each Python thread's CPU clock, summed
by thread role) between the first and the last event inside the window,
over the wall between them. 100 = one core, which is all the
interpreter lock lets Python threads have: near 100 the lock is
saturated and only less Python per row buys rate; well under it a
thread that waits milliseconds for the lock is waiting for a hand-over,
not for work to finish. ``thread_ledger.moved`` says every role's share
(``native``: XLA's, the TPU runtime's and the transfer threads', beside
the lock: what the Python roles leave of ``process_cpu_s``) and the
pump role's CPU against its own spans' ``cpu_s``."""

import thread_ledger as tl


def read(run):
    m = tl.moved(run)
    if m is None:
        return None
    python = sum(r["cpu_s"] for role, r in m["roles"].items()
                 if role != tl.NATIVE)
    return 100.0 * python / m["wall_s"]
