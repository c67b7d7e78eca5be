"""CPU microseconds a ``submit`` costs the ingest server's handler
thread, dispatch and reply (``_op_submit``: the frontend's admission,
the ack; not the socket read and the unpickle before it): the ``submit``
row of the handlers' cumulative ``rpc_ops`` tables (PR 39), CPU seconds
over the requests whose CPU clock was read (one in
``REFLOW_TRACE_SAMPLE`` of an operation: the clock is a system call
under the interpreter lock), differenced over the window and summed
over the handler tracks. With ``python_cpu_pct`` it says what one edit
costs the interpreter."""

import thread_ledger as tl


def read(run):
    ops = tl.ops_moved(run)
    if ops is None or ops.get("submit", [0, 0.0, 0.0, 0])[3] <= 0:
        return None
    _n, _busy, cpu, n_cpu = ops["submit"]
    return 1e6 * cpu / n_cpu
