"""The ingest server's own handling of one submit: ``rpc_serve`` (PR
24), request frame fully received -> reply written, on the handler's
thread: unpickle, dispatch, the frontend lock, admission, the reply.
The inside twin of ``rpc_admit_ms.paced``, which starts at the
generator's send stamp; the difference is the wire and the handler
thread's wake-up. Median over the spans that began inside the window."""

from measure import percentile


def read(run):
    ms = [1e3 * (s["t1"] - s["t0"]) for s in run.spans_named("rpc_serve")]
    return percentile(ms, 50) if ms else None
