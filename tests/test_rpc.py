"""Ingestion RPC: the ``IngestFrontend.submit() -> Ticket`` contract
over the wire (``serve/rpc.py``).

Everything here runs hermetically over ``LoopbackTransport`` — same
framing, same protocol, no kernel; the multi-process bench and
``tests/test_proc.py`` soak the TCP twin. The load-bearing invariant is
exactly-once across producer death: a producer that dies mid-submit
resubmits the same ``batch_id`` after respawn, the ``hello`` dedup
handshake reports it admitted, and the fold count stays one.
"""

from reflow_tpu.net import LoopbackTransport
from reflow_tpu.serve import (APPLIED, DEDUPED, REJECTED,
                              IngestFrontend, RemoteProducer,
                              RpcIngestServer)
from reflow_tpu.wal import DurableScheduler
from reflow_tpu.workloads import wordcount


def make_stack(tmp_path, *, start=True, max_tickets=None):
    g, src, sink = wordcount.build_graph()
    sched = DurableScheduler(g, wal_dir=str(tmp_path / "wal"),
                             fsync="tick")
    fe = IngestFrontend(sched, start=start)
    lt = LoopbackTransport()
    srv = RpcIngestServer(fe, lt, max_tickets=max_tickets).start()
    return sched, fe, lt, srv, src, sink


def batch(words: str):
    return wordcount.ingest_lines([words])


def test_submit_applied_deduped_and_status(tmp_path):
    sched, fe, lt, srv, src, sink = make_stack(tmp_path)
    prod = RemoteProducer(lt, srv.address, name="p0")
    try:
        t = prod.submit(src, batch("aa bb aa"), batch_id="b0")
        res = t.result(10)
        assert res.status == APPLIED
        assert res.lsn is not None          # durable before the ack
        assert res.tick >= 0
        assert prod.in_doubt_ids() == ()
        # the hello handshake carried the server's identity
        assert prod.last_hello["graph"] == sched.graph.name
        assert prod.last_hello["epoch"] == 0

        # same id again: the dedup mirror collapses it, one fold total
        t2 = prod.submit(src, batch("aa bb aa"), batch_id="b0")
        assert t2.result(10).status == DEDUPED
        assert prod.deduped_total == 1
        fe.flush()
        assert sched.view(sink.name)[("aa", 2.0)] == 1
        assert srv.submits_total == 2
    finally:
        prod.close()
        srv.close()
        fe.close()
        sched.wal.close()


def test_unknown_source_rejects_deterministically(tmp_path):
    sched, fe, lt, srv, src, sink = make_stack(tmp_path)
    prod = RemoteProducer(lt, srv.address, name="p0")
    try:
        t = prod.submit("no-such-source", batch("xx"), batch_id="b0")
        res = t.result(10)
        # a protocol rejection resolves the ticket (retrying the same
        # request cannot succeed) instead of parking it in doubt
        assert res.status == REJECTED
        assert "no-such-source" in res.reason
        assert prod.in_doubt_ids() == ()
    finally:
        prod.close()
        srv.close()
        fe.close()
        sched.wal.close()


def test_long_poll_keeps_a_resolved_ticket_while_waiting_on_another(
        tmp_path):
    """One resolve long-poll over a finished ticket AND a pending one:
    the server reports the finished one and drops it from its table in
    the first slice of the poll; a later slice must not look it up
    again, read "unknown", and make the producer resubmit an applied
    batch (which then came back DEDUPED — with its full payload resent).
    """
    sched, fe, lt, srv, src, sink = make_stack(tmp_path)
    prod = RemoteProducer(lt, srv.address, name="p0")
    try:
        t0 = prod.submit(src, batch("aa"), batch_id="b0")
        fe.flush()                # b0 applied server-side, not yet polled
        fe.pause()
        t1 = prod.submit(src, batch("bb"), batch_id="b1")   # stays pending
        res = t0.result(10)       # polls (b0, b1) together
        assert res.status == APPLIED
        assert t0.submits == 1 and prod.resubmits_total == 0
        fe.resume()
        assert t1.result(10).status == APPLIED
    finally:
        prod.close()
        srv.close()
        fe.close()
        sched.wal.close()


def test_resubmit_after_producer_death_exactly_once(tmp_path):
    """The reconnect-dedup satellite: producer dies mid-submit, the
    respawned producer resubmits the same batch_id — the hello
    handshake reports it admitted, the resolve says DEDUPED, and the
    batch folded exactly once."""
    sched, fe, lt, srv, src, sink = make_stack(tmp_path)
    prod1 = RemoteProducer(lt, srv.address, name="p0")
    # submit and die without learning the fate — the ack window is
    # exactly where a kill -9 leaves a real producer in doubt
    prod1.submit(src, batch("zz0 zz1 zz0"), batch_id="boom-1")
    prod1.close()

    prod2 = RemoteProducer(lt, srv.address, name="p0-respawn")
    try:
        t = prod2.submit(src, batch("zz0 zz1 zz0"), batch_id="boom-1")
        res = t.result(10)
        assert res.status == DEDUPED
        # the handshake made the outcome observable: the dial inside
        # submit() carried the in-doubt id, the mirror remembered it
        assert "boom-1" in prod2.last_hello["admitted"]
        assert prod2.deduped_total == 1
        fe.flush()
        view = sched.view(sink.name)
        assert view[("zz0", 2.0)] == 1   # one fold, not two
        assert view[("zz1", 1.0)] == 1
    finally:
        prod2.close()
        srv.close()
        fe.close()
        sched.wal.close()


def test_link_reset_resubmits_on_replacement_endpoint(tmp_path):
    """A server restart (the promoted-replacement shape: empty ticket
    table, recovered mirror) never double-folds and never loses an
    acked write — the producer re-dials, re-handshakes and resubmits."""
    sched, fe, lt, srv, src, sink = make_stack(tmp_path)
    prod = RemoteProducer(lt, srv.address, name="p0")
    srv2 = None
    try:
        assert prod.submit(src, batch("m0"),
                           batch_id="b0").result(10).status == APPLIED
        srv.close()                       # the link resets under us
        t = prod.submit(src, batch("m1 m1"), batch_id="b1")
        assert not t.done()               # in doubt, payload retained
        srv2 = RpcIngestServer(fe, lt).start()   # same frontend
        prod.retarget(srv2.address)
        res = t.result(10)
        assert res.status in (APPLIED, DEDUPED)
        assert prod.reconnects_total >= 1
        assert prod.submits_total >= 3    # b0 + b1 + the resubmit
        fe.flush()
        assert sched.view(sink.name)[("m1", 2.0)] == 1   # one fold
        assert prod.in_doubt_ids() == ()
    finally:
        prod.close()
        if srv2 is not None:
            srv2.close()
        srv.close()
        fe.close()
        sched.wal.close()


def test_ticket_eviction_resolves_unknown_then_dedups(tmp_path):
    """The bounded ticket table: an evicted in-flight ticket resolves
    "unknown", the producer resubmits, and the dedup mirror keeps the
    duplicate from folding twice."""
    # no pump: tickets stay undecided, making the eviction deterministic
    sched, fe, lt, srv, src, sink = make_stack(tmp_path, start=False,
                                               max_tickets=1)
    prod = RemoteProducer(lt, srv.address, name="p0")
    try:
        t0 = prod.submit(src, batch("e0"), batch_id="b0")
        prod.submit(src, batch("e1"), batch_id="b1")  # evicts b0
        assert srv.evicted_tickets == 1
        # driving b0 now resolves it: resolve -> "unknown" -> resubmit
        # -> DEDUPED against the mirror (b0 was admitted, just evicted)
        res = t0.result(10)
        assert res.status == DEDUPED
        assert prod.deduped_total == 1
        assert prod.resubmits_total >= 1
    finally:
        prod.close()
        srv.close()
        fe.close(flush=False)   # nothing pumps the queued batches
        sched.wal.close()


def test_flush_view_and_ping_ops(tmp_path):
    """The sideband ops the bench leans on: flush quiesces the
    frontend, view reads the sink at the current tick, ping reports
    graph/tick/lsn/state."""
    sched, fe, lt, srv, src, sink = make_stack(tmp_path)
    prod = RemoteProducer(lt, srv.address, name="p0")
    try:
        for i in range(3):
            prod.submit(src, batch("vv ww"), batch_id=f"b{i}")
        prod.flush(10)
        conn = lt.connect(srv.address)
        try:
            conn.send_msg(("flush", 10.0))
            assert conn.recv_msg(10.0) == ("ok",)
            conn.send_msg(("view", sink.name))
            ok, tick, view = conn.recv_msg(10.0)
            assert ok == "ok" and tick == sched._tick
            assert view[("vv", 3.0)] == 1
            conn.send_msg(("ping",))
            ok, st = conn.recv_msg(10.0)
            assert st["tick"] == sched._tick and st["state"] == "running"
            conn.send_msg(("bogus",))
            assert conn.recv_msg(10.0)[0] == "err"
        finally:
            conn.close()
    finally:
        prod.close()
        srv.close()
        fe.close()
        sched.wal.close()
