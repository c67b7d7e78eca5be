"""Ingestion RPC: the ``IngestFrontend.submit() -> Ticket`` contract
over the wire (``serve/rpc.py``).

Everything here runs hermetically over ``LoopbackTransport`` — same
framing, same protocol, no kernel; the multi-process bench and
``tests/test_proc.py`` soak the TCP twin. The load-bearing invariant is
exactly-once across producer death: a producer that dies mid-submit
resubmits the same ``batch_id`` after respawn, the ``hello`` dedup
handshake reports it admitted, and the fold count stays one.
"""

import pickle

import pytest

from reflow_tpu.net import (FaultyTransport, LoopbackTransport,
                            ReconnectPolicy, TcpTransport)
from reflow_tpu.serve import (APPLIED, DEDUPED, REJECTED,
                              IngestFrontend, RemoteProducer,
                              RpcIngestServer)
from reflow_tpu.serve.rpc import SubmitAck, SubmitReq, _trim
from reflow_tpu.utils.faults import WireFaults
from reflow_tpu.wal import DurableScheduler
from reflow_tpu.workloads import wordcount


def make_stack(tmp_path, *, start=True, max_tickets=None, kind="loopback"):
    """``(sched, fe, the client's transport, srv, src, sink)``: loopback
    shares one transport between the two ends, TCP must not."""
    g, src, sink = wordcount.build_graph()
    sched = DurableScheduler(g, wal_dir=str(tmp_path / "wal"),
                             fsync="tick")
    fe = IngestFrontend(sched, start=start)
    lt = LoopbackTransport() if kind == "loopback" else TcpTransport()
    srv = RpcIngestServer(fe, lt, max_tickets=max_tickets).start()
    return (sched, fe, lt if kind == "loopback" else TcpTransport(), srv,
            src, sink)


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


# -- fates ride the acks ------------------------------------------------------

LINKS = ["loopback", "tcp"]


def shut(prod, srv, fe, sched, flush=True):
    prod.close()
    srv.close()
    fe.close(flush=flush)
    sched.wal.close()


@pytest.mark.parametrize("kind", LINKS)
def test_second_submit_reply_decides_the_first_ticket(tmp_path, kind):
    sched, fe, ct, srv, src, sink = make_stack(tmp_path, kind=kind)
    prod = RemoteProducer(ct, srv.address, name="p0")
    try:
        fe.pause()
        t0 = prod.submit(src, batch("aa aa"), batch_id="b0")
        t1 = prod.submit(src, batch("bb"), batch_id="b1")
        # neither decided: the pump is paused and nothing rode b1's ack
        assert not t0.done() and not t1.done()
        assert srv.fates_on_ack_total == 0
        fe.resume()
        fe.flush()                # both applied and durable server-side
        t2 = prod.submit(src, batch("cc"), batch_id="b2")
        # b2's own ack carried the two fates: decided, in order, with
        # the fields a resolve would have brought
        assert t0.done() and t1.done()
        r0, r1 = t0.result(0), t1.result(0)
        assert (r0.status, r1.status) == (APPLIED, APPLIED)
        assert r0.batch_id == "b0" and r0.lsn is not None
        assert r0.tick <= r1.tick
        assert prod.fates_on_ack_total == 2 and prod.resolves_total == 0
        assert srv.fates_on_ack_total == 2
        assert srv.fates_by_resolve_total == 0
        # hello + three submits: no resolve crossed the wire
        assert srv.requests_total == 4 and srv.submits_total == 3
        assert prod.in_doubt_ids() in ((), ("b2",))
        # a fate is reported once: the table has let both go
        assert set(srv._tickets) <= {"b2"}
        assert t2.result(10).status == APPLIED
        fe.flush()
        assert sched.view(sink.name)[("aa", 2.0)] == 1
    finally:
        shut(prod, srv, fe, sched)


@pytest.mark.parametrize("kind", LINKS)
def test_request_in_the_older_form_gets_the_older_reply(tmp_path, kind):
    """A client that does not say it takes fates is sent none, and its
    replies are the pre-fates protocol's byte for byte."""
    sched, fe, ct, srv, src, sink = make_stack(tmp_path, kind=kind)
    conn = ct.connect(srv.address)
    try:
        fe.pause()
        conn.send_msg(("submit", "b0", src.name, batch("aa"), None))
        first = conn.recv_msg(10.0)
        assert pickle.dumps(first) == pickle.dumps(
            ("ack", "b0", "pending", None, None))
        fe.resume()
        fe.flush()                # b0 decided, and this link submitted it
        fe.pause()
        conn.send_msg(("submit", "b1", src.name, batch("bb"), None))
        assert pickle.dumps(conn.recv_msg(10.0)) == pickle.dumps(
            ("ack", "b1", "pending", None, None))
        assert srv.fates_on_ack_total == 0
        # resolve says what it said: a dict of trimmed acks
        conn.send_msg(("resolve", ("b0", "b1"), 0.0))
        ok, acks = conn.recv_msg(10.0)
        assert ok == "ok" and acks["b1"] == ("b1", "pending", None, None)
        assert acks["b0"][:2] == ("b0", APPLIED) and len(acks["b0"]) == 4
        assert srv.fates_by_resolve_total == 1
        # and the request's own new field, unset, is trimmed with cause
        assert pickle.dumps(_trim(tuple(SubmitReq("b", "s", (), None)))) \
            == pickle.dumps(("b", "s", (), None))
        assert _trim(tuple(SubmitReq("b", "s", (), None, None, True))) \
            == ("b", "s", (), None, None, True)
        fe.resume()
    finally:
        conn.close()
        srv.close()
        fe.close()
        sched.wal.close()


@pytest.mark.parametrize("kind", LINKS)
def test_reply_lost_with_its_fates_ends_in_deduped_one_fold(tmp_path, kind):
    """The link resets between a fate's reply and its receipt: the
    server has reported b0's fate (and dropped its ticket) in the reply
    to b1, which the client never read. Both stay in doubt, both are
    resubmitted on the next link and both come back DEDUPED: one fold
    each, none missing."""
    sched, fe, ct, srv, src, sink = make_stack(tmp_path, kind=kind)
    faults = WireFaults()
    prod = RemoteProducer(FaultyTransport(ct, faults), srv.address,
                          name="p0", io_timeout_s=2.0,
                          policy=ReconnectPolicy("p0", base_s=0.001,
                                                 cap_s=0.005, jitter=0.0))
    try:
        fe.pause()
        t0 = prod.submit(src, batch("xx xx yy"), batch_id="b0")
        fe.resume()
        fe.flush()                        # b0 applied, fate undelivered
        faults.partition("s2c")           # requests arrive, replies vanish
        t1 = prod.submit(src, batch("zz"), batch_id="b1")
        assert srv.fates_on_ack_total == 1        # sent...
        assert not t0.done() and not t1.done()    # ...and never received
        assert prod.link_failures == 1
        assert set(prod.in_doubt_ids()) == {"b0", "b1"}
        assert "b0" not in srv._tickets   # reported once, then let go
        faults.heal()
        r0, r1 = t0.result(10), t1.result(10)
        assert r0.status == DEDUPED and r1.status in (APPLIED, DEDUPED)
        assert t0.submits == 2 and prod.reconnects_total == 1
        # the hello of the new link saw both in the mirror
        assert set(prod.last_hello["admitted"]) == {"b0", "b1"}
        fe.flush()
        view = sched.view(sink.name)
        assert view[("xx", 2.0)] == 1 and view[("yy", 1.0)] == 1
        assert view[("zz", 1.0)] == 1
        assert ("xx", 4.0) not in view and ("zz", 2.0) not in view
        assert prod.in_doubt_ids() == ()
    finally:
        shut(prod, srv, fe, sched)


@pytest.mark.parametrize("kind", LINKS)
def test_idle_link_learns_its_fates_by_resolve(tmp_path, kind):
    sched, fe, ct, srv, src, sink = make_stack(tmp_path, kind=kind)
    prod = RemoteProducer(ct, srv.address, name="p0")
    try:
        fe.pause()
        t0 = prod.submit(src, batch("qq"), batch_id="b0")
        t1 = prod.submit(src, batch("rr"), batch_id="b1")
        fe.resume()
        # no further submit: the fates come the way they always did
        assert t0.result(10).status == APPLIED
        assert t1.result(10).status == APPLIED
        assert prod.resolves_total >= 1 and prod.fates_on_ack_total == 0
        assert srv.fates_by_resolve_total == 2
        assert srv.fates_on_ack_total == 0
        # the link's list still names them; the next submit finds their
        # tickets gone from the table and reports nothing twice
        t2 = prod.submit(src, batch("ss"), batch_id="b2")
        assert srv.fates_on_ack_total == 0
        assert t2.result(10).status == APPLIED
        assert t0.submits == t1.submits == 1 and prod.deduped_total == 0
    finally:
        shut(prod, srv, fe, sched)


def test_ticket_bound_and_eviction_with_the_links_list_beside(tmp_path):
    """The table's bound holds with the per-connection list beside it:
    the list names ids, never tickets; an evicted id is passed over (it
    resolves "unknown" -> resubmit -> DEDUPED as before); and a closed
    connection's list goes with its handler."""
    sched, fe, lt, srv, src, sink = make_stack(tmp_path, max_tickets=2)
    prod = RemoteProducer(lt, srv.address, name="p0")
    try:
        fe.pause()      # tickets stay undecided: eviction is deterministic
        ts = [prod.submit(src, batch(f"e{i}"), batch_id=f"b{i}")
              for i in range(4)]
        assert srv.evicted_tickets == 2 and list(srv._tickets) == ["b2", "b3"]
        (handler,) = srv._handlers
        fe.resume()
        fe.flush()                # b0..b3 all applied (admitted before)
        t4 = prod.submit(src, batch("e4"), batch_id="b4")
        # b4's ack carried the fates of the two the table still held;
        # the evicted two were passed over, not reported and not kept
        assert ts[2].done() and ts[3].done()
        assert not ts[0].done() and not ts[1].done()
        assert srv.fates_on_ack_total == 2
        assert len(srv._tickets) <= 2
        # the evicted ones: resolve -> "unknown" -> resubmit -> DEDUPED
        assert ts[0].result(10).status == DEDUPED
        assert ts[1].result(10).status == DEDUPED
        assert t4.result(10).status == APPLIED
        assert prod.deduped_total == 2
        fe.flush()
        view = sched.view(sink.name)
        assert all(view[(f"e{i}", 1.0)] == 1 for i in range(5))
        # nothing is left behind: every ticket reported and let go
        assert len(srv._tickets) == 0
        prod.close()
        handler.join(5.0)
        assert not handler.is_alive()
        assert srv._conns == []
    finally:
        shut(prod, srv, fe, sched)


def test_links_list_holds_ids_and_is_bounded(tmp_path):
    sched, fe, lt, srv, src, sink = make_stack(tmp_path, max_tickets=3)
    try:
        fe.pause()
        # the handler's own view, by driving ``_dispatch`` on this thread
        for i in range(5):
            reply = srv._dispatch(("submit", f"b{i}", src.name,
                                   batch(f"w{i}"), None, None, True))
            assert reply == ("ack", f"b{i}", "pending", None, None)
        link = srv._link()
        assert list(link.undecided) == ["b2", "b3", "b4"]   # ids, bounded
        assert link.undecided.maxlen == srv.max_tickets
        assert list(srv._tickets) == ["b2", "b3", "b4"]
        # a ticket's one holder is the table
        assert all(isinstance(b, str) for b in link.undecided)
        fe.resume()
        fe.flush()
        reply = srv._dispatch(("submit", "b5", src.name, batch("w5"),
                               None, None, True))
        ack = SubmitAck(*reply[1:])
        assert [f[0] for f in ack.fates] == ["b2", "b3", "b4"]
        assert all(f[1] == APPLIED and len(f) == 4 for f in ack.fates)
        assert list(srv._tickets) in ([], ["b5"])
        assert link.counters()["fates_on_ack"] == 3
        assert link.counters()["sock_calls"] == 0    # no connection here
    finally:
        srv.close()
        fe.close()
        sched.wal.close()


def test_rpc_ops_event_carries_the_links_counters_beside_ops(tmp_path):
    """Under tracing a handler's ``rpc_ops`` event has the connection's
    counters under ``link``, beside ``ops`` and no row of it: over TCP,
    three socket calls a request that arrives whole, and the fates by
    the reply that took them."""
    from reflow_tpu import obs
    from reflow_tpu.obs import trace as trace_mod
    obs.disable()
    trace_mod.reset()
    sched, fe, ct, srv, src, sink = make_stack(tmp_path, kind="tcp")
    prod = RemoteProducer(ct, srv.address, name="p0")
    obs.enable()
    try:
        fe.pause()
        t0 = prod.submit(src, batch("aa"), batch_id="b0")
        fe.resume()
        fe.flush()
        t1 = prod.submit(src, batch("bb"), batch_id="b1")   # carries b0's
        assert t0.done() and t1.result(10).status == APPLIED  # a resolve
        prod.close()
        (handler,) = srv._handlers
        handler.join(10)
        evs = [e for e in obs.chrome_events()
               if e.get("ph") == "X" and e["name"] == "rpc_ops"]
        last = max(evs, key=lambda e: e["ts"])["args"]
        assert set(last) == {"since", "ops", "link"}
        assert set(last["ops"]) <= {"submit", "resolve", "other"}
        link = last["link"]
        requests = sum(row[0] for row in last["ops"].values())
        assert link["frames_in"] == requests == srv.requests_total
        assert link["fates_on_ack"] == srv.fates_on_ack_total == 1
        assert link["fates_by_resolve"] == srv.fates_by_resolve_total == 1
        # poll, recv_into, send a request; the handler's wait for the
        # next request may have timed out a few 0.2 s slices besides,
        # and its last wait ended in the close
        assert 3 * requests <= link["sock_calls"] <= 3 * requests + 12
    finally:
        obs.disable()
        trace_mod.reset()
        shut(prod, srv, fe, sched)


def test_many_links_learn_every_fate_once_under_a_short_switch_interval(
        tmp_path):
    """More links than cores, each a closed loop that submits and now
    and then looks at its oldest ticket (the load generator's shape),
    the interpreter switching threads every 50 us: every ticket is
    decided APPLIED once — by an ack or by a resolve, never both, never
    neither — nothing is resubmitted, and each batch folded once."""
    import sys
    import threading
    sched, fe, ct, srv, src, sink = make_stack(tmp_path, kind="tcp")
    lanes, per_lane = 12, 40
    prods = [RemoteProducer(ct, srv.address, name=f"L{i}")
             for i in range(lanes)]
    errors = []

    def lane(i):
        try:
            prod, pending = prods[i], []
            for k in range(per_lane):
                pending.append(prod.submit(src, batch(f"w{i}x{k}"),
                                           batch_id=f"L{i}-{k}"))
                if k % 4 == 3:
                    try:
                        pending[0].result(timeout=1e-4)
                    except TimeoutError:
                        pass
                    pending = [t for t in pending if not t.done()]
            for t in pending:
                assert t.result(30).status == APPLIED
        except BaseException as e:  # noqa: BLE001 - reported below
            errors.append(repr(e))

    was = sys.getswitchinterval()
    sys.setswitchinterval(5e-5)
    try:
        threads = [threading.Thread(target=lane, args=(i,))
                   for i in range(lanes)]
        for t in threads:
            t.start()
        for t in threads:
            t.join(120)
        assert not any(t.is_alive() for t in threads)
    finally:
        sys.setswitchinterval(was)
    try:
        assert errors == []
        n = lanes * per_lane
        assert sum(p.submits_total for p in prods) == n
        assert sum(p.resubmits_total + p.deduped_total + p.link_failures
                   for p in prods) == 0
        assert all(p.in_doubt_ids() == () for p in prods)
        # each fate went out once: on an ack or by a resolve (or, where
        # the pump was quicker than the handler, on the submit's own ack)
        assert srv.fates_on_ack_total + srv.fates_by_resolve_total <= n
        assert sum(p.fates_on_ack_total for p in prods) \
            == srv.fates_on_ack_total > 0
        assert len(srv._tickets) == 0
        fe.flush()
        view = sched.view(sink.name)
        assert all(view[(f"w{i}x{k}", 1.0)] == 1
                   for i in range(lanes) for k in range(per_lane))
    finally:
        for p in prods:
            p.close()
        srv.close()
        fe.close()
        sched.wal.close()
