"""Ingestion-frontend tests (``reflow_tpu.serve``).

The contract under test: N concurrent producers ``submit()`` to a
frontend-owned scheduler and (a) every micro-batch's fate is reported
through its ticket (applied / deduped / rejected / shed — never silent),
(b) the coalesced macro-tick results equal the bare one-tick-per-batch
loop's (the differential property), (c) lifecycle edges — blocked
producers at ``close()``, a crashing pump, a durable crash + recover —
leave no ticket unresolved and no batch folded twice.

Tests that need a deterministically full queue use ``pause()`` (the
pump stops draining, admission keeps queueing), which is exactly the
backpressure regime a slow device executor produces.
"""

from __future__ import annotations

import threading

import numpy as np
import pytest

from reflow_tpu.delta import DeltaBatch
from reflow_tpu.graph import GraphError
from reflow_tpu.scheduler import DirtyScheduler, SourceCursor
from reflow_tpu.serve import (APPLIED, DEDUPED, REJECTED, SHED,
                              CoalesceWindow, FrontendClosed, IngestFrontend,
                              PumpCrashed, build_feeds)
from reflow_tpu.serve.queues import Entry, batch_nbytes
from reflow_tpu.serve.tickets import Ticket
from reflow_tpu.utils.faults import CrashInjector, CrashPoint
from reflow_tpu.utils.metrics import summarize_serve
from reflow_tpu.workloads import wordcount

WINDOW = CoalesceWindow(max_rows=256, max_ticks=8, max_latency_s=0.002)


def make_frontend(**kw):
    g, src, sink = wordcount.build_graph()
    sched = DirtyScheduler(g)
    kw.setdefault("window", WINDOW)
    return IngestFrontend(sched, **kw), sched, src, sink


def lines_batch(*words: str) -> DeltaBatch:
    return wordcount.ingest_lines([" ".join(words)])


# -- the happy path ---------------------------------------------------------

def test_submit_applies_and_reports_tick():
    fe, sched, src, sink = make_frontend()
    with fe:
        t = fe.submit(src, lines_batch("a", "b", "a"))
        r = t.result(timeout=5)
        assert r.applied and r.status == APPLIED
        assert r.tick >= 1
        fe.flush()
        assert dict(sched.view(sink.name)) == {("a", 2.0): 1, ("b", 1.0): 1}


def test_multi_producer_differential_matches_bare_loop():
    fe, sched, src, sink = make_frontend()
    n_prod, per = 8, 25
    payload = lambda p, j: lines_batch(f"w{p}", f"w{(p + j) % 5}", "c")

    def produce(p):
        for j in range(per):
            fe.submit(src, payload(p, j)).result(timeout=10)

    threads = [threading.Thread(target=produce, args=(p,))
               for p in range(n_prod)]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    fe.flush()
    fe.close()

    g2, src2, sink2 = wordcount.build_graph()
    bare = DirtyScheduler(g2)
    for p in range(n_prod):
        for j in range(per):
            bare.push(src2, payload(p, j))
            bare.tick()
    assert dict(sched.view(sink.name)) == dict(bare.view(sink2.name))
    # coalescing actually engaged: fewer ticks than micro-batches,
    # and the pump never forced a mid-stream sync
    assert sched._tick < n_prod * per
    assert sched.forced_syncs == 0
    sm = summarize_serve(fe)
    assert sm.applied == n_prod * per
    assert sm.coalesce_factor > 1.0


def test_empty_batch_is_reported_applied_without_a_tick():
    fe, sched, src, _sink = make_frontend()
    with fe:
        r = fe.submit(src, DeltaBatch.empty()).result(timeout=5)
        assert r.applied and r.tick is None and r.reason == "empty batch"


def test_submit_to_non_source_rejected():
    fe, sched, _src, sink = make_frontend()
    with fe:
        with pytest.raises(GraphError):
            fe.submit(sink, lines_batch("a"))


# -- exactly-once admission -------------------------------------------------

def test_duplicate_batch_id_resolves_deduped():
    fe, sched, src, sink = make_frontend()
    with fe:
        r1 = fe.submit(src, lines_batch("a"), batch_id="b0").result(timeout=5)
        fe.flush()
        r2 = fe.submit(src, lines_batch("a"), batch_id="b0").result(timeout=5)
        assert r1.status == APPLIED
        assert r2.status == DEDUPED
        fe.flush()
        assert dict(sched.view(sink.name)) == {("a", 1.0): 1}


def test_duplicate_within_one_window_deduped_before_tick():
    fe, sched, src, sink = make_frontend()
    with fe:
        fe.pause()
        t1 = fe.submit(src, lines_batch("a"), batch_id="dup")
        t2 = fe.submit(src, lines_batch("a"), batch_id="dup")
        assert t2.result(timeout=5).status == DEDUPED  # before any tick
        fe.resume()
        assert t1.result(timeout=5).status == APPLIED
        fe.flush()
        assert dict(sched.view(sink.name)) == {("a", 1.0): 1}


def test_minted_ids_resume_past_recovered_window(tmp_path):
    from reflow_tpu.wal import DurableScheduler, recover

    g, src, sink = wordcount.build_graph()
    sched = DurableScheduler(g, wal_dir=str(tmp_path / "wal"))
    fe = IngestFrontend(sched, window=WINDOW)
    for w in ("a", "b"):
        fe.submit(src, lines_batch(w))
    fe.flush()
    fe.close()

    g2, src2, sink2 = wordcount.build_graph()
    fresh = DurableScheduler(g2, wal_dir=str(tmp_path / "wal"))
    recover(fresh, str(tmp_path / "wal"))
    fe2 = IngestFrontend(fresh, window=WINDOW)
    # the new frontend's mint must not collide with recovered ids
    r = fe2.submit(src2, lines_batch("c")).result(timeout=5)
    assert r.status == APPLIED
    fe2.flush()
    fe2.close()
    assert dict(fresh.view(sink2.name)) == {
        ("a", 1.0): 1, ("b", 1.0): 1, ("c", 1.0): 1}


# -- backpressure policies --------------------------------------------------

def test_reject_policy_resolves_rejected_when_full():
    fe, sched, src, _sink = make_frontend(policy="reject", queue_batches=2)
    fe.pause()
    try:
        t1 = fe.submit(src, lines_batch("a"))
        t2 = fe.submit(src, lines_batch("b"))
        t3 = fe.submit(src, lines_batch("c"))
        r3 = t3.result(timeout=5)
        assert r3.status == REJECTED and "backpressure" in r3.reason
        assert not t1.done() and not t2.done()
    finally:
        fe.resume()
        fe.close()
    assert t1.result(timeout=5).applied and t2.result(timeout=5).applied


def test_block_policy_waits_for_room_then_applies():
    fe, sched, src, sink = make_frontend(policy="block", queue_batches=1)
    fe.pause()
    fe.submit(src, lines_batch("a"))
    done = threading.Event()
    holder = {}

    def blocked_producer():
        holder["r"] = fe.submit(src, lines_batch("b")).result(timeout=10)
        done.set()

    th = threading.Thread(target=blocked_producer)
    th.start()
    assert not done.wait(0.1)       # genuinely blocked on admission
    fe.resume()                     # pump drains; room opens
    assert done.wait(5)
    th.join()
    assert holder["r"].applied
    fe.flush()
    fe.close()
    assert dict(sched.view(sink.name)) == {("a", 1.0): 1, ("b", 1.0): 1}


def test_block_policy_timeout_resolves_rejected():
    fe, _sched, src, _sink = make_frontend(policy="block", queue_batches=1)
    fe.pause()
    try:
        fe.submit(src, lines_batch("a"))
        r = fe.submit(src, lines_batch("b"),
                      timeout=0.05).result(timeout=5)
        assert r.status == REJECTED and "timed out" in r.reason
    finally:
        fe.resume()
        fe.close()


def test_shed_oldest_policy_evicts_and_reports():
    fe, sched, src, sink = make_frontend(policy="shed-oldest",
                                         queue_batches=2)
    fe.pause()
    t1 = fe.submit(src, lines_batch("a"))
    t2 = fe.submit(src, lines_batch("b"))
    t3 = fe.submit(src, lines_batch("c"))
    r1 = t1.result(timeout=5)
    assert r1.status == SHED and "re-send" in r1.reason
    fe.resume()
    fe.flush()
    fe.close()
    assert t2.result(timeout=5).applied and t3.result(timeout=5).applied
    # the shed batch's rows were NOT folded
    assert dict(sched.view(sink.name)) == {("b", 1.0): 1, ("c", 1.0): 1}


def test_shed_batch_resent_with_same_id_is_admitted():
    # the SHED contract: the ticket tells the upstream to re-send, so a
    # re-send with the SAME batch_id must be admitted (the batch never
    # reached the scheduler), not swallowed as DEDUPED
    fe, sched, src, sink = make_frontend(policy="shed-oldest",
                                         queue_batches=2)
    fe.pause()
    t1 = fe.submit(src, lines_batch("a"), batch_id="r0")
    fe.submit(src, lines_batch("b"), batch_id="r1")
    fe.submit(src, lines_batch("c"), batch_id="r2")
    assert t1.result(timeout=5).status == SHED
    fe.resume()
    fe.flush()
    r = fe.submit(src, lines_batch("a"), batch_id="r0").result(timeout=5)
    assert r.status == APPLIED
    fe.flush()
    fe.close()
    assert dict(sched.view(sink.name)) == {
        ("a", 1.0): 1, ("b", 1.0): 1, ("c", 1.0): 1}


def test_blocked_duplicate_submits_fold_exactly_once():
    # two producers race the same batch_id through a full queue under
    # the block policy: the admission wait drops the lock, so the loser
    # must re-check dedup on wakeup — exactly one APPLIED, one DEDUPED
    fe, sched, src, sink = make_frontend(policy="block", queue_batches=1)
    fe.pause()
    fe.submit(src, lines_batch("x"), batch_id="seed")   # fills the queue
    results = []

    def dup_producer():
        results.append(
            fe.submit(src, lines_batch("d"), batch_id="dup").result(
                timeout=10))

    threads = [threading.Thread(target=dup_producer) for _ in range(2)]
    for th in threads:
        th.start()
    import time
    time.sleep(0.05)               # both reach the admission wait
    fe.resume()
    for th in threads:
        th.join(timeout=10)
    fe.flush()
    fe.close()
    assert sorted(r.status for r in results) == [APPLIED, DEDUPED]
    assert dict(sched.view(sink.name)) == {("x", 1.0): 1, ("d", 1.0): 1}


def test_oversized_batch_rejected_not_shed():
    fe, _sched, src, _sink = make_frontend(policy="shed-oldest",
                                           max_bytes=8)
    with fe:
        r = fe.submit(src, lines_batch("a", "b", "c")).result(timeout=5)
        assert r.status == REJECTED and "budget" in r.reason


# -- lifecycle --------------------------------------------------------------

def test_close_releases_blocked_producers():
    fe, _sched, src, _sink = make_frontend(policy="block", queue_batches=1)
    fe.pause()
    fe.submit(src, lines_batch("a"))
    errs = []
    started = threading.Event()

    def blocked_producer():
        started.set()
        try:
            fe.submit(src, lines_batch("b"))
        except FrontendClosed as e:
            errs.append(e)

    th = threading.Thread(target=blocked_producer)
    th.start()
    started.wait(5)
    import time
    time.sleep(0.05)               # let it reach the admission wait
    fe.close()                     # must release, not deadlock
    th.join(timeout=5)
    assert not th.is_alive()
    assert len(errs) == 1
    with pytest.raises(FrontendClosed):
        fe.submit(src, lines_batch("c"))


def test_close_with_flush_ticks_remaining_backlog():
    fe, sched, src, sink = make_frontend()
    fe.pause()
    t = fe.submit(src, lines_batch("a"))
    fe.close(flush=True)
    assert t.result(timeout=5).applied
    assert dict(sched.view(sink.name)) == {("a", 1.0): 1}


def test_close_without_flush_fails_queued_tickets():
    fe, sched, src, sink = make_frontend()
    fe.pause()
    t = fe.submit(src, lines_batch("a"))
    fe.close(flush=False)
    with pytest.raises(FrontendClosed):
        t.result(timeout=5)
    assert dict(sched.view(sink.name)) == {}


def test_close_timeout_does_not_seal_while_pump_drains():
    # a close() whose join times out mid-macro-tick must NOT report
    # closed / seal the scheduler's WAL while the pump can still append
    fe, sched, src, _sink = make_frontend()
    sealed = []
    sched.close = lambda: sealed.append(1)
    entered, release = threading.Event(), threading.Event()
    orig = sched.tick_many

    def slow_tick_many(*a, **kw):
        entered.set()
        release.wait(10)
        return orig(*a, **kw)

    sched.tick_many = slow_tick_many
    t = fe.submit(src, lines_batch("a"))
    assert entered.wait(5)          # pump is mid-macro-tick
    with pytest.raises(TimeoutError):
        fe.close(timeout=0.05)
    assert not sealed               # WAL-seal must not have run
    with pytest.raises(FrontendClosed):
        fe.submit(src, lines_batch("b"))   # admission already refused
    release.set()
    fe.close()                      # retry finishes the shutdown
    assert sealed
    assert t.result(timeout=5).applied


def test_close_is_idempotent():
    fe, _sched, _src, _sink = make_frontend()
    fe.close()
    fe.close()


def test_drain_runs_scheduler_drain_under_pause():
    fe, sched, src, sink = make_frontend()
    fe.submit(src, lines_batch("a")).result(timeout=5)
    # wordcount quiesces per tick: one probe tick confirms it
    assert fe.drain() <= 1
    fe.close()
    assert dict(sched.view(sink.name)) == {("a", 1.0): 1}


def test_latency_trigger_fires_under_light_traffic():
    # neither the rows nor the ticks trigger can fire for one tiny
    # batch; only the latency bound gets it ticked
    fe, _sched, src, _sink = make_frontend(window=CoalesceWindow(
        max_rows=1 << 20, max_ticks=1 << 20, max_latency_s=0.01))
    with fe:
        r = fe.submit(src, lines_batch("a")).result(timeout=5)
        assert r.applied


# -- pump crash -------------------------------------------------------------

def test_pump_crash_fails_tickets_and_closes_frontend():
    crash = CrashInjector(1, only="pump_before_tick")
    fe, _sched, src, _sink = make_frontend(crash=crash)
    t = fe.submit(src, lines_batch("a"))
    with pytest.raises(PumpCrashed):
        t.result(timeout=5)
    assert crash.fired
    assert isinstance(fe.pump_error, CrashPoint)
    with pytest.raises(FrontendClosed):
        fe.submit(src, lines_batch("b"))
    with pytest.raises(PumpCrashed):
        fe.flush()
    fe.close()                      # still clean to close


def test_revive_restarts_a_dead_pump_thread():
    """When the pump THREAD died with the crash (vs only the state
    flag flipping from another thread), revive() must re-arm the loop
    itself — otherwise nothing drains the queues and flush() waits
    forever (the chaos-bench failover hang)."""
    crash = CrashInjector(1, only="pump_before_tick")
    fe, sched, src, sink = make_frontend(crash=crash)
    with pytest.raises(PumpCrashed):
        fe.submit(src, lines_batch("a")).result(timeout=5)
    fe._thread.join(timeout=5)
    assert not fe._thread.is_alive()     # the loop really exited
    fe.revive()
    assert fe._thread.is_alive()         # ...and revive re-armed it
    assert fe.submit(src, lines_batch("z")).result(timeout=5).applied
    fe.flush(timeout=5)                  # regression: hung forever
    assert dict(sched.view(sink.name)).get(("z", 1.0)) == 1
    fe.close()


def test_producer_submit_crash_dies_in_submitting_thread():
    """producer_submit is a PRODUCER-thread seam: the kill surfaces out
    of submit() itself, before any frontend state mutates — the pump
    survives and the next submit applies normally."""
    crash = CrashInjector(1, only="producer_submit")
    fe, _sched, src, _sink = make_frontend(crash=crash)
    with pytest.raises(CrashPoint):
        fe.submit(src, lines_batch("a"))
    assert crash.fired and crash.fired_seam == "producer_submit"
    assert fe.submitted == 0 and fe.pump_error is None
    r = fe.submit(src, lines_batch("a")).result(timeout=5)
    assert r.applied
    fe.close()


def test_producer_admitted_crash_batch_survives_and_resend_dedups():
    """producer_admitted fires AFTER the batch is queued and its id
    noted: the producer dies, but the pump still applies the batch, and
    the upstream's resend (it cannot know the fate) dedups — the
    exactly-once story for a producer killed mid-return."""
    crash = CrashInjector(1, only="producer_admitted")
    fe, sched, src, sink = make_frontend(crash=crash)
    with pytest.raises(CrashPoint):
        fe.submit(src, lines_batch("a"), batch_id="k0")
    assert crash.fired and crash.fired_seam == "producer_admitted"
    r = fe.submit(src, lines_batch("a"), batch_id="k0").result(timeout=5)
    assert r.status == DEDUPED
    fe.flush()
    fe.close()
    assert dict(sched.view(sink.name)) == {("a", 1.0): 1}


def test_pump_coalesce_crash_fails_window_tickets():
    """pump_coalesce cuts between the host-side merge and everything
    durable/device-side: the whole drained window's tickets must fail
    PumpCrashed (nothing was pushed, so nothing half-applied)."""
    crash = CrashInjector(1, only="pump_coalesce")
    fe, _sched, src, _sink = make_frontend(crash=crash)
    t = fe.submit(src, lines_batch("a"))
    with pytest.raises(PumpCrashed):
        t.result(timeout=5)
    assert crash.fired and crash.fired_seam == "pump_coalesce"
    assert isinstance(fe.pump_error, CrashPoint)
    fe.close()


def test_durable_pump_crash_then_recover_exactly_once(tmp_path):
    """The acceptance differential: kill the pump mid-stream on a
    durable scheduler, recover a fresh one, re-send EVERYTHING (the
    upstream can't know what committed), and the final views must equal
    a clean run's — committed batches dedup, lost ones apply."""
    from reflow_tpu.wal import DurableScheduler, recover

    batches = [(f"b{i}", lines_batch(f"w{i % 3}", "c")) for i in range(12)]

    g, src, sink = wordcount.build_graph()
    sched = DurableScheduler(g, wal_dir=str(tmp_path / "wal"))
    crash = CrashInjector(3, only="pump_after_tick")
    fe = IngestFrontend(sched, crash=crash, window=CoalesceWindow(
        max_rows=4, max_ticks=2, max_latency_s=0.001))
    outcomes = {}
    for bid, b in batches:
        try:
            outcomes[bid] = fe.submit(src, b, batch_id=bid).result(timeout=5)
        except (PumpCrashed, FrontendClosed):
            break
    assert crash.fired
    fe.close()

    g2, src2, sink2 = wordcount.build_graph()
    fresh = DurableScheduler(g2, wal_dir=str(tmp_path / "wal"))
    report = recover(fresh, str(tmp_path / "wal"))
    fe2 = IngestFrontend(fresh, window=WINDOW)
    statuses = {bid: fe2.submit(src2, b, batch_id=bid).result(timeout=5)
                for bid, b in batches}
    fe2.flush()
    fe2.close()
    # everything the first run confirmed applied must now dedup
    for bid, r in outcomes.items():
        if r.applied:
            assert statuses[bid].status == DEDUPED, bid

    g3, src3, sink3 = wordcount.build_graph()
    clean = DirtyScheduler(g3)
    for bid, b in batches:
        clean.push(src3, b, batch_id=bid)
        clean.tick()
    assert dict(fresh.view(sink2.name)) == dict(clean.view(sink3.name))
    assert report.wal_records > 0


# -- coalescing unit tests --------------------------------------------------

def _entry(source, batch, bid, device=False, rows=None):
    return Entry(Ticket(bid), source, batch, bid, batch_nbytes(batch),
                 0.0, device,
                 0 if device else (len(batch) if rows is None else rows))


def test_build_feeds_merges_host_runs_up_to_max_rows():
    g, src, _sink = wordcount.build_graph()
    entries = [_entry(src, lines_batch(f"w{i}"), f"b{i}") for i in range(5)]
    feeds = build_feeds({src.id: entries}, max_rows=2)
    # 5 one-row batches at max_rows=2 -> 3 feeds: [2, 2, 1]
    assert [len(f.ids[src]) for f in feeds] == [2, 2, 1]
    assert len(feeds[0].batches[src]) == 2
    assert feeds[0].ids[src] == ["b0", "b1"]


def test_build_feeds_device_batch_rides_alone():
    class FakeDevice:
        # quacks like a device-resident batch (scheduler detection is
        # hasattr(batch, "nonzero")); concat with it would force a sync
        nonzero = None
        keys = values = weights = None

    g, src, _sink = wordcount.build_graph()
    dev = FakeDevice()
    entries = [_entry(src, lines_batch("a"), "h0"),
               _entry(src, dev, "d0", device=True),
               _entry(src, lines_batch("b"), "h1"),
               _entry(src, lines_batch("c"), "h2")]
    feeds = build_feeds({src.id: entries}, max_rows=256)
    # the device batch splits the host run: [h0], [d0], [h1+h2]
    assert [f.ids[src] for f in feeds] == [["h0"], ["d0"], ["h1", "h2"]]
    assert feeds[1].batches[src] is dev


def test_build_feeds_parallel_across_sources():
    g, src, _sink = wordcount.build_graph()
    g2, src2, _sink2 = wordcount.build_graph()
    a = [_entry(src, lines_batch("a"), "a0")]
    b = [_entry(src2, lines_batch("b"), "b0"),
         _entry(src2, lines_batch("c"), "b1")]
    # distinct queue keys: build_feeds groups by the frontend's queue
    # key, the Node objects inside the entries carry the identity
    feeds = build_feeds({0: a, 1: b}, max_rows=1)
    # feed 0 carries BOTH sources' first chunks (one macro-tick, not
    # one tick per source); feed 1 carries only src2's leftover
    assert len(feeds) == 2
    assert set(feeds[0].batches) == {src, src2}
    assert set(feeds[1].batches) == {src2}


def test_degenerate_window_rejected():
    with pytest.raises(ValueError):
        CoalesceWindow(max_rows=0)
    with pytest.raises(ValueError):
        CoalesceWindow(max_ticks=0)


# -- SourceCursor.resume edge cases (satellite) -----------------------------

def test_cursor_resume_skips_malformed_and_foreign_ids():
    g, src, _sink = wordcount.build_graph()
    sched = DirtyScheduler(g)
    for bid in ("words@3", "words@xyz", "words@", "other@9",
                "words7", "@5", "words@1"):
        sched._seen_batch_ids[bid] = None
    cur = SourceCursor.resume(sched, src)
    assert cur.next_id() == "words@4"   # max valid own id (3) + 1


def test_cursor_resume_empty_window_starts_at_zero():
    g, src, _sink = wordcount.build_graph()
    sched = DirtyScheduler(g)
    assert SourceCursor.resume(sched, src).next_id() == "words@0"


# -- dedup-window eviction order (satellite) --------------------------------

def test_rejected_replay_does_not_refresh_eviction_order():
    g, src, _sink = wordcount.build_graph()
    sched = DirtyScheduler(g, dedup_window=3)
    for bid in ("a", "b", "c"):
        assert sched.push(src, lines_batch("x"), batch_id=bid)
    # replaying "a" is rejected and must NOT move it to the back
    assert not sched.push(src, lines_batch("x"), batch_id="a")
    assert list(sched._seen_batch_ids) == ["a", "b", "c"]
    # a new accepted id evicts "a" (the oldest ACCEPTED), not "b"
    assert sched.push(src, lines_batch("x"), batch_id="d")
    assert list(sched._seen_batch_ids) == ["b", "c", "d"]
    # "a" is now past the horizon: a replay is silently re-accepted —
    # exactly the documented at-least-once boundary
    assert sched.push(src, lines_batch("x"), batch_id="a")


def test_replay_past_horizon_order_under_interleaving():
    g, src, _sink = wordcount.build_graph()
    sched = DirtyScheduler(g, dedup_window=2)
    assert sched.push(src, lines_batch("x"), batch_id="p0")
    assert sched.push(src, lines_batch("x"), batch_id="p1")
    assert not sched.push(src, lines_batch("x"), batch_id="p0")  # in window
    assert sched.push(src, lines_batch("x"), batch_id="p2")      # evicts p0
    assert list(sched._seen_batch_ids) == ["p1", "p2"]
    assert not sched.push(src, lines_batch("x"), batch_id="p1")
    assert sched.push(src, lines_batch("x"), batch_id="p0")      # past it
