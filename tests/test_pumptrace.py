"""The pump cycle under tracing (docs/guide.md "Span catalog", PR 24).

The contract under test: with tracing on, (a) the private pump's spans
tile its thread's wall, every span of one window carries the same
``win``, and the ticket sub-span ``wire_wait`` nests inside ``fsync``
while the six stages still tile submit -> resolve exactly; (b) the
executor's own device-completion span ``window_device`` appears once per
dispatched window, for a loop graph and for a loop-free graph, in
dispatch order, and its watcher never touches an array a later window
donated; (d) the ingest server records ``rpc_serve`` and the frontend
``admit_lock_wait`` for a sampled ticket that carried no wire ``cause``,
and nothing for an unsampled one; (e) ``cpu_s <= dur`` wherever a span
carries it. With tracing off, (c), none of it exists: no watcher thread,
no ring, no busy seconds.
"""

from __future__ import annotations

import os
import threading
import time

import numpy as np
import pytest

from reflow_tpu import DirtyScheduler, FlowGraph, obs
from reflow_tpu.delta import DeltaBatch, Spec
from reflow_tpu.executors import get_executor
from reflow_tpu.net import LoopbackTransport
from reflow_tpu.obs import REGISTRY
from reflow_tpu.obs import trace as trace_mod
from reflow_tpu.serve import (CoalesceWindow, IngestFrontend,
                              RemoteProducer, RpcIngestServer)
from reflow_tpu.wal import DurableScheduler
from reflow_tpu.workloads import pagerank

K_SPACE = 32
PUMP = "reflow-ingest-pump"
#: the pump's top-level spans: together they must tile its wall (the
#: umbrella ``window`` overlaps them all and does not count)
TILING = ("pump_wait", "pump_turn", "host_merge", "window_stage",
          "pump_execute", "window_retire")


@pytest.fixture
def traced(monkeypatch):
    """Tracing on, every ticket sampled; rings cleared before/after."""
    obs.disable()
    trace_mod.reset()
    monkeypatch.setattr(trace_mod, "SAMPLE_EVERY", 1)
    obs.enable()
    yield
    obs.disable()
    trace_mod.reset()


def _loop_free():
    """source -> map -> reduce(sum): loop-free, sink-free, one source,
    so every feed takes the fused window path (the ``scan_fn`` whose
    outputs are all donated but the completion token)."""
    g = FlowGraph("pumptrace")
    spec = Spec((), np.float32, key_space=K_SPACE)
    s = g.source("s", spec)
    m = g.map(s, lambda v: v * np.float32(2), vectorized=True)
    g.reduce(m, "sum", tol=0.0)
    return g, s


def _batches(seed, n, rows=6):
    rng = np.random.default_rng(seed)
    return [DeltaBatch(rng.integers(0, K_SPACE, rows).astype(np.int64),
                       rng.integers(0, 8, rows).astype(np.float32),
                       np.ones(rows, np.int64)) for _ in range(n)]


def _loop():
    """A small PageRank: the fused fixpoint window program."""
    n = 64
    pr = pagerank.build_graph(n)
    web = pagerank.WebGraph.random(n, 400, seed=3)
    sched = DirtyScheduler(pr.graph, get_executor("tpu"))
    sched.push(pr.teleport, pagerank.teleport_batch(n))
    sched.push(pr.edges, web.initial_batch())
    sched.tick()
    return sched, pr.edges, lambda: web.churn(0.02)


def _spans():
    """``(name, track, t0_us, t1_us, args)`` of every recorded span."""
    evs = obs.chrome_events()
    tracks = {e["tid"]: e["args"]["name"] for e in evs
              if e.get("ph") == "M" and e["name"] == "thread_name"}
    return [(e["name"], tracks[e["tid"]], e["ts"], e["ts"] + e["dur"],
             e.get("args", {})) for e in evs if e.get("ph") == "X"]


def _drive_pipelined(sched, source, batches, *, max_rows, k):
    """One paused wave through a depth-2 pump: everything queues, then
    resume drains it as back-to-back windows of ``k`` ticks."""
    fe = IngestFrontend(sched, depth=2, window=CoalesceWindow(
        max_rows=max_rows, max_ticks=k, max_latency_s=0.002))
    fe.pause()
    tickets = [fe.submit(source, b) for b in batches]
    fe.resume()
    for t in tickets:
        assert t.result(timeout=60).applied
    fe.flush(timeout=60)
    return fe


# -- (a) tiling, window ids, wire_wait ---------------------------------------

def test_pump_spans_tile_and_share_window_ids(tmp_path, traced):
    g, s = _loop_free()
    ex = get_executor("tpu")
    sched = DurableScheduler(g, ex, wal_dir=str(tmp_path / "wal"),
                             fsync="tick", committer="thread")
    fe = _drive_pipelined(sched, s, _batches(1, 24), max_rows=6, k=2)
    assert fe.windows_pipelined >= 1
    ex.drain_device_watch()
    spans = _spans()
    fe.close()

    # the pump thread's wall, first to last top-level span, is covered
    top = sorted((t0, t1) for name, track, t0, t1, _ in spans
                 if track == PUMP and name in TILING)
    wall = max(t1 for _, t1 in top) - top[0][0]
    covered, edge = 0.0, top[0][0]
    for t0, t1 in top:                       # union, in case of overlap
        if t1 > edge:
            covered += t1 - max(t0, edge)
            edge = t1
    assert covered >= 0.95 * wall, (covered, wall)
    assert {n for n, tr, *_ in spans if tr == PUMP} >= set(TILING) - {
        "pump_wait"}

    # every span of one window carries the same id, once per window
    per_window = ("window_stage", "pump_execute", "device_dispatch",
                  "window_retire", "window_device", "resolve_block",
                  "queue_write", "tick_many")
    wins = {}
    for name, _, _, _, args in spans:
        if name in per_window:
            wins.setdefault(name, []).append(args["win"])
    n = fe.windows_staged
    assert n >= 12
    for name in per_window:
        assert sorted(wins[name]) == list(range(1, n + 1)), name
    merged = [a for nm, *_, a in spans if nm == "host_merge"]
    assert all(a["win"] <= a["win_last"] for a in merged)
    assert max(a["win_last"] for a in merged) == n
    # window -> fsync joins by LSN: every window's lsn is covered
    covered_lsn = max(a["lsn"] for nm, *_, a in spans if nm == "wal_fsync")
    assert all(a["lsn"] <= covered_lsn for nm, *_, a in spans
               if nm == "pump_execute")

    # tickets: six stages tile [t0, t_res]; wire_wait nests in fsync
    by_ticket = {}
    for name, track, t0, t1, args in spans:
        if track.startswith("ticket/"):
            by_ticket.setdefault(track, {})[name] = (t0, t1, args)
    assert len(by_ticket) == 24
    for track, st in by_ticket.items():
        edge = st["admission"][0]
        for stage in trace_mod.STAGES:
            assert st[stage][0] == pytest.approx(edge, abs=0.01), track
            edge = st[stage][1]
        f0, f1, fargs = st["fsync"]
        w0, w1, wargs = st["wire_wait"]
        assert w0 == pytest.approx(f0, abs=0.01)
        assert w0 <= w1 <= f1 + 0.01    # still emitted, and reads >= 0
        assert wargs["win"] == fargs["win"] == st["execute"][2]["win"]
        a0, a1, _ = st["admit_lock_wait"]
        assert st["admission"][0] <= a0 + 0.01
        assert a1 <= st["admission"][1] + 0.01
    tl = obs.ticket_timelines(obs.chrome_events())
    assert all(set(t["stages"]) == set(trace_mod.STAGES)
               and t["sum_us"] == pytest.approx(t["e2e_us"], abs=0.05)
               for t in tl.values())
    # every window's block says where it resolved and how many windows
    # were dispatched and unretired then; the frontend counts the
    # blocks that did not wait for their retire, and publishes it
    blocks = [a for nm, *_, a in spans if nm == "resolve_block"]
    assert all(a["where"] in ("committer", "pump")
               and 0 <= a["inflight"] <= fe.depth for a in blocks)
    # whether a block resolves before its window retires is a race
    # this drive does not decide (the next test holds the retire)
    assert 0 <= fe.blocks_resolved_before_retire <= n
    from reflow_tpu.utils.metrics import summarize_serve
    assert summarize_serve(fe).to_dict()[
        "blocks_resolved_before_retire"] == fe.blocks_resolved_before_retire


def test_block_resolves_before_a_held_retire(tmp_path, traced):
    """The count that does not wait for the retire, made certain: the
    lone window's retire is held until its block has resolved (at its
    durability point, on whichever thread got there), so the block
    finds its window dispatched and unretired."""
    g, s = _loop_free()
    sched = DurableScheduler(g, get_executor("tpu"),
                             wal_dir=str(tmp_path / "wal"),
                             fsync="tick", committer="thread")
    fe = IngestFrontend(sched, depth=2, window=CoalesceWindow(
        max_rows=6, max_ticks=2, max_latency_s=0.002))
    retire = sched.retire_staged
    held = []

    def held_retire(handle):
        deadline = time.monotonic() + 30
        while fe.applied < 2 and time.monotonic() < deadline:
            time.sleep(0.001)
        held.append(fe.applied)
        return retire(handle)

    sched.retire_staged = held_retire
    fe.pause()
    tickets = [fe.submit(s, b) for b in _batches(8, 2)]
    fe.resume()
    for t in tickets:
        assert t.result(timeout=60).applied
    fe.flush(timeout=60)
    spans = _spans()
    fe.close()
    assert held == [2] and fe.windows_staged == 1
    assert fe.blocks_resolved_before_retire == 1
    (blk,) = [a for nm, *_, a in spans if nm == "resolve_block"]
    assert blk["win"] == 1 and blk["tickets"] == 2
    (ret,) = [(t0, t1) for nm, _, t0, t1, _ in spans
              if nm == "window_retire"]
    (res,) = [(t0, t1) for nm, _, t0, t1, _ in spans
              if nm == "resolve_block"]
    assert res[0] <= ret[1]


@pytest.mark.parametrize("durable", [True, False])
def test_resolve_block_where_and_wire_wait_at_dispatch(durable, tmp_path,
                                                       traced, monkeypatch):
    """The window's block is wired when its dispatch returns: with the
    committer's fsync held a little, a durable window's ``resolve_block``
    runs on the committer with the window still in flight, its tickets'
    ``wire_wait`` is a sliver of their ``fsync`` stage (which holds the
    disk wait), and the six stages tile; a non-durable window resolves
    on the pump at its dispatch, itself in flight."""
    real = os.fsync

    def slow(fd):
        if threading.current_thread().name == "reflow-wal-committer":
            time.sleep(0.1)
        return real(fd)

    monkeypatch.setattr(os, "fsync", slow)
    g, s = _loop_free()
    ex = get_executor("tpu")
    sched = (DurableScheduler(g, ex, wal_dir=str(tmp_path / "wal"),
                              fsync="tick", committer="thread")
             if durable else DirtyScheduler(g, ex))
    fe = _drive_pipelined(sched, s, _batches(7, 2), max_rows=6, k=2)
    spans = _spans()
    fe.close()
    (blk,) = [a for nm, *_, a in spans if nm == "resolve_block"]
    assert blk["where"] == ("committer" if durable else "pump")
    assert blk["win"] == 1 and blk["tickets"] == 2
    # a lone window is retired as soon as its dispatch has returned:
    # held 100 ms at the disk, its block resolves after that retire
    assert blk["inflight"] == (0 if durable else 1)
    assert fe.blocks_resolved_before_retire == (0 if durable else 1)
    stages = {}
    for name, track, t0, t1, _ in spans:
        if track.startswith("ticket/"):
            stages.setdefault(track, {})[name] = t1 - t0
    assert len(stages) == 2
    for st in stages.values():
        assert 0.0 <= st["wire_wait"] <= st["fsync"] + 10.0
        if durable:
            assert st["fsync"] >= 50e3
            assert st["wire_wait"] < st["fsync"] / 2
    tl = obs.ticket_timelines(obs.chrome_events())
    assert len(tl) == 2 and all(
        set(t["stages"]) == set(trace_mod.STAGES)
        and t["sum_us"] == pytest.approx(t["e2e_us"], abs=0.05)
        for t in tl.values())


# -- (b) window_device ---------------------------------------------------------

@pytest.mark.parametrize("kind", ["loop", "loop_free"])
def test_window_device_one_span_per_window_in_order(kind, traced):
    if kind == "loop":
        sched, source, mk = _loop()
        batches = [mk() for _ in range(8)]
        max_rows = max(len(b) for b in batches)
    else:
        g, source = _loop_free()
        sched = DirtyScheduler(g, get_executor("tpu"))
        batches, max_rows = _batches(2, 8), 6
    fe = _drive_pipelined(sched, source, batches, max_rows=max_rows, k=2)
    sched.executor.drain_device_watch()
    dev = [(t0, t1, a) for n, tr, t0, t1, a in _spans()
           if n == "window_device"]
    fe.close()
    assert sched.executor.device_watch_error is None
    assert [a["win"] for *_, a in dev] == list(
        range(1, sched.megatick_windows + 1))
    assert all(tr.startswith("device/") for n, tr, *_ in _spans()
               if n == "window_device")
    # spans end in dispatch order and never overlap: each starts at the
    # later of its launch and the previous window's completion
    for (_, e0, _), (s1, e1, a1) in zip(dev, dev[1:]):
        assert s1 >= e0 - 0.01 and e1 >= s1
        assert a1["queued_s"] >= 0.0
    assert sched.executor.windows_done == len(dev) > 0
    if kind == "loop_free":
        assert any(k[0] == "pass_many" and k[-1] == "token"
                   for k in sched.executor._cache)
    assert sched.executor.device_busy_s == pytest.approx(
        1e-6 * sum(t1 - t0 for t0, t1, _ in dev), rel=1e-3)


def test_window_device_200_windows_touch_no_donated_array(traced):
    """Depth 2, K = 1, 200 windows back to back: every output of the
    loop-free window program except the completion token is donated by
    a later window while the watcher may still be waiting."""
    g, s = _loop_free()
    sched = DirtyScheduler(g, get_executor("tpu"))
    fe = _drive_pipelined(sched, s, _batches(3, 200), max_rows=6, k=1)
    ex = sched.executor
    ex.drain_device_watch()
    assert fe.windows_staged == 200
    assert ex.device_watch_error is None
    assert ex.windows_done == 200
    fe.close()
    assert not any(t.name.startswith("reflow-device-watch")
                   for t in threading.enumerate())


# -- (c) tracing off -----------------------------------------------------------

def test_tracing_off_builds_nothing(monkeypatch):
    obs.disable()
    trace_mod.reset()
    # every per-thread CPU clock read on the served path sits behind
    # ``trace.ENABLED``: the pump's, the committer's, and (PR 39) the
    # two a request costs an RPC handler
    cpu_reads = []
    real_thread_time = time.thread_time
    monkeypatch.setattr(time, "thread_time", lambda: (
        cpu_reads.append(threading.current_thread().name),
        real_thread_time())[1])
    g, s = _loop_free()
    sched = DirtyScheduler(g, get_executor("tpu"))
    key = sched.publish_metrics(name="pumptrace-off")
    fe = _drive_pipelined(sched, s, _batches(4, 12), max_rows=6, k=2)
    assert fe.windows_staged >= 6
    # the same frontend behind the ingest server: submits and the
    # tickets' resolve long-polls through a handler thread
    lt = LoopbackTransport()
    srv = RpcIngestServer(fe, lt).start()
    prod = RemoteProducer(lt, srv.address, name="p-off")
    try:
        for t in [prod.submit(s, b) for b in _batches(9, 6)]:
            assert t.result(timeout=30).applied
        assert srv.submits_total == 6 < srv.requests_total
        assert any(t.name.startswith("rpc-serve/")
                   for t in threading.enumerate())
    finally:
        prod.close()
        srv.close()
    assert cpu_reads == []
    assert not any(t.name.startswith("reflow-device-watch")
                   for t in threading.enumerate())
    assert sched.executor._watch is None
    # the untraced leader runs the window program it always ran: only
    # a traced dispatch builds the twin with the completion token
    progs = [k for k in sched.executor._cache if k[0] == "pass_many"]
    assert progs and not any(k[-1] == "token" for k in progs)
    assert fe._clk is None
    # no ring, so no ``thread_ledger``, no ``rpc_ops``, no ``rpc_serve``
    assert trace_mod._rings == [] and obs.chrome_events() == []
    snap = REGISTRY.snapshot()["gauges"]
    assert snap[f"{key}.device_busy_s"] == 0.0
    assert snap[f"{key}.windows_done"] == 0
    assert snap[f"{key}.megatick_windows"] == fe.windows_staged
    fe.close()


def test_submit_reads_the_epoch_outside_the_frontend_lock(tmp_path, traced):
    """A followed ticket that brings no token mints one from the WAL's
    epoch, read under the WAL's lock — which the committer holds while
    it resolves a block under the frontend lock. So ``submit`` must not
    hold the frontend lock while it asks (ROADMAP D0b: it did, and this
    file's durable, in-process, traced drives stood still one run in
    four under load)."""
    g, s = _loop_free()
    sched = DurableScheduler(g, get_executor("tpu"),
                             wal_dir=str(tmp_path / "wal"),
                             fsync="tick", committer="thread")
    fe = IngestFrontend(sched, depth=2, window=CoalesceWindow(
        max_rows=6, max_ticks=2, max_latency_s=0.002))
    got = []
    with sched.wal._lock:           # as the committer holds it
        t = threading.Thread(
            target=lambda: got.append(fe.submit(s, _batches(10, 1)[0])))
        t.start()
        time.sleep(0.2)
        # the submit stands at the WAL's lock for the epoch, and the
        # frontend lock is free for the committer meanwhile
        assert t.is_alive() and not got
        assert fe._lock.acquire(timeout=10)
        fe._lock.release()
    t.join(timeout=30)
    assert got[0].result(timeout=60).applied
    assert got[0].trace.cause.split("#")[1] == str(sched.epoch)
    fe.close()


# -- (d) server-side RPC spans -------------------------------------------------

def test_rpc_serve_and_lock_wait_for_sampled_tickets_only(monkeypatch):
    """The producer samples nothing (so no request carries a wire
    ``cause``); the leader samples every second ticket."""
    obs.disable()
    trace_mod.reset()
    monkeypatch.setattr(trace_mod, "SAMPLE_EVERY", 2)
    g, s = _loop_free()
    sched = DirtyScheduler(g, get_executor("tpu"))
    fe = IngestFrontend(sched, window=CoalesceWindow(
        max_rows=64, max_ticks=2, max_latency_s=0.002))
    lt = LoopbackTransport()
    srv = RpcIngestServer(fe, lt).start()
    prod = RemoteProducer(lt, srv.address, name="p0")
    try:
        monkeypatch.setattr(trace_mod, "sample", lambda: False)
        obs.enable()
        tickets = [prod.submit(s, b) for b in _batches(5, 10)]
        for t in tickets:
            assert t.result(timeout=30).applied
            assert t.cause is None
        fe.flush(timeout=30)
    finally:
        obs.disable()
        prod.close()
        srv.close()
        fe.close()
    spans = _spans()
    trace_mod.reset()
    sampled = {tr[7:] for n, tr, *_ in spans if n == "admission"}
    assert len(sampled) == 5
    served = [(tr, a) for n, tr, _, _, a in spans if n == "rpc_serve"]
    assert {a["batch_id"] for _, a in served} == sampled
    assert all(tr.startswith("rpc-serve/") for tr, _ in served)
    for _, a in served:
        assert "cause" not in a and a["bytes"] > 0
        assert a["decode_s"] >= 0.0 and a["reply_s"] >= 0.0
    waits = {tr[7:] for n, tr, *_ in spans if n == "admit_lock_wait"}
    assert waits == sampled
    # rpc_admit is the cross-process chain's link: no wire cause, no span
    assert not [n for n, *_ in spans if n == "rpc_admit"]
    for n, tr, t0, t1, a in spans:
        if n == "rpc_serve":
            adm = [(s0, s1) for m, mtr, s0, s1, _ in spans
                   if m == "admission" and mtr[7:] == a["batch_id"]]
            assert t0 <= adm[0][0] + 0.01 and adm[0][1] <= t1 + 0.01


# -- (e) cpu_s -----------------------------------------------------------------

def test_cpu_seconds_never_exceed_wall(tmp_path, traced):
    g, s = _loop_free()
    sched = DurableScheduler(g, get_executor("tpu"),
                             wal_dir=str(tmp_path / "wal"),
                             fsync="tick", committer="thread")
    fe = IngestFrontend(sched, depth=2, window=CoalesceWindow(
        max_rows=6, max_ticks=2, max_latency_s=0.002))
    tickets = []
    for i, b in enumerate(_batches(6, 30)):
        tickets.append(fe.submit(s, b))
        if i % 10 == 9:
            time.sleep(0.02)              # let the pump run dry: pump_wait
    for t in tickets:
        assert t.result(timeout=60).applied
    fe.flush(timeout=60)
    spans = _spans()
    fe.close()
    with_cpu = [(n, t1 - t0, a["cpu_s"]) for n, _, t0, t1, a in spans
                if "cpu_s" in a]
    assert {n for n, *_ in with_cpu} >= {
        "pump_wait", "pump_turn", "host_merge", "window_stage",
        "pump_execute", "window_retire", "resolve_block", "wal_fsync",
        "device_dispatch", "queue_write"}
    for name, dur_us, cpu_s in with_cpu:
        assert 0.0 <= cpu_s <= 1e-6 * dur_us + 1e-9, (name, dur_us, cpu_s)
    waits = [a for n, *_, a in spans if n == "pump_wait"]
    assert all(a["woke"] in ("notify", "timeout") for a in waits)
