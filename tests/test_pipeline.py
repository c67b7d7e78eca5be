"""Pipelined window execution (docs/guide.md "Pipelined windows").

The contract under test: splitting the fused window lifecycle into
stage → dispatch → retire with a bounded in-flight depth changes WHEN
work happens, never WHAT is computed —

- depth 2/4 drives through an ``IngestFrontend`` produce tables EXACTLY
  equal (bitwise) to the depth-1 drive on identical batches, and both
  match the per-tick CPU oracle;
- staging window N+1 never writes a buffer set an in-flight window
  program owns (generation rotation), including when the pump crashes
  with windows dispatched but unretired — every ticket still resolves;
- a window's tickets resolve at its durability point — as soon as its
  dispatch has returned and the WAL watermark has passed its LSN, in
  LSN order, never before the fsync — and a window's retire (the hand-
  back of its ingress buffers) has no part in it, nor has a pump crash
  after the dispatch;
- a producer blocked on the admission budget wakes at STAGE-complete
  (the chunk's rows live in the device queue, their host bytes no
  longer occupy the frontend), not at retire;
- the ingress queue refuses int64 keys outside the int32 slot range
  instead of silently wrapping them.
"""

import os
import threading
import time

import numpy as np
import pytest

from reflow_tpu import DirtyScheduler, FlowGraph
from reflow_tpu.delta import DeltaBatch, Spec
from reflow_tpu.executors import get_executor
from reflow_tpu.executors.device_delta import DeviceDelta
from reflow_tpu.executors.ingress_queue import DeviceIngressQueue, slot_nbytes
from reflow_tpu.serve import CoalesceWindow, IngestFrontend, PumpCrashed
from reflow_tpu.utils.faults import CrashInjector, DeliveryError
from reflow_tpu.wal import DurableScheduler, recover

K_SPACE = 32
ROWS = 6


def _batch(rows):
    return DeltaBatch(np.array([r[0] for r in rows], np.int64),
                      np.array([r[1] for r in rows], np.float32),
                      np.array([r[2] for r in rows], np.int64))


def _graph():
    """source -> map -> reduce(sum): loop-free, sink-free, ONE source so
    every feed is uniform and the fused window path always engages."""
    g = FlowGraph("pipeline")
    spec = Spec((), np.float32, key_space=K_SPACE)
    s = g.source("s", spec)
    m = g.map(s, lambda v: v * np.float32(2), vectorized=True)
    r = g.reduce(m, "sum", tol=0.0)
    return g, s, r


def _mk_batches(seed, n=8, rows=ROWS):
    rng = np.random.default_rng(seed)
    return [_batch([(int(rng.integers(0, K_SPACE)),
                     float(rng.integers(0, 8)), 1) for _ in range(rows)])
            for _ in range(n)]


def _table(sched, node, nd=None):
    return {int(k): (float(np.asarray(v).reshape(()))
                     if nd is None
                     else round(float(np.asarray(v).reshape(())), nd))
            for k, v in sched.read_table(node).items()}


def _oracle(batches):
    g, s, r = _graph()
    sched = DirtyScheduler(g, get_executor("cpu"))
    for b in batches:
        sched.push(s, b)
        sched.tick()
    return _table(sched, r, nd=3)


def _frontend_drive(batches, depth, k):
    """One paused wave through a frontend pump: all batches queue, then
    resume drains them as one multi-chunk backlog (chunks of ``k``
    ticks), which is what makes consecutive windows actually pipeline
    at depth > 1. Returns (exact table, sched, frontend)."""
    g, s, r = _graph()
    sched = DirtyScheduler(g, get_executor("tpu"))
    fe = IngestFrontend(sched, depth=depth, window=CoalesceWindow(
        max_rows=ROWS, max_ticks=k, max_latency_s=0.001))
    try:
        fe.pause()
        tks = [fe.submit(s, b) for b in batches]
        fe.resume()
        fe.flush(timeout=30)
        assert all(t.result(timeout=10).applied for t in tks)
    finally:
        fe.close()
    return _table(sched, r), sched, fe


def _queue(sched) -> DeviceIngressQueue:
    qkeys = [key for key in sched.executor._cache
             if isinstance(key, tuple) and key and key[0] == "ingress_q"]
    assert len(qkeys) == 1
    return sched.executor._cache[qkeys[0]]


# -- differential fuzz: depths x window sizes x seeds ----------------------

@pytest.mark.parametrize("k", [2, 4])
@pytest.mark.parametrize("seed", [0, 1, 2])
def test_depth_fuzz_parity(seed, k):
    """Depth 2 and 4 are bit-for-bit depth 1 (same fused program, same
    slot contents, same dispatch order), and all match the oracle."""
    batches = _mk_batches(seed)
    want = _oracle(batches)
    t1, s1, fe1 = _frontend_drive(batches, depth=1, k=k)
    t2, s2, fe2 = _frontend_drive(batches, depth=2, k=k)
    t4, s4, fe4 = _frontend_drive(batches, depth=4, k=k)
    assert t2 == t1 and t4 == t1          # EXACT float equality
    assert {key: round(v, 3) for key, v in t1.items()} == want
    for sched in (s1, s2, s4):
        assert sched.megatick_fallbacks == 0
        assert sched.megatick_windows == len(batches) // k
    # depth 1 is literally the serial tick_many path; deeper drives
    # stage every chunk and overlap all but each wave's first
    assert fe1.windows_staged == 0 and fe1.stage_overlap_frac == 0.0
    for fe in (fe2, fe4):
        assert fe.windows_staged == len(batches) // k
        assert fe.windows_pipelined >= 1
        assert fe.stage_overlap_frac > 0.0


# -- stage never touches an in-flight generation ---------------------------

def test_stage_rotates_off_inflight_generation():
    """While window A is dispatched-but-unretired, staging window B
    lands in a DIFFERENT buffer generation: no array object of A's
    donated stack is reused, so B's slot writes can't corrupt A."""
    g, s, red = _graph()
    sched = DirtyScheduler(g, get_executor("tpu"))
    waves = [_mk_batches(5, n=2), _mk_batches(6, n=2)]

    h1 = sched.stage_window([{s: b} for b in waves[0]])
    assert h1 is not None
    bufs1 = {id(arr) for dd in h1.sw.stack.values()
             for arr in (dd.keys, dd.values, dd.weights)}
    sched.dispatch_staged(h1)
    q = _queue(sched)
    assert q.in_flight == 1

    h2 = sched.stage_window([{s: b} for b in waves[1]])
    assert h2 is not None
    assert h2.sw.gen != h1.sw.gen
    bufs2 = {id(arr) for dd in h2.sw.stack.values()
             for arr in (dd.keys, dd.values, dd.weights)}
    assert not (bufs1 & bufs2)
    assert q.generations == 2
    sched.dispatch_staged(h2)
    assert q.in_flight == 2

    sched.retire_staged(h1)
    sched.retire_staged(h2)
    assert q.in_flight == 0
    assert sched.megatick_fallbacks == 0
    # both windows' rows landed: views equal the per-tick oracle
    g2, s2, r2 = _graph()
    per = DirtyScheduler(g2, get_executor("cpu"))
    for b in waves[0] + waves[1]:
        per.push(s2, b)
        per.tick()
    assert _table(sched, red, nd=3) == _table(per, r2, nd=3)


def test_depth1_pingpong_reuses_generation_zero():
    """The serial flow (seal -> dispatch -> retire -> seal) never
    allocates a second generation — same memory footprint as before
    pipelining."""
    g, s, _r = _graph()
    sched = DirtyScheduler(g, get_executor("tpu"))
    for seed in (7, 8, 9):
        res = sched.tick_many([{s: b} for b in _mk_batches(seed, n=2)])
        res.block()
    q = _queue(sched)
    assert sched.megatick_windows == 3
    assert q.generations == 1
    assert q.in_flight == 0


def test_crash_with_window_in_flight_keeps_dispatched_tickets():
    """Kill the pump between chunk dispatches (chunk 1 dispatched and
    unretired, chunk 2 about to stage) on a NON-durable scheduler: chunk
    1's tickets resolved APPLIED when its dispatch returned, as at depth
    1 (there is no durability point to wait for), and stay so; chunk 2
    never reached the scheduler and fails. The dispatched window dies
    unretired, and its ids stay in the dedup mirror, so a re-send
    dedups instead of double-folding."""
    g, s, _r = _graph()
    sched = DirtyScheduler(g, get_executor("tpu"))
    crash = CrashInjector(2, only="pump_before_tick")
    fe = IngestFrontend(sched, crash=crash, depth=2,
                        window=CoalesceWindow(max_rows=ROWS, max_ticks=2,
                                              max_latency_s=0.001))
    fe.pause()
    tks = [fe.submit(s, b, batch_id=f"b{i}")
           for i, b in enumerate(_mk_batches(3, n=4))]
    fe.resume()
    for i, t in enumerate(tks[:2]):
        res = t.result(timeout=10)
        assert res.applied and res.tick == i + 1
    for t in tks[2:]:
        with pytest.raises(PumpCrashed):
            t.result(timeout=10)
    assert crash.fired
    assert not fe._inflight
    assert fe._pending_res == 0
    assert fe.applied == 2
    # dispatched ids stay admitted, and so do the crashed chunk's (it
    # may have executed): a resend dedups
    assert "b0" in fe._admitted and "b3" in fe._admitted
    fe.close()


# -- tickets resolve at the durability point, not at the retire -------------

COMMITTER = "reflow-wal-committer"


class _FsyncGate:
    """Holds the WAL committer's fsync (and nobody else's) until
    ``release()``; ``release(fail=True)`` makes that fsync raise, which
    kills the committer."""

    def __init__(self, monkeypatch):
        self._real = os.fsync
        self._open = threading.Event()
        self._fail = False
        self.held = threading.Event()
        monkeypatch.setattr(os, "fsync", self._fsync)

    def _fsync(self, fd):
        if threading.current_thread().name == COMMITTER:
            self.held.set()
            assert self._open.wait(30)
            if self._fail:
                raise OSError("injected: the disk is gone")
        return self._real(fd)

    def release(self, fail=False):
        self._fail = fail
        self._open.set()


def _durable_frontend(tmp_path, *, depth=2, crash=None):
    """An externally pumped frontend over a DurableScheduler whose
    end-of-window settle is stubbed out: a dispatched window stays in
    ``_inflight`` until the test retires it (the loop in ``_run_window``
    still retires the oldest when the pipeline is full)."""
    g, s, r = _graph()
    sched = DurableScheduler(g, get_executor("tpu"),
                             wal_dir=str(tmp_path / "wal"), fsync="tick",
                             committer="thread")
    fe = IngestFrontend(sched, start=False, depth=depth, crash=crash,
                        window=CoalesceWindow(max_rows=ROWS, max_ticks=2,
                                              max_latency_s=0.001))
    fe._real_settle_all = fe._settle_all
    fe._settle_all = lambda: None
    return fe, sched, s, r


def _pump_once(fe):
    """One pool-style pump iteration: take the backlog, run it, unlatch."""
    with fe._lock:
        drained = fe._take_window()
    try:
        fe._run_window(drained)
    except BaseException as e:  # noqa: BLE001 - what the tier's pool does
        fe._on_pump_crash(e, window=drained)
        return e
    with fe._lock:
        fe._finish_window()
    return None


def _wait_for(cond, timeout=10.0):
    deadline = time.perf_counter() + timeout
    while not cond():
        assert time.perf_counter() < deadline, "condition never held"
        time.sleep(0.002)


@pytest.mark.parametrize("case", ["while_inflight", "fsync_held",
                                  "lsn_order"])
def test_tickets_resolve_at_durability_not_at_retire(case, tmp_path,
                                                     monkeypatch):
    """(while_inflight) a dispatched window's tickets resolve while it
    still sits in ``_inflight``, unretired; (fsync_held) with the
    committer's fsync held they do NOT, however long the window has been
    dispatched and even once it has been retired, and do when the fsync
    is let through; (lsn_order) three windows parked behind one held
    fsync resolve in dispatch = LSN order, on the committer."""
    nwin = 3 if case == "lsn_order" else 1
    gate = _FsyncGate(monkeypatch) if case != "while_inflight" else None
    fe, sched, s, _r = _durable_frontend(tmp_path, depth=4)
    order = []
    real_complete = fe._complete_block

    def spy(block, err, where="pump"):
        order.append((block.win, block.lsn, where))
        real_complete(block, err, where)

    fe._complete_block = spy
    tks = [fe.submit(s, b, batch_id=f"b{i}")
           for i, b in enumerate(_mk_batches(11, n=2 * nwin))]
    assert _pump_once(fe) is None
    assert len(fe._inflight) == nwin       # dispatched, NOT retired
    if gate is not None:
        assert gate.held.wait(10)
        time.sleep(0.1)
        assert not any(t.done() for t in tks)
        assert fe._pending_res == nwin
        if case == "fsync_held":
            # the retire changes nothing: durability is what is missing
            fe._real_settle_all()
            assert not fe._inflight
            time.sleep(0.05)
            assert not any(t.done() for t in tks)
        gate.release()
    for i, t in enumerate(tks):
        res = t.result(timeout=10)
        assert res.applied and res.tick == i + 1
    _wait_for(lambda: fe._pending_res == 0)
    assert [w for w, _, _ in order] == list(range(1, nwin + 1))
    assert [lsn for _, lsn, _ in order] == sorted(
        lsn for _, lsn, _ in order)
    assert len({lsn for _, lsn, _ in order}) == nwin
    assert [t.result().lsn for t in tks] == [
        lsn for _, lsn, _ in order for _ in range(2)]
    if gate is not None:
        assert all(where == "committer" for _, _, where in order)
    if case == "fsync_held":
        assert fe.blocks_resolved_before_retire == 0
    else:
        assert len(fe._inflight) == nwin   # still dispatched, unretired
        assert fe.blocks_resolved_before_retire == nwin
    fe._real_settle_all()
    assert not fe._inflight
    fe._settle_all = fe._real_settle_all
    fe.close()


@pytest.mark.parametrize("case", ["lsn_durable", "committer_dead"])
def test_pump_crash_after_dispatch_leaves_tickets_to_the_watermark(
        case, tmp_path, monkeypatch):
    """A pump that dies with a window dispatched (and wired) does not
    decide that window's tickets. (lsn_durable) the watermark had passed
    the LSN: they are APPLIED and stay so, and ``recover()`` reads the
    batches back; the drained set the pump died in fails. (committer_
    dead) the fsync never came and the committer died: they fail
    ``PumpCrashed``. ``_pending_res`` ends at 0 either way — the block's
    unit comes back through its continuation, once."""
    gate = _FsyncGate(monkeypatch) if case == "committer_dead" else None
    crash = CrashInjector(2, only="pump_before_tick")
    fe, sched, s, r = _durable_frontend(tmp_path, crash=crash)
    batches = _mk_batches(12, n=4)
    first = [fe.submit(s, b, batch_id=f"b{i}")
             for i, b in enumerate(batches[:2])]
    assert _pump_once(fe) is None          # window 1: dispatched, wired
    assert len(fe._inflight) == 1 and not crash.fired
    if gate is None:
        for t in first:
            assert t.result(timeout=10).applied
        _wait_for(lambda: fe._pending_res == 0)
    else:
        assert gate.held.wait(10)
        assert fe._pending_res == 1
    second = [fe.submit(s, b, batch_id=f"b{i + 2}")
              for i, b in enumerate(batches[2:])]
    assert _pump_once(fe) is not None      # dies before window 2 stages
    assert crash.fired and fe._state == "failed"
    assert not fe._inflight
    for t in second:
        with pytest.raises(PumpCrashed):
            t.result(timeout=10)
    if gate is None:
        assert all(t.result().applied for t in first)
        assert fe._pending_res == 0
        fe.close()
        g2, s2, r2 = _graph()
        fresh = DurableScheduler(g2, get_executor("cpu"),
                                 wal_dir=str(tmp_path / "wal"))
        recover(fresh, str(tmp_path / "wal"))
        assert {"b0", "b1"} <= set(fresh._seen_batch_ids)
        assert "b2" not in fresh._seen_batch_ids
        assert _table(fresh, r2, nd=3) == _oracle(batches[:2])
        fresh.close()
    else:
        # undecided, and not the crashed pump's to decide
        assert not any(t.done() for t in first)
        assert fe._pending_res == 1
        gate.release(fail=True)
        for t in first:
            with pytest.raises(PumpCrashed):
                t.result(timeout=10)
        _wait_for(lambda: fe._pending_res == 0)
        assert sched.wal.committer_error is not None
        assert "b0" in fe._admitted
        fe.close()


@pytest.mark.parametrize("mode", ["depth1", "unfused"])
def test_serial_paths_resolve_as_before(mode, tmp_path):
    """Depth 1 and the unfused fallback (a chunk ``stage_window``
    refuses at depth 2) wire a chunk's tickets right after ``tick_many``
    returns, as they always did: every ticket's tick, LSN and
    coalescing — and the tables — are those of the staged depth-2 drive
    of the same batches, no window is staged, none resolves 'before its
    retire'."""
    batches = _mk_batches(13, n=6)

    def drive(sub, depth, refuse_stage):
        g, s, r = _graph()
        sched = DurableScheduler(g, get_executor("tpu"),
                                 wal_dir=str(tmp_path / sub), fsync="tick",
                                 committer="thread")
        if refuse_stage:
            sched.stage_window = lambda *a, **k: None
        fe = IngestFrontend(sched, depth=depth, window=CoalesceWindow(
            max_rows=ROWS, max_ticks=2, max_latency_s=0.001))
        fe.pause()
        tks = [fe.submit(s, b, batch_id=f"b{i}")
               for i, b in enumerate(batches)]
        fe.resume()
        fe.flush(timeout=30)
        res = [t.result(timeout=10) for t in tks]
        table = _table(sched, r)
        fe.close()
        return [(x.status, x.tick, x.coalesced_with, x.lsn)
                for x in res], table, fe, sched

    got, table, fe, sched = drive(
        "serial", 1 if mode == "depth1" else 2, mode == "unfused")
    want, table2, fe2, _ = drive("staged", 2, False)
    assert got == want and table == table2
    assert [x[:3] for x in got] == [("applied", i + 1, 0)
                                    for i in range(len(batches))]
    assert fe.windows_staged == 0
    assert fe.blocks_resolved_before_retire == 0
    assert fe.applied == len(batches) and fe._pending_res == 0
    assert sched.megatick_windows == 3 and sched.megatick_fallbacks == 0
    assert fe2.windows_staged == 3


# -- device-resident submissions log their host pre-image -------------------

def _sink_graph():
    """``_graph`` plus a sink: the shape the pre-imaged ingest protocol
    is served on (a sink keeps the graph on the per-tick path, where a
    device-resident batch rides its own feed slot)."""
    g, s, r = _graph()
    return g, s, g.sink(r, "out")


def _view(sched, sink):
    return {(int(k), round(float(v), 3)): w
            for (k, v), w in sched.view(sink.name).items() if w}


@pytest.mark.parametrize("committer", ["inline", "thread"])
def test_preimaged_device_submissions_log_without_a_readback(committer,
                                                             tmp_path):
    """A device-resident batch submitted with ``preimage=`` is logged
    from the host pre-image: ``log_readbacks`` stays 0 under either
    committer, every applied ticket names its covering LSN, the view
    equals the CPU oracle's (so inline == pipelined), and the log
    replays into a fresh host scheduler to the same view. Without the
    pre-image the same submission costs exactly one materialize."""
    from reflow_tpu.executors.device_delta import to_device

    batches = _mk_batches(11, n=6)
    extra = _mk_batches(12, n=1)[0]
    g0, s0, k0 = _sink_graph()
    oracle = DirtyScheduler(g0, get_executor("cpu"))
    for b in batches:
        oracle.push(s0, b)
        oracle.tick()
    want = _view(oracle, k0)
    oracle.push(s0, extra)
    oracle.tick()

    g, s, sink = _sink_graph()
    sched = DurableScheduler(g, get_executor("tpu"),
                             wal_dir=str(tmp_path / "wal"), fsync="record",
                             committer=committer)
    fe = IngestFrontend(sched, window=CoalesceWindow(
        max_rows=64, max_ticks=1, max_latency_s=0.001))
    try:
        results = [fe.submit(s, to_device(b, s.spec), batch_id=f"b{j}",
                             preimage=b).result(timeout=30)
                   for j, b in enumerate(batches)]
        fe.flush(timeout=30)
        assert all(x.applied and x.lsn for x in results)
        assert sched.log_readbacks == 0
        assert _view(sched, sink) == want
        assert fe.submit(s, to_device(extra, s.spec),
                         batch_id="bare").result(timeout=30).applied
        assert sched.log_readbacks == 1
    finally:
        fe.close()
        sched.close()
    g2, _s2, k2 = _sink_graph()
    fresh = DirtyScheduler(g2)
    report = recover(fresh, str(tmp_path / "wal"))
    assert report.replayed_pushes == len(batches) + 1
    assert _view(fresh, k2) == _view(oracle, k0)


# -- stage-complete budget release -----------------------------------------

def test_stage_release_unblocks_producer_before_retire():
    """A budget-blocked producer wakes when the current chunk finishes
    STAGING (its rows now live in the device queue), not when the window
    retires — the regression for release-at-stage-complete. Settling is
    stubbed out, so only the stage-complete release can unblock it."""
    g, s, _r = _graph()
    sched = DirtyScheduler(g, get_executor("tpu"))
    rows = 4
    fe = IngestFrontend(sched, start=False, depth=2, policy="block",
                        max_bytes=slot_nbytes(s.spec, rows),
                        window=CoalesceWindow(max_rows=rows, max_ticks=2,
                                              max_latency_s=0.001))
    mk = lambda v: _batch([(i, float(v), 1) for i in range(rows)])
    t1 = fe.submit(s, mk(1))
    admitted = threading.Event()
    t2_box = []

    def produce():
        t2_box.append(fe.submit(s, mk(2)))
        admitted.set()

    th = threading.Thread(target=produce, daemon=True)
    th.start()
    time.sleep(0.05)
    assert not admitted.is_set()       # genuinely blocked on the budget
    real_settle = fe._settle_all
    fe._settle_all = lambda: None
    try:
        with fe._lock:
            drained = fe._take_window()
        fe._run_window(drained)
        assert fe._inflight            # dispatched, NOT retired
        assert admitted.wait(5), ("producer still blocked after "
                                  "stage-complete")
    finally:
        fe._settle_all = real_settle
    fe._settle_all()
    with fe._lock:
        fe._finish_window()
    with fe._lock:
        drained = fe._take_window()
    fe._run_window(drained)
    with fe._lock:
        fe._finish_window()
    th.join(timeout=5)
    assert t1.result(timeout=5).applied
    assert t2_box[0].result(timeout=5).applied
    fe.close()


# -- ingress queue: generation rotation + key-range guard ------------------

def _unit_queue(k=2, cap=4, key_space=8):
    spec = Spec((), np.float32, key_space=key_space)
    return DeviceIngressQueue({0: spec}, {0: cap}, k), spec


def _fresh_stack(k, cap):
    import jax.numpy as jnp

    return {0: DeviceDelta(jnp.zeros((k, cap), jnp.int32),
                           jnp.zeros((k, cap), jnp.float32),
                           jnp.zeros((k, cap), jnp.int32))}


def test_seal_rotates_and_retire_frees():
    q, _spec = _unit_queue()
    q.write(0, 0, _batch([(1, 2.0, 1)]))
    st1 = q.stacked()
    g0 = q.seal()
    assert q.in_flight == 1
    q.write(0, 0, _batch([(2, 3.0, 1)]))   # rotates onto a fresh gen
    st2 = q.stacked()
    assert q.generations == 2
    assert {id(a) for dd in st1.values()
            for a in (dd.keys, dd.values, dd.weights)}.isdisjoint(
        {id(a) for dd in st2.values()
         for a in (dd.keys, dd.values, dd.weights)})
    # the sealed gen's contents are untouched by the new gen's writes
    assert int(np.asarray(st1[0].weights[0]).sum()) == 1
    q.retire(g0, _fresh_stack(2, 4))
    assert q.in_flight == 0
    with pytest.raises(ValueError):
        q.retire(g0, _fresh_stack(2, 4))   # no longer in flight
    with pytest.raises(ValueError):
        q.retire(99, _fresh_stack(2, 4))


def test_retire_validates_stack_keys():
    q, _spec = _unit_queue()
    q.write(0, 0, _batch([(1, 1.0, 1)]))
    g0 = q.seal()
    with pytest.raises(ValueError):
        q.retire(g0, {5: _fresh_stack(2, 4)[0]})


def test_cancel_returns_generation_without_adoption():
    q, _spec = _unit_queue()
    q.write(0, 0, _batch([(1, 1.0, 1)]))
    g0 = q.seal()
    q.cancel(g0)
    assert q.in_flight == 0
    q.write(1, 0, _batch([(2, 1.0, 1)]))   # reuses g0: no new allocation
    assert q.generations == 1
    assert q._staging == g0


def test_rebind_requires_inflight_generation():
    q, _spec = _unit_queue()
    with pytest.raises(ValueError):
        q.rebind(_fresh_stack(2, 4))


def test_int64_keys_beyond_int32_rejected():
    """Keys >= 2^31 used to be silently truncated by the int32 slot
    assignment (wrapping to a DIFFERENT key and corrupting the fold);
    now the host boundary refuses them."""
    q, _spec = _unit_queue(key_space=2 ** 40)
    with pytest.raises(DeliveryError):
        q.write(0, 0, _batch([(2 ** 31, 1.0, 1)]))
    with pytest.raises(DeliveryError):
        q.write(0, 0, _batch([(-2 ** 31 - 1, 1.0, 1)]))
    # boundary values are fine
    q.write(0, 0, _batch([(2 ** 31 - 1, 1.0, 1)]))
    assert q.writes == 1
