"""Reactive reads (``reflow_tpu/subs/``): standing queries with
per-window delta fan-out.

The load-bearing invariants, each a hard assert here:

- **Exactness**: a delta-reconstructed answer equals the pull path
  (`view_at` / `lookup` / `top_k`) at the same horizon, for every
  query kind — including through conflation, shedding, crash-rebase,
  and reconnect.
- **Gap-free, duplicate-free resume**: a wire subscriber that loses
  its link mid-stream resumes from a one-integer cursor with
  ``gaps_total == 0`` and no double-applied frame (the client-side
  contiguity rule *counts* violations, so the assertion is direct).
- **Apply never blocks on fan-out**: a subscriber that never drains
  keeps a bounded outbox (conflated, then shed to snapshot) while the
  replica applies at full speed.
- **Crash seam** ``sub_fanout``: killing the fan-out thread after a
  window is consumed but before the mirror folds it loses freshness,
  never truth — restart rebases every subscriber from replica state.
"""

import time
from types import SimpleNamespace

import numpy as np
import pytest

from reflow_tpu.net import LoopbackTransport, ReconnectPolicy
from reflow_tpu.obs import SNAPSHOT_SCHEMA, MetricsRegistry
from reflow_tpu.obs.fleet import FleetAggregator
from reflow_tpu.serve import ReplicaScheduler
from reflow_tpu.serve.control import ControlConfig, ControlPlane
from reflow_tpu.subs import (DeltaFrame, QueryState, Subscriber,
                             SubscriptionHub, SubscriptionServer,
                             canon_query, merge_frames)
from reflow_tpu.subs.query import topk_rows
from reflow_tpu.subs.cli import SUB_SCHEMA, make_update, render_update
from reflow_tpu.utils.faults import CrashInjector
from reflow_tpu.wal import DurableScheduler, SegmentShipper
from reflow_tpu.workloads import wordcount


def make_stack(tmp_path, **hub_kw):
    """Leader -> shipper -> replica -> hub, all in-process."""
    g, src, sink = wordcount.build_graph()
    sched = DurableScheduler(g, wal_dir=str(tmp_path / "wal"),
                             fsync="tick")
    ship = SegmentShipper(sched.wal, leader_tick=lambda: sched._tick)
    g2, _s, _k = wordcount.build_graph()
    rep = ReplicaScheduler(g2, str(tmp_path / "r0"), name="r0")
    ship.attach(rep)
    hub_kw.setdefault("idle_poll_s", 0.005)
    hub = SubscriptionHub(rep, name="r0", **hub_kw)
    rep.attach_hub(hub)
    return sched, ship, rep, hub, src, sink


def drive(sched, src, n_ticks, seed=0, start=0, vocab=40):
    rng = np.random.default_rng(seed + start)
    for t in range(start, start + n_ticks):
        for j in range(2):
            words = " ".join(
                f"w{int(x)}" for x in rng.integers(0, vocab, 8))
            sched.push(src, wordcount.ingest_lines([words]),
                       batch_id=f"t{t}b{j}")
        sched.tick()


def pump_until_caught(ship, sched, rep, max_rounds=200):
    sched.wal.sync()
    for _ in range(max_rounds):
        ship.pump_once()
        if rep.published_horizon() == sched._tick:
            return
    raise AssertionError(
        f"replica stuck at {rep.published_horizon()}, "
        f"leader at {sched._tick}")


def close_stack(sched, ship, hub):
    hub.close()
    sched.close()


def pull_value(rep, sink, query):
    """The pull-path answer for ``query`` (the parity oracle). For
    topk the oracle is the deterministic ranking over the pull view —
    ``replica.top_k``'s argpartition breaks weight ties arbitrarily,
    so raw list equality would flake; the ranked *weights* are still
    cross-checked against it."""
    if query.kind == "view":
        return rep.view_at(sink.name)[1]
    if query.kind == "lookup":
        return rep.lookup(sink.name, query.params[0])[1]
    k, by = query.params
    ranked = topk_rows(rep.view_at(sink.name)[1], k, by)
    pulled = rep.top_k(sink.name, k, by=by)[1]
    assert [w for _kv, w in ranked] == [w for _kv, w in pulled]
    return ranked


# -- the frame contiguity rule (pure) ---------------------------------------

def test_query_state_contiguity_counts_dups_and_gaps():
    q = canon_query("s", "view")
    st = QueryState(q)
    # pre-snapshot delta: a gap (no base to apply onto)
    assert not st.apply(DeltaFrame(0, 1, "view", ((("a", 1.0), 1),),
                                   False))
    assert st.gaps == 1 and st.horizon == -1
    assert st.apply(DeltaFrame(-1, 3, "view", ((("a", 1.0), 2),), True))
    assert st.horizon == 3 and st.value() == {("a", 1.0): 2}
    # contiguous delta applies; the changeless overlap (from_h < h) too
    assert st.apply(DeltaFrame(3, 5, "view", ((("b", 1.0), 1),), False))
    assert st.apply(DeltaFrame(4, 7, "view", ((("a", 1.0), -2),),
                               False))
    assert st.horizon == 7 and st.value() == {("b", 1.0): 1}
    # duplicate (to_h <= h): skipped, counted, state unchanged
    assert not st.apply(DeltaFrame(5, 7, "view", ((("b", 1.0), 9),),
                                   False))
    assert st.dups_skipped == 1 and st.value() == {("b", 1.0): 1}
    # gap (from_h > h): counted, NOT applied — wrong is worse than late
    assert not st.apply(DeltaFrame(9, 11, "view", ((("c", 1.0), 1),),
                                   False))
    assert st.gaps == 2 and st.horizon == 7
    # an empty poll carrying the fan-out horizon advances past
    # changeless windows; a stale heartbeat never rewinds
    st.note_horizon(10)
    assert st.horizon == 10
    st.note_horizon(4)
    assert st.horizon == 10
    # snapshot at a LOWER horizon is a deliberate rewind (bootstrap /
    # promote moved replica state non-monotonically): accepted
    assert st.apply(DeltaFrame(-1, 2, "view", (), True))
    assert st.horizon == 2 and st.value() == {}


def test_merge_frames_matches_sequential_apply():
    frames = [
        DeltaFrame(-1, 2, "view", ((("a", 1.0), 2), (("b", 1.0), 1)),
                   True),
        DeltaFrame(2, 4, "view", ((("a", 1.0), -2), (("c", 1.0), 3)),
                   False),
        DeltaFrame(4, 5, "view", ((("c", 1.0), -1),), False),
    ]
    seq = QueryState(canon_query("s", "view"))
    for f in frames:
        seq.apply(f)
    merged = merge_frames(frames)
    assert merged.snapshot and merged.to_h == 5
    one = QueryState(canon_query("s", "view"))
    one.apply(merged)
    assert one.value() == seq.value() and one.horizon == seq.horizon
    # zero-net rows are dropped from the merged frame entirely
    assert not any(kv == ("a", 1.0) for kv, _w in merged.rows)
    # topk conflation keeps only the newest ranked list
    t1 = DeltaFrame(0, 1, "topk", ((("a", 1.0), 5),), False)
    t2 = DeltaFrame(1, 3, "topk", ((("b", 1.0), 9),), False)
    m = merge_frames([t1, t2])
    assert m.rows == t2.rows and (m.from_h, m.to_h) == (0, 3)


# -- in-process: parity with the pull path ----------------------------------

def test_inprocess_parity_all_kinds(tmp_path):
    sched, ship, rep, hub, src, sink = make_stack(tmp_path)
    try:
        drive(sched, src, 3)
        pump_until_caught(ship, sched, rep)
        h_view = hub.open(sink.name)
        h_top = hub.open(sink.name, "topk", (5,))
        key = sorted(rep.view_at(sink.name)[1])[0]
        h_look = hub.open(sink.name, "lookup", (key,))
        # more windows after subscribing: snapshots first, then deltas
        drive(sched, src, 5, start=3)
        pump_until_caught(ship, sched, rep)
        horizon = rep.published_horizon()
        for h in (h_view, h_top, h_look):
            assert h.wait_horizon(horizon), \
                f"{h.state.query.kind} stuck at {h.horizon}"
            assert h.value() == pull_value(rep, sink, h.state.query)
            assert h.state.gaps == 0
        # the view handle saw real deltas, not a snapshot per window
        assert h_view.state.applied > 1
        h_view.close()
        assert hub.active_subs() == 2
    finally:
        close_stack(sched, ship, hub)


def test_changeless_windows_advance_horizon_without_frames(tmp_path):
    sched, ship, rep, hub, src, sink = make_stack(tmp_path)
    try:
        drive(sched, src, 2)
        pump_until_caught(ship, sched, rep)
        # a lookup on a key this workload never produces: every window
        # is changeless for it, yet the horizon must still advance
        # (freshness is part of the answer)
        h = hub.open(sink.name, "lookup", (("never", -1.0),))
        assert h.wait_horizon(rep.published_horizon())
        drive(sched, src, 4, start=2)
        pump_until_caught(ship, sched, rep)
        assert h.wait_horizon(rep.published_horizon())
        assert h.value() == 0.0
        assert h.state.applied == 1          # the seed snapshot only
        assert h.state.gaps == 0
    finally:
        close_stack(sched, ship, hub)


# -- slow subscribers: conflate / shed, never stall apply -------------------

def test_slow_subscriber_conflates_and_never_blocks_apply(tmp_path):
    sched, ship, rep, hub, src, sink = make_stack(tmp_path,
                                                  outbox_max=4)
    try:
        slow = hub.open(sink.name)            # never drained below
        fast = hub.open(sink.name, "topk", (3,))
        for leg in range(6):
            drive(sched, src, 4, start=leg * 4)
            pump_until_caught(ship, sched, rep)   # apply NEVER stalls
            fast.drain(wait_s=0.05)
        horizon = rep.published_horizon()
        assert horizon == 24
        assert fast.wait_horizon(horizon)
        deadline = time.monotonic() + 5.0
        while hub.conflations_total == 0 \
                and time.monotonic() < deadline:
            time.sleep(0.01)
        assert hub.conflations_total > 0
        # the un-drained outbox is bounded by conflation, not unbounded
        shard = hub._shard(slow.token)
        assert len(shard.subs[slow.token].outbox) <= 4 + 1
        # and the conflated stream still reconstructs exactly
        assert slow.wait_horizon(horizon)
        assert slow.value() == pull_value(rep, sink, slow.state.query)
        assert slow.state.gaps == 0
    finally:
        close_stack(sched, ship, hub)


def test_overloaded_subscriber_sheds_to_snapshot(tmp_path):
    # a backlog too large even to conflate (conflate_max_rows tiny) is
    # shed: outbox cleared, one fresh snapshot on the next round
    sched, ship, rep, hub, src, sink = make_stack(
        tmp_path, outbox_max=2, conflate_max_rows=4)
    try:
        slow = hub.open(sink.name)
        for leg in range(4):
            drive(sched, src, 3, start=leg * 3)
            pump_until_caught(ship, sched, rep)
        deadline = time.monotonic() + 5.0
        while hub.sheds_total == 0 and time.monotonic() < deadline:
            time.sleep(0.01)
        assert hub.sheds_total > 0
        horizon = rep.published_horizon()
        assert slow.wait_horizon(horizon)
        assert slow.value() == pull_value(rep, sink, slow.state.query)
        # the un-drained outbox held only a snapshot at shed time (a
        # shed clears it and a fresh snapshot replaces it), so the
        # client sees exactly one rebase — and zero gaps: shedding is
        # invisible to the contiguity rule
        assert slow.state.rebases >= 1
        assert slow.state.gaps == 0
    finally:
        close_stack(sched, ship, hub)


def test_shed_level_two_pauses_emission_then_rebases(tmp_path):
    sched, ship, rep, hub, src, sink = make_stack(tmp_path)
    try:
        h = hub.open(sink.name, "topk", (3,))
        drive(sched, src, 2)
        pump_until_caught(ship, sched, rep)
        assert h.wait_horizon(rep.published_horizon())
        hub.set_shed_level(2)                 # brownout: pause pushes
        drive(sched, src, 3, start=2)
        pump_until_caught(ship, sched, rep)
        frozen = h.horizon
        time.sleep(0.1)
        h.drain(wait_s=0.05)
        assert h.horizon == frozen          # nothing emitted
        hub.set_shed_level(0)                 # recover: snapshot rebase
        assert h.wait_horizon(rep.published_horizon())
        assert h.value() == pull_value(rep, sink, h.state.query)
        assert h.state.gaps == 0
    finally:
        close_stack(sched, ship, hub)


# -- min_horizon: read-your-writes for subscriptions ------------------------

def test_min_horizon_parks_snapshot_until_caught_up(tmp_path):
    sched, ship, rep, hub, src, sink = make_stack(tmp_path)
    try:
        drive(sched, src, 2)
        pump_until_caught(ship, sched, rep)
        want = rep.published_horizon() + 3
        h = hub.open(sink.name, min_horizon=want)
        h.drain(wait_s=0.1)
        assert h.horizon == -1              # parked, not served stale
        drive(sched, src, 3, start=2)
        pump_until_caught(ship, sched, rep)
        assert h.wait_horizon(want)
        assert h.state.rebases == 1
        assert h.value() == pull_value(rep, sink, h.state.query)
    finally:
        close_stack(sched, ship, hub)


# -- the crash seam ---------------------------------------------------------

def test_crash_seam_sub_fanout_rebases_on_restart(tmp_path):
    # CrashInjector(only='sub_fanout') kills the fan-out thread at the
    # worst point: the window queue is drained, the mirrors have not
    # folded it. Restart must rebase from replica state — freshness
    # lost, truth kept.
    crash = CrashInjector(1, only="sub_fanout")
    sched, ship, rep, hub, src, sink = make_stack(tmp_path,
                                                  crash=crash)
    try:
        h = hub.open(sink.name)
        drive(sched, src, 3)
        pump_until_caught(ship, sched, rep)
        deadline = time.monotonic() + 5.0
        while not crash.fired and time.monotonic() < deadline:
            time.sleep(0.01)
        assert crash.fired and crash.fired_seam == "sub_fanout"
        deadline = time.monotonic() + 5.0
        while hub.alive and time.monotonic() < deadline:
            time.sleep(0.01)
        assert not hub.alive                  # the thread really died
        drive(sched, src, 2, start=3)         # writes continue meanwhile
        pump_until_caught(ship, sched, rep)
        hub.start()                           # supervision revives it
        assert h.wait_horizon(rep.published_horizon())
        assert h.value() == pull_value(rep, sink, h.state.query)
        assert h.state.gaps == 0
        assert hub.rebases_total >= 1
    finally:
        close_stack(sched, ship, hub)


# -- over the wire: reconnect-resume ----------------------------------------

def wire_policy(name):
    return ReconnectPolicy(name, base_s=0.01, cap_s=0.05, jitter=0.0)


def pump_to(sub, horizon, timeout_s=10.0):
    deadline = time.monotonic() + timeout_s
    while sub.horizon < horizon and time.monotonic() < deadline:
        sub.pump(wait_s=0.05)
    assert sub.horizon >= horizon, \
        f"subscriber stuck at {sub.horizon} (< {horizon})"


@pytest.mark.parametrize("kind,params", [
    ("view", ()), ("topk", (5, "weight")),
    # a row live before the partition and retracted during it
    ("lookup", (("w0", 5.0),))])
def test_wire_reconnect_resumes_gap_free_dup_free(tmp_path, kind, params):
    sched, ship, rep, hub, src, sink = make_stack(tmp_path)
    lt = LoopbackTransport()
    srv = SubscriptionServer(hub, lt).start()
    sub = Subscriber(lt, srv.address, sink.name, kind=kind, params=params,
                     policy=wire_policy("sub-p0"))
    srv2 = None
    try:
        drive(sched, src, 3)
        pump_until_caught(ship, sched, rep)
        pump_to(sub, rep.published_horizon())
        assert sub.mode == "snapshot"
        applied_before = sub.frames_applied_total

        srv.close()                           # the partition
        for _ in range(3):
            sub.pump(wait_s=0.01)             # never raises while down
        drive(sched, src, 4, start=3)         # writes continue
        pump_until_caught(ship, sched, rep)

        srv2 = SubscriptionServer(hub, lt).start()   # the heal
        sub.retarget(srv2.address)
        pump_to(sub, rep.published_horizon())
        # the resume contract, asserted mechanically:
        assert sub.mode == "resume"           # cursor, not re-snapshot
        assert sub.gaps_total == 0
        assert sub.dups_skipped_total == 0
        assert sub.rebases_total == 1         # only the initial seed
        assert sub.frames_applied_total > applied_before
        assert sub.value() == pull_value(rep, sink, sub.query)
        assert sub.reconnects_total >= 1
    finally:
        sub.close()
        for s in (srv, srv2):
            if s is not None:
                s.close()
        close_stack(sched, ship, hub)


def test_wire_expired_subscription_answers_gone_then_reregisters(
        tmp_path):
    sched, ship, rep, hub, src, sink = make_stack(tmp_path,
                                                  expire_s=0.2)
    lt = LoopbackTransport()
    srv = SubscriptionServer(hub, lt).start()
    sub = Subscriber(lt, srv.address, sink.name, kind="topk",
                     params=(4,), policy=wire_policy("sub-p1"))
    try:
        drive(sched, src, 2)
        pump_until_caught(ship, sched, rep)
        pump_to(sub, rep.published_horizon())
        time.sleep(0.5)                       # idle past expire_s
        deadline = time.monotonic() + 5.0
        while hub.reaped_total == 0 and time.monotonic() < deadline:
            time.sleep(0.02)
        assert hub.reaped_total >= 1          # server forgot us
        drive(sched, src, 2, start=2)
        pump_until_caught(ship, sched, rep)
        pump_to(sub, rep.published_horizon())  # "gone" -> re-handshake
        assert sub.handshakes_total >= 2
        assert sub.gaps_total == 0
        assert sub.value() == pull_value(rep, sink, sub.query)
    finally:
        sub.close()
        srv.close()
        close_stack(sched, ship, hub)


# -- control plane: the conflate -> pause ladder ----------------------------

class _FakeTier:
    _closed = False
    live_workers = 1
    pump_threads = 1

    def graphs(self):
        return {}

    def ensure_workers(self):
        return 0


class _FakeHub:
    def __init__(self):
        self.levels = []
        self.backlog = 0

    def load(self):
        return {"active": 7, "backlog_windows": self.backlog,
                "slowest_lag": 0, "shed_level": 0, "horizon": 5}

    def set_shed_level(self, level):
        self.levels.append(level)


def test_control_plane_sub_shed_ladder_steps_and_recovers():
    fh = _FakeHub()
    cp = ControlPlane(
        _FakeTier(), registry=MetricsRegistry(),
        sampler=lambda now: {"graphs": {}, "ready_depth": 0,
                             "live_workers": 1},
        config=ControlConfig(sub_backlog_windows_max=4,
                             sub_breach_intervals=2,
                             sub_recover_intervals=2),
        subs=fh)
    now = 0.0

    def step():
        nonlocal now
        now += 0.05
        return cp.step(now)

    fh.backlog = 10                           # breached
    assert step() == []                       # hysteresis: 1st breach
    acts = step()                             # 2nd -> conflate
    assert [a["kind"] for a in acts] == ["sub_shed_step"]
    assert acts[0]["mode"] == "conflate" and acts[0]["level"] == 1
    assert acts[0]["active_subs"] == 7
    step()
    acts = step()                             # 2 more -> pause
    assert [a["kind"] for a in acts] == ["sub_shed_step"]
    assert acts[0]["mode"] == "pause" and cp.sub_shed_level == 2
    fh.backlog = 0                            # healthy again
    step()
    acts = step()                             # recover one rung
    assert [a["kind"] for a in acts] == ["sub_shed_recover"]
    assert acts[0]["level"] == 1
    step()
    acts = step()
    assert acts[0]["level"] == 0 and cp.sub_shed_level == 0
    assert fh.levels == [1, 2, 1, 0]


def test_control_plane_survives_hub_load_errors():
    class _Broken(_FakeHub):
        def load(self):
            raise RuntimeError("hub closing")

    cp = ControlPlane(
        _FakeTier(), registry=MetricsRegistry(),
        sampler=lambda now: {"graphs": {}, "ready_depth": 0,
                             "live_workers": 1},
        config=ControlConfig(sub_backlog_windows_max=1,
                             sub_breach_intervals=1),
        subs=_Broken())
    assert cp.step(0.05) == []                # tolerated, not fatal
    assert cp.sub_shed_level == 0


# -- consoles and telemetry -------------------------------------------------

def _snap(mono, **gauges):
    return {"schema": SNAPSHOT_SCHEMA, "ts_mono": mono,
            "ts_wall": 1000.0 + mono, "gauges": gauges}


def test_fleet_derives_sub_gauges_with_backfill_tolerance():
    clk_v = [10.0]
    agg = FleetAggregator(retention=8, stale_after_s=5.0,
                          clock=lambda: clk_v[0])
    agg.ingest("r0", _snap(1.0, **{"subs.active": 3,
                                   "subs.fanout_rows_total": 100,
                                   "subs.slowest_lag": 1,
                                   "subs.conflations_total": 2,
                                   "subs.sheds_total": 1}))
    agg.ingest("r0", _snap(3.0, **{"subs.active": 5,
                                   "subs.fanout_rows_total": 300,
                                   "subs.slowest_lag": 4,
                                   "subs.conflations_total": 2,
                                   "subs.sheds_total": 1}))
    agg.ingest("r1", _snap(1.0))              # pre-subs node: tolerated
    snap = agg.fleet_snapshot()
    r0, r1 = snap["nodes"]["r0"], snap["nodes"]["r1"]
    assert r0["subs_active"] == 5
    assert r0["sub_rows_s"] == pytest.approx(100.0)   # (300-100)/2s
    assert r0["sub_conflations"] == 3
    assert r0["sub_lag_windows"] == 4
    assert r1["subs_active"] is None and r1["sub_rows_s"] is None
    g = snap["gauges"]
    assert g["subs_active"] == 5
    assert g["sub_rows_s"] == pytest.approx(100.0)
    assert g["sub_lag_windows"] == 4
    # a fleet with no subs anywhere reports None, not zero
    agg2 = FleetAggregator(retention=4, stale_after_s=5.0,
                           clock=lambda: clk_v[0])
    agg2.ingest("r0", _snap(1.0))
    g2 = agg2.fleet_snapshot()["gauges"]
    assert g2["subs_active"] is None and g2["sub_rows_s"] is None


def test_hub_publishes_sub_gauges(tmp_path):
    sched, ship, rep, hub, src, sink = make_stack(tmp_path)
    reg = MetricsRegistry()
    try:
        hub.publish_metrics(reg)
        h = hub.open(sink.name)
        drive(sched, src, 2)
        pump_until_caught(ship, sched, rep)
        assert h.wait_horizon(rep.published_horizon())
        gauges = reg.snapshot()["gauges"]
        assert gauges["subs.active"] == 1
        assert gauges["subs.horizon"] == rep.published_horizon()
        assert gauges["subs.fanout_rows_total"] >= 1
        assert gauges["subs.shed_level"] == 0
    finally:
        close_stack(sched, ship, hub)
        assert "subs.active" not in reg.snapshot()["gauges"]


def test_cli_update_schema_and_render():
    q = canon_query("counts", "topk", (3,))
    ranked = ((("the", 2.0), 9), (("a", 1.0), 7))
    sub = SimpleNamespace(query=q, horizon=42,
                          value=lambda: ranked,
                          frames_applied_total=5, gaps_total=0,
                          dups_skipped_total=1, rebases_total=1,
                          conn_state="healthy")
    upd = make_update(sub, ts_wall=123.456)
    assert upd["schema"] == SUB_SCHEMA == "reflow.sub/1"
    assert upd["horizon"] == 42 and upd["kind"] == "topk"
    assert upd["rows"] == [[["the", 2.0], 9], [["a", 1.0], 7]]
    line = render_update(upd)
    assert "h=42" in line and "counts/topk" in line
    assert "gaps=0" in line
    # lookup updates carry the bare number
    sub.query = canon_query("counts", "lookup", (("the", 2.0),))
    sub.value = lambda: 9.0
    upd = make_update(sub, ts_wall=123.5)
    assert upd["rows"] == 9.0
    assert "value=9.0" in render_update(upd)
