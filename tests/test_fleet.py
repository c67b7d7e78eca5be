"""Fleet telemetry plane: cross-process trace correlation, snapshot
shipping, and the fleet consumers (docs/guide.md "Fleet telemetry").

The contract under test: (a) causality tokens stitch one shipment's
``ship_segment`` → ``net_send`` → ``replica_replay`` spans into a
single chain, while unstamped legacy ``Shipment`` frames stay
byte-identical on the wire, (b) the subscribe handshake piggybacks a
display-only clock anchor that old servers may omit, (c) telemetry
loss is always tolerated — a dead aggregator is a dropped-snapshot
counter, a silent node is a stale-marked entry, never an exception,
(d) the aggregator derives the cross-node gauges (lag spread, epoch
agreement, read QPS from ring deltas) correctly, and (e) the consumers
— ``fleet_inspect``, ``reflow_top``, ``ControlPlane(fleet=)`` —
render/act on the same ``reflow.fleet/1`` snapshot.
"""

from __future__ import annotations

import importlib.util
import json
import os
import pickle
import time

import numpy as np
import pytest

from reflow_tpu import obs
from reflow_tpu.net import (ReconnectPolicy, RemoteFollower,
                            ReplicaServer, TcpTransport)
from reflow_tpu.net.framing import TransportError
from reflow_tpu.obs import trace as trace_mod
from reflow_tpu.obs.fleet import (FLEET_SCHEMA, FleetAggregator,
                                  TelemetryShipper)
from reflow_tpu.obs.wire import TelemetryLink, TelemetryServer, node_id
from reflow_tpu.serve import ReplicaScheduler, ServeTier
from reflow_tpu.serve.control import ControlPlane
from reflow_tpu.wal import DurableScheduler, SegmentShipper
from reflow_tpu.wal.ship import Shipment
from reflow_tpu.workloads import wordcount

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _load_tool(name):
    spec = importlib.util.spec_from_file_location(
        name, os.path.join(REPO, "tools", f"{name}.py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


@pytest.fixture
def traced():
    obs.disable()
    trace_mod.reset()
    obs.enable()
    yield
    obs.disable()
    trace_mod.reset()


class FakeClock:
    def __init__(self, t=0.0):
        self.t = t

    def __call__(self):
        return self.t

    def advance(self, dt):
        self.t += dt
        return self.t


def drive(sched, src, n_ticks, seed=0):
    rng = np.random.default_rng(seed)
    for t in range(n_ticks):
        words = " ".join(f"w{int(x)}" for x in rng.integers(0, 40, 8))
        sched.push(src, wordcount.ingest_lines([words]),
                   batch_id=f"t{t}")
        sched.tick()


def pump_until_caught(ship, sched, replicas, max_rounds=200):
    sched.wal.sync()
    for _ in range(max_rounds):
        ship.pump_once()
        if all(r.published_horizon() == sched._tick for r in replicas):
            return
    raise AssertionError("replicas never caught up")


# -- the Shipment wire frame (legacy compat + cause stamping) ---------------

class _ScriptConn:
    def __init__(self, replies):
        self.sent = []
        self._replies = list(replies)

    def send_msg(self, msg, timeout_s=None):
        self.sent.append(msg)

    def recv_msg(self, timeout_s=None):
        if not self._replies:
            raise TransportError("script exhausted")
        return self._replies.pop(0)

    def close(self):
        pass


class _ScriptTransport:
    def __init__(self, conn):
        self._conn = conn

    def connect(self, address):
        return self._conn


def _follower(conn, name="r0"):
    return RemoteFollower(
        _ScriptTransport(conn), ("stub", 0), name=name,
        policy=ReconnectPolicy(name, base_s=0.001, cap_s=0.01, seed=0))


def test_legacy_shipment_frame_is_byte_identical(tmp_path):
    """An unstamped shipment's receive frame pickles to exactly the
    pre-trace 8-field protocol — the trailing None cause never reaches
    the wire, so mixed-version fleets interoperate."""
    obs.disable()
    legacy = Shipment(0, 0, b"xx", 2, False, None, 3, 1)
    assert legacy.cause is None  # pre-trace constructor still valid
    conn = _ScriptConn([("ok", None),               # subscribe (legacy)
                        ("ack", (0, 2), 3)])
    f = _follower(conn)
    f.receive(legacy)  # first call dials + resyncs
    ack = f.receive(legacy)
    assert ack.horizon == 3
    sent = conn.sent[-1]
    assert sent == ("receive", 0, 0, b"xx", 2, False, None, 3, 1)
    # exactly what a pre-cause client pickled: op + 8 fields, no cause
    pre_trace = ("receive",) + tuple(legacy)[:8]
    assert pickle.dumps(sent) == pickle.dumps(pre_trace)


def test_stamped_shipment_carries_cause_and_span_echoes_it(traced):
    stamped = Shipment(0, 0, b"xx", 2, False, None, 3, 1,
                       trace_mod.mint_cause("leader", 1))
    conn = _ScriptConn([("ok", None), ("ack", (0, 2), 3)])
    f = _follower(conn)
    f.receive(stamped)
    f.receive(stamped)
    sent = conn.sent[-1]
    assert len(sent) == 10 and sent[-1] == stamped.cause
    sends = [e for e in obs.chrome_events()
             if e.get("ph") == "X" and e["name"] == "net_send"]
    assert any(e.get("args", {}).get("cause") == stamped.cause
               for e in sends)


def test_subscribe_anchor_captured_and_legacy_server_tolerated():
    anchored = _ScriptConn([("ok", None,
                             {"node": "r0", "mono": 1.0, "wall": 2.0})])
    f = _follower(anchored)
    f.subscribe()
    assert f.anchor is not None
    assert f.anchor["node"] == "r0"
    assert f.anchor["rtt_s"] >= 0.0
    assert "wall_offset_s" in f.anchor  # display-only skew estimate
    legacy = _ScriptConn([("ok", None)])  # pre-anchor 2-tuple reply
    f2 = _follower(legacy, name="r1")
    assert f2.subscribe() is None
    assert f2.anchor is None


def test_cause_tokens_stitch_ship_send_replay_over_tcp(tmp_path,
                                                       traced):
    """The tentpole proof at test scale: one leader, one TCP replica,
    and every shipped chunk's three hops share one causality token."""
    g, src, _sink = wordcount.build_graph()
    sched = DurableScheduler(g, wal_dir=str(tmp_path / "wal"),
                             fsync="tick")
    gr, _s, _k = wordcount.build_graph()
    r = ReplicaScheduler(gr, str(tmp_path / "r0"), name="r0")
    srv = ReplicaServer(r, TcpTransport()).start()
    link = RemoteFollower(
        TcpTransport(), srv.address, name="r0",
        policy=ReconnectPolicy("r0", base_s=0.005, cap_s=0.05, seed=0),
        io_timeout_s=2.0)
    ship = SegmentShipper(sched.wal, leader_tick=lambda: sched._tick)
    ship.attach(link)
    try:
        drive(sched, src, 4)
        pump_until_caught(ship, sched, [r])
        by_cause = {}
        for e in obs.chrome_events():
            if e.get("ph") != "X":
                continue
            cause = e.get("args", {}).get("cause")
            if cause:
                by_cause.setdefault(cause, set()).add(e["name"])
        full = [c for c, names in by_cause.items()
                if {"ship_segment", "net_send",
                    "replica_replay"} <= names]
        assert full, f"no complete chain in {by_cause}"
        origin = node_id()
        for c in full:
            assert c.startswith(f"{origin}#")  # origin#epoch#seq
        path = str(tmp_path / "trace.json")
        obs.export_chrome_trace(path)
        ti = _load_tool("trace_inspect")
        out = ti.inspect(path, require_chain=[
            "ship_segment", "net_send", "replica_replay"])
        assert out["causal"]["required_chains"] >= 1
        assert ti.main([path, "--require-chain",
                        "ship_segment,net_send,replica_replay",
                        "--json"]) == 0
    finally:
        ship.close()
        link.close()
        srv.close()
        r.close()
        sched.wal.close()


def test_tracing_disabled_ships_no_cause(tmp_path):
    obs.disable()
    g, src, _sink = wordcount.build_graph()
    sched = DurableScheduler(g, wal_dir=str(tmp_path / "wal"),
                             fsync="tick")
    gr, _s, _k = wordcount.build_graph()
    r = ReplicaScheduler(gr, str(tmp_path / "r0"), name="r0")
    ship = SegmentShipper(sched.wal, leader_tick=lambda: sched._tick)
    seen = []
    orig = r.receive

    def spy(sh):
        seen.append(sh)
        return orig(sh)

    r.receive = spy
    ship.attach(r)
    try:
        drive(sched, src, 2)
        pump_until_caught(ship, sched, [r])
        assert seen and all(sh.cause is None for sh in seen)
    finally:
        ship.close()
        r.close()
        sched.wal.close()


# -- FleetAggregator derivation ---------------------------------------------

def _snap(mono, **gauges):
    return {"schema": obs.SNAPSHOT_SCHEMA, "ts_mono": mono,
            "ts_wall": 1000.0 + mono, "gauges": gauges}


def test_aggregator_derives_lag_spread_epochs_and_qps():
    clk = FakeClock()
    agg = FleetAggregator(retention=8, stale_after_s=5.0, clock=clk,
                          wall=lambda: 42.0)
    agg.ingest("r0", _snap(1.0, **{"replica.r0.horizon": 10,
                                   "replica.r0.lag_ticks": 0,
                                   "replica.r0.epoch": 1,
                                   "replica.r0.conn_state": "healthy",
                                   "tier.replica_reads": 100}))
    agg.ingest("r0", _snap(3.0, **{"replica.r0.horizon": 12,
                                   "replica.r0.lag_ticks": 0,
                                   "replica.r0.epoch": 1,
                                   "replica.r0.conn_state": "healthy",
                                   "tier.replica_reads": 200}))
    agg.ingest("r1", _snap(1.0, **{"replica.r1.horizon": 4,
                                   "replica.r1.lag_ticks": 8,
                                   "replica.r1.epoch": 1}))
    snap = agg.fleet_snapshot()
    assert snap["schema"] == FLEET_SCHEMA and snap["ts_wall"] == 42.0
    g = snap["gauges"]
    assert g["nodes_total"] == 2 and g["nodes_stale"] == 0
    assert g["lag_spread"] == 8          # 12 - 4
    assert g["epochs"] == [1] and g["epoch_agree"] is True
    # 100 reads over 2s of the sender's monotonic clock
    assert g["aggregate_read_qps"] == pytest.approx(50.0)
    assert snap["nodes"]["r0"]["horizon"] == 12
    assert snap["nodes"]["r1"]["lag_ticks"] == 8
    assert snap["nodes"]["r0"]["conn_states"] == {
        "replica.r0.conn_state": "healthy"}
    assert snap["alerts"] == []  # spread 8 <= default limit
    json.dumps(snap)
    agg.close()


def test_aggregator_epoch_disagreement_and_spread_alerts():
    clk = FakeClock()
    agg = FleetAggregator(retention=4, stale_after_s=5.0, clock=clk)
    agg.lag_spread_max = 16
    agg.ingest("r0", _snap(1.0, **{"replica.r0.horizon": 100,
                                   "replica.r0.epoch": 2}))
    agg.ingest("r1", _snap(1.0, **{"replica.r1.horizon": 10,
                                   "replica.r1.epoch": 1}))
    snap = agg.fleet_snapshot()
    assert snap["gauges"]["epoch_agree"] is False
    assert snap["gauges"]["epochs"] == [1, 2]
    assert any("epoch disagreement" in a for a in snap["alerts"])
    assert any("lag spread 90 ticks exceeds 16" in a
               for a in snap["alerts"])
    agg.close()


def test_aggregator_stale_marks_but_keeps_serving():
    """A silent node stays in the fleet view with an honest age on it
    — staleness is a display state, never an eviction or an error."""
    clk = FakeClock()
    agg = FleetAggregator(retention=4, stale_after_s=1.0, clock=clk)
    agg.ingest("r0", _snap(1.0, **{"replica.r0.horizon": 5}))
    agg.ingest("r1", _snap(1.0, **{"replica.r1.horizon": 5}))
    clk.advance(0.5)
    assert agg.stale_nodes() == []
    clk.advance(2.0)
    agg.ingest("r1", _snap(4.0, **{"replica.r1.horizon": 7}))
    snap = agg.fleet_snapshot()
    assert agg.stale_nodes() == ["r0"]
    assert snap["nodes"]["r0"]["stale"] is True
    assert snap["nodes"]["r0"]["horizon"] == 5  # last-known, served
    assert snap["nodes"]["r1"]["stale"] is False
    assert snap["gauges"]["nodes_stale"] == 1
    assert any(a.startswith("stale: r0") for a in snap["alerts"])
    agg.close()


def test_aggregator_retention_bounds_ring():
    agg = FleetAggregator(retention=3, stale_after_s=5.0,
                          clock=FakeClock())
    for i in range(10):
        agg.ingest("r0", _snap(float(i)))
    snap = agg.fleet_snapshot()
    assert snap["nodes"]["r0"]["snapshots"] == 3
    assert snap["gauges"]["snapshots_total"] == 10
    agg.close()


def test_aggregator_publish_metrics_and_unregister():
    reg = obs.MetricsRegistry()
    agg = FleetAggregator(retention=4, stale_after_s=5.0,
                          clock=FakeClock())
    agg.ingest("r0", _snap(1.0, **{"replica.r0.horizon": 5}))
    agg.publish_metrics(reg)
    snap = reg.snapshot()
    assert snap["gauges"]["fleet.nodes_total"] == 1
    assert snap["gauges"]["fleet.snapshots_total"] == 1
    agg.close()
    assert "fleet.nodes_total" not in reg.snapshot()["gauges"]


# -- snapshot shipping over the wire ----------------------------------------

def test_shipper_to_aggregator_over_tcp_and_fleet_query():
    reg = obs.MetricsRegistry()
    reg.counter("serve.applied").inc(7)
    reg.gauge("replica.r0.horizon", lambda: 9)
    agg = FleetAggregator(retention=8, stale_after_s=5.0)
    tsrv = TelemetryServer(agg, TcpTransport()).start()
    sh = TelemetryShipper(
        reg, TcpTransport(), tsrv.address, node="r0",
        policy=ReconnectPolicy("tele/r0", base_s=0.005, cap_s=0.05,
                               seed=0),
        io_timeout_s=2.0)
    probe = TelemetryLink(TcpTransport(), tsrv.address,
                          node="probe", io_timeout_s=2.0)
    try:
        snap = sh.build_snapshot()
        assert snap["schema"] == obs.SNAPSHOT_SCHEMA
        assert snap["node"] == "r0" and "ts_mono" in snap
        assert sh.ship_once() and sh.shipped == 1
        assert agg.node_count() == 1
        # the hello handshake recorded r0's clock anchor
        assert "r0" in agg.fleet_snapshot()["anchors"]
        fleet = probe.fetch_fleet()
        assert fleet is not None and fleet["schema"] == FLEET_SCHEMA
        assert fleet["nodes"]["r0"]["horizon"] == 9
        assert probe.anchor is not None and probe.anchor["rtt_s"] >= 0
    finally:
        probe.close()
        sh.close()
        tsrv.close()
        agg.close()


def test_fleet_view_equals_ground_truth_and_serves_through_partition(
        tmp_path):
    """The whole plane on a live topology: each replica's own registry
    rides a TelemetryShipper over TCP, and at quiesce the aggregator's
    per-node horizons / lag / spread EQUAL what the replicas report
    directly. Then one node's telemetry link is partitioned: the fleet
    view keeps answering with that node stale-marked (last-known
    horizon, an alert, never an error), the shipper counts its dropped
    beats, and the node comes back once the link heals."""
    from reflow_tpu.net import FaultyTransport
    from reflow_tpu.utils.faults import WireFaults

    clk = FakeClock()
    g, src, _sink = wordcount.build_graph()
    sched = DurableScheduler(g, wal_dir=str(tmp_path / "wal"),
                             fsync="tick")
    ship = SegmentShipper(sched.wal, leader_tick=lambda: sched._tick)
    agg = FleetAggregator(retention=8, stale_after_s=1.0, clock=clk)
    tsrv = TelemetryServer(agg, TcpTransport()).start()
    replicas, shippers, faults = [], [], []
    probe = TelemetryLink(TcpTransport(), tsrv.address, node="probe",
                          io_timeout_s=2.0)
    try:
        for i in range(2):
            gr, _s, _k = wordcount.build_graph()
            r = ReplicaScheduler(gr, str(tmp_path / f"r{i}"),
                                 name=f"r{i}")
            ship.attach(r)
            reg = obs.MetricsRegistry()
            r.publish_metrics(reg)
            wf = WireFaults(seed=91 + i)
            shippers.append(TelemetryShipper(
                reg, FaultyTransport(TcpTransport(), wf), tsrv.address,
                node=f"r{i}",
                policy=ReconnectPolicy(f"tele/r{i}", base_s=0.0,
                                       cap_s=0.0, seed=0),
                io_timeout_s=0.25))
            replicas.append(r)
            faults.append(wf)
        drive(sched, src, 5)
        pump_until_caught(ship, sched, replicas)
        assert all(sh.ship_once() for sh in shippers)
        truth = {r.name: r.published_horizon() for r in replicas}
        snap = agg.fleet_snapshot()
        assert {n: e["horizon"] for n, e in snap["nodes"].items()} \
            == truth == {"r0": sched._tick, "r1": sched._tick}
        assert all(e["lag_ticks"] == 0 for e in snap["nodes"].values())
        assert snap["gauges"]["lag_spread"] == 0
        assert snap["gauges"]["epoch_agree"] is True
        assert snap["gauges"]["nodes_total"] == 2 and not snap["alerts"]

        faults[0].partition("c2s")          # r0's telemetry link only
        drive(sched, src, 2, seed=1)
        pump_until_caught(ship, sched, replicas)
        clk.advance(2.0)
        assert shippers[0].ship_once() is False
        assert shippers[1].ship_once()
        assert shippers[0].dropped >= 1
        during = probe.fetch_fleet()        # served, never an error
        assert agg.stale_nodes() == ["r0"]
        assert during["nodes"]["r0"]["stale"] is True
        assert during["nodes"]["r0"]["horizon"] == truth["r0"]
        assert during["nodes"]["r1"]["horizon"] == sched._tick
        assert any(a.startswith("stale: r0") for a in during["alerts"])

        faults[0].heal()
        deadline = time.monotonic() + 10
        while not shippers[0].ship_once():
            assert time.monotonic() < deadline, "link never healed"
            time.sleep(0.01)
        after = probe.fetch_fleet()
        assert after["nodes"]["r0"]["stale"] is False
        assert after["nodes"]["r0"]["horizon"] == sched._tick
    finally:
        probe.close()
        for sh in shippers:
            sh.close()
        tsrv.close()
        agg.close()
        ship.close()
        for r in replicas:
            r.close()
        sched.wal.close()


def test_telemetry_loss_tolerated_never_raises():
    """A dead aggregator: every beat is a dropped counter, the data
    path never sees an exception, and the link state degrades."""

    class _DeadTransport:
        def connect(self, address):
            raise TransportError("nothing listening")

    reg = obs.MetricsRegistry()
    sh = TelemetryShipper(
        reg, _DeadTransport(), ("nowhere", 0), node="r0",
        policy=ReconnectPolicy("tele/r0", base_s=0.0, cap_s=0.0,
                               seed=0))
    for _ in range(5):
        assert sh.ship_once() is False
    assert sh.dropped == 5 and sh.shipped == 0
    assert sh.link.conn_state != "healthy"
    sh.close()


def test_telemetry_server_survives_poison_and_keeps_serving():
    agg = FleetAggregator(retention=4, stale_after_s=5.0)
    tsrv = TelemetryServer(agg, TcpTransport()).start()
    try:
        conn = TcpTransport().connect(tsrv.address)
        conn.send_msg(("bogus-op", 1, 2), 2.0)
        resp = conn.recv_msg(2.0)
        assert resp[0] == "err"
        conn.send_msg("not-a-tuple", 2.0)
        assert conn.recv_msg(2.0)[0] == "err"
        # malformed snap degrades, then a healthy request still works
        conn.send_msg(("snap", "r0"), 2.0)
        assert conn.recv_msg(2.0)[0] == "err"
        conn.send_msg(("ping",), 2.0)
        ok, info = conn.recv_msg(2.0)
        assert ok == "ok" and info["nodes"] == 0
        conn.close()
    finally:
        tsrv.close()
        agg.close()


def test_shipper_publishes_its_own_metrics():
    reg = obs.MetricsRegistry()
    agg = FleetAggregator(retention=4, stale_after_s=5.0)
    tsrv = TelemetryServer(agg, TcpTransport()).start()
    sh = TelemetryShipper(reg, TcpTransport(), tsrv.address, node="r0",
                          io_timeout_s=2.0)
    sh.publish_metrics()
    try:
        sh.ship_once()
        snap = reg.snapshot()
        assert snap["gauges"]["telemetry.shipped"] == 1
        assert snap["gauges"]["telemetry.dropped"] == 0
        assert snap["gauges"]["telemetry.conn_state"] == "healthy"
    finally:
        sh.close()
        tsrv.close()
        agg.close()
    assert "telemetry.shipped" not in reg.snapshot()["gauges"]


# -- consumers --------------------------------------------------------------

def _fleet_fixture():
    agg = FleetAggregator(retention=4, stale_after_s=5.0,
                          clock=FakeClock())
    agg.ingest("r0", _snap(1.0, **{"replica.r0.horizon": 12,
                                   "replica.r0.lag_ticks": 0,
                                   "replica.r0.epoch": 1,
                                   "replica.r0.conn_state": "healthy"}))
    agg.ingest("r1", _snap(1.0, **{"replica.r1.horizon": 4,
                                   "replica.r1.lag_ticks": 8,
                                   "replica.r1.epoch": 1}))
    snap = agg.fleet_snapshot()
    agg.close()
    return snap


def test_fleet_inspect_file_json_and_fail_on_alert(tmp_path, capsys):
    snap = _fleet_fixture()
    path = str(tmp_path / "fleet.json")
    with open(path, "w") as f:
        json.dump(snap, f)
    fi = _load_tool("fleet_inspect")
    assert fi.main([path, "--json"]) == 0
    out = json.loads(capsys.readouterr().out)
    assert out["schema"] == FLEET_SCHEMA
    assert out["gauges"]["lag_spread"] == 8
    assert fi.main([path]) == 0  # human table renders
    human = capsys.readouterr().out
    assert "r0" in human and "lag spread" in human
    # alerts are reported, not fatal — unless the CI smoke asks
    snap["alerts"] = ["stale: r1 last seen 9.0s ago"]
    with open(path, "w") as f:
        json.dump(snap, f)
    assert fi.main([path]) == 0
    capsys.readouterr()
    assert fi.main([path, "--fail-on-alert"]) == 1
    with open(path, "w") as f:
        f.write(json.dumps({"schema": "other/1"}))
    with pytest.raises(SystemExit):
        fi.main([path, "--json"])


def test_reflow_top_render_marks_stale_and_disconnect():
    rt = _load_tool("reflow_top")
    snap = _fleet_fixture()
    snap["nodes"]["r1"]["stale"] = True
    snap["nodes"]["r1"]["age_s"] = 9.3
    snap["alerts"] = ["stale: r1 last seen 9.3s ago"]
    frame = rt.render(snap)
    assert "reflow-top" in frame and "2 node(s)" in frame
    assert "STALE(9.3s)" in frame
    assert "ALERT: stale: r1" in frame
    assert "lag spread 8" in frame
    # the console survives a dead aggregator: last frame, flagged
    assert "[disconnected]" in rt.render(snap, stale_link=True)


def test_reflow_top_once_renders_saved_snapshot(tmp_path, capsys):
    snap = _fleet_fixture()
    path = str(tmp_path / "fleet.json")
    with open(path, "w") as f:
        json.dump(snap, f)
    rt = _load_tool("reflow_top")
    assert rt.main([path, "--once"]) == 0
    out = capsys.readouterr().out
    assert "r0" in out and "r1" in out


def test_control_plane_fleet_advisory_edge_triggered():
    """The lag-spread breach surfaces exactly one advisory action per
    episode (plus one on recovery) and never actuates anything."""

    class _FakeFleet:
        lag_spread_max = 4

        def __init__(self):
            self.spread = 10

        def fleet_snapshot(self):
            return {"gauges": {"lag_spread": self.spread,
                               "nodes_stale": 1},
                    "alerts": [f"lag spread {self.spread} ticks "
                               f"exceeds 4"]}

    tier = ServeTier(max_bytes=1 << 20, pump_threads=1)
    fleet = _FakeFleet()
    clk = FakeClock()
    reg = obs.MetricsRegistry()
    sampler = lambda now: {"graphs": {}, "ready_depth": 0,
                           "live_workers": tier.live_workers}
    cp = ControlPlane(tier, registry=reg, clock=clk, sampler=sampler,
                      fleet=fleet)
    a1 = cp.step(clk.advance(0.05))
    assert [a["kind"] for a in a1] == ["fleet_lag_spread"]
    assert a1[0]["advisory"] is True and a1[0]["lag_spread"] == 10
    assert cp.step(clk.advance(0.05)) == []  # still breached: no spam
    fleet.spread = 1
    a2 = cp.step(clk.advance(0.05))
    assert [a["kind"] for a in a2] == ["fleet_lag_recovered"]
    assert cp.step(clk.advance(0.05)) == []
    assert reg.value("control.fleet_lag_breaches") == 1
    cp.stop()
    tier.close()


def test_control_plane_tolerates_fleet_snapshot_failure():
    class _BrokenFleet:
        lag_spread_max = 4

        def fleet_snapshot(self):
            raise RuntimeError("telemetry weather")

    tier = ServeTier(max_bytes=1 << 20, pump_threads=1)
    clk = FakeClock()
    cp = ControlPlane(tier, registry=obs.MetricsRegistry(), clock=clk,
                      sampler=lambda now: {"graphs": {},
                                           "ready_depth": 0,
                                           "live_workers": 0},
                      fleet=_BrokenFleet())
    assert cp.step(clk.advance(0.05)) == []  # loss tolerated
    assert cp.errors == 0
    cp.stop()
    tier.close()
