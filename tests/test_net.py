"""Replication over the wire: framed transport, fault injection,
reconnect/backoff, and partition tolerance.

The protocol matrix runs over BOTH transports — the in-process
``LoopbackTransport`` (tier-1, hermetic) and real ``TcpTransport``
sockets (marked ``slow``; the chaos bench soaks TCP further) — through
the same shipping protocol the in-process followers speak. Fault-path
tests drive ``WireFaults``/``FaultyTransport`` deterministically
(scripted partitions/resets and probability-1 rates, never dice), and
the backoff/debounce state machines run on fake clocks with no real
sleeps.
"""

import importlib.util
import json
import os
import socket
import threading
import time

import numpy as np
import pytest

from reflow_tpu.net import (FaultyTransport, LoopbackTransport,
                            ReconnectPolicy, RemoteFollower,
                            ReplicaServer, TcpTransport, TransportError,
                            WireTimeout)
from reflow_tpu.net.framing import (HEADER, MAGIC, FrameError,
                                    decode_frame, encode_frame,
                                    frame_size, split_frames)
from reflow_tpu.net.transport import _RBUF, _TcpConn
from reflow_tpu.obs import REGISTRY
from reflow_tpu.serve import (FailoverCoordinator, ReadTier,
                              ReplicaScheduler)
from reflow_tpu.utils.faults import WireFaults
from reflow_tpu.wal import DurableScheduler, SegmentShipper
from reflow_tpu.workloads import wordcount

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


class FakeClock:
    def __init__(self, t=100.0):
        self.t = t

    def __call__(self):
        return self.t

    def advance(self, dt):
        self.t += dt
        return self.t


def _load_tool(name):
    spec = importlib.util.spec_from_file_location(
        name, os.path.join(REPO, "tools", f"{name}.py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def make_leader(tmp_path, **kw):
    g, src, sink = wordcount.build_graph()
    sched = DurableScheduler(g, wal_dir=str(tmp_path / "wal"),
                             fsync="tick", **kw)
    return sched, src, sink


def make_replica(tmp_path, name="r0"):
    g, _src, _sink = wordcount.build_graph()
    return ReplicaScheduler(g, str(tmp_path / name), name=name)


def drive(sched, src, n_ticks, seed=0, start=0):
    rng = np.random.default_rng(seed + start)
    for t in range(start, start + n_ticks):
        for j in range(2):
            words = " ".join(
                f"w{int(x)}" for x in rng.integers(0, 40, 8))
            sched.push(src, wordcount.ingest_lines([words]),
                       batch_id=f"t{t}b{j}")
        sched.tick()


def live_view(sched, sink):
    return {kv: w for kv, w in sched.view(sink.name).items() if w != 0}


def fast_policy(name, **kw):
    """Real-clock policy tuned so tests never wait perceptibly."""
    kw.setdefault("base_s", 0.001)
    kw.setdefault("cap_s", 0.005)
    kw.setdefault("jitter", 0.0)
    return ReconnectPolicy(name, **kw)


def pump_until_caught(ship, sched, replicas, timeout_s=20.0):
    """Pump tolerant of link stalls: a remote follower mid-backoff
    makes whole passes report zero progress without being done."""
    sched.wal.sync()
    deadline = time.monotonic() + timeout_s
    while time.monotonic() < deadline:
        ship.pump_once()
        if all(r.published_horizon() == sched._tick for r in replicas):
            return
        time.sleep(0.002)
    raise AssertionError(
        f"replicas stuck: leader tick {sched._tick}, horizons "
        f"{[r.published_horizon() for r in replicas]}")


# -- transports: one matrix, two implementations ----------------------------

TRANSPORTS = [
    "loopback",
    pytest.param("tcp", marks=pytest.mark.slow),
]


def make_transports(kind):
    """(server_transport, client_transport) — loopback must share the
    instance (addresses are process-local), TCP must not."""
    if kind == "loopback":
        t = LoopbackTransport()
        return t, t
    return TcpTransport(), TcpTransport()


# -- framing ----------------------------------------------------------------

def test_frame_round_trip_and_split():
    msgs = [("subscribe",), ("ack", (0, 128), 7),
            ("blob", b"\x00" * 4096)]
    buf = b"".join(encode_frame(m) for m in msgs)
    got, consumed = split_frames(buf)
    assert got == msgs and consumed == len(buf)
    # a partial tail frame stays unconsumed in the buffer
    buf2 = buf + encode_frame(("tail",))[:-3]
    got2, consumed2 = split_frames(buf2)
    assert got2 == msgs and consumed2 == len(buf)


def test_frame_crc_and_magic_are_enforced():
    raw = encode_frame(("hello", 1))
    hdr = len(MAGIC) + HEADER.size
    header, payload = raw[:hdr], raw[hdr:]
    assert frame_size(header) == len(payload)
    assert decode_frame(header, payload) == ("hello", 1)
    flipped = bytearray(payload)
    flipped[-1] ^= 0x01            # payload bit flip: CRC mismatch
    with pytest.raises(FrameError):
        decode_frame(header, bytes(flipped))
    with pytest.raises(FrameError):
        decode_frame(b"XXNOPE00" + header[8:], payload)
    with pytest.raises(FrameError):
        decode_frame(header, payload[:-1])     # truncated payload


# -- transport matrix -------------------------------------------------------

@pytest.mark.parametrize("kind", TRANSPORTS)
def test_transport_round_trip_and_close(kind):
    st, ct = make_transports(kind)
    lst = st.listen()
    conn = ct.connect(lst.address, timeout_s=2.0)
    srv = lst.accept(timeout_s=2.0)
    big = ("payload", b"\xab" * (1 << 20))
    conn.send_msg(big, 2.0)
    assert srv.recv_msg(2.0) == big
    srv.send_msg(("ok",), 2.0)
    assert conn.recv_msg(2.0) == ("ok",)
    srv.close()
    with pytest.raises(TransportError):
        for _ in range(64):        # close may race one buffered frame
            conn.recv_msg(0.2)
    lst.close()


@pytest.mark.parametrize("kind", TRANSPORTS)
def test_transport_idle_timeout_is_wire_timeout(kind):
    st, ct = make_transports(kind)
    lst = st.listen()
    conn = ct.connect(lst.address, timeout_s=2.0)
    srv = lst.accept(timeout_s=2.0)
    t0 = time.monotonic()
    with pytest.raises(WireTimeout):
        conn.recv_msg(0.05)
    assert time.monotonic() - t0 < 5.0
    # an idle timeout is NOT fatal: the link still works afterwards
    srv.send_msg(("late",), 2.0)
    assert conn.recv_msg(2.0) == ("late",)
    conn.close()
    srv.close()
    lst.close()


# -- server/client protocol matrix ------------------------------------------

@pytest.mark.parametrize("kind", TRANSPORTS)
def test_remote_follower_ships_exact_parity(tmp_path, kind):
    st, ct = make_transports(kind)
    sched, src, sink = make_leader(tmp_path, segment_bytes=2048)
    replica = make_replica(tmp_path)
    srv = ReplicaServer(replica, st).start()
    ship = SegmentShipper(sched.wal, leader_tick=lambda: sched._tick)
    link = RemoteFollower(ct, srv.address, name="r0",
                          policy=fast_policy("r0"), io_timeout_s=2.0)
    ship.attach(link)
    drive(sched, src, 8)
    pump_until_caught(ship, sched, [replica])
    h, got = replica.view_at(sink.name)
    assert h == sched._tick
    assert got == live_view(sched, sink)
    assert link.conn_state == "healthy"
    snap = link.transport_snapshot()
    assert snap["state"] == "healthy" and snap["failures"] == 0
    ping = link.ping()
    assert ping["name"] == "r0" and ping["horizon"] == sched._tick
    srv.close()
    sched.close()
    replica.close()


@pytest.mark.parametrize("kind", TRANSPORTS)
def test_server_answers_err_for_unknown_op(kind):
    st, ct = make_transports(kind)
    replica = object()  # never reached by an unknown op
    srv = ReplicaServer(replica, st).start()
    conn = ct.connect(srv.address, timeout_s=2.0)
    conn.send_msg(("warp", 9), 2.0)
    resp = conn.recv_msg(2.0)
    assert resp[0] == "err" and "warp" in resp[1]
    conn.close()
    srv.close()


# -- fault paths (deterministic: scripted switches, probability-1 rates) ----

def _wired_cluster(tmp_path, faults, **link_kw):
    t = LoopbackTransport()
    sched, src, sink = make_leader(tmp_path)
    replica = make_replica(tmp_path)
    srv = ReplicaServer(replica, t).start()
    link_kw.setdefault("policy", fast_policy("r0"))
    link_kw.setdefault("io_timeout_s", 0.2)
    link = RemoteFollower(FaultyTransport(t, faults), srv.address,
                          name="r0", **link_kw)
    ship = SegmentShipper(sched.wal, leader_tick=lambda: sched._tick)
    ship.attach(link)
    return sched, src, sink, replica, srv, link, ship


def test_partition_drives_unreachable_then_heal_resyncs(tmp_path):
    faults = WireFaults()
    sched, src, sink, replica, srv, link, ship = _wired_cluster(
        tmp_path, faults)
    drive(sched, src, 2)
    pump_until_caught(ship, sched, [replica])
    faults.partition("c2s")
    sched_tick_before = replica.published_horizon()
    drive(sched, src, 2, start=2)
    sched.wal.sync()
    deadline = time.monotonic() + 10
    while link.conn_state != "unreachable" \
            and time.monotonic() < deadline:
        ship.pump_once()
        time.sleep(0.002)
    assert link.conn_state == "unreachable"
    assert replica.published_horizon() == sched_tick_before  # no leak
    assert ship.link_stalls > 0 and ship.nacks == 0
    faults.heal()
    pump_until_caught(ship, sched, [replica])
    assert link.conn_state == "healthy"
    assert link.reconnects_total >= 1
    h, got = replica.view_at(sink.name)
    assert h == sched._tick and got == live_view(sched, sink)
    # loss forced the WAL-as-retransmit-buffer path for real
    assert ship.retransmit_bytes > 0
    srv.close()
    sched.close()
    replica.close()


def test_scripted_reset_reconnects_idempotently(tmp_path):
    faults = WireFaults()
    sched, src, sink, replica, srv, link, ship = _wired_cluster(
        tmp_path, faults)
    drive(sched, src, 3)
    pump_until_caught(ship, sched, [replica])
    before = live_view(sched, sink)
    faults.reset_once(1)
    drive(sched, src, 3, start=3)
    pump_until_caught(ship, sched, [replica])
    assert link.reconnects_total >= 1
    h, got = replica.view_at(sink.name)
    assert h == sched._tick and got == live_view(sched, sink)
    assert got != before  # the post-reset windows actually landed
    srv.close()
    sched.close()
    replica.close()


def test_corrupt_payload_is_nacked_by_record_crc(tmp_path):
    # frame CRC passes (the flip happens before framing); the replica's
    # record-level CRC must reject the shipment and NACK its cursor
    faults = WireFaults()
    sched, src, sink, replica, srv, link, ship = _wired_cluster(
        tmp_path, faults)
    drive(sched, src, 2)
    pump_until_caught(ship, sched, [replica])
    faults.set_rates(corrupt_payload=1.0)
    drive(sched, src, 2, start=2)
    sched.wal.sync()
    deadline = time.monotonic() + 10
    while ship.nacks == 0 and time.monotonic() < deadline:
        ship.pump_once()
        time.sleep(0.002)
    assert ship.nacks >= 1
    faults.quiesce()
    pump_until_caught(ship, sched, [replica])
    h, got = replica.view_at(sink.name)
    assert h == sched._tick and got == live_view(sched, sink)
    srv.close()
    sched.close()
    replica.close()


def test_corrupt_frame_resets_connection_then_recovers(tmp_path):
    faults = WireFaults()
    sched, src, sink, replica, srv, link, ship = _wired_cluster(
        tmp_path, faults)
    drive(sched, src, 2)
    pump_until_caught(ship, sched, [replica])
    faults.set_rates(corrupt_frame=1.0)
    drive(sched, src, 2, start=2)
    sched.wal.sync()
    deadline = time.monotonic() + 10
    while link.link_failures == 0 and time.monotonic() < deadline:
        ship.pump_once()
        time.sleep(0.002)
    assert link.link_failures >= 1      # desynced stream = link failure
    faults.quiesce()
    pump_until_caught(ship, sched, [replica])
    assert srv.frame_resets >= 1
    h, got = replica.view_at(sink.name)
    assert h == sched._tick and got == live_view(sched, sink)
    srv.close()
    sched.close()
    replica.close()


def test_duplicates_and_reorders_never_skew_state(tmp_path):
    # every ack/nack carries the receiver's authoritative cursor, so a
    # mis-paired response is still a true statement — parity must hold
    faults = WireFaults()
    sched, src, sink, replica, srv, link, ship = _wired_cluster(
        tmp_path, faults)
    faults.set_rates(dup=0.5, reorder=0.5)
    drive(sched, src, 6)
    sched.wal.sync()
    deadline = time.monotonic() + 20
    while replica.published_horizon() != sched._tick \
            and time.monotonic() < deadline:
        ship.pump_once()
        time.sleep(0.002)
    faults.quiesce()
    pump_until_caught(ship, sched, [replica])
    assert faults.stats["dup"] + faults.stats["reorder"] > 0
    h, got = replica.view_at(sink.name)
    assert h == sched._tick and got == live_view(sched, sink)
    srv.close()
    sched.close()
    replica.close()


def test_drop_s2c_applies_but_retransmits(tmp_path):
    # a dropped RESPONSE means the server applied and the client never
    # heard: the re-offer is counted as retransmission and the dedup/
    # cursor machinery keeps the replay exactly-once
    faults = WireFaults()
    sched, src, sink, replica, srv, link, ship = _wired_cluster(
        tmp_path, faults)
    drive(sched, src, 2)
    pump_until_caught(ship, sched, [replica])
    faults.set_rates(drop_s2c=1.0)
    drive(sched, src, 2, start=2)
    sched.wal.sync()
    for _ in range(8):
        ship.pump_once()
        time.sleep(0.002)
    faults.quiesce()
    pump_until_caught(ship, sched, [replica])
    assert faults.stats["drop_s2c"] >= 1
    assert ship.retransmit_bytes > 0
    h, got = replica.view_at(sink.name)
    assert h == sched._tick and got == live_view(sched, sink)
    srv.close()
    sched.close()
    replica.close()


# -- backoff state machine (fake clock, no sleeps) --------------------------

def test_backoff_growth_caps_and_states():
    clk = FakeClock()
    p = ReconnectPolicy("r0", base_s=0.1, cap_s=0.8, jitter=0.0,
                        degraded_after=1, unreachable_after=4,
                        clock=clk)
    assert p.state == "connecting"
    delays = [p.failed() for _ in range(6)]
    assert delays == [0.1, 0.2, 0.4, 0.8, 0.8, 0.8]  # 2^n capped
    assert p.state == "unreachable"
    assert not p.due()                      # gated until the clock moves
    assert p.seconds_until_due() == pytest.approx(0.8)
    clk.advance(0.8)
    assert p.due()
    assert p.ok() is True                   # a failure run just ended
    assert p.state == "healthy" and p.failures == 0
    assert p.reconnects == 1
    assert p.ok() is False                  # steady-state ok: no event
    snap = p.snapshot()
    assert snap["state"] == "healthy" and snap["reconnects"] == 1


def test_backoff_jitter_is_bounded_and_seeded():
    clk = FakeClock()
    a = ReconnectPolicy("r0", base_s=0.1, cap_s=10.0, jitter=0.25,
                        seed=7, clock=clk)
    b = ReconnectPolicy("r0", base_s=0.1, cap_s=10.0, jitter=0.25,
                        seed=7, clock=clk)
    da = [a.failed() for _ in range(8)]
    db = [b.failed() for _ in range(8)]
    assert da == db                          # same seed+name: same storm
    for i, d in enumerate(da):
        raw = min(10.0, 0.1 * 2 ** i)
        assert raw * 0.75 <= d <= raw * 1.25
    c = ReconnectPolicy("r1", base_s=0.1, cap_s=10.0, jitter=0.25,
                        seed=7, clock=clk)
    assert [c.failed() for _ in range(8)] != da  # per-name decorrelated


def test_backoff_degraded_threshold_and_recovery_cycle():
    clk = FakeClock()
    p = ReconnectPolicy("r0", base_s=0.05, cap_s=1.0, jitter=0.0,
                        degraded_after=2, unreachable_after=3,
                        clock=clk)
    p.failed()
    assert p.state == "connecting"      # below degraded_after, no flap
    p.failed()
    assert p.state == "degraded"
    p.failed()
    assert p.state == "unreachable"
    clk.advance(10)
    p.ok()
    assert p.state == "healthy"
    p.failed()
    # one failure below degraded_after: still nominally healthy, and
    # the backoff growth restarted from base
    assert p.state == "healthy" and p.failures == 1
    assert p.last_backoff_s == pytest.approx(0.05)


# -- partition detection (fake clock, _stub_coord style) --------------------

class _StubReplica:
    def __init__(self, name, horizon):
        self.name = name
        self._h = horizon
        self.promoted = False

    def published_horizon(self):
        return self._h


def _stub_coord(sample, **kw):
    calls = []

    def promote_fn(winner, epoch):
        calls.append((winner.name, epoch))
        return object()

    kw.setdefault("confirm_intervals", 2)
    coord = FailoverCoordinator(
        [_StubReplica("a", 5), _StubReplica("b", 7)],
        sampler=sample, promote_fn=promote_fn, **kw)
    return coord, calls


def test_partitioned_sample_fires_debounced():
    clk = FakeClock()
    part = {"v": False}
    coord, calls = _stub_coord(
        lambda now: {"committer_dead": False, "pump_failed": False,
                     "beat": 1, "partitioned": part["v"]})
    assert coord.step(clk.advance(0.05)) == []
    part["v"] = True
    assert coord.step(clk.advance(0.05)) == []        # streak 1 of 2
    acts = coord.step(clk.advance(0.05))              # streak 2: fire
    assert [a["kind"] for a in acts] == ["failover_promote"]
    assert acts[0]["reason"] == "leader_partitioned"
    assert calls == [("b", 1)]
    assert coord.partitions_detected == 1


def test_partition_flapping_never_fires():
    clk = FakeClock()
    seq = iter([True, False] * 10)
    coord, calls = _stub_coord(
        lambda now: {"committer_dead": False, "pump_failed": False,
                     "beat": 1, "partitioned": next(seq)})
    for _ in range(10):
        assert coord.step(clk.advance(0.05)) == []
    assert calls == [] and coord.partitions_detected == 0


def test_heartbeat_stall_with_live_committer_is_partition():
    # a stalled beat while the committer provably lives is a partition,
    # not a death — the reason must say so (the bare-stall label
    # "heartbeat_timeout" is pinned by test_failover)
    clk = FakeClock()
    coord, calls = _stub_coord(
        lambda now: {"committer_dead": False, "pump_failed": False,
                     "beat": 1, "committer_alive": True},
        heartbeat_timeout_s=0.2, confirm_intervals=2)
    coord.step(clk.advance(0.05))
    coord.step(clk.advance(0.3))                      # stale: streak 1
    acts = coord.step(clk.advance(0.3))               # streak 2: fire
    assert acts[0]["reason"] == "leader_partitioned"
    assert coord.partitions_detected == 1


# -- read tier ejection / restore -------------------------------------------

class _FakeLink:
    def __init__(self, state="healthy"):
        self.conn_state = state


class _FakeReplica:
    def __init__(self, name, horizon=10, fail=None):
        self.name = name
        self._h = horizon
        self.fail = fail
        self.reads = 0

    def published_horizon(self):
        return self._h

    def lag_ticks(self):
        return 0

    def top_k(self, sink, k, by="weight"):
        if self.fail is not None:
            raise self.fail
        self.reads += 1
        return self._h, [((sink, "x"), 1.0)]


def test_read_tier_ejects_unreachable_link_and_restores():
    r0, r1 = _FakeReplica("r0"), _FakeReplica("r1")
    link = _FakeLink()
    tier = ReadTier([r0, r1])
    tier.bind_link(r0, link)
    link.conn_state = "unreachable"
    for _ in range(4):
        res = tier.top_k("s", 1)
        assert res.source == "r1"
    assert tier.ejects == 1
    assert any(r is r0 for r in tier.ejected_replicas)
    assert r0.reads == 0
    link.conn_state = "healthy"
    sources = {tier.top_k("s", 1).source for _ in range(4)}
    assert sources == {"r0", "r1"}        # restored into rotation
    assert tier.restores == 1


def test_read_tier_ejects_on_link_flavored_read_error():
    r0 = _FakeReplica("r0", fail=ConnectionError("peer gone"))
    r1 = _FakeReplica("r1")
    tier = ReadTier([r0, r1])
    tier.bind_link(r0, _FakeLink("unreachable"))
    res = tier.top_k("s", 1)
    assert res.source == "r1" and tier.ejects == 1
    # a StaleRead-path value error still propagates (not link-flavored)
    r1.fail = ValueError("boom")
    with pytest.raises(ValueError):
        tier.top_k("s", 1)


# -- observability surfaces --------------------------------------------------

def test_conn_state_gauges_and_transport_sidecar(tmp_path):
    t = LoopbackTransport()
    sched, src, sink = make_leader(tmp_path)
    replica = make_replica(tmp_path)
    srv = ReplicaServer(replica, t).start()
    ship = SegmentShipper(sched.wal, leader_tick=lambda: sched._tick)
    link = RemoteFollower(t, srv.address, name="r0",
                          policy=fast_policy("r0"), io_timeout_s=0.5)
    ship.attach(link)
    ship.publish_metrics()
    try:
        drive(sched, src, 3)
        pump_until_caught(ship, sched, [replica])
        assert REGISTRY.value("replica.r0.conn_state", "?") == "healthy"
        assert REGISTRY.value("net.reconnects_total", -1) == 0
        assert REGISTRY.value("net.retransmit_bytes", -1) >= 0

        state = json.load(
            open(os.path.join(sched.wal.wal_dir, "ship-state.json")))
        assert state["transport"]["r0"]["state"] == "healthy"

        wi = _load_tool("wal_inspect")
        summary = wi.inspect(sched.wal.wal_dir, verbose=False)
        tsec = summary["shipping"]["transport"]
        assert tsec["r0"]["state"] == "healthy"
        assert tsec["r0"]["reconnects"] == 0
        assert tsec["r0"]["retransmit_bytes"] == 0
        assert "last_backoff_s" in tsec["r0"]
    finally:
        ship.close()
        srv.close()
        sched.close()
        replica.close()


def test_net_trace_spans_surface_in_trace_inspect(tmp_path, capsys):
    from reflow_tpu import obs
    obs.enable()
    try:
        t = LoopbackTransport()
        sched, src, sink = make_leader(tmp_path)
        replica = make_replica(tmp_path)
        srv = ReplicaServer(replica, t).start()
        ship = SegmentShipper(sched.wal,
                              leader_tick=lambda: sched._tick)
        link = RemoteFollower(t, srv.address, name="r0",
                              policy=fast_policy("r0"),
                              io_timeout_s=0.5)
        ship.attach(link)
        drive(sched, src, 3)
        pump_until_caught(ship, sched, [replica])
        path = str(tmp_path / "trace.json")
        obs.export_chrome_trace(path)
        ti = _load_tool("trace_inspect")
        assert ti.main([path, "--json"]) == 0
        out = json.loads(capsys.readouterr().out)
        net = out["network"]["r0"]
        assert net["sends"] >= 1 and net["send_failures"] == 0
        assert "receive" in net["ops"]
        assert net["last_state"] == "healthy"
        ship.close()
        srv.close()
        sched.close()
        replica.close()
    finally:
        obs.disable()


# -- protocol responses remain the shipping tuples --------------------------

def test_remote_follower_receive_speaks_ack_nack(tmp_path):
    t = LoopbackTransport()
    sched, src, sink = make_leader(tmp_path)
    replica = make_replica(tmp_path)
    srv = ReplicaServer(replica, t).start()
    ship = SegmentShipper(sched.wal, leader_tick=lambda: sched._tick)
    link = RemoteFollower(t, srv.address, name="r0",
                          policy=fast_policy("r0"), io_timeout_s=0.5)
    cur = link.subscribe()
    assert cur is None or isinstance(cur, tuple)
    drive(sched, src, 1)
    sched.wal.sync()
    ship.attach(link)
    deadline = time.monotonic() + 10
    while replica.published_horizon() != sched._tick \
            and time.monotonic() < deadline:
        ship.pump_once()
        time.sleep(0.002)
    # the link's receive() really returned ShipAck objects to the
    # shipper (cursor advanced past subscribe, zero nacks)
    st = ship._followers["r0"]
    assert st.nacks == 0 and st.cursor is not None
    assert replica.published_horizon() == sched._tick
    srv.close()
    sched.close()
    replica.close()


# -- the TCP connection's buffer: what a frame costs in socket calls ---------

class CountingSock:
    """A real socket that counts the calls ``_TcpConn`` makes on it."""

    def __init__(self, sock):
        self._sock = sock
        self.calls = {}

    def __getattr__(self, name):
        fn = getattr(self._sock, name)
        if name == "fileno" or not callable(fn):
            return fn

        def counted(*a, **kw):
            self.calls[name] = self.calls.get(name, 0) + 1
            return fn(*a, **kw)
        return counted


def tcp_pair():
    """(connection under test over a counting socket, the raw peer
    socket, the counting socket). The counts start after set-up."""
    a, b = socket.socketpair()
    sock = CountingSock(a)
    conn = _TcpConn(sock)
    sock.calls.clear()
    return conn, b, sock


def recv_exact(sock, n):
    buf = bytearray()
    sock.settimeout(5.0)
    while len(buf) < n:
        chunk = sock.recv(n - len(buf))
        assert chunk
        buf += chunk
    return bytes(buf)


def test_whole_small_frame_is_one_recv_into_and_no_settimeout():
    conn, peer, sock = tcp_pair()
    try:
        for i in range(3):      # the timeout asked for does not change
            peer.sendall(encode_frame(("req", i)))
            calls = conn.sock_calls
            assert conn.recv_msg(0.2) == ("req", i)
            # one wait and one read: header and payload came together
            assert conn.sock_calls - calls == 2
        assert sock.calls == {"recv_into": 3}
        # and under another timeout the socket's own is still not set
        peer.sendall(encode_frame(("req", 3)))
        assert conn.recv_msg(5.0) == ("req", 3)
        assert "settimeout" not in sock.calls
        # a reply that fits the socket's buffer is one send
        calls = conn.sock_calls
        conn.send_msg(("ok",), 5.0)
        assert conn.sock_calls - calls == 1
        assert sock.calls == {"recv_into": 4, "send": 1}
        assert (conn.frames_in, conn.frames_out) == (4, 1)
    finally:
        conn.close()
        peer.close()


def test_second_frame_of_a_segment_costs_no_socket_call():
    conn, peer, sock = tcp_pair()
    try:
        third = encode_frame(("c", b"z" * 100))
        peer.sendall(encode_frame(("a", 1)) + encode_frame(("b", 2))
                     + third[:20])
        assert conn.recv_msg(0.2) == ("a", 1)
        calls = conn.sock_calls
        assert conn.recv_msg(0.2) == ("b", 2)
        assert conn.sock_calls == calls and sock.calls == {"recv_into": 1}
        # the cut third one is finished from the socket
        peer.sendall(third[20:])
        assert conn.recv_msg(0.2) == ("c", b"z" * 100)
        assert conn.frames_in == 3
    finally:
        conn.close()
        peer.close()


_CUT_MSG = ("cut", b"\x5a" * 40, 7)
_CUTS = sorted({1, len(MAGIC) - 1, len(MAGIC), len(MAGIC) + 1,
                len(MAGIC) + HEADER.size - 1, len(MAGIC) + HEADER.size,
                len(MAGIC) + HEADER.size + 1,
                len(encode_frame(_CUT_MSG)) - 1})


@pytest.mark.parametrize("cut", _CUTS)
def test_frame_cut_at_a_boundary_decodes_equal(cut):
    conn, peer, _sock = tcp_pair()
    try:
        raw = encode_frame(_CUT_MSG)
        peer.sendall(raw[:cut])
        t = threading.Timer(0.05, peer.sendall, (raw[cut:] + raw,))
        t.start()
        assert conn.recv_msg(2.0) == _CUT_MSG
        assert conn.recv_msg(2.0) == _CUT_MSG     # and the stream is in sync
        t.join()
    finally:
        conn.close()
        peer.close()


def test_long_frame_is_received_into_a_buffer_of_its_own():
    conn, peer, sock = tcp_pair()
    try:
        big = ("payload", bytes(range(256)) * 4096)     # 1 MB
        raw = encode_frame(big) + encode_frame(("next",))
        t = threading.Thread(target=peer.sendall, args=(raw,))
        t.start()
        assert conn.recv_msg(5.0) == big
        assert conn.recv_msg(5.0) == ("next",)
        t.join()
        # nothing was appended or copied through the connection's buffer
        assert len(conn._rbuf) == _RBUF < len(raw)
        assert set(sock.calls) == {"recv_into"}
        # and the other way: a frame the socket's buffer cannot take in
        # one send goes out whole, under the call's deadline
        got = []
        t = threading.Thread(
            target=lambda: got.append(recv_exact(peer, len(raw))))
        t.start()
        conn.send_raw(raw, 5.0)
        t.join()
        assert got == [raw]
    finally:
        conn.close()
        peer.close()


def test_idle_is_wire_timeout_and_a_stall_inside_a_frame_is_not():
    conn, peer, _sock = tcp_pair()
    try:
        with pytest.raises(WireTimeout):
            conn.recv_msg(0.05)
        # idle left the stream in sync: the next frame still reads
        raw = encode_frame(("late", 1))
        peer.sendall(raw)
        assert conn.recv_msg(1.0) == ("late", 1)
        # some bytes of a frame, then nothing past the deadline
        peer.sendall(raw[:len(MAGIC) + 3])
        t0 = time.monotonic()
        with pytest.raises(TransportError) as e:
            conn.recv_msg(0.05)
        assert not isinstance(e.value, WireTimeout)
        assert time.monotonic() - t0 < 2.0
        # a long frame that stalls in its payload is no idle link either
        conn2, peer2, _ = tcp_pair()
        long = encode_frame(("big", b"q" * (2 * _RBUF)))
        peer2.sendall(long[:_RBUF])
        with pytest.raises(TransportError) as e:
            conn2.recv_msg(0.05)
        assert not isinstance(e.value, WireTimeout)
        conn2.close()
        peer2.close()
    finally:
        conn.close()
        peer.close()


def test_close_from_another_thread_ends_a_blocked_recv():
    conn, peer, _sock = tcp_pair()
    try:
        t = threading.Timer(0.05, conn.close)
        t.start()
        t0 = time.monotonic()
        with pytest.raises(TransportError) as e:
            conn.recv_msg(10.0)
        assert not isinstance(e.value, WireTimeout)
        assert time.monotonic() - t0 < 5.0
        t.join()
        with pytest.raises(TransportError):
            conn.send_msg(("x",), 1.0)
        # the peer going away ends one too
        conn2, peer2, _ = tcp_pair()
        t = threading.Timer(0.05, peer2.close)
        t.start()
        with pytest.raises(TransportError, match="closed by peer"):
            conn2.recv_msg(10.0)
        t.join()
        conn2.close()
    finally:
        conn.close()
        peer.close()


def test_bad_magic_length_and_crc_are_frame_errors_over_tcp():
    good = encode_frame(("hello", 1))
    hdr = len(MAGIC) + HEADER.size
    flipped = bytearray(good)
    flipped[-1] ^= 0x01
    for raw in (b"XXNOPE00" + good[len(MAGIC):],
                MAGIC + HEADER.pack((64 << 20) + 1, 0) + good[hdr:],
                bytes(flipped)):
        conn, peer, _sock = tcp_pair()
        try:
            peer.sendall(raw)
            with pytest.raises(FrameError):
                conn.recv_msg(1.0)
        finally:
            conn.close()
            peer.close()


def test_corrupt_frame_over_tcp_resets_connection_then_recovers(tmp_path):
    """``test_corrupt_frame_resets_connection_then_recovers`` on real
    sockets: ``send_raw`` is still the injector's seam, the mangled
    frame fails the receiver's checks out of the buffer, the link
    resets and the next one ships."""
    faults = WireFaults()
    sched, src, sink = make_leader(tmp_path)
    replica = make_replica(tmp_path)
    srv = ReplicaServer(replica, TcpTransport()).start()
    link = RemoteFollower(FaultyTransport(TcpTransport(), faults),
                          srv.address, name="r0",
                          policy=fast_policy("r0"), io_timeout_s=0.5)
    ship = SegmentShipper(sched.wal, leader_tick=lambda: sched._tick)
    ship.attach(link)
    try:
        drive(sched, src, 2)
        pump_until_caught(ship, sched, [replica])
        faults.set_rates(corrupt_frame=1.0)
        drive(sched, src, 2, start=2)
        sched.wal.sync()
        deadline = time.monotonic() + 10
        while link.link_failures == 0 and time.monotonic() < deadline:
            ship.pump_once()
            time.sleep(0.002)
        assert link.link_failures >= 1
        faults.quiesce()
        pump_until_caught(ship, sched, [replica])
        assert srv.frame_resets >= 1
        h, got = replica.view_at(sink.name)
        assert h == sched._tick and got == live_view(sched, sink)
    finally:
        srv.close()
        sched.close()
        replica.close()
