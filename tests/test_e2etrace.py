"""Follow-the-write: cross-process causality tokens, the ack→push
freshness decomposition, and the crash-surviving flight recorder.

Three contracts under test, hermetically (the kill -9 chaos twin is
``tests/test_proc.py::test_leader_kill9_promotes_a_replica_child_exactly_once``):

- **wire compatibility** — the causality token is a defaulted trailing
  field on ``SubmitReq``/``SubmitAck``/``DeltaFrame``, trimmed when
  tracing is off, so an unstamped message pickles byte-identically to
  the pre-trace protocol and a stamped sender interoperates with an
  unstamped receiver (and vice versa).
- **sampling coherence** — the 1-in-N decision is made ONCE at the
  producer and rides the token: every process records the same writes;
  an unsampled write appears nowhere (no torn chains).
- **decomposition & post-mortem** — ``trace_inspect`` stitches
  token-keyed chains across files and tiles each write's ack→deliver
  freshness exactly, even when the replica's replay span encloses the
  fan-out (synchronous on_window) or an ack was lost and the write was
  re-admitted; the flight recorder's ring survives rotation, respawn
  (``.prev``) and torn tails, and ``reflow_flight`` merges the corners
  into one timeline.
"""

import importlib.util
import json
import os
import pickle

from reflow_tpu import obs
from reflow_tpu.net import LoopbackTransport
from reflow_tpu.obs import trace
from reflow_tpu.obs.flight import FlightRecorder
from reflow_tpu.obs.fleet import FleetAggregator
from reflow_tpu.serve import (APPLIED, IngestFrontend, RemoteProducer,
                              RpcIngestServer)
from reflow_tpu.serve.rpc import SubmitAck, SubmitReq, _trim
from reflow_tpu.subs.query import (DeltaFrame, frames_from_wire,
                                   frames_to_wire)
from reflow_tpu.wal import DurableScheduler
from reflow_tpu.workloads import wordcount

_TOOLS = os.path.join(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))), "tools")


def _load_tool(name):
    spec = importlib.util.spec_from_file_location(
        name, os.path.join(_TOOLS, f"{name}.py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


# -- wire compatibility -----------------------------------------------------

def test_submit_req_unstamped_pickles_byte_identical():
    req = SubmitReq("b0", "src", ("payload",), 5.0)
    assert req.cause is None
    legacy = ("b0", "src", ("payload",), 5.0)   # pre-trace 4-tuple
    assert pickle.dumps(_trim(tuple(req))) == pickle.dumps(legacy)
    # an old sender's 4-tuple fills the receiving default
    assert SubmitReq(*legacy).cause is None


def test_submit_req_stamped_round_trips():
    req = SubmitReq("b0", "src", (), None, "p#1#7")
    wire = _trim(tuple(req))
    assert len(wire) == 5
    assert SubmitReq(*wire).cause == "p#1#7"


def test_submit_ack_trim_and_one_sided_tolerance():
    ack = SubmitAck("b0", "pending")
    legacy = ("b0", "pending", None, None)
    assert pickle.dumps(_trim(tuple(ack))) == pickle.dumps(legacy)
    assert SubmitAck(*legacy).cause is None
    stamped = SubmitAck("b0", "pending", cause="p#0#3")
    assert _trim(tuple(stamped))[-1] == "p#0#3"


def test_delta_frame_unstamped_wire_identity_and_stamped():
    fr = DeltaFrame(0, 4, "view", ((("k", 1.0), 1),), False)
    legacy = ((0, 4, "view", ((("k", 1.0), 1),), False),)
    assert pickle.dumps(frames_to_wire([fr])) == pickle.dumps(legacy)
    # an unstamped wire frame from an old hub reads back cause-less
    assert frames_from_wire(legacy)[0].cause is None
    stamped = DeltaFrame(0, 4, "view", (), False, ("p#0#1", "p#0#2"))
    wire = frames_to_wire([stamped])
    assert wire[0][-1] == ("p#0#1", "p#0#2")
    assert frames_from_wire(wire)[0].cause == ("p#0#1", "p#0#2")


# -- cross-process sampling coherence ---------------------------------------

def _rpc_stack(tmp_path):
    g, src, sink = wordcount.build_graph()
    sched = DurableScheduler(g, wal_dir=str(tmp_path / "wal"),
                             fsync="tick")
    fe = IngestFrontend(sched, start=True)
    lt = LoopbackTransport()
    srv = RpcIngestServer(fe, lt).start()
    return sched, fe, lt, srv, src


def _spans(path):
    with open(path) as f:
        return [e for e in json.load(f)["traceEvents"]
                if e.get("ph") == "X"]


def test_sampled_write_recorded_at_every_hop(tmp_path, monkeypatch):
    monkeypatch.setattr(trace, "SAMPLE_EVERY", 1)   # every write draws
    sched, fe, lt, srv, src = _rpc_stack(tmp_path)
    obs.enable()
    trace.reset()
    prod = RemoteProducer(lt, srv.address, name="p0")
    try:
        t = prod.submit(src, wordcount.ingest_lines(["aa bb"]),
                        batch_id="b0")
        res = t.result(10)
        assert res.status == APPLIED
        tok = t.cause
        assert tok and tok.startswith("p0#0#")   # origin#epoch#seq
        out = tmp_path / "trace.json"
        obs.export_chrome_trace(str(out))
        by_name = {}
        for e in _spans(str(out)):
            if (e.get("args") or {}).get("cause") == tok:
                by_name.setdefault(e["name"], []).append(e)
        # producer, RPC server, frontend admission, and the WAL all
        # recorded THIS write under the SAME token — no re-rolling
        for name in ("producer_submit", "rpc_admit", "admission",
                     "wal_append"):
            assert name in by_name, (name, sorted(by_name))
        assert by_name["wal_append"][0]["args"]["lsn"] is not None
    finally:
        obs.disable()
        trace.reset()
        prod.close()
        srv.close()
        fe.close()
        sched.wal.close()


def test_unsampled_write_appears_nowhere(tmp_path, monkeypatch):
    monkeypatch.setattr(trace, "SAMPLE_EVERY", 1 << 30)
    trace.sample()   # burn the counter's possible zero-phase draw
    sched, fe, lt, srv, src = _rpc_stack(tmp_path)
    obs.enable()
    trace.reset()
    prod = RemoteProducer(lt, srv.address, name="p0")
    try:
        t = prod.submit(src, wordcount.ingest_lines(["aa"]),
                        batch_id="b0")
        assert t.result(10).status == APPLIED
        assert t.cause is None
        out = tmp_path / "trace.json"
        obs.export_chrome_trace(str(out))
        causes = [e for e in _spans(str(out))
                  if (e.get("args") or {}).get("cause")
                  or (e.get("args") or {}).get("causes")]
        assert causes == []          # no torn chain anywhere
        assert not any(e["name"] == "rpc_admit"
                       for e in _spans(str(out)))
    finally:
        obs.disable()
        trace.reset()
        prod.close()
        srv.close()
        fe.close()
        sched.wal.close()


def test_real_stack_records_the_full_chain_and_freshness_tiles(
        tmp_path, monkeypatch):
    """One sampled write followed through the REAL stack — producer ->
    ingestion RPC -> frontend -> WAL -> shipper -> TCP replica link ->
    replay -> hub fan-out -> wire subscriber — carries one token at all
    nine links, minted in the leader's epoch, and ``trace_inspect``'s
    ack->deliver stages, read off those real spans, tile the write's
    end-to-end latency."""
    import time

    from reflow_tpu.net import (ReconnectPolicy, RemoteFollower,
                                ReplicaServer, TcpTransport)
    from reflow_tpu.serve import ReplicaScheduler
    from reflow_tpu.subs import (Subscriber, SubscriptionHub,
                                 SubscriptionServer)
    from reflow_tpu.wal import SegmentShipper

    monkeypatch.setattr(trace, "SAMPLE_EVERY", 1)
    ti = _load_tool("trace_inspect")
    g, src, sink = wordcount.build_graph()
    # epoch 1: what a promoted leader serves under
    sched = DurableScheduler(g, wal_dir=str(tmp_path / "wal"),
                             fsync="tick", epoch=1)
    fe = IngestFrontend(sched, start=True)
    lt = LoopbackTransport()
    srv = RpcIngestServer(fe, lt).start()
    g2, _s, _k = wordcount.build_graph()
    rep = ReplicaScheduler(g2, str(tmp_path / "r0"), name="r0")
    rsrv = ReplicaServer(rep, TcpTransport()).start()
    link = RemoteFollower(
        TcpTransport(), rsrv.address, name="r0",
        policy=ReconnectPolicy("r0", base_s=0.005, cap_s=0.05, seed=0),
        io_timeout_s=2.0)
    ship = SegmentShipper(sched.wal, leader_tick=lambda: sched._tick)
    hub = SubscriptionHub(rep, name="r0", idle_poll_s=0.005)
    rep.attach_hub(hub)
    ssrv = SubscriptionServer(hub, lt).start()
    obs.enable()
    trace.reset()
    prod = RemoteProducer(lt, srv.address, name="p0")
    sub = Subscriber(lt, ssrv.address, sink.name, kind="view",
                     name="sub-r0")
    try:
        ship.attach(link)
        sub.pump(wait_s=0.01)       # registered before the writes land
        toks = []
        for i in range(3):
            t = prod.submit(src, wordcount.ingest_lines([f"aa b{i}"]),
                            batch_id=f"b{i}")
            assert t.result(10).status == APPLIED
            toks.append(t.cause)
        # once the hello has landed, the token's epoch field is the
        # serving leader's (the first is minted before the connect)
        assert all(tok.startswith("p0#1#") for tok in toks[1:]), toks
        fe.flush()
        sched.wal.sync()
        deadline = time.monotonic() + 20
        while time.monotonic() < deadline and not (
                rep.published_horizon() == sched._tick
                and sub.horizon >= sched._tick):
            ship.pump_once()
            sub.pump(wait_s=0.01)
        assert sub.horizon >= sched._tick and sub.gaps_total == 0
        path = str(tmp_path / "trace.json")
        obs.export_chrome_trace(path)
        report = ti.inspect([path], require_chain=list(ti.FULL_CHAIN))
        causal = report["causal"]
        assert causal["full_chains"] >= 1, causal["span_names"]
        assert causal["required_chains"] >= 1
        fresh = report["freshness"]
        assert fresh is not None and fresh["chains"] >= 1
        assert set(fresh["stages"]) == set(ti.FRESHNESS_STAGES)
        assert fresh["max_dev_frac"] <= 0.10, fresh["worst"]
    finally:
        obs.disable()
        trace.reset()
        sub.close()
        prod.close()
        ssrv.close()
        hub.close()
        ship.close()
        link.close()
        rsrv.close()
        rep.close()
        srv.close()
        fe.close()
        sched.wal.close()


# -- trace_inspect: chains, freshness tiling, schema ------------------------

TOK = "p0#0#1"      # the write's own token
CHUNK = "n0#0#9"    # the shipped chunk's token (bridges net_send)


def _ev(name, ts, dur, **args):
    return {"ph": "X", "name": name, "ts": ts, "dur": dur,
            "tid": 1, "pid": 1, "args": args or None}


def _chain_events(*, replay_dur=500.0, extra=()):
    evs = [
        _ev("producer_submit", 0.0, 1000.0, cause=TOK),
        _ev("rpc_admit", 100.0, 100.0, cause=TOK),
        _ev("admission", 120.0, 50.0, cause=TOK),
        _ev("wal_append", 300.0, 200.0, cause=TOK, lsn=3),
        _ev("ship_segment", 600.0, 300.0, cause=CHUNK, causes=[TOK]),
        _ev("net_send", 620.0, 100.0, cause=CHUNK),
        _ev("replica_replay", 1000.0, replay_dur, cause=CHUNK,
            causes=[TOK]),
        _ev("sub_fanout", 1600.0, 100.0, causes=[TOK]),
        _ev("sub_deliver", 1800.0, 50.0, causes=[TOK]),
    ]
    evs.extend(extra)
    return evs


def _write_trace(path, events, base_s=10.0, node="n0"):
    with open(path, "w") as f:
        json.dump({"traceEvents": events, "baseTimeS": base_s,
                   "node": node}, f)
    return str(path)


def test_inspect_stitches_full_chain_across_files(tmp_path):
    ti = _load_tool("trace_inspect")
    evs = _chain_events()
    # split producer / leader / replica+sub spans across three files
    # with different baseTimeS — the merge must re-anchor them
    producer = [e for e in evs if e["name"] == "producer_submit"]
    leader = [e for e in evs
              if e["name"] in ("rpc_admit", "admission", "wal_append",
                               "ship_segment", "net_send")]
    rest = [e for e in evs if e not in producer and e not in leader]
    for e in rest:      # this file's clock starts 1ms later
        e["ts"] -= 1000.0
    files = [
        _write_trace(tmp_path / "p.json", producer, node="p0"),
        _write_trace(tmp_path / "l.json", leader, node="leader"),
        _write_trace(tmp_path / "r.json", rest, base_s=10.001,
                     node="r0"),
    ]
    rep = ti.inspect(files, require_chain=list(ti.FULL_CHAIN))
    assert rep["schema"] == "reflow.trace_inspect/2"
    assert rep["causal"]["full_chains"] == 1
    assert rep["causal"]["required_chains"] == 1
    fresh = rep["freshness"]
    assert fresh["chains"] == 1
    assert fresh["max_dev_frac"] == 0.0
    assert fresh["e2e_p50_us"] == 1850.0
    assert fresh["stages"]["admission"]["p50_us"] == 200.0
    assert fresh["stages"]["durability"]["p50_us"] == 300.0
    assert fresh["worst"]["token"] == TOK


def test_require_chain_fails_on_missing_link(tmp_path):
    ti = _load_tool("trace_inspect")
    evs = [e for e in _chain_events() if e["name"] != "net_send"]
    f = _write_trace(tmp_path / "t.json", evs)
    rep = ti.inspect([f], require_chain=list(ti.FULL_CHAIN))
    assert rep["causal"]["required_chains"] == 0
    assert rep["causal"]["full_chains"] == 0


def test_freshness_tiles_when_replay_encloses_fanout(tmp_path):
    # the hub fans out synchronously inside the replay span, so the
    # replay can CLOSE after the push — and even after the delivery.
    # The apply cut must take the earlier of (replay end, push end) or
    # the fanout stage goes negative and the tiling breaks.
    ti = _load_tool("trace_inspect")
    f = _write_trace(tmp_path / "t.json",
                     _chain_events(replay_dur=900.0))   # ends at 1900
    rep = ti.inspect([f], require_chain=list(ti.FULL_CHAIN))
    fresh = rep["freshness"]
    assert fresh["max_dev_frac"] == 0.0
    assert fresh["worst"]["raw_stage_us"]["fanout"] == 0.0
    assert fresh["worst"]["raw_stage_us"]["apply"] == 700.0


def test_freshness_uses_first_admit_of_a_resubmitted_write(tmp_path):
    # a lost ack makes the producer resubmit; the dedup re-admit emits
    # a SECOND rpc_admit much later. Freshness reads the FIRST admit
    # end (the write was in the system from then on), so the tiling
    # still closes exactly.
    ti = _load_tool("trace_inspect")
    f = _write_trace(
        tmp_path / "t.json",
        _chain_events(extra=[_ev("rpc_admit", 900.0, 100.0,
                                 cause=TOK)]))
    rep = ti.inspect([f], require_chain=list(ti.FULL_CHAIN))
    assert rep["freshness"]["max_dev_frac"] == 0.0


def test_chain_freshness_two_element_bounds_fallback():
    # report data predating min-end tracking carries 2-element bounds;
    # the cut helper must fall back to the max end instead of blowing
    # up on the missing slot
    ti = _load_tool("trace_inspect")
    bounds = {"producer_submit": [0.0, 100.0],
              "rpc_admit": [10.0, 20.0],
              "wal_append": [30.0, 40.0],
              "replica_replay": [50.0, 60.0],
              "sub_fanout": [70.0, 80.0],
              "sub_deliver": [90.0, 95.0]}
    stages, e2e, dev, _raw = ti._chain_freshness(bounds)
    assert e2e == 95.0
    assert dev == 0.0
    assert stages["admission"] == 20.0


def test_read_report_backfills_v1_to_v2_keys():
    ti = _load_tool("trace_inspect")
    old = {"causal": {"chains": 2, "links": 5},
           "trace_file": "x.json", "tickets": 4}
    rep = ti.read_report(old)
    assert rep["schema"] == "reflow.trace_inspect/1"
    assert rep["freshness"] is None
    assert rep["trace_files"] == ["x.json"]
    assert rep["causal"]["groups"] == 2       # chains alias
    assert rep["causal"]["full_chains"] == 0


# -- flight recorder --------------------------------------------------------

def test_flight_ring_rotates_and_respawn_archives_prev(tmp_path):
    corner = str(tmp_path / "n0" / "flight")
    rec = FlightRecorder(corner, node="n0", cap_bytes=8192,
                         flush_every=1)
    for i in range(200):
        rec.record("ship_segment", float(i), 1.0, "wal",
                   {"cause": f"n0#0#{i}"})
    assert rec.rotations_total >= 1
    rec.note("promote", epoch=1, horizon=42)    # eager flush
    rec.close()
    # a respawn reopens the same corner; the dead incarnation's ring
    # must survive as .prev, not be truncated over
    rec2 = FlightRecorder(corner, node="n0", cap_bytes=8192,
                          flush_every=1)
    rec2.note("breaker_open", graph="g0")
    rec2.close()
    names = sorted(os.listdir(corner))
    assert any(n.endswith(".prev") for n in names)
    # torn tail: a kill -9 mid-write leaves half a line — the reader
    # must drop it, not die on it
    with open(os.path.join(corner, "flight-a.jsonl"), "a") as f:
        f.write('{"seq": 999, "kind": "sp')
    rf = _load_tool("reflow_flight")
    merged = rf.merge([str(tmp_path)])
    assert "n0" in merged["nodes"]
    node = merged["nodes"]["n0"]
    assert node["files"] >= 2            # live ring + .prev generation
    names = [ev["name"] for ev in merged["events"]]
    assert "promote" in names and "breaker_open" in names
    assert not any(ev.get("seq") == 999 for ev in merged["events"])


def test_flight_recording_survives_a_real_kill9(tmp_path):
    """What a process wrote to its flight ring before SIGKILL took it —
    no close, no atexit, no trace export — is still in its disk corner:
    the eagerly flushed note and the spans flushed before it."""
    import signal
    import subprocess
    import sys

    corner = str(tmp_path / "leader" / "flight")
    child = (
        "import os, signal\n"
        "from reflow_tpu.obs import flight\n"
        f"rec = flight.install({corner!r}, node='leader')\n"
        "for i in range(3):\n"
        "    rec.record('wal_append', float(i), 1.0, 'wal',\n"
        "               {'cause': f'p0#0#{i}'})\n"
        "flight.note('promote', epoch=1, horizon=7)\n"
        "rec.record('wal_append', 9.0, 1.0, 'wal', {'cause': 'lost'})\n"
        "os.kill(os.getpid(), signal.SIGKILL)\n")
    repo = os.path.dirname(_TOOLS)
    proc = subprocess.run(
        [sys.executable, "-c", child], cwd=repo, timeout=120,
        env=dict(os.environ, JAX_PLATFORMS="cpu", PYTHONPATH=repo))
    assert proc.returncode == -signal.SIGKILL
    merged = _load_tool("reflow_flight").merge([str(tmp_path)])
    assert merged["nodes"]["leader"]["events"] >= 4
    names = [ev["name"] for ev in merged["events"]]
    assert names.count("wal_append") >= 3 and "promote" in names


def test_flight_publish_metrics_unregisters_on_close(tmp_path):
    reg = obs.MetricsRegistry()
    rec = FlightRecorder(str(tmp_path / "flight"), node="n0",
                         flush_every=4)
    rec.publish_metrics(reg)
    rec.record("sub_push", 0.0, 1.0, None, {"cause": "x#0#0"})
    snap = reg.snapshot()["gauges"]
    assert snap["flight.events_total"] == 1
    rec.close()
    assert "flight.events_total" not in reg.snapshot()["gauges"]


# -- fleet aggregation: new gauges with pre-upgrade tolerance ---------------

def test_fleet_freshness_and_flight_gauges_backfill_tolerant():
    agg = FleetAggregator(retention=4, stale_after_s=60.0)
    agg.ingest("new", {"gauges": {"subs.freshness_p50": 0.002,
                                  "subs.freshness_p99": 0.010,
                                  "flight.events_total": 42}})
    agg.ingest("old", {"gauges": {}})       # pre-upgrade node
    snap = agg.fleet_snapshot()
    assert snap["nodes"]["old"]["sub_freshness_p50"] is None
    assert snap["nodes"]["old"]["flight_events"] is None
    assert snap["nodes"]["new"]["sub_freshness_p99"] == 0.010
    assert snap["gauges"]["subs.freshness_p50"] == 0.002
    assert snap["gauges"]["flight.events_total"] == 42
    assert not snap["alerts"]


def test_fleet_gauges_none_when_no_node_ships_them():
    agg = FleetAggregator(retention=4, stale_after_s=60.0)
    agg.ingest("old", {"gauges": {"r0.horizon": 7}})
    g = agg.fleet_snapshot()["gauges"]
    assert g["subs.freshness_p50"] is None
    assert g["subs.freshness_p99"] is None
    assert g["flight.events_total"] is None


# -- hub freshness gauges feed the fleet plane ------------------------------

def test_hub_freshness_gauge_populates_after_fanout(tmp_path):
    import numpy as np
    from reflow_tpu.serve import ReplicaScheduler
    from reflow_tpu.subs import SubscriptionHub
    from reflow_tpu.wal import SegmentShipper
    g, src, sink = wordcount.build_graph()
    sched = DurableScheduler(g, wal_dir=str(tmp_path / "wal"),
                             fsync="tick")
    ship = SegmentShipper(sched.wal, leader_tick=lambda: sched._tick)
    g2, _s, _k = wordcount.build_graph()
    rep = ReplicaScheduler(g2, str(tmp_path / "r0"), name="r0")
    ship.attach(rep)
    hub = SubscriptionHub(rep, name="r0", idle_poll_s=0.005)
    rep.attach_hub(hub)
    reg = obs.MetricsRegistry()
    hub.publish_metrics(reg)
    try:
        h = hub.open(sink.name, "view")
        rng = np.random.default_rng(0)
        for t in range(3):
            words = " ".join(f"w{int(x)}"
                             for x in rng.integers(0, 20, 8))
            sched.push(src, wordcount.ingest_lines([words]),
                       batch_id=f"t{t}")
            sched.tick()
        sched.wal.sync()
        for _ in range(200):
            ship.pump_once()
            if rep.published_horizon() == sched._tick:
                break
        assert h.wait_horizon(rep.published_horizon())
        snap = reg.snapshot()["gauges"]
        # the in-hub slice of ack->push freshness is live and sane
        assert snap["subs.freshness_p50"] > 0.0
        assert snap["subs.freshness_p99"] >= snap["subs.freshness_p50"]
    finally:
        hub.close()
        sched.close()


# -- the promoted leader advertises its true epoch --------------------------

def test_durable_scheduler_exposes_wal_epoch(tmp_path):
    g, _src, _sink = wordcount.build_graph()
    sched = DurableScheduler(g, wal_dir=str(tmp_path / "wal"),
                             fsync="tick", epoch=3)
    try:
        # the ingestion RPC's hello reads getattr(sched, "epoch", 0) —
        # before this property existed a promoted leader advertised 0
        # and reconnecting producers minted stale epoch-0 tokens
        assert sched.epoch == 3
        assert sched.epoch == sched.wal.epoch
    finally:
        sched.wal.close()
