"""The multi-process harness: ownership layout, the cross-process
horizon barrier, the chaos seams, and one real child-process cycle.

The hermetic half exercises the parent-side machinery without spawning
anything: ``OwnershipMap`` round-robin + per-node disk layout,
``horizon_barrier`` convergence and timeout semantics on closure
probes, and the harness's crash seams (``proc_spawn@<node>`` /
``proc_kill9@<node>`` / ``proc_respawn@<node>``) driven by a
``CrashInjector`` exactly like the WAL/serve seams. The subprocess half
spawns a real leader + replica + producer fleet (``python -m
reflow_tpu.proc``), kill -9s the replica mid-stream, respawns it over
the same state directory and requires it to rejoin through the
barrier; children are reaped with timeouts so a wedged child fails the
test instead of hanging the suite.
"""

import json
import os
import subprocess
import sys
import time

import pytest

from reflow_tpu.net import TcpTransport
from reflow_tpu.proc import (BarrierTimeout, OwnershipMap, ProcHarness,
                             horizon_barrier)
from reflow_tpu.proc.harness import ControlClient
from reflow_tpu.proc.worker import producer_batch_words
from reflow_tpu.serve import ReplicaScheduler
from reflow_tpu.utils.faults import CrashInjector, CrashPoint
from reflow_tpu.workloads import wordcount

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


# -- ownership + barrier (hermetic) ------------------------------------


def test_ownership_map_round_robin_and_layout(tmp_path):
    m = OwnershipMap(str(tmp_path), ["a", "b"], sources=["s0", "s1",
                                                         "s2"])
    assert m.owner("s0") == "a" and m.owner("s1") == "b"
    assert m.owner("s2") == "a"
    assert m.sources_of("a") == ["s0", "s2"]
    for d in (m.wal_dir("a"), m.ckpt_dir("a")):
        assert os.path.isdir(d)
    # mirror_dir only NAMES the path — the ReplicaScheduler lays it out
    assert m.mirror_dir("b") == os.path.join(str(tmp_path), "b", "wal")
    m2 = OwnershipMap.from_spec(m.spec())
    assert m2.owner("s2") == "a" and m2.sources_of("b") == ["s1"]


def test_horizon_barrier_waits_for_the_straggler():
    horizons = {"a": 5, "b": 2}

    def probe(name):
        def read():
            h = horizons[name]
            horizons[name] = h + 1       # advances on every poll
            return h
        return read

    out = horizon_barrier({n: probe(n) for n in horizons},
                          timeout_s=5.0, poll_s=0.001)
    # the target was pinned on the first full pass (max = 5); everyone
    # reached it even though "b" started behind
    assert out["a"] >= 5 and out["b"] >= 5


def test_horizon_barrier_timeout_reports_last_observations():
    probes = {"up": lambda: 7, "down": lambda: None}  # never reachable
    with pytest.raises(BarrierTimeout) as ei:
        horizon_barrier(probes, min_horizon=7, timeout_s=0.2,
                        poll_s=0.01)
    assert ei.value.horizons["up"] == 7
    assert ei.value.horizons["down"] is None


def test_deterministic_producer_batches():
    # the bench oracle refolds acked batches from (index, seq) alone
    assert producer_batch_words(0, 0) == producer_batch_words(0, 0)
    assert producer_batch_words(0, 1) != producer_batch_words(0, 0)
    assert producer_batch_words(1, 0) != producer_batch_words(0, 0)


# -- chaos seams (hermetic) --------------------------------------------


def test_spawn_seam_cuts_before_the_child_exists(tmp_path):
    crash = CrashInjector(1, only="proc_spawn@r0")
    h = ProcHarness(str(tmp_path), crash=crash, fleet=False)
    try:
        with pytest.raises(CrashPoint):
            h.spawn_replica("r0")
        assert crash.fired_seam == "proc_spawn@r0"
        assert "r0" not in h.children    # nothing leaked half-spawned
    finally:
        h.close()


def test_kill9_and_respawn_seams_cut_before_acting(tmp_path):
    crash = CrashInjector(1, only="proc_kill9@r0")
    h = ProcHarness(str(tmp_path), crash=crash, fleet=False)
    try:
        with pytest.raises(CrashPoint):
            h.kill9("r0")
        assert h.kills == 0              # the seam fired before the kill
        crash2 = CrashInjector(1, only="proc_respawn@r0")
        h._crash = crash2
        with pytest.raises(CrashPoint):
            h.respawn("r0")
        assert h.respawns == 0
    finally:
        h.close()


# -- port 0 / OS-assigned addressing -----------------------------------


def test_parallel_replica_servers_get_distinct_ports(tmp_path):
    """Two fleets' worth of replica endpoints bind port 0 side by side:
    the OS assigns every port, nothing collides, and each reported
    address is dialable."""
    from reflow_tpu.net import ReplicaServer

    g, _src, sink = wordcount.build_graph()
    servers = []
    try:
        for i in range(3):
            rep = ReplicaScheduler(g, str(tmp_path / f"r{i}"),
                                   name=f"r{i}")
            servers.append(ReplicaServer(rep, TcpTransport()).start())
        ports = [s.address[1] for s in servers]
        assert len(set(ports)) == 3 and all(p > 0 for p in ports)
        for s in servers:
            ok, horizon, view = ControlClient(s.address).call(
                "view", sink.name)
            assert ok == "ok" and horizon == 0 and view == {}
    finally:
        for s in servers:
            s.close()


# -- one real child-process cycle --------------------------------------


def test_child_kill9_respawn_rejoins_the_barrier(tmp_path):
    """A real proc_spawn / proc_kill9 / proc_respawn cycle: the replica
    child dies by SIGKILL mid-stream, comes back over the same state
    directory, and rejoins the fleet at a consistent horizon while a
    paced producer keeps writing."""
    # the children inherit what a machine with a chip exports; every
    # role runs the CPU oracle and must pin itself to the CPU anyway
    # (one process per chip — the first child to checkpoint would
    # otherwise take it)
    h = ProcHarness(str(tmp_path), fleet=False,
                    child_env={"JAX_PLATFORMS": "tpu,cpu"})
    try:
        ready = h.spawn_leader()
        assert ready["ingest"][1] > 0        # OS-assigned, reported
        assert ready["jax_platforms"] == "cpu"
        assert h.spawn_replica("r0")["jax_platforms"] == "cpu"
        assert h.replica_address("r0")[1] > 0
        h.attach_replicas()
        h.spawn_producer("p0", index=0, pace_s=0.02)
        time.sleep(0.5)

        h.kill9("r0")
        assert not h.child("r0").alive
        h.respawn("r0")
        h.attach_replicas(["r0"])
        out = h.barrier(timeout_s=60.0)      # recovered AND caught up
        assert out["r0"] >= 0
        assert h.kills == 1 and h.respawns == 1

        st = h.child("p0").stop()
        assert st is not None and st["ok"]
        assert st["in_doubt"] == []          # every batch fully acked
        assert len(st["acked"]) >= 1
    finally:
        h.close()


def test_leader_kill9_promotes_a_replica_child_exactly_once(tmp_path):
    """kill -9 the leader *process*: ``ProcHarness.coordinator`` drains
    the dead leader's on-disk WAL, a replica child promotes in place
    (epoch 1) and serves ingestion, ``retarget_producers`` swings the
    producer children, their in-doubt batches resubmit — and every
    acked batch is in the promoted leader's view exactly once, the
    survivor agrees at the same horizon, and a late epoch-0 shipment
    from the ex-leader is NACKed ``fenced`` over the wire."""
    from reflow_tpu.net import RemoteFollower
    from reflow_tpu.scheduler import DirtyScheduler
    from reflow_tpu.wal.log import _MAGIC
    from reflow_tpu.wal.ship import Shipment, ShipNack

    n_prod, rnames = 2, ["r0", "r1"]
    h = ProcHarness(str(tmp_path))
    try:
        h.spawn_leader(fsync="tick", epoch=0)
        for nm in rnames:
            h.spawn_replica(nm)
        h.attach_replicas()
        for i in range(n_prod):
            h.spawn_producer(f"p{i}", index=i, pace_s=0.05)
        # every child ships telemetry to the parent's aggregator
        assert h.aggregator.await_nodes(1 + len(rnames) + n_prod,
                                        timeout_s=15.0)
        time.sleep(0.5)

        coord = h.coordinator(epoch=0, confirm_intervals=2,
                              drain_timeout_s=10.0)
        h.kill9("leader")
        promote_evt, now = None, 0.0
        deadline = time.monotonic() + 60.0
        while promote_evt is None and time.monotonic() < deadline:
            for e in coord.step(now):
                if e.get("kind") == "failover_promote":
                    promote_evt = e
            now += 1.0
            time.sleep(0.02)
        assert promote_evt is not None, "leader death never promoted"
        assert promote_evt["winner"] in rnames
        assert promote_evt["epoch"] == 1
        assert h.leader_name == promote_evt["winner"]
        time.sleep(0.5)     # producers reconnect + resubmit in doubt

        exits = [h.child(f"p{i}").stop() for i in range(n_prod)]
        for st in exits:
            assert st is not None and st["ok"], st
            assert st["in_doubt"] == [], st["name"]
        # the kill really forced the reconnect and resubmit paths
        assert sum(st["reconnects"] for st in exits) >= n_prod
        assert sum(st["resubmits"] for st in exits) >= 1

        g, src, sink = wordcount.build_graph()
        ingest = ControlClient(h.ingest_address, io_timeout_s=30.0)
        ingest.call("flush", 20.0)
        _, leader_tick, leader_view = ingest.call("view", sink.name)
        # zero acked-write loss, no double fold: refold every acked
        # (producer, seq) — the content is a pure function of the id
        oracle = DirtyScheduler(g)
        for i, st in enumerate(exits):
            assert st["acked"]
            for seq, _status in st["acked"]:
                oracle.push(src, wordcount.ingest_lines(
                    [" ".join(producer_batch_words(i, seq))]),
                    batch_id=f"p{i}-{seq}")
        oracle.tick()
        want = {kv: w for kv, w in oracle.view(sink.name).items() if w}
        assert {kv: w for kv, w in leader_view.items() if w} == want

        # the survivor: exact parity at the new leader's horizon ...
        (survivor,) = [nm for nm in rnames if nm != h.leader_name]
        h.barrier(names=[survivor], min_horizon=leader_tick,
                  timeout_s=30.0)
        _, rh, rv = h.control(survivor).call("view", sink.name)
        assert rh == leader_tick
        assert {kv: w for kv, w in rv.items() if w} == want
        # ... and a zombie's late shipment is refused by epoch
        link = RemoteFollower(TcpTransport(),
                              h.replica_address(survivor), name=survivor)
        try:
            link.subscribe()
            resp = link.receive(Shipment(0, len(_MAGIC), b"", len(_MAGIC),
                                         False, None, 0, epoch=0))
        finally:
            link.close()
        assert isinstance(resp, ShipNack)
        assert resp.reason.startswith("fenced")
        assert h.kills == 1 and h.respawns == 0

    finally:
        h.close()


def test_cli_role_replica_json_status(tmp_path):
    """tools/reflow_proc.py --role replica --json: first stdout line is
    the ready JSON with the OS-assigned address, EOF on stdin is a
    clean stop, and the last line is the exit-status JSON."""
    proc = subprocess.Popen(
        [sys.executable, os.path.join(REPO, "tools", "reflow_proc.py"),
         "--role", "replica", "--name", "rx",
         "--root", str(tmp_path / "rx"), "--json"],
        cwd=REPO, text=True, stdin=subprocess.PIPE,
        stdout=subprocess.PIPE,
        env=dict(os.environ, JAX_PLATFORMS="cpu"))
    try:
        ready = json.loads(proc.stdout.readline())
        assert ready["event"] == "ready" and ready["name"] == "rx"
        assert ready["addr"][1] > 0
        proc.stdin.close()                   # EOF doubles as stop
        out = proc.stdout.read()
        assert proc.wait(timeout=30) == 0
        status = json.loads(out.strip().splitlines()[-1])
        assert status["event"] == "exit" and status["ok"]
        assert not status["promoted"]
    finally:
        if proc.poll() is None:
            proc.kill()
