"""The thread ledger and the ingest server's cost by operation (PR 39;
docs/guide.md "Span catalog": ``thread_ledger``, ``rpc_ops``,
``rpc_serve``'s ``cpu_s``, the registry source ``proc.threads``).

The contract under test: ``obs.threads.ledger()`` groups every Python
thread of the process by role (its name up to its first ``/``) and puts
what they leave of the process's CPU time under ``native``; ``cpu_s``
follows who computed and ``vol`` who slept, a role keeps what an exited
thread had used, the Python roles never claim more than the process
used, and without ``schedstat`` (gVisor, which the TPU machines run
under) the CPU seconds are still there and ``runq_s`` and the switch
counts are not. Under tracing the device
watcher records it as ``thread_ledger`` and every RPC handler thread
its cumulative ``rpc_ops`` table; with tracing off the same ledger is
the registry source ``proc.threads``. Bounds are loose: the suite runs
six workers to a machine.
"""

from __future__ import annotations

import threading
import time

import numpy as np
import pytest

from reflow_tpu import DirtyScheduler, FlowGraph, obs
from reflow_tpu.delta import DeltaBatch, Spec
from reflow_tpu.executors import get_executor
from reflow_tpu.net import LoopbackTransport
from reflow_tpu.obs import MetricsRegistry, threads
from reflow_tpu.obs import trace as trace_mod
from reflow_tpu.serve import (CoalesceWindow, IngestFrontend,
                              RemoteProducer, RpcIngestServer)
from reflow_tpu.serve.rpc import TicketResolve

K_SPACE = 32


def _graph():
    g = FlowGraph("ledger")
    s = g.source("s", Spec((), np.float32, key_space=K_SPACE))
    g.reduce(g.map(s, lambda v: v * np.float32(2), vectorized=True),
             "sum", tol=0.0)
    return g, s


def _batches(seed, n, rows=6):
    rng = np.random.default_rng(seed)
    return [DeltaBatch(rng.integers(0, K_SPACE, rows).astype(np.int64),
                       rng.integers(0, 8, rows).astype(np.float32),
                       np.ones(rows, np.int64)) for _ in range(n)]


def _events(name):
    """``(track, t_us, args)`` of every recorded event of one name."""
    evs = obs.chrome_events()
    tracks = {e["tid"]: e["args"]["name"] for e in evs
              if e.get("ph") == "M" and e["name"] == "thread_name"}
    return [(tracks[e["tid"]], e["ts"], e["dur"], e.get("args", {}))
            for e in evs if e.get("ph") == "X" and e["name"] == name]


class _Held:
    """Threads that stay alive, doing ``body``, until released."""

    def __init__(self, names, body=None):
        self.stop = threading.Event()
        self.threads = [threading.Thread(
            target=body or self.stop.wait, name=n, daemon=True)
            for n in names]
        for t in self.threads:
            t.start()

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        self.stop.set()
        for t in self.threads:
            t.join(timeout=10)


def test_roles_group_by_name_prefix_and_native_is_the_rest():
    assert [threads.role_of(n) for n in (
        "rpc-serve/12", "rpc-accept", "reflow-ingest-pump",
        "reflow-device-watch/tpu:0", "reflow-tier-pump-3", "MainThread",
        "bench-heartbeat", "Thread-7 (read)", "lane-3")] == [
        "rpc-serve", "rpc-accept", "reflow-ingest-pump",
        "reflow-device-watch", "reflow-tier-pump", "MainThread",
        "bench-heartbeat", "other", "other"]
    before = threads.ledger()["roles"]
    with _Held(["rpc-serve/901", "rpc-serve/902", "reflow-wal-committer",
                "somebody-else"]):
        led = threads.ledger()
    roles = led["roles"]
    n0 = {r: before.get(r, {}).get("n", 0) for r in roles}
    assert roles["rpc-serve"]["n"] - n0["rpc-serve"] == 2
    assert roles["reflow-wal-committer"]["n"] \
        - n0["reflow-wal-committer"] == 1
    assert roles["other"]["n"] - n0["other"] >= 1
    assert roles["MainThread"]["n"] == 1
    # every Python thread is in exactly one role; native is what they
    # leave of the process's CPU (here NumPy's and XLA's pools)
    assert sum(r.get("n", 0) for r in roles.values()) - sum(
        n0.values()) == 4
    assert set(roles["native"]) == {"cpu_s"}
    assert set(roles["MainThread"]) == {"n", "cpu_s", "runq_s", "vol",
                                        "invol"}
    assert led["switch_interval_s"] > 0 and led["cores"] >= 1
    assert led["process_cpu_s"] > 0


def test_a_spinner_has_the_cpu_and_a_sleeper_the_switches():
    naps = 20

    def spin():
        end = time.thread_time() + 0.3
        while time.thread_time() < end:
            pass
        hold.wait()

    def nap():
        for _ in range(naps):
            time.sleep(0.005)
        hold.wait()

    hold = threading.Event()
    before = threads.ledger()["roles"]
    ts = [threading.Thread(target=spin, name="rpc-serve/911"),
          threading.Thread(target=nap, name="bench-sleeper/1")]
    for t in ts:
        t.start()
    deadline = time.monotonic() + 30
    while time.monotonic() < deadline:
        roles = threads.ledger()["roles"]
        spun = roles["rpc-serve"]["cpu_s"] - before.get(
            "rpc-serve", {"cpu_s": 0.0})["cpu_s"]
        slept = roles.get("bench-sleeper", {"vol": 0})["vol"]
        if spun >= 0.2 and slept >= naps:
            break
        time.sleep(0.02)
    hold.set()
    for t in ts:
        t.join(timeout=10)
    assert spun >= 0.2
    assert roles["bench-sleeper"]["vol"] >= naps
    assert roles["bench-sleeper"]["cpu_s"] < 0.05
    assert roles["rpc-serve"]["runq_s"] >= 0.0


def test_a_role_keeps_what_its_exited_threads_used():
    def spin(stop):
        end = time.thread_time() + 0.1
        while time.thread_time() < end:
            pass
        stop.wait()

    seen = []
    for i in range(2):
        stop = threading.Event()
        base = threads.ledger()["roles"].get(
            "rpc-serve", {"cpu_s": 0.0})["cpu_s"]
        t = threading.Thread(target=spin, args=(stop,),
                             name=f"rpc-serve/{920 + i}")
        t.start()
        deadline = time.monotonic() + 30
        while time.monotonic() < deadline:
            led = threads.ledger()["roles"]["rpc-serve"]
            if not -0.001 < led["cpu_s"] - base < 0.09:
                break               # spun its tenth, or fell: see below
            time.sleep(0.01)
        seen.append(led)
        stop.set()
        t.join(timeout=10)
        seen.append(threads.ledger()["roles"]["rpc-serve"])
    # read while the first lived, after it left, while the second
    # lived, after it left: nothing ever falls
    for a, b in zip(seen, seen[1:]):
        for k in ("cpu_s", "runq_s", "vol", "invol"):
            assert b[k] >= a[k], (k, seen)
    assert seen[1]["cpu_s"] - seen[0]["cpu_s"] >= 0.0
    assert seen[-1]["cpu_s"] - seen[0]["cpu_s"] >= 0.09


def test_roles_sum_to_the_process_cpu():
    def spin():
        end = time.thread_time() + 0.15
        while time.thread_time() < end:
            pass
        hold.wait()

    hold = threading.Event()
    threads.ledger()
    ts = [threading.Thread(target=spin, name=f"rpc-serve/{930 + i}")
          for i in range(3)]
    for t in ts:
        t.start()
    end = time.thread_time() + 0.1
    while time.thread_time() < end:
        pass
    led = threads.ledger()
    hold.set()
    for t in ts:
        t.join(timeout=10)
    total = sum(r["cpu_s"] for r in led["roles"].values())
    assert abs(total - led["process_cpu_s"]) <= max(
        0.05 * led["process_cpu_s"], 0.05)
    # native is what the Python roles leave of the whole: they are read
    # a thread at a time from the kernel, and never claim more than
    # the process used nor, with four threads computing, much less
    python = total - led["roles"]["native"]["cpu_s"]
    assert python <= led["process_cpu_s"] + 0.05
    assert python >= 0.5
    assert led["roles"]["rpc-serve"]["cpu_s"] >= 0.4


def test_without_schedstat_cpu_stays_and_runq_goes(monkeypatch):
    real = threads._slurp

    def no_schedstat(path):
        if path.endswith("schedstat"):
            raise FileNotFoundError(path)
        return real(path)

    def spin():
        end = time.thread_time() + 0.1
        while time.thread_time() < end:
            pass
        hold.wait()

    hold = threading.Event()
    monkeypatch.setattr(threads, "_slurp", no_schedstat)
    monkeypatch.setattr(threads, "_schedstat", None)    # ask again
    t = threading.Thread(target=spin, name="reflow-ingest-pump")
    t.start()
    deadline = time.monotonic() + 30
    while time.monotonic() < deadline:
        led = threads.ledger()
        if led["roles"]["reflow-ingest-pump"]["cpu_s"] >= 0.09:
            break
        time.sleep(0.01)
    hold.set()
    t.join(timeout=10)
    pump = led["roles"]["reflow-ingest-pump"]
    assert pump["cpu_s"] >= 0.09 and set(pump) == {"n", "cpu_s"}
    assert all(set(r) <= {"n", "cpu_s"} for r in led["roles"].values())
    # what no thread's clock shows is native's: the whole still closes
    total = sum(r["cpu_s"] for r in led["roles"].values())
    assert total == pytest.approx(led["process_cpu_s"], abs=0.05)


@pytest.fixture
def served(request, monkeypatch):
    """A frontend behind a real ``RpcIngestServer`` on loopback, tracing
    on with one ticket in ``request.param`` sampled (every one, unless
    the test says); ``(fe, srv, prod, source)``."""
    obs.disable()
    trace_mod.reset()
    monkeypatch.setattr(trace_mod, "SAMPLE_EVERY",
                        getattr(request, "param", 1))
    g, s = _graph()
    sched = DirtyScheduler(g, get_executor("tpu"))
    fe = IngestFrontend(sched, window=CoalesceWindow(
        max_rows=64, max_ticks=2, max_latency_s=0.002))
    lt = LoopbackTransport()
    srv = RpcIngestServer(fe, lt).start()
    prod = RemoteProducer(lt, srv.address, name="p0")
    obs.enable()
    yield fe, srv, prod, s
    obs.disable()
    prod.close()
    srv.close()
    fe.close()
    trace_mod.reset()


@pytest.mark.parametrize("served", [1, 4], indirect=True)
def test_rpc_ops_counts_every_request_by_operation(served):
    fe, srv, prod, s = served
    every = trace_mod.SAMPLE_EVERY
    n_submit, n_resolve = 9, 4
    tickets = [prod.submit(s, b) for b in _batches(1, n_submit)]
    for t in tickets:
        assert t.result(timeout=30).applied
    fe.flush(timeout=30)
    resolves0 = srv.requests_total
    for _ in range(n_resolve):
        prod._roundtrip(("resolve",) + tuple(TicketResolve(
            ("no-such-batch",), 0.0)))
    assert srv.requests_total - resolves0 == n_resolve
    time.sleep(1.05)                 # the next request is a second on
    prod._roundtrip(("ping",))
    prod.close()                     # the handler ends: its last table
    for h in srv._handlers:
        h.join(timeout=10)
    evs = _events("rpc_ops")
    assert evs and all(tr.startswith("rpc-serve/") and dur == 0
                       for tr, _, dur, _ in evs)
    by_track = {}
    for tr, t_us, _, args in evs:
        by_track.setdefault(tr, []).append((t_us, args))
    (track, rows), = by_track.items()
    # the first request, then at most one a second while serving, and
    # one more as the handler ends
    assert 3 <= len(rows) <= 4
    assert sum(r[0] for r in rows[0][1]["ops"].values()) == 1
    gaps = [b[0] - a[0] for a, b in zip(rows, rows[1:-1])]
    assert all(g >= 1e6 for g in gaps)
    # cumulative: nothing falls from one event to the next
    for (_, a), (_, b) in zip(rows, rows[1:]):
        assert a["since"] == b["since"]
        for op, (n, busy_s, cpu_s, n_cpu) in a["ops"].items():
            assert b["ops"][op][0] >= n and b["ops"][op][1] >= busy_s
    last = rows[-1][1]["ops"]
    resolved_by_tickets = last["resolve"][0] - n_resolve
    assert last["submit"][0] == n_submit
    assert resolved_by_tickets >= 0      # the tickets' own long-polls
    assert last["other"][0] >= 2         # hello, ping
    assert sum(r[0] for r in last.values()) == srv.requests_total
    # every request is counted and timed; the CPU clock (a system call
    # under the interpreter lock) is read for the first of every
    # ``SAMPLE_EVERY`` of an operation
    for n, busy_s, cpu_s, n_cpu in last.values():
        assert n_cpu == -(-n // every)
        assert 0.0 <= cpu_s <= busy_s + 1e-4 * n_cpu    # two clocks


def test_rpc_serve_carries_cpu_within_its_wall(served):
    fe, srv, prod, s = served
    tickets = [prod.submit(s, b) for b in _batches(2, 6)]
    for t in tickets:
        assert t.result(timeout=30).applied
    spans = _events("rpc_serve")
    assert len(spans) == 6
    for _, _, dur_us, args in spans:
        assert 0.0 <= args["cpu_s"] <= 1e-6 * dur_us + 1e-9
        assert args["cpu_s"] <= 1e-6 * dur_us - args["decode_s"] + 1e-4


def test_watcher_records_the_ledger_at_most_twice_a_second(served):
    fe, srv, prod, s = served
    t_end = time.monotonic() + 1.3
    seq = 0
    while time.monotonic() < t_end:
        for b in _batches(seq, 4):
            assert prod.submit(s, b).result(timeout=30).applied
        seq += 1
    fe.flush(timeout=30)
    fe.sched.executor.drain_device_watch()
    evs = _events("thread_ledger")
    assert 2 <= len(evs) <= 4
    assert all(tr == "proc" and dur == 0 for tr, _, dur, _ in evs)
    ts = [t for _, t, _, _ in evs]
    assert all(b - a >= 0.5e6 for a, b in zip(ts, ts[1:]))
    first, last = evs[0][3], evs[-1][3]
    assert {"reflow-ingest-pump", "reflow-device-watch", "rpc-serve",
            "rpc-accept", "MainThread"} <= set(last["roles"])
    for role, row in first["roles"].items():
        assert last["roles"][role]["cpu_s"] >= row["cpu_s"]
    assert last["read_s"] >= 0.0
    # one measurement per thread, one per span: the pump's CPU between
    # the two events is what its outermost spans' cpu_s sum to
    moved = (last["roles"]["reflow-ingest-pump"]["cpu_s"]
             - first["roles"]["reflow-ingest-pump"]["cpu_s"])
    tiling = ("pump_wait", "pump_turn", "host_merge", "window_stage",
              "pump_execute", "window_retire")
    by_span = sum(a["cpu_s"] for name in tiling
                  for _, t, _, a in _events(name) if ts[0] <= t < ts[-1])
    assert moved > 0.0
    assert abs(moved - by_span) <= max(0.25 * moved, 0.02), (moved, by_span)


def test_proc_threads_is_published_with_tracing_off():
    obs.disable()
    trace_mod.reset()
    reg = MetricsRegistry()
    g, s = _graph()
    fes = [IngestFrontend(DirtyScheduler(g, get_executor("tpu")),
                          name=f"ledger{i}") for i in range(2)]
    try:
        keys = [fe.publish_metrics(reg) for fe in fes]
        snap = reg.snapshot()["sources"]
        assert set(snap) == set(keys) | {threads.SOURCE}
        led = snap[threads.SOURCE]
        assert led["roles"]["reflow-ingest-pump"]["n"] >= 2
        assert led["roles"]["MainThread"]["cpu_s"] > 0
        # the process's, not a frontend's: one close leaves it there
        fes[0].close()
        after = reg.snapshot()["sources"]
        assert set(after) == {keys[1], threads.SOURCE}
        assert after[threads.SOURCE]["process_cpu_s"] >= led[
            "process_cpu_s"]
    finally:
        for fe in fes:
            fe.close()
        threads.unpublish(reg)
    assert threads.SOURCE not in reg.snapshot()["sources"]
    assert trace_mod._rings == [] and obs.chrome_events() == []
