"""TPC-H Q3 as the benchmark's ``tpch-q3`` configuration serves it
(``benchmarks/configs/tpch-q3.py``): refresh pairs through
``DurableScheduler`` -> ``IngestFrontend`` windows over arenas whose
slack is a few windows' appends, so that ``join_reindex`` runs again
and again between served windows: the view against per-tick execution
and the reference, the reindex programs compiled before the first served
window and nothing compiled after warm-up, the joins' counters against
the CPU oracle's and on the traced windows' token, the executor's spans
around a reindex and a count read. Small seeded sizes, CPU."""

import importlib.util
import json
import os
import sys

import jax.monitoring
import numpy as np
import pytest

from reflow_tpu import DirtyScheduler
from reflow_tpu.executors import CpuExecutor, get_executor
from reflow_tpu.executors.lowerings import OP_COUNTERS
from reflow_tpu.serve import APPLIED, CoalesceWindow, IngestFrontend
from reflow_tpu.wal import DurableScheduler

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
BENCH = os.path.join(ROOT, "benchmarks")
COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"


def _config():
    """The benchmark configuration's module and its ``tiny`` sizes."""
    if BENCH not in sys.path:
        sys.path.insert(0, BENCH)
    path = os.path.join(BENCH, "configs", "tpch-q3")
    spec = importlib.util.spec_from_file_location("tpch_q3", path + ".py")
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    with open(path + ".json") as f:
        cfg = json.load(f)
    cfg.update(cfg.pop("tiny"))
    return mod, cfg


MOD, CFG = _config()
#: a history of 512 orders: ~ 250 orders before the date and ~ 1 100
#: lineitems after it are live, and each arena has room for seven to ten
#: two-tick windows of 64-row feeds beyond them
SMALL = dict(CFG, customers=600, orders=512, order_keys=2048,
             orders_arena=480, lineitem_arena=1600,
             load_orders_per_tick=16, load_customers_per_tick=64)
LANES = 2
N_BATCHES = 82      # one, two, then 39 windows of two
_COMPILES = []
jax.monitoring.register_event_duration_secs_listener(
    lambda event, duration, **kw: _COMPILES.append(event)
    if event == COMPILE_EVENT else None)


def _loaded(cfg, seed, sched_of):
    stream = MOD.Stream(cfg, seed, LANES)
    dep = MOD.build(cfg)
    sched = sched_of(dep.graph)
    for batches in stream.load():
        for source, batch, bid in batches:
            sched.push(dep.sources[source], batch, batch_id=bid)
        assert sched.tick().quiesced
    return stream, MOD.Reference(stream), dep, sched


def _window(fe, dep, stream, ref, batches, first):
    """``batches`` refresh pairs submitted while the pump is paused: one
    served window of as many ticks (a pair nearly fills a 64-row tick)."""
    fe.pause()
    tickets = []
    for i in range(batches):
        m = stream.next((first + i) % LANES)
        ref.apply(m.ref)
        tickets.append(fe.submit(dep.sources[stream.source], m.delta,
                                 batch_id=f"b{first + i}"))
    fe.resume()
    fe.flush(timeout=120)
    assert all(t.result(120).status == APPLIED for t in tickets)


@pytest.mark.parametrize("seed", [2**31 + 3, 17])
def test_served_windows_reindex_between_windows_and_compile_nothing(
        tmp_path, seed):
    """Warm the two window shapes, then forty two-tick windows: the
    reindex programs exist before the first served window runs and
    before any reindex has, nothing is compiled after warm-up although
    both joins are reindexed several times, and the view, the arenas'
    rows and the top ten are the reference's and per-tick execution's."""
    stream, ref, dep, sched = _loaded(
        SMALL, seed, lambda g: DurableScheduler(
            g, get_executor("tpu"), wal_dir=str(tmp_path / "wal"),
            fsync="tick", committer="thread"))
    ex = sched.executor
    fe = IngestFrontend(sched, depth=2, window=CoalesceWindow(
        max_rows=64, max_ticks=2, max_latency_s=0.002))
    try:
        assert not ex._room._programs
        _window(fe, dep, stream, ref, 1, 0)            # warm: one tick
        # built with the first window program, one a join shape
        assert len(ex._room._programs) == 2
        assert all(c["index_rebuilds"] == 0
                   for c in ex.op_counters().values())
        _window(fe, dep, stream, ref, 2, 1)            # warm: two ticks
        ex.op_counters()
        del _COMPILES[:]
        for first in range(3, N_BATCHES - 1, 2):
            _window(fe, dep, stream, ref, 2, first)
        assert sched.megatick_fallbacks == 0
        counters = ex.op_counters()
        ex.check_errors()
        assert _COMPILES == []
        assert counters["q3_join"]["index_rebuilds"] >= 3
        assert counters["q3_orders"]["index_rebuilds"] >= 3
        want = ref.expected()
        served = MOD.read_state(SMALL, dep, sched)
        checks = MOD.compare(SMALL, served, want)
        assert all(c.ok for c in checks), checks
    finally:
        fe.close()
        sched.close()

    # the same pairs one tick() each: no windows, no log
    stream2, _, dep2, plain = _loaded(
        SMALL, seed, lambda g: DirtyScheduler(g, get_executor("tpu")))
    for i in range(N_BATCHES - 1):
        plain.push(dep2.sources["changes"], stream2.next(i % LANES).delta)
        assert plain.tick().quiesced
    ticked = MOD.read_state(SMALL, dep2, plain)
    for name in ("keys", "revenue", "orderdate", "top10"):
        np.testing.assert_array_equal(ticked[name], served[name])
    assert ticked["lineitems_live"] == served["lineitems_live"]


def _count_oracle_pairs(dep):
    """The CPU oracle's two joins, watched: every pair each emits, by
    weight (its output batch nets equal rows, the device's does not)."""
    emitted = {}
    for node in (dep.nodes.q3_orders, dep.nodes.q3_join):
        op, inner = node.op, node.op._emit
        emitted[node.name] = 0

        def counted(out, k, va, wa, vb, wb, _inner=inner, _n=node.name):
            emitted[_n] += abs(wa * wb)
            return _inner(out, k, va, wa, vb, wb)
        op._emit = counted
    return emitted


def test_counters_equal_the_cpu_oracle_and_ride_the_traced_token():
    """``pairs`` is what the CPU oracle's joins emit on the same feeds,
    ``retracted`` the right-side rows of negative weight that pass the
    filters below each join; under tracing every ``window_device`` span
    carries the counters of both joins, every name, a ``join_reindex``
    span says which arena went from how many rows to how many, and a
    count read has its span."""
    from reflow_tpu import obs
    from reflow_tpu.obs import trace as trace_mod

    seed = 23
    stream_o, _, dep_o, oracle = _loaded(
        SMALL, seed, lambda g: DirtyScheduler(g, CpuExecutor()))
    stream, _, dep, sched = _loaded(
        SMALL, seed, lambda g: DirtyScheduler(g, get_executor("tpu")))
    ex = sched.executor
    base = ex.op_counters()
    pairs = _count_oracle_pairs(dep_o)
    retracted = {"q3_orders": 0, "q3_join": 0}
    obs.disable()
    trace_mod.reset()
    obs.enable()
    try:
        for first in range(0, 40, 2):
            feeds = []
            for i in range(2):
                d = stream.next((first + i) % LANES).delta
                d_o = stream_o.next((first + i) % LANES).delta
                v, gone = d.values, d.weights < 0
                retracted["q3_orders"] += int(np.count_nonzero(
                    gone & (v[:, 0] == MOD.ORDERS) & (v[:, 3] < MOD.Q3_DATE)))
                retracted["q3_join"] += int(np.count_nonzero(
                    gone & (v[:, 0] == MOD.LINEITEM)
                    & (v[:, 3] > MOD.Q3_DATE)))
                feeds.append({dep.sources["changes"]: d})
                oracle.push(dep_o.sources["changes"], d_o)
                oracle.tick()
            sched.tick_many(feeds)
        ex.drain_device_watch()
        events = [e for e in obs.chrome_events() if e.get("ph") == "X"]
    finally:
        obs.disable()
        trace_mod.reset()
        ex.close()
    assert ex.device_watch_error is None
    now = ex.op_counters()
    for name in ("q3_orders", "q3_join"):
        assert now[name]["pairs"] - base[name]["pairs"] == pairs[name] > 0
        assert now[name]["retracted"] == retracted[name] > 0
        assert now[name]["index_rebuilds"] >= 1
    spans = [e for e in events if e["name"] == "window_device"]
    assert len(spans) == 20
    last = spans[-1]["args"]["counters"]
    assert {k: list(v.values()) for k, v in now.items()} == last
    assert all(len(v) == len(OP_COUNTERS["join"]) for v in last.values())
    reindexes = [e["args"] for e in events if e["name"] == "join_reindex"]
    assert len(reindexes) == sum(c["index_rebuilds"] for c in now.values())
    for a in reindexes:
        assert a["node"] in now and a["rows_before"] > a["rows_after"] > 0
    reads = [e["args"] for e in events if e["name"] == "arena_rcount_read"]
    assert len(reads) >= len(reindexes) and all(r["rows"] > 0 for r in reads)
