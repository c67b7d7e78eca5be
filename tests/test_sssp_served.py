"""Incremental SSSP as the benchmark's ``sssp-graph500`` configuration
serves it (``benchmarks/configs/sssp-graph500.py``: a Kronecker dataset,
half loaded and half streamed as insert batches, its plain reference and
comparison): the served path against per-tick ``tick()`` and the
reference, with the WAL re-read; a hub far over its candidate buffer;
a hub whose improvement passes the loop join's pair budget inside one
tick; the device counters of the row fixpoint program, its join and its
minimum against the host loop and the CPU oracle; which fixpoint engine
a graph lands on. Small seeded sizes, CPU."""

import importlib.util
import json
import os
import sys

import numpy as np
import pytest

from reflow_tpu import DirtyScheduler
from reflow_tpu.executors import CpuExecutor, get_executor
from reflow_tpu.executors.arena import view_budget
from reflow_tpu.net import LoopbackTransport
from reflow_tpu.serve import (APPLIED, CoalesceWindow, IngestFrontend,
                              RemoteProducer, RpcIngestServer)
from reflow_tpu.wal import DurableScheduler, scan_wal
from reflow_tpu.workloads import pagerank, sssp

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
BENCH = os.path.join(ROOT, "benchmarks")


def _config():
    """The benchmark configuration's module and its ``tiny`` sizes."""
    if BENCH not in sys.path:
        sys.path.insert(0, BENCH)
    path = os.path.join(BENCH, "configs", "sssp-graph500")
    spec = importlib.util.spec_from_file_location("sssp_graph500",
                                                  path + ".py")
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    with open(path + ".json") as f:
        cfg = json.load(f)
    cfg.update(cfg.pop("tiny"))
    return mod, cfg


MOD, CFG = _config()
#: 8 insert batches of 32 tuples over a 256-vertex Kronecker graph
SMALL = dict(CFG, scale=8, stream_batches=64)
N_BATCHES = 8


def _loaded(cfg, seed, sched_of):
    """A deployment with its history in: -> (stream, reference, dep,
    scheduler)."""
    stream = MOD.Stream(cfg, seed, 1)
    ref = MOD.Reference(stream)
    dep = MOD.build(cfg)
    sched = sched_of(dep.graph)
    for batches in stream.load():
        for source, batch, bid in batches:
            sched.push(dep.sources[source], batch, batch_id=bid)
        assert sched.tick().quiesced
    return stream, ref, dep, sched


def _holds(cfg, dep, sched, ref):
    checks = MOD.compare(cfg, MOD.read_state(cfg, dep, sched),
                         ref.expected())
    assert all(c.ok for c in checks), checks


# -- (a) the served path ----------------------------------------------------


@pytest.mark.parametrize("seed", [2**31 + 5, 41])
def test_served_path_equals_per_tick_and_the_reference(tmp_path, seed):
    """RemoteProducer -> leader -> WAL -> ``FixpointProgram`` windows
    ends at the distances of per-tick ``tick()`` on the same batches and
    of the configuration's reference; the WAL, re-read from disk, holds
    every acked batch once, byte for byte."""
    wal_dir = str(tmp_path / "wal")
    stream, ref, dep, sched = _loaded(
        SMALL, seed, lambda g: DurableScheduler(
            g, get_executor("tpu"), wal_dir=wal_dir, fsync="tick",
            committer="thread"))
    fe = IngestFrontend(sched, depth=2, window=CoalesceWindow(
        max_rows=2 * stream.batch, max_ticks=1, max_latency_s=0.002))
    lt = LoopbackTransport()
    srv = RpcIngestServer(fe, lt).start()
    prod = RemoteProducer(lt, srv.address, name="p0")
    sent = {}
    try:
        tickets = []
        for i in range(N_BATCHES):
            m = stream.next(0)
            ref.apply(m.ref)
            sent[f"b{i}"] = m.delta
            tickets.append(prod.submit(stream.source, m.delta,
                                       batch_id=f"b{i}"))
        assert all(t.result(120).status == APPLIED for t in tickets)
        fe.flush(timeout=120)
        assert sched.megatick_fallbacks == 0
        assert sched.megatick_windows == N_BATCHES
        assert sched.executor.fixpoint_engine == "FixpointProgram"
        _holds(SMALL, dep, sched, ref)
        served = MOD.read_state(SMALL, dep, sched)["dist"]
        loop = sched.executor.op_counters()["dist"]
        assert loop["ticks"] == 1 + N_BATCHES and loop["unquiesced"] == 0
    finally:
        prod.close()
        srv.close()
        fe.close()
        sched.close()

    # the same batches one tick() each, no server, no log
    stream2, _, dep2, plain = _loaded(
        SMALL, seed, lambda g: DirtyScheduler(g, get_executor("tpu")))
    for i in range(N_BATCHES):
        m = stream2.next(0)
        np.testing.assert_array_equal(m.delta.keys, sent[f"b{i}"].keys)
        plain.push(dep2.sources["edges"], m.delta)
        assert plain.tick().quiesced
    np.testing.assert_array_equal(
        MOD.read_state(SMALL, dep2, plain)["dist"], served)

    logged = {}
    for _pos, rec in scan_wal(wal_dir)[0]:
        if rec.get("kind") == "push":
            for bid in rec.get("batch_ids") or [rec["batch_id"]]:
                assert bid not in logged, f"{bid} logged twice"
                logged[bid] = rec
    for bid, b in sent.items():
        for col in ("keys", "values", "weights"):
            np.testing.assert_array_equal(np.asarray(logged[bid][col]),
                                          getattr(b, col))


def test_the_control_fails_the_distance_check():
    """Relaxation sums rounded to bfloat16 are not the float32 the
    configuration states: the comparison has to say so."""
    stream = MOD.Stream(SMALL, 7, 1)
    ref = MOD.Reference(stream)
    for _ in range(N_BATCHES):
        ref.apply(stream.next(0).ref)
    checks = {c.name: c for c in MOD.compare(
        SMALL, ref.expected("bfloat16"), ref.expected())}
    assert not checks["dist_mismatches"].ok
    assert checks["reach_mismatch"].ok


# -- (b) a hub far over its candidate buffer ---------------------------------


@pytest.mark.parametrize("candidates", [4, 16])
def test_a_hub_over_its_buffer_stays_exact_under_inserts(candidates):
    """One vertex takes candidates from ``8 x candidates`` + in-edges
    and more, and its neighbours keep improving as insert batches
    arrive: every retraction of a candidate evicted long ago rides with
    a smaller insert from the same edge, so the first positive rank
    stays the true minimum, strictly below everything evicted; the
    sticky error stays unset and ``evicted`` counts what went."""
    n, hub, spokes = 256, 255, 8 * candidates + 40
    rng = np.random.default_rng(candidates)
    quantum = 1.0 / 256
    # a chain 0 -> 1 -> ... so that the spokes start far from the root,
    # every spoke -> hub, and shortcuts root -> spoke arriving later
    chain = np.arange(spokes)
    src = np.concatenate([chain, chain + 1])
    dst = np.concatenate([chain + 1, np.full(spokes, hub)])
    w = (1 + rng.integers(0, 256, 2 * spokes)) * quantum
    sg = sssp.build_graph(n, arena_capacity=1 << 12,
                          candidates=candidates)
    sched = DirtyScheduler(sg.graph, get_executor("tpu"))
    sched.push(sg.edges, sssp.edge_batch(src, dst, w))
    sched.push(sg.seeds, sssp.seed_batch(0))
    assert sched.tick().quiesced
    order = rng.permutation(np.arange(2, spokes + 1))
    for part in np.array_split(order, 6):
        sw = (1 + rng.integers(0, 64, len(part))) * quantum
        src = np.concatenate([src, np.zeros(len(part), np.int64)])
        dst, w = np.concatenate([dst, part]), np.concatenate([w, sw])
        sched.push(sg.edges, sssp.edge_batch(np.zeros(len(part)), part, sw))
        assert sched.tick().quiesced
        sched.executor.check_errors()
        got = {int(k): float(v)
               for k, v in sched.read_table(sg.best).items()}
        assert got == sssp.reference_distances(n, src, dst, w, 0)
    st = sched.executor.states[sg.best.id]
    assert not bool(st["error"])
    assert int(np.sum(dst == hub)) >= 8 * candidates
    counters = sched.executor.op_counters()["best"]
    assert counters["evicted"] > 0
    assert bool(np.asarray(st["over_maybe_pos"])[hub])


def test_a_hubs_improvement_takes_a_rung_of_its_own_inside_a_tick():
    """One vertex fans out to 300 edges; a shortcut to it arrives and
    every one of those candidates is retracted and bettered in one pass:
    600 live rows, past the minimum's first two rungs (``n`` and ``4 n``
    slots of the join's 2 048), so that pass merges 1 024 slots, the
    pass after it (the fan's heads improved, their ~170 rows) 256, the
    tick's other passes 64 — read from ``merged_slots`` — and the
    distances are Bellman-Ford's after every tick, on the fused
    program and on the host-driven loop alike, every tick quiesced, no
    sticky error."""
    from reflow_tpu.executors import lowerings as lw

    n, hub, far, fan, arena = 64, 63, 20, 300, 1 << 10
    assert lw._merge_rungs(2 * arena, n) == (64, 256, 1024, 2048)
    rng = np.random.default_rng(5)
    quantum = 1.0 / 256
    chain = np.arange(far)
    heads = np.arange(far + 1, hub - 4)       # 59 .. 62 are leaves
    src = np.concatenate([chain, [far], np.full(fan, hub),
                          np.repeat(heads, 2)])
    dst = np.concatenate([chain + 1, [hub], rng.choice(heads, fan),
                          rng.choice(heads, 2 * len(heads))])
    w = (1 + rng.integers(0, 256, len(src))) * quantum
    # a leaf edge, then two shortcuts to the hub, each better than the
    # last, then a leaf edge again
    late = [(5, 60, 3 * quantum), (10, hub, 2 * quantum),
            (0, hub, quantum), (0, 61, 200 * quantum)]
    seen = {}
    for name, fused in (("fused", True), ("host", False)):
        sg = sssp.build_graph(n, arena_capacity=arena, candidates=4)
        sched = DirtyScheduler(sg.graph,
                               get_executor("tpu", fixpoint=fused))
        sched.push(sg.edges, sssp.edge_batch(src, dst, w))
        sched.push(sg.seeds, sssp.seed_batch(0))
        assert sched.tick().quiesced
        es, ed, ew = src, dst, w
        was = sched.executor.op_counters()["best"]["merged_slots"]
        seen[name] = []
        for a, b, ww in late:
            es, ed, ew = (np.concatenate([x, [y]])
                          for x, y in ((es, a), (ed, b), (ew, ww)))
            sched.push(sg.edges, sssp.edge_batch([a], [b], [ww]))
            r = sched.tick()
            assert r.quiesced
            sched.executor.check_errors()
            got = {int(k): float(v)
                   for k, v in sched.read_table(sg.best).items()}
            assert got == sssp.reference_distances(n, es, ed, ew, 0)
            now = sched.executor.op_counters()["best"]["merged_slots"]
            seen[name].append((int(r.passes), now - was))
            was = now
        assert not bool(sched.executor.states[sg.best.id]["error"])
        if fused:
            assert sched.executor.op_counters()["dist"]["unquiesced"] == 0
    # one merge a pass: a leaf's two passes take the first rung, a
    # hub's tick 64 + 1 024 + 256 + 64
    assert seen["fused"] == seen["host"] == [
        (2, 128), (4, 1408), (4, 1408), (2, 128)]


@pytest.mark.parametrize("fused", [True, False], ids=["fused", "host-loop"])
def test_a_hubs_improvement_passes_the_joins_pair_budget_inside_a_tick(fused):
    """The same growing graph, read at the join: its pair budget is the
    key space, 64 pairs (``arena.view_budget``), a leaf's improvement
    pairs with nothing and a hub's with its 300 edges twice over
    (retracted and inserted). So a leaf's tick only probes the view, and
    in a hub's tick the passes of the hub and of its fan's heads sweep
    while the tick's last, back under the budget, probes again: both
    forms inside one tick, on the fused program (one ``lax.cond`` a
    pass) and on the host-driven loop alike, the distances
    Bellman-Ford's after every tick, every tick quiesced, no sticky
    error, nothing dropped."""
    n, hub, far, fan, arena = 64, 63, 20, 300, 1 << 10
    assert view_budget(n, arena) == 64
    rng = np.random.default_rng(5)
    quantum = 1.0 / 256
    chain = np.arange(far)
    heads = np.arange(far + 1, hub - 4)
    src = np.concatenate([chain, [far], np.full(fan, hub),
                          np.repeat(heads, 2)])
    dst = np.concatenate([chain + 1, [hub], rng.choice(heads, fan),
                          rng.choice(heads, 2 * len(heads))])
    w = (1 + rng.integers(0, 256, len(src))) * quantum
    late = [(5, 60, 3 * quantum), (10, hub, 2 * quantum),
            (0, hub, quantum), (0, 61, 200 * quantum)]
    sg = sssp.build_graph(n, arena_capacity=arena, candidates=4)
    sched = DirtyScheduler(sg.graph, get_executor("tpu", fixpoint=fused))
    sched.push(sg.edges, sssp.edge_batch(src, dst, w))
    sched.push(sg.seeds, sssp.seed_batch(0))
    assert sched.tick().quiesced
    es, ed, ew = src, dst, w
    was = sched.executor.op_counters()["relax"]
    seen = []
    for a, b, ww in late:
        es, ed, ew = (np.concatenate([x, [y]])
                      for x, y in ((es, a), (ed, b), (ew, ww)))
        sched.push(sg.edges, sssp.edge_batch([a], [b], [ww]))
        r = sched.tick()
        assert r.quiesced
        sched.executor.check_errors()
        got = {int(k): float(v)
               for k, v in sched.read_table(sg.best).items()}
        assert got == sssp.reference_distances(n, es, ed, ew, 0)
        now = sched.executor.op_counters()["relax"]
        seen.append(tuple(now[c] - was[c] for c in ("sweeps", "probes")))
        assert sum(seen[-1]) == int(r.passes) - 1
        assert (now["swept_rows"] - was["swept_rows"]
                == seen[-1][0] * 2 * arena + seen[-1][1] * 2 * 64)
        was = now
    relax = next(x for x in sg.graph.nodes if x.name == "relax")
    assert not bool(sched.executor.states[relax.id]["error"])
    if fused:
        assert sched.executor.op_counters()["dist"]["unquiesced"] == 0
    # (sweeps, probes) a tick: a leaf's, the hub's twice, a leaf's
    assert seen == [(0, 1), (2, 1), (2, 1), (0, 1)]


# -- (c) the counters ---------------------------------------------------------


def _feeds(cfg, seed):
    stream = MOD.Stream(cfg, seed, 1)
    return stream, stream.load()[0], [stream.next(0).delta
                                      for _ in range(N_BATCHES)]


@pytest.mark.parametrize("seed", [2**31 + 7, 3])
def test_counters_equal_the_host_loop_and_the_cpu_oracle(seed):
    """``passes`` is the host-driven loop's pass count on the same
    feeds; a pass takes its left delta's product through the view or by
    the sweep exactly when the loop has a delta, i.e. every pass but a
    tick's first, and ``swept_rows`` is ``2 x arena capacity`` a sweep
    and twice the pair budget a probe; ``pairs`` is the live rows the
    CPU oracle's join emits on the same feeds."""
    runs = {}
    for name, ex in (("fused", get_executor("tpu")),
                     ("host", get_executor("tpu", fixpoint=False)),
                     ("oracle", CpuExecutor())):
        stream, load, feeds = _feeds(SMALL, seed)
        dep = MOD.build(SMALL)
        sched = DirtyScheduler(dep.graph, ex)
        emitted = [0]
        if name == "oracle":
            # the oracle's join, watched: every pair it emits, by weight
            # (its output batch nets equal rows, the device's does not)
            op = dep.relax.op
            inner = op._emit

            def counted(out, k, va, wa, vb, wb, _inner=inner):
                emitted[0] += abs(wa * wb)
                return _inner(out, k, va, wa, vb, wb)
            op._emit = counted
        for source, batch, _bid in load:
            sched.push(dep.sources[source], batch)
        passes = [int(sched.tick().passes)]
        for d in feeds:
            sched.push(dep.sources["edges"], d)
            r = sched.tick()
            assert r.quiesced
            passes.append(int(r.passes))
        runs[name] = (sched, dep, passes, emitted[0])
    fused, dep, passes, _ = runs["fused"]
    assert passes == runs["host"][2] == runs["oracle"][2]
    c = fused.executor.op_counters()
    assert c["dist"] == {"passes": sum(passes), "ticks": len(passes),
                         "unquiesced": 0}
    R = dep.relax.op.arena_capacity
    T = view_budget(dep.relax.inputs[0].spec.key_space, R)
    assert (c["relax"]["sweeps"] + c["relax"]["probes"]
            == sum(passes) - len(passes))
    assert c["relax"]["probes"] > 0
    assert c["relax"]["swept_rows"] == (c["relax"]["sweeps"] * 2 * R
                                        + c["relax"]["probes"] * 2 * T)
    assert c["relax"]["pairs"] == runs["oracle"][3] > 0
    # the host-driven loop runs the same join lowering pass by pass
    assert runs["host"][0].executor.op_counters()["relax"] == c["relax"]
    assert "dist" not in runs["host"][0].executor.op_counters()
    assert (fused.read_table(dep.best)
            == runs["host"][0].read_table(runs["host"][1].best))


def test_counters_ride_the_traced_windows_token():
    """Under tracing the row program's window twin hands the device
    watcher the loop-free windows' token, so every ``window_device``
    span says what the three nodes' counters stood at."""
    from reflow_tpu import obs
    from reflow_tpu.obs import trace as trace_mod

    stream, load, feeds = _feeds(SMALL, 11)
    dep = MOD.build(SMALL)
    ex = get_executor("tpu")
    sched = DirtyScheduler(dep.graph, ex)
    for source, batch, _bid in load:
        sched.push(dep.sources[source], batch)
    sched.tick()
    obs.disable()
    trace_mod.reset()
    obs.enable()
    try:
        for d in feeds[:3]:
            sched.tick_many([{dep.sources["edges"]: d}])
        ex.drain_device_watch()
        spans = [e for e in obs.chrome_events()
                 if e.get("ph") == "X" and e["name"] == "window_device"]
    finally:
        obs.disable()
        trace_mod.reset()
        ex.close()
    assert ex.device_watch_error is None
    assert len(spans) == 3
    last = spans[-1]["args"]["counters"]
    now = ex.op_counters()
    assert {k: list(v.values()) for k, v in now.items()} == last
    assert last["dist"][1] == 4 and len(last["relax"]) == 11


# -- (d) which engine ---------------------------------------------------------


@pytest.mark.parametrize("graph,engine", [
    ("sssp", "FixpointProgram"), ("pagerank", "LinearFixpointProgram")])
def test_engine_by_graph(graph, engine):
    ex = get_executor("tpu")
    if graph == "sssp":
        _, _, dep, sched = _loaded(SMALL, 5,
                                   lambda g: DirtyScheduler(g, ex))
        assert "counters" in ex.states[dep.loop.id]
        assert "counters" in ex.states[dep.relax.id]
    else:
        pr = pagerank.build_graph(64)
        web = pagerank.WebGraph.random(64, 512, seed=1)
        sched = DirtyScheduler(pr.graph, ex)
        sched.push(pr.teleport, pagerank.teleport_batch(64))
        sched.push(pr.edges, web.initial_batch())
        sched.tick()
        # the fused linear engine's state is what it was: no counters
        assert not any("counters" in (st or ())
                       for st in ex.states.values())
    assert ex.fixpoint_engine == engine


# -- the pump does not run ahead of a fixpoint window ----------------------------


@pytest.mark.parametrize("graph", ["sssp", "loop-free"])
def test_a_fixpoint_windows_retire_waits_for_the_device(monkeypatch, graph):
    """A fixpoint window costs its passes, and every call of a window's
    lifecycle is asynchronous: the executor retires a fixpoint window
    once the device has finished it (the window's own ``converged``
    output), so the pump's ``depth`` bounds the windows the device has
    not finished. A loop-free window has no such output and retires as
    it always did."""
    from reflow_tpu.delta import DeltaBatch, Spec
    from reflow_tpu.executors.tpu import TpuExecutor
    from reflow_tpu.graph import FlowGraph

    seen = []
    real = TpuExecutor.retire_window

    def spy(self, sw):
        done = sw.done
        real(self, sw)
        seen.append(None if done is None else done.is_ready())

    monkeypatch.setattr(TpuExecutor, "retire_window", spy)
    if graph == "sssp":
        stream, _ref, dep, sched = _loaded(
            SMALL, 9, lambda g: DirtyScheduler(g, get_executor("tpu")))
        source = dep.sources["edges"]
        batches = [stream.next(0).delta for _ in range(4)]
        rows = 2 * stream.batch
    else:
        g = FlowGraph("loop_free")
        source = g.source("s", Spec((), np.float32, key_space=64))
        g.reduce(source, "sum", name="r")
        sched = DirtyScheduler(g, get_executor("tpu"))
        batches = [DeltaBatch(np.array([i, i + 1]), np.ones(2, np.float32),
                              np.ones(2, np.int64)) for i in range(4)]
        rows = 2
    fe = IngestFrontend(sched, depth=2, window=CoalesceWindow(
        max_rows=rows, max_ticks=1, max_latency_s=0.002))
    try:
        fe.pause()
        tickets = [fe.submit(source, b) for b in batches]
        fe.resume()
        assert all(t.result(120).applied for t in tickets)
        fe.flush(timeout=120)
    finally:
        fe.close()
    assert sched.megatick_windows == 4 and len(seen) == 4
    assert seen == [True] * 4 if graph == "sssp" else [None] * 4
