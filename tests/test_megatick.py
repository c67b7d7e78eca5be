"""Compiled mega-tick window path (docs/guide.md "Compiled mega-ticks").

The contract under test: ``tick_many`` over the device-resident ingress
queue (``TpuExecutor.run_window``) is view-identical to the per-tick
streaming path on the SAME feeds — ragged per-tick source sets are
padded to the window's union with zero-row deltas (weight-0 rows are
semantic no-ops), and every refusal (divergent dirty sets above the
waste threshold, over-capacity batches, unsupported graphs) falls back
cleanly to the stacked/per-tick paths with ``megatick_fallbacks``
counting the events, never a crash or a wrong view.
"""

import numpy as np
import pytest

from reflow_tpu import DirtyScheduler, FlowGraph
from reflow_tpu.delta import DeltaBatch, Spec
from reflow_tpu.executors import get_executor

K_SPACE = 32


def _batch(rows):
    return DeltaBatch(np.array([r[0] for r in rows], np.int64),
                      np.array([r[1] for r in rows], np.float32),
                      np.array([r[2] for r in rows], np.int64))


def _small_graph():
    """source -> map -> union(source2) -> reduce(sum): loop-free,
    sink-free, two sources so per-tick source sets can be ragged."""
    g = FlowGraph("megatick")
    spec = Spec((), np.float32, key_space=K_SPACE)
    s0 = g.source("s0", spec)
    s1 = g.source("s1", spec)
    m = g.map(s0, lambda v: v * np.float32(2), vectorized=True)
    u = g.union(m, s1)
    r = g.reduce(u, "sum", tol=0.0)
    return g, (s0, s1), r


def _ragged_ticks(n_ticks=4, rows=6, seed=3):
    """s0 fed every tick, s1 only on even ticks (pad share = 0.25)."""
    rng = np.random.default_rng(seed)
    ticks = []
    for t in range(n_ticks):
        tick = {0: [(int(rng.integers(0, K_SPACE)),
                     float(rng.integers(0, 8)), 1) for _ in range(rows)]}
        if t % 2 == 0:
            tick[1] = [(int(rng.integers(0, K_SPACE)),
                        float(rng.integers(0, 8)), 1) for _ in range(rows)]
        ticks.append(tick)
    return ticks


def _table(sched, node):
    return {int(k): round(float(np.asarray(v).reshape(())), 3)
            for k, v in sched.read_table(node).items()}


def _oracle(ticks):
    """CPU per-tick drive of the same feeds — the reference views."""
    g, (s0, s1), r = _small_graph()
    sched = DirtyScheduler(g, get_executor("cpu"))
    srcs = {0: s0, 1: s1}
    for tick in ticks:
        for s_ix, rows in tick.items():
            sched.push(srcs[s_ix], _batch(rows))
        sched.tick()
    return _table(sched, r)


def _window_drive(ticks, k, **tweak):
    """TPU tick_many drive in windows of ``k``; returns (table, sched)."""
    g, (s0, s1), r = _small_graph()
    ex = get_executor("tpu")
    for attr, v in tweak.pop("executor", {}).items():
        setattr(ex, attr, v)
    sched = DirtyScheduler(g, ex)
    for attr, v in tweak.items():
        setattr(sched, attr, v)
    srcs = {0: s0, 1: s1}
    results = []
    for lo in range(0, len(ticks), k):
        feeds = [{srcs[s_ix]: _batch(rows) for s_ix, rows in tick.items()}
                 for tick in ticks[lo:lo + k]]
        results.append(sched.tick_many(feeds))
    for res in results:
        res.block()
    return _table(sched, r), sched


def test_ragged_feeds_padded_to_window_union():
    """Ragged per-tick feeds ride ONE fused window (zero-row padding for
    the missing source slots) and the views match the per-tick oracle."""
    ticks = _ragged_ticks()
    want = _oracle(ticks)
    got, sched = _window_drive(ticks, k=4)
    assert got == want
    assert sched.megatick_windows == 1
    assert sched.megatick_fallbacks == 0


def test_divergent_dirty_sets_fall_back_cleanly():
    """With the waste threshold at zero, any padding means the dirty
    sets diverge 'too much': the window falls back (counter increments)
    and the per-tick path still produces the oracle views."""
    ticks = _ragged_ticks()
    want = _oracle(ticks)
    got, sched = _window_drive(ticks, k=4, megatick_waste=0.0)
    assert got == want
    assert sched.megatick_windows == 0
    assert sched.megatick_fallbacks == 1


def test_over_capacity_batches_fall_back_cleanly():
    """Batches above the executor's per-source row ceiling refuse the
    queue (no crash): fallback counter increments, views stay right."""
    ticks = _ragged_ticks(rows=12)
    want = _oracle(ticks)
    got, sched = _window_drive(
        ticks, k=4, executor={"megatick_max_rows": 8})
    assert got == want
    assert sched.megatick_windows == 0
    assert sched.megatick_fallbacks == 1


def test_queue_and_program_reused_across_windows():
    """Two same-shaped windows share one ingress queue and one compiled
    program: the second window is a pure dispatch."""
    ticks = _ragged_ticks(n_ticks=8)
    want = _oracle(ticks)
    got, sched = _window_drive(ticks, k=4)
    assert got == want
    assert sched.megatick_windows == 2
    assert sched.executor.window_dispatches == 2
    qkeys = [key for key in sched.executor._cache
             if isinstance(key, tuple) and key and key[0] == "ingress_q"]
    assert len(qkeys) == 1


def test_uniform_feeds_no_fallback_k2():
    """Uniform source sets (zero padding) fuse at any window size."""
    ticks = [{0: [(i, 1.0, 1)], 1: [(i, 2.0, 1)]} for i in range(4)]
    want = _oracle(ticks)
    got, sched = _window_drive(ticks, k=2)
    assert got == want
    assert sched.megatick_windows == 2
    assert sched.megatick_fallbacks == 0


# -- differential fuzz: window sizes x seeds vs the per-tick oracle --------

@pytest.mark.parametrize("k", [2, 4])
@pytest.mark.parametrize("seed", [10, 11, 12])
def test_fuzz_window_vs_pertick(seed, k):
    """test_fuzz_differential's streaming generator, driven through the
    fused window path in windows of ``k`` vs the cpu per-tick oracle:
    every aggregate table must agree (inserts AND retractions)."""
    from test_fuzz_differential import (build_streaming_graph, random_ticks,
                                        run_streaming)

    rng = np.random.default_rng(seed)
    graph_seed = rng.integers(0, 1 << 30)
    ticks_seed = rng.integers(0, 1 << 30)
    n_sources = len(build_streaming_graph(
        np.random.default_rng(graph_seed))[1])
    ticks = random_ticks(np.random.default_rng(ticks_seed), n_sources)

    g, sources, reduces = build_streaming_graph(
        np.random.default_rng(graph_seed))
    want = run_streaming(get_executor("cpu"), g, sources, reduces, ticks)

    g, sources, reduces = build_streaming_graph(
        np.random.default_rng(graph_seed))
    sched = DirtyScheduler(g, get_executor("tpu"))
    results = []
    for lo in range(0, len(ticks), k):
        feeds = []
        for tick in ticks[lo:lo + k]:
            feeds.append({sources[s_ix]: _batch(rows)
                          for s_ix, rows in tick})
        results.append(sched.tick_many(feeds))
    for res in results:
        res.block()
    got = {}
    for ix, node in enumerate(reduces):
        got[ix] = {int(key): round(float(np.asarray(v).reshape(())), 3)
                   for key, v in sched.read_table(node).items()}
    assert got == want, f"seed {seed} k {k}"
    assert sched.megatick_fallbacks == 0
    assert sched.megatick_windows == len(range(0, len(ticks), k))


def test_pagerank_loop_window_parity():
    """The fixpoint (loops) flavor of the window program: a churn window
    over PageRank matches a per-tick twin fed identical batches."""
    from reflow_tpu.workloads import pagerank

    n_nodes, n_edges, k = 128, 512, 4
    web = pagerank.WebGraph.random(n_nodes, n_edges, seed=5)
    init = web.initial_batch()
    churn = [web.churn(0.02) for _ in range(k)]

    tables = []
    scheds = []
    for _ in range(2):
        pr = pagerank.build_graph(n_nodes, tol=1e-5,
                                  arena_capacity=1 << 12)
        sched = DirtyScheduler(pr.graph, get_executor("tpu"))
        sched.push(pr.teleport, pagerank.teleport_batch(n_nodes))
        sched.push(pr.edges, init)
        sched.tick(sync=False)
        scheds.append((sched, pr))
    mega, pr_m = scheds[0]
    per, pr_p = scheds[1]
    mega.tick_many([{pr_m.edges: b} for b in churn]).block()
    for b in churn:
        per.push(pr_p.edges, b)
        per.tick(sync=False)
    ranks_m = pagerank.ranks_to_array(mega.read_table(pr_m.new_rank),
                                      n_nodes)
    ranks_p = pagerank.ranks_to_array(per.read_table(pr_p.new_rank),
                                      n_nodes)
    assert mega.megatick_windows == 1
    assert mega.megatick_fallbacks == 0
    np.testing.assert_allclose(ranks_m, ranks_p, atol=1e-6)


def test_window_donates_and_rebinds_queue_buffers():
    """The ingress stack is DONATED to the window program: after each
    window the queue must have adopted the program's fresh zeroed stack
    (old handles are dead), and the NEXT window over the same (now
    zeroed) buffers must still match the oracle — no stale rows, no
    use-after-donate."""
    ticks = _ragged_ticks(n_ticks=8)
    want = _oracle(ticks)
    got, sched = _window_drive(ticks, k=4)
    assert got == want
    assert sched.megatick_windows == 2
    qkeys = [key for key in sched.executor._cache
             if isinstance(key, tuple) and key and key[0] == "ingress_q"]
    queue = sched.executor._cache[qkeys[0]]
    for dd in queue.stacked().values():
        # rebind adopted the program's zeroed pass-through: every slot
        # is blank until the next window writes it
        assert int(np.asarray(dd.weights).sum()) == 0
        assert float(np.abs(np.asarray(dd.values)).sum()) == 0.0


def test_window_program_shared_across_identical_graphs():
    """Two tenants with identically-built graphs share ONE traced window
    program via the plan-signature cache: the second executor records
    cache hits instead of re-tracing, and its views still match."""
    ticks = _ragged_ticks(n_ticks=4, seed=9)
    want = _oracle(ticks)
    got_a, sched_a = _window_drive(ticks, k=4)
    got_b, sched_b = _window_drive(ticks, k=4)
    assert got_a == want and got_b == want
    assert sched_b.executor.megatick_cache_hits >= 1
    assert sched_a.megatick_fallbacks == 0
    assert sched_b.megatick_fallbacks == 0


# -- ingress queue unit behavior -------------------------------------------

def test_zero_padding_overwrites_stale_slot():
    """Queue buffers persist across windows: a padding (zero-row) write
    must CLEAR its slot, or the next window would replay last window's
    rows. The zero image is device-cached — counted in zero_writes."""
    from reflow_tpu.executors.ingress_queue import DeviceIngressQueue

    spec = Spec((), np.float32, key_space=8)
    q = DeviceIngressQueue({0: spec}, {0: 64}, 2)
    q.write(0, 0, _batch([(1, 2.0, 3)]))
    q.write(1, 0, _batch([(2, 1.0, 1)]))
    stacked = q.stacked()[0]
    assert int(np.asarray(stacked.weights[0]).sum()) == 3
    q.write(0, 0, _batch([]))          # next window, empty slot
    stacked = q.stacked()[0]
    assert int(np.asarray(stacked.weights[0]).sum()) == 0
    assert int(np.asarray(stacked.weights[1]).sum()) == 1
    assert q.zero_writes == 1


def test_slot_writes_never_share_a_host_buffer():
    """The slot writer dispatches asynchronously and the runtime may read
    a host argument after the call returns (the CPU client aliases an
    aligned buffer outright), so every write hands it arrays nothing
    writes again. A scratch set reused across writes let slot t+1's fill
    rewrite slot t's rows — wrong PageRank state in roughly one served
    run in three on the CPU backend, whenever numpy's allocation happened
    to be aligned."""
    from reflow_tpu.executors.ingress_queue import DeviceIngressQueue

    spec = Spec((), np.float32, key_space=8)
    q = DeviceIngressQueue({0: spec}, {0: 64}, 2)
    a, b = _batch([(1, 2.0, 3)]), _batch([(2, 1.0, 1)])
    first = q._pad_host(0, len(a), 64, np.asarray(a.keys), a)
    second = q._pad_host(0, len(b), 64, np.asarray(b.keys), b)
    for x, y in zip(first, second):
        assert not np.shares_memory(x, y)
    assert first[0][0] == 1 and second[0][0] == 2


def test_queue_rejects_over_capacity_rows():
    from reflow_tpu.executors.ingress_queue import DeviceIngressQueue

    spec = Spec((), np.float32, key_space=8)
    q = DeviceIngressQueue({0: spec}, {0: 4}, 1)
    with pytest.raises(ValueError):
        q.write(0, 0, _batch([(i % 8, 1.0, 1) for i in range(5)]))


def test_slot_nbytes_is_bucketed_footprint():
    from reflow_tpu.executors.device_delta import bucket_capacity
    from reflow_tpu.executors.ingress_queue import slot_nbytes

    spec = Spec((), np.float32, key_space=8)
    cap = bucket_capacity(10)
    assert slot_nbytes(spec, 10) == cap * (4 + 4 + 4)
    vec = Spec((3,), np.float32, key_space=8)
    assert slot_nbytes(vec, 10) == cap * (4 + 4 + 12)


# -- serve wiring: admission keyed on device queue headroom ----------------

def test_frontend_advertises_megatick_and_device_admission():
    g, _srcs, _r = _small_graph()
    sched = DirtyScheduler(g, get_executor("tpu"))
    from reflow_tpu.serve import IngestFrontend

    fe = IngestFrontend(sched, start=False)
    assert fe.megatick is True
    assert fe.admission == "device"

    g2, _s, _r2 = _small_graph()
    cpu_sched = DirtyScheduler(g2, get_executor("cpu"))
    fe_cpu = IngestFrontend(cpu_sched, start=False)
    assert fe_cpu.megatick is False
    assert fe_cpu.admission == "host"

    g3, _s3, _r3 = _small_graph()
    fe_host = IngestFrontend(DirtyScheduler(g3, get_executor("tpu")),
                             start=False, admission="host")
    assert fe_host.admission == "host"
    with pytest.raises(ValueError):
        IngestFrontend(cpu_sched, start=False, admission="bogus")


def test_device_admission_charges_slot_bytes():
    """Under device-keyed admission a host batch charges its bucketed
    queue-slot footprint, not its payload bytes."""
    from reflow_tpu.executors.ingress_queue import slot_nbytes
    from reflow_tpu.serve import IngestFrontend
    from reflow_tpu.serve.queues import batch_nbytes

    g, (s0, _s1), _r = _small_graph()
    sched = DirtyScheduler(g, get_executor("tpu"))
    fe = IngestFrontend(sched, start=False)
    b = _batch([(1, 1.0, 1), (2, 2.0, 1)])
    assert fe._charge_bytes(s0, b, device=False) == slot_nbytes(s0.spec, 2)
    fe.admission = "host"
    assert fe._charge_bytes(s0, b, device=False) == batch_nbytes(b)


def test_frontend_pump_runs_fused_windows():
    """End to end through the serve pump: submissions over a tpu-backed
    sink-free scheduler commit via the fused window path."""
    from reflow_tpu.serve import IngestFrontend

    g, (s0, _s1), r = _small_graph()
    sched = DirtyScheduler(g, get_executor("tpu"))
    fe = IngestFrontend(sched)
    try:
        for i in range(8):
            fe.submit(s0, _batch([(i % K_SPACE, float(i), 1)]))
        fe.flush()
    finally:
        fe.close()
    assert sched.megatick_windows >= 1
    assert sched.megatick_fallbacks == 0
    total = sum(v * 2 for v in range(8))   # map doubles every value
    got = sum(float(np.asarray(v).reshape(()))
              for v in sched.read_table(r).values())
    assert got == pytest.approx(total)
