"""PageRank end-to-end: fixpoint iteration, both executors, vs NumPy oracle
(SURVEY.md §4e — small-scale benchmark-config test)."""

import numpy as np
import pytest

from reflow_tpu import DirtyScheduler
from reflow_tpu.executors import get_executor
from reflow_tpu.workloads import pagerank

N, E = 40, 160
TOL = 1e-5


def run_pagerank(executor_name, web, churn_ticks=0):
    pg = pagerank.build_graph(web.n_nodes, tol=TOL)
    sched = DirtyScheduler(pg.graph, get_executor(executor_name),
                           max_loop_iters=500)
    sched.push(pg.teleport, pagerank.teleport_batch(web.n_nodes))
    sched.push(pg.edges, web.initial_batch())
    r = sched.tick()
    assert r.quiesced, "fixpoint did not converge"
    churn_results = []
    for _ in range(churn_ticks):
        sched.push(pg.edges, web.churn(0.05))
        cr = sched.tick()
        assert cr.quiesced
        churn_results.append(cr)
    ranks = sched.read_table(pg.new_rank)
    return ranks, churn_results, sched


as_array = pagerank.ranks_to_array


@pytest.mark.parametrize("shards", [1, 4])
def test_churn_arena_capacity_at_the_1m_edge_deployment(shards):
    # the pagerank-1m deployment: 1M live edges, 1% rewired a tick.
    # live rows round up to 2^20; a tick's 2*10_000 + 2 retract+insert
    # rows round up to 2^15, eight of them as headroom; a mesh bounds
    # every tick per shard under worst-case skew, so times the shards
    cap = pagerank.churn_arena_capacity(1_000_000, 0.01, shards)
    assert cap == shards * ((1 << 20) + 8 * (1 << 15))
    pr = pagerank.build_graph(64, arena_capacity=cap)
    assert pr.join.op.arena_capacity == cap


def test_pagerank_cpu_matches_numpy_reference():
    web = pagerank.WebGraph.random(N, E, seed=1)
    ranks, _, _ = run_pagerank("cpu", web)
    ref = pagerank.reference_ranks(web)
    np.testing.assert_allclose(as_array(ranks, N), ref, atol=5e-4)


def test_pagerank_tpu_matches_numpy_reference():
    web = pagerank.WebGraph.random(N, E, seed=1)
    ranks, _, _ = run_pagerank("tpu", web)
    ref = pagerank.reference_ranks(web)
    np.testing.assert_allclose(as_array(ranks, N), ref, atol=5e-4)


def test_pagerank_incremental_churn_differential():
    """After churn ticks, cpu and tpu agree with each other AND with a
    from-scratch NumPy recompute on the churned graph (incremental-vs-full)."""
    web_cpu = pagerank.WebGraph.random(N, E, seed=7)
    web_tpu = pagerank.WebGraph.random(N, E, seed=7)
    ranks_cpu, _, _ = run_pagerank("cpu", web_cpu, churn_ticks=3)
    ranks_tpu, _, _ = run_pagerank("tpu", web_tpu, churn_ticks=3)
    assert np.array_equal(web_cpu.dst, web_tpu.dst)  # same churn sequence
    a, b = as_array(ranks_cpu, N), as_array(ranks_tpu, N)
    np.testing.assert_allclose(a, b, atol=2e-3)
    ref = pagerank.reference_ranks(web_cpu)
    np.testing.assert_allclose(a, ref, atol=2e-3)


def test_churn_tick_is_incremental():
    """A churn tick must touch far fewer deltas than the cold start."""
    web = pagerank.WebGraph.random(200, 800, seed=3)
    pg = pagerank.build_graph(web.n_nodes, tol=1e-4)
    sched = DirtyScheduler(pg.graph, max_loop_iters=500)
    sched.push(pg.teleport, pagerank.teleport_batch(web.n_nodes))
    sched.push(pg.edges, web.initial_batch())
    cold = sched.tick()
    sched.push(pg.edges, web.churn(0.01))
    warm = sched.tick()
    assert warm.quiesced
    assert warm.delta_ops < cold.delta_ops / 5


def test_loop_requires_close():
    g = pagerank.build_graph(8).graph
    assert g.loops[0].back_input is not None


def test_pagerank_streaming_matches_synced():
    """VERDICT r2 weak #6: tick(sync=False) had zero test coverage. The
    pipelined streaming path must produce bit-for-bit the same converged
    state as synchronous ticking over the same churn sequence."""
    web_a = pagerank.WebGraph.random(N, E, seed=11)
    web_b = pagerank.WebGraph.random(N, E, seed=11)

    def run(web, sync):
        pg = pagerank.build_graph(web.n_nodes, tol=TOL)
        sched = DirtyScheduler(pg.graph, get_executor("tpu"),
                               max_loop_iters=500)
        sched.push(pg.teleport, pagerank.teleport_batch(web.n_nodes))
        sched.push(pg.edges, web.initial_batch())
        sched.tick()  # cold build synced in both runs
        results = []
        for _ in range(4):
            sched.push(pg.edges, web.churn(0.05))
            results.append(sched.tick(sync=sync))
        for r in results:
            r.block()  # streaming sync point (no-op when sync=True)
        assert all(r.quiesced for r in results)
        return sched.read_table(pg.new_rank), results

    ranks_sync, res_sync = run(web_a, True)
    ranks_stream, res_stream = run(web_b, False)
    assert np.array_equal(web_a.dst, web_b.dst)  # same churn sequence
    assert set(ranks_sync) == set(ranks_stream)
    for k in ranks_sync:
        assert ranks_sync[k] == ranks_stream[k]  # same programs: bitwise
    # streaming reports the same per-tick pass/row counts after block()
    assert [r.passes for r in res_sync] == [r.passes for r in res_stream]
    assert ([r.deltas_in for r in res_sync]
            == [r.deltas_in for r in res_stream])


def test_pagerank_macro_tick_matches_sequential():
    """tick_many (K ticks lax.scan-fused into ONE device execution — the
    per-dispatch-overhead amortization fast path) must produce bit-for-bit the
    same state and the same aggregate tick metadata as K sequential
    streaming ticks over the same churn sequence."""
    web_a = pagerank.WebGraph.random(N, E, seed=13)
    web_b = pagerank.WebGraph.random(N, E, seed=13)
    K = 3

    def prep(web):
        pg = pagerank.build_graph(web.n_nodes, tol=TOL)
        sched = DirtyScheduler(pg.graph, get_executor("tpu"),
                               max_loop_iters=500)
        sched.push(pg.teleport, pagerank.teleport_batch(web.n_nodes))
        sched.push(pg.edges, web.initial_batch())
        sched.tick()
        return pg, sched, [web.churn(0.05) for _ in range(K)]

    pg_a, sched_a, churns_a = prep(web_a)
    results = []
    for b in churns_a:
        sched_a.push(pg_a.edges, b)
        results.append(sched_a.tick(sync=False))
    for r in results:
        r.block()

    pg_b, sched_b, churns_b = prep(web_b)
    agg = sched_b.tick_many([{pg_b.edges: b} for b in churns_b]).block()

    ranks_a = sched_a.read_table(pg_a.new_rank)
    ranks_b = sched_b.read_table(pg_b.new_rank)
    assert set(ranks_a) == set(ranks_b)
    for k in ranks_a:
        assert ranks_a[k] == ranks_b[k]
    assert agg.quiesced
    assert agg.passes == sum(r.passes for r in results)
    assert agg.deltas_in == sum(r.deltas_in for r in results)
    assert agg.tick == sched_a._tick


def test_macro_tick_fallback_cpu_executor():
    """tick_many on an executor without the fused path (the CPU oracle)
    falls back to sequential ticks with identical semantics."""
    web = pagerank.WebGraph.random(N, E, seed=17)
    web2 = pagerank.WebGraph.random(N, E, seed=17)

    def prep(web, name):
        pg = pagerank.build_graph(web.n_nodes, tol=TOL)
        sched = DirtyScheduler(pg.graph, get_executor(name),
                               max_loop_iters=500)
        sched.push(pg.teleport, pagerank.teleport_batch(web.n_nodes))
        sched.push(pg.edges, web.initial_batch())
        sched.tick()
        return pg, sched

    pg, sched = prep(web, "cpu")
    churns = [web.churn(0.05) for _ in range(2)]
    agg = sched.tick_many([{pg.edges: b} for b in churns]).block()
    assert agg.quiesced

    pg2, sched2 = prep(web2, "cpu")
    for b in churns:
        sched2.push(pg2.edges, b)
        sched2.tick()
    assert (sched.read_table(pg.new_rank)
            == sched2.read_table(pg2.new_rank))


# -- deferred fixpoint (cross-tick residual deferral, VERDICT r4 #1) -------

def _run_deferred(executor_name, defer, seed=21, churn_ticks=6,
                  drain=True, arena=4096, settle=False):
    web = pagerank.WebGraph.random(N, E, seed=seed)
    pg = pagerank.build_graph(web.n_nodes, tol=TOL, arena_capacity=arena,
                              defer_passes=defer)
    sched = DirtyScheduler(pg.graph, get_executor(executor_name),
                           max_loop_iters=500)
    sched.push(pg.teleport, pagerank.teleport_batch(web.n_nodes))
    sched.push(pg.edges, web.initial_batch())
    sched.tick(sync=False)
    if settle:
        # converge the cold build before streaming churn: mid-stream
        # accuracy then reflects steady-state churn-tracking lag, not
        # the (deliberately amortized) initial convergence
        sched.drain(pg.edges)
    for _ in range(churn_ticks):
        sched.push(pg.edges, web.churn(0.05))
        sched.tick(sync=False)
    if drain:
        sched.drain(pg.edges)
    return web, pg, sched


def test_deferred_drain_matches_reference():
    """defer_passes caps loop passes per tick; drain() flushes the carried
    residue to the same fixpoint a quiescent schedule reaches (within the
    tol-lag band of the independent NumPy oracle)."""
    for defer in (1, 2, 4):
        web, pg, sched = _run_deferred("tpu", defer)
        ranks = as_array(sched.read_table(pg.new_rank), N)
        ref = pagerank.reference_ranks(web)
        np.testing.assert_allclose(ranks, ref, atol=5e-4)


def test_deferred_left_table_consistency():
    """After drain the Join's folded left table must equal the Reduce's
    emitted table exactly — the deferred left-table patch (A = emitted -
    resid) reduces to the quiescent formula at resid == 0."""
    web, pg, sched = _run_deferred("tpu", 2)
    jt = sched.read_table(pg.join)
    rt = sched.read_table(pg.new_rank)
    assert set(jt) == set(rt)
    for k in rt:
        assert jt[k] == rt[k]


def test_deferred_mid_stream_accuracy_bounded():
    """Without drain, ranks lag full convergence by the in-flight mass;
    for PageRank the lag is geometrically damped (d/(1-d) amplification),
    so mid-stream views stay within a small multiple of the drained
    band. This is the accuracy contract of docs/guide.md."""
    web, pg, sched = _run_deferred("tpu", 2, drain=False, settle=True)
    ranks = as_array(sched.read_table(pg.new_rank), N)
    ref = pagerank.reference_ranks(web)
    mid_err = np.abs(ranks - ref).max()
    sched.drain(pg.edges)
    drained = as_array(sched.read_table(pg.new_rank), N)
    drained_err = np.abs(drained - ref).max()
    # 5% churn/tick at defer=2 on a 64-node graph is a brutal regime (the
    # whole rank vector reshuffles every few ticks); the contract is that
    # the lag stays within a small multiple of the per-tick injected mass
    # and collapses to the drained band on drain
    assert mid_err < 0.2, mid_err
    assert drained_err < 5e-4, drained_err


def test_deferred_sharded_matches_tpu():
    """The sharded executor runs the identical deferred schedule inside
    one shard_map region — results agree with the single-device program
    to f32 reduction-order noise."""
    web_a, pg_a, sched_a = _run_deferred("tpu", 2)
    web_b, pg_b, sched_b = _run_deferred("sharded", 2)
    assert np.array_equal(web_a.dst, web_b.dst)
    a = as_array(sched_a.read_table(pg_a.new_rank), N)
    b = as_array(sched_b.read_table(pg_b.new_rank), N)
    np.testing.assert_allclose(a, b, atol=1e-5)


def test_deferred_checkpoint_roundtrip_with_live_residue():
    """The carried residue is SEMANTIC state: a checkpoint taken
    mid-stream (residue live) must restore it, or in-flight rank mass
    would be silently lost. Restore drops the derived CSR cache, so
    agreement is to f32 reduction-order noise, not bitwise."""
    import tempfile

    from reflow_tpu.utils.checkpoint import load_checkpoint, save_checkpoint

    web = pagerank.WebGraph.random(N, E, seed=23)
    pg = pagerank.build_graph(N, tol=TOL, arena_capacity=4096,
                              defer_passes=2)
    sched = DirtyScheduler(pg.graph, get_executor("tpu"), max_loop_iters=500)
    sched.push(pg.teleport, pagerank.teleport_batch(N))
    sched.push(pg.edges, web.initial_batch())
    sched.tick(sync=False)
    churns = [web.churn(0.05) for _ in range(6)]
    for b in churns[:3]:
        sched.push(pg.edges, b)
        sched.tick(sync=False)
    with tempfile.TemporaryDirectory() as td:
        save_checkpoint(sched, td)
        pg2 = pagerank.build_graph(N, tol=TOL, arena_capacity=4096,
                                   defer_passes=2)
        sched2 = DirtyScheduler(pg2.graph, get_executor("tpu"),
                                max_loop_iters=500)
        load_checkpoint(sched2, td)
    # the restored residue must be live (mid-stream, defer=2)
    resid = np.asarray(sched2.executor.states[pg2.ranks.id]["resid"])
    assert np.any(resid != 0)
    for sch, pgx in ((sched, pg), (sched2, pg2)):
        for b in churns[3:]:
            sch.push(pgx.edges, b)
            sch.tick(sync=False)
        sch.drain(pgx.edges)
    a = as_array(sched.read_table(pg.new_rank), N)
    b = as_array(sched2.read_table(pg2.new_rank), N)
    np.testing.assert_allclose(a, b, atol=1e-5)


def test_deferred_macro_tick_matches_sequential():
    """tick_many carries the residue through its lax.scan (it lives in
    the op-state carry): K fused deferred ticks == K sequential streaming
    deferred ticks, bitwise."""
    web_a = pagerank.WebGraph.random(N, E, seed=29)
    web_b = pagerank.WebGraph.random(N, E, seed=29)

    def prep(web):
        pg = pagerank.build_graph(web.n_nodes, tol=TOL, arena_capacity=4096,
                                  defer_passes=2)
        sched = DirtyScheduler(pg.graph, get_executor("tpu"),
                               max_loop_iters=500)
        sched.push(pg.teleport, pagerank.teleport_batch(web.n_nodes))
        sched.push(pg.edges, web.initial_batch())
        sched.tick(sync=False)
        return pg, sched, [web.churn(0.05) for _ in range(3)]

    pg_a, sched_a, churns_a = prep(web_a)
    for b in churns_a:
        sched_a.push(pg_a.edges, b)
        sched_a.tick(sync=False)

    pg_b, sched_b, churns_b = prep(web_b)
    sched_b.tick_many([{pg_b.edges: b} for b in churns_b]).block()

    ranks_a = sched_a.read_table(pg_a.new_rank)
    ranks_b = sched_b.read_table(pg_b.new_rank)
    assert set(ranks_a) == set(ranks_b)
    for k in ranks_a:
        assert ranks_a[k] == ranks_b[k]
    ra = np.asarray(sched_a.executor.states[pg_a.ranks.id]["resid"])
    rb = np.asarray(sched_b.executor.states[pg_b.ranks.id]["resid"])
    assert np.array_equal(ra, rb)
