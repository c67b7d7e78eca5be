"""ShardedTpuExecutor on the 8-device virtual CPU mesh (SURVEY.md §4d):
collectives (psum_scatter, all_gather) + key-range sharding, differential
against the single-device TpuExecutor and the CPU oracle."""

import numpy as np
import pytest

from reflow_tpu import DeltaBatch, DirtyScheduler, FlowGraph, Spec
from reflow_tpu.executors import CpuExecutor
from reflow_tpu.executors.tpu import TpuExecutor
from reflow_tpu.parallel import make_mesh
from reflow_tpu.parallel.shard import ShardedTpuExecutor


@pytest.fixture(scope="module")
def mesh():
    return make_mesh(8)


def _reduce_graph(K=64):
    spec = Spec((), np.float32, key_space=K)
    g = FlowGraph("wc")
    src = g.source("src", spec)
    ones = g.map(src, lambda v: v * 0 + 1, vectorized=True, name="ones")
    counts = g.reduce(ones, "sum", name="counts",
                      spec=Spec((), np.float32, key_space=K))
    out = g.sink(counts, "out")
    return g, src, out


def _push_ticks(sched, src, rng, K, ticks=3):
    views = []
    for t in range(ticks):
        n = 50 + 30 * t
        keys = rng.integers(0, K, n)
        w = np.where(rng.random(n) < 0.25, -1, 1)
        sched.push(src, DeltaBatch(keys, np.ones(n, np.float32), w))
        sched.tick()
        views.append(dict(sched.view_dict("out")))
    return views


def test_sharded_reduce_matches_cpu(mesh):
    K = 64
    g1, s1, _ = _reduce_graph(K)
    g2, s2, _ = _reduce_graph(K)
    sh = DirtyScheduler(g1, ShardedTpuExecutor(mesh))
    cp = DirtyScheduler(g2, CpuExecutor())
    v_sh = _push_ticks(sh, s1, np.random.default_rng(0), K)
    v_cp = _push_ticks(cp, s2, np.random.default_rng(0), K)
    for a, b in zip(v_sh, v_cp):
        assert {int(k): float(v) for k, v in a.items()} == \
               {int(k): float(v) for k, v in b.items()}


def test_sharded_pagerank_matches_single_device(mesh):
    from reflow_tpu.workloads import pagerank

    N, E = 64, 512
    ref_ranks = {}
    for ex in (ShardedTpuExecutor(mesh), TpuExecutor()):
        web = pagerank.WebGraph.random(N, E, seed=11)
        pg = pagerank.build_graph(N, tol=1e-5, arena_capacity=1 << 13)
        sched = DirtyScheduler(pg.graph, ex, max_loop_iters=500)
        sched.push(pg.teleport, pagerank.teleport_batch(N))
        sched.push(pg.edges, web.initial_batch())
        r = sched.tick()
        assert r.quiesced
        for _ in range(2):
            sched.push(pg.edges, web.churn(0.05))
            assert sched.tick().quiesced
        ref_ranks[ex.name] = sched.read_table(pg.new_rank)
        ref = pagerank.reference_ranks(web)

    a, b = ref_ranks["sharded"], ref_ranks["tpu"]
    assert set(a) == set(b)
    # distinct accumulation orders (row-based sharded vs fused linear)
    # give two tol-converged fixpoints within ~tol/(1-damping)
    bound = 1e-5 / (1.0 - pagerank.DAMPING) + 1e-4
    for k in a:
        assert abs(float(a[k]) - float(b[k])) < bound
    # and both match the NumPy oracle on the churned graph
    np.testing.assert_allclose(pagerank.ranks_to_array(a, N), ref,
                               atol=5e-4)


def test_sharded_join_matches_cpu(mesh):
    K = 32
    left_spec = Spec((), np.float32, key_space=K, unique=True)
    right_spec = Spec((), np.float32, key_space=K)

    def build():
        g = FlowGraph("j")
        a = g.source("a", left_spec)
        b = g.source("b", right_spec)
        j = g.join(a, b, merge=lambda k, va, vb: va * 10 + vb,
                   spec=right_spec, name="j", arena_capacity=1 << 10)
        out = g.sink(j, "out")
        return g, a, b

    ga, a1, b1 = build()
    gb, a2, b2 = build()
    sh = DirtyScheduler(ga, ShardedTpuExecutor(mesh))
    cp = DirtyScheduler(gb, CpuExecutor())

    def drive(sched, a, b):
        rng = np.random.default_rng(5)
        ka = rng.permutation(K)[:16]
        sched.push(a, DeltaBatch(ka, ka.astype(np.float32)))
        kb = rng.integers(0, K, 40)
        sched.push(b, DeltaBatch(kb, np.ones(40, np.float32)))
        sched.tick()
        # retract some right rows, add more left keys next tick
        sched.push(b, DeltaBatch(kb[:10], np.ones(10, np.float32),
                                 -np.ones(10, np.int64)))
        sched.tick()
        return {kv: w for kv, w in sched.view("out").items()}

    va = drive(sh, a1, b1)
    # CPU merge gets scalar args; device merge gets arrays — same formula
    vb = drive(cp, a2, b2)
    norm = lambda d: {(int(k), float(v)): int(w) for (k, v), w in d.items()}
    assert norm(va) == norm(vb)


def test_key_space_divisibility_enforced(mesh):
    g = FlowGraph("bad")
    src = g.source("s", Spec((), np.float32, key_space=30))
    r = g.reduce(src, "sum", spec=Spec((), np.float32, key_space=30))
    g.sink(r, "out")
    from reflow_tpu.graph import GraphError

    with pytest.raises(GraphError, match="multiple of the mesh"):
        DirtyScheduler(g, ShardedTpuExecutor(mesh))


def test_sharded_route_overflow_surfaces(mesh):
    """ADVICE r2 (high): pathological key skew past the ROUTE_SLACK budget
    must raise through check_errors for LINEAR reducers too — never a
    silently wrong aggregate."""
    K = 512  # Kl=64 per shard; delta cap 64 -> Cl=8 -> sparse regime
    g, src, _ = _reduce_graph(K)
    sh = DirtyScheduler(g, ShardedTpuExecutor(mesh))
    n = 64
    keys = np.arange(n) % 64  # every key owned by shard 0: worst-case skew
    sh.push(src, DeltaBatch(keys, np.ones(n, np.float32),
                            np.ones(n, np.int64)))
    with pytest.raises(RuntimeError, match="route overflow"):
        sh.tick()


def test_sharded_linear_fixpoint_engages(mesh):
    """VERDICT r2 item 5: the fused delta-vector loop must actually run on
    the sharded executor (not silently fall back to the row program), and
    match the single-device executor bit-for-bit on the ranks table."""
    from reflow_tpu.workloads import pagerank

    N, E = 64, 512
    results = {}
    for name, ex in (("sharded", ShardedTpuExecutor(mesh)),
                     ("single", TpuExecutor())):
        web = pagerank.WebGraph.random(N, E, seed=21)
        pg = pagerank.build_graph(N, tol=1e-6, arena_capacity=1 << 13)
        sched = DirtyScheduler(pg.graph, ex, max_loop_iters=500)
        sched.push(pg.teleport, pagerank.teleport_batch(N))
        sched.push(pg.edges, web.initial_batch())
        r = sched.tick()
        assert r.quiesced
        for _ in range(2):
            sched.push(pg.edges, web.churn(0.05))
            assert sched.tick().quiesced
        assert ex.fixpoint_engine == "LinearFixpointProgram", (
            f"{name}: fused loop fell back")
        assert ex._linear_structure is not None
        results[name] = sched.read_table(pg.new_rank)
    assert set(results["sharded"]) == set(results["single"])
    for k in results["single"]:
        a = np.asarray(results["sharded"][k], np.float32)
        b = np.asarray(results["single"][k], np.float32)
        np.testing.assert_allclose(a, b, rtol=1e-6, atol=1e-7)


def test_sharded_join_routed_path_differential(mesh):
    """Large deltas take the routed (all_to_all) join path — per-dest
    budget >= _MIN_ROUTE_BUDGET rows — and must match the CPU oracle."""
    K = 1024
    rows = 2048  # Cl=256/shard, budget=128: routing engages on n=8
    spec = Spec((), np.float32, key_space=K)

    def build():
        g = FlowGraph("join")
        left_src = g.source("L", spec)
        right_src = g.source("R", spec)
        lt = g.reduce(left_src, "sum", name="lt")   # unique-keyed left
        j = g.join(lt, right_src, merge=lambda k, x, y: x + y,
                   spec=spec, name="j", arena_capacity=1 << 15)
        g.sink(j, "out")
        return g, left_src, right_src

    rng = np.random.default_rng(5)
    outs = []
    for ex in (ShardedTpuExecutor(mesh), CpuExecutor()):
        g, ls, rs = build()
        sched = DirtyScheduler(g, ex)
        r = np.random.default_rng(5)
        lk = r.integers(0, K, rows)
        sched.push(ls, DeltaBatch(
            lk, r.integers(0, 100, rows).astype(np.float32),
            np.ones(rows, np.int64)))
        sched.tick()
        rk = r.integers(0, K, rows)
        sched.push(rs, DeltaBatch(
            rk, r.integers(0, 100, rows).astype(np.float32),
            np.ones(rows, np.int64)))
        sched.tick()
        # second right batch incl. retractions of the first
        sched.push(rs, DeltaBatch(rk[:rows // 2],
                                  np.zeros(rows // 2, np.float32),
                                  -np.ones(rows // 2, np.int64)))
        sched.tick()
        outs.append(dict(sched.view("out")))
    a, b = outs
    assert set(a) == set(b)
    for k in a:
        assert a[k] == b[k], (k, a[k], b[k])


def test_sharded_minmax_matches_cpu(mesh):
    """Sharded scalar min/max: rows routed to key owners, candidate-buffer
    kernel per shard — exact under retraction churn within the buffer."""
    K = 64
    spec = Spec((), np.float32, key_space=K)
    for how in ("min", "max"):
        g = FlowGraph(how)
        src = g.source("s", spec)
        g.sink(g.reduce(src, how, name="m"), "out")
        g2 = FlowGraph(how)
        src2 = g2.source("s", spec)
        g2.sink(g2.reduce(src2, how, name="m"), "out")
        sh = DirtyScheduler(g, ShardedTpuExecutor(mesh))
        cp = DirtyScheduler(g2, CpuExecutor())
        # identical delta sequence on both: inserts + exact retractions
        rng = np.random.default_rng(8)
        inserted = []
        ticks = []
        for _ in range(3):
            rows = []
            for _ in range(96):
                if inserted and rng.random() < 0.3:
                    k, v = inserted.pop(int(rng.integers(0, len(inserted))))
                    rows.append((k, v, -1))
                else:
                    k = int(rng.integers(0, K))
                    v = float(rng.integers(-50, 50))
                    rows.append((k, v, 1))
                    inserted.append((k, v))
            ticks.append(rows)
        for sched, src_n in ((sh, src), (cp, src2)):
            for rows in ticks:
                sched.push(src_n, DeltaBatch(
                    np.array([r[0] for r in rows]),
                    np.array([r[1] for r in rows], np.float32),
                    np.array([r[2] for r in rows])))
                sched.tick()
        a = {int(k): float(v) for k, v in sh.view_dict("out").items()}
        b = {int(k): float(v) for k, v in cp.view_dict("out").items()}
        assert a == b, how


def test_sharded_minmax_buffer_exhaustion_flags_error(mesh):
    """candidates=1 on the mesh: hollowing a key's buffer past its one
    eviction trips the sticky error through the routed path too."""
    K = 64
    spec = Spec((), np.float32, key_space=K)
    g = FlowGraph("mm1")
    src = g.source("s", spec)
    g.sink(g.reduce(src, "max", name="m", candidates=1), "out")
    sh = DirtyScheduler(g, ShardedTpuExecutor(mesh))
    sh.push(src, DeltaBatch(np.array([3, 3]),
                            np.array([2.0, 1.0], np.float32),
                            np.ones(2, np.int64)))
    sh.tick()    # buffer [2.0], overflow {1.0}
    sh.push(src, DeltaBatch(np.array([3]), np.array([2.0], np.float32),
                            -np.ones(1, np.int64)))
    with pytest.raises(RuntimeError, match="min/max"):
        sh.tick()


def test_sharded_macro_tick_matches_sequential(mesh):
    """tick_many on the sharded executor: the scan-fused macro-tick must
    run the SPMD tick program per scan step and match sequential
    streaming ticks bit for bit."""
    from reflow_tpu.workloads import pagerank

    N, E, K = 64, 256, 3
    web_a = pagerank.WebGraph.random(N, E, seed=23)
    web_b = pagerank.WebGraph.random(N, E, seed=23)

    def prep(web):
        pg = pagerank.build_graph(N, tol=1e-5, arena_capacity=1 << 13)
        sched = DirtyScheduler(pg.graph, ShardedTpuExecutor(mesh),
                               max_loop_iters=500)
        sched.push(pg.teleport, pagerank.teleport_batch(N))
        sched.push(pg.edges, web.initial_batch())
        sched.tick()
        return pg, sched, [web.churn(0.1) for _ in range(K)]

    pg_a, sched_a, churns_a = prep(web_a)
    for b in churns_a:
        sched_a.push(pg_a.edges, b)
        sched_a.tick(sync=False)

    pg_b, sched_b, churns_b = prep(web_b)
    agg = sched_b.tick_many(
        [{pg_b.edges: b} for b in churns_b]).block()
    assert agg.quiesced

    ranks_a = sched_a.read_table(pg_a.new_rank)
    ranks_b = sched_b.read_table(pg_b.new_rank)
    assert set(ranks_a) == set(ranks_b)
    for k in ranks_a:
        assert float(ranks_a[k]) == float(ranks_b[k])


def test_shard_batch_presharded_ingress_matches_host_push(mesh):
    """parallel.mesh.shard_batch builds a row-sharded DeviceDelta from
    per-shard host chunks (the single-controller form of the multi-host
    ingestion recipe); pushing it must equal pushing the equivalent
    host batch."""
    from reflow_tpu.parallel.mesh import shard_batch

    K = 64
    rng = np.random.default_rng(21)
    n = 8 * 16
    keys = rng.integers(0, K, n)
    w = np.where(rng.random(n) < 0.25, -1, 1)
    vals = np.ones(n, np.float32)

    g1, s1, _ = _reduce_graph(K)
    a = DirtyScheduler(g1, ShardedTpuExecutor(mesh))
    a.push(s1, DeltaBatch(keys, vals, w))
    a.tick()

    g2, s2, _ = _reduce_graph(K)
    b = DirtyScheduler(g2, ShardedTpuExecutor(mesh))
    chunks = [DeltaBatch(keys[i::8], vals[i::8], w[i::8]) for i in range(8)]
    b.push(s2, shard_batch(chunks, s2.spec, mesh))
    b.tick()

    assert dict(a.view_dict("out")) == dict(b.view_dict("out"))


def test_two_axis_dcn_mesh_single_controller(mesh):
    """make_mesh(dcn=2) on one controller: the executor shards over the
    flattened (dcn, delta) product axis and matches the 1-axis result."""
    from reflow_tpu.parallel.mesh import shard_batch_process_local
    from reflow_tpu.workloads import pagerank

    N, E = 64, 512
    results = {}
    for name in ("flat", "dcn"):
        web = pagerank.WebGraph.random(N, E, seed=41)
        pg = pagerank.build_graph(N, tol=1e-5, arena_capacity=1 << 13)
        m = mesh if name == "flat" else make_mesh(dcn=2)
        ex = ShardedTpuExecutor(m)
        if name == "dcn":
            assert ex.axis == ("dcn", "delta") and ex.n == 8
        sched = DirtyScheduler(pg.graph, ex, max_loop_iters=500)
        # process-local ingestion helper (single-controller degenerate
        # form: one process holds everything)
        sched.push(pg.teleport, shard_batch_process_local(
            pagerank.teleport_batch(N), pg.teleport.spec, m,
            capacity=1 << 7))
        sched.push(pg.edges, shard_batch_process_local(
            web.initial_batch(), pg.edges.spec, m, capacity=1 << 10))
        assert sched.tick().quiesced
        sched.push(pg.edges, web.churn(0.05))
        assert sched.tick().quiesced
        results[name] = sched.read_table(pg.new_rank)
    assert set(results["flat"]) == set(results["dcn"])
    bound = 1e-5 / (1.0 - pagerank.DAMPING) + 1e-4
    for k in results["flat"]:
        assert abs(float(results["flat"][k])
                   - float(results["dcn"][k])) < bound
