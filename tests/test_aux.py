"""Aux subsystems (SURVEY.md §5): durable checkpoint/resume (orbax for
device state), exactly-once ingestion, metrics summary, device min/max."""

import numpy as np
import pytest

from reflow_tpu import DeltaBatch, DirtyScheduler, FlowGraph, Spec
from reflow_tpu.executors import CpuExecutor, get_executor
from reflow_tpu.utils import load_checkpoint, save_checkpoint, summarize
from reflow_tpu.workloads import pagerank

N, E = 48, 200


def _pagerank_sched(executor):
    pg = pagerank.build_graph(N, tol=1e-5)
    sched = DirtyScheduler(pg.graph, executor, max_loop_iters=500)
    web = pagerank.WebGraph.random(N, E, seed=2)
    sched.push(pg.teleport, pagerank.teleport_batch(N))
    sched.push(pg.edges, web.initial_batch())
    sched.tick()
    return sched, pg, web


@pytest.mark.parametrize("executor_name", ["cpu", "tpu"])
def test_checkpoint_resume_replays_identically(tmp_path, executor_name):
    sched, pg, web = _pagerank_sched(get_executor(executor_name))
    save_checkpoint(sched, str(tmp_path / "ckpt"))

    churn = web.churn(0.05)
    sched.push(pg.edges, churn)
    sched.tick()
    after = sched.read_table(pg.new_rank)

    # fresh scheduler over the same graph: restore + replay the same churn
    sched2 = DirtyScheduler(pg.graph, get_executor(executor_name),
                            max_loop_iters=500)
    load_checkpoint(sched2, str(tmp_path / "ckpt"))
    sched2.push(pg.edges, churn)
    sched2.tick()
    replay = sched2.read_table(pg.new_rank)
    assert set(after) == set(replay)
    for k in after:
        assert abs(float(after[k]) - float(replay[k])) < 1e-6


def test_checkpoint_resume_sharded(tmp_path):
    from reflow_tpu.parallel import make_mesh
    from reflow_tpu.parallel.shard import ShardedTpuExecutor

    mesh = make_mesh(8)
    pg = pagerank.build_graph(64, tol=1e-5, arena_capacity=1 << 13)
    sched = DirtyScheduler(pg.graph, ShardedTpuExecutor(mesh),
                           max_loop_iters=500)
    web = pagerank.WebGraph.random(64, 256, seed=5)
    sched.push(pg.teleport, pagerank.teleport_batch(64))
    sched.push(pg.edges, web.initial_batch())
    sched.tick()
    before = sched.read_table(pg.new_rank)
    save_checkpoint(sched, str(tmp_path / "ck"))

    sched2 = DirtyScheduler(pg.graph, ShardedTpuExecutor(mesh),
                            max_loop_iters=500)
    load_checkpoint(sched2, str(tmp_path / "ck"))
    restored = sched2.read_table(pg.new_rank)
    assert {k: float(v) for k, v in before.items()} == \
           {k: float(v) for k, v in restored.items()}


def test_exactly_once_ingestion():
    g, src, sink = _wordcountish()
    sched = DirtyScheduler(g)
    b = DeltaBatch(np.array([1, 2]), np.ones(2, np.float32))
    assert sched.push(src, b, batch_id="b-1")
    assert not sched.push(src, b, batch_id="b-1")  # duplicate dropped
    sched.tick()
    v = sched.view_dict("out")
    assert v == {1: 1.0, 2: 1.0}, v


def test_exactly_once_survives_checkpoint(tmp_path):
    g, src, sink = _wordcountish()
    sched = DirtyScheduler(g)
    sched.push(src, DeltaBatch(np.array([1]), np.ones(1, np.float32)),
               batch_id="b-7")
    sched.tick()
    save_checkpoint(sched, str(tmp_path / "ck"))
    # fresh scheduler on the same graph: restore must reject redelivery
    sched2 = DirtyScheduler(g)
    load_checkpoint(sched2, str(tmp_path / "ck"))
    assert not sched2.push(src, DeltaBatch(np.array([1]),
                                           np.ones(1, np.float32)),
                           batch_id="b-7")


def _wordcountish():
    g = FlowGraph("wc")
    spec = Spec((), np.float32, key_space=64)
    src = g.source("src", spec)
    counts = g.reduce(g.map(src, lambda v: v * 0 + 1, vectorized=True),
                      "sum", spec=spec)
    sink = g.sink(counts, "out")
    return g, src, sink


def test_metrics_summary():
    sched, pg, web = _pagerank_sched(CpuExecutor())
    for _ in range(2):
        sched.push(pg.edges, web.churn(0.05))
        sched.tick()
    s = summarize(sched.history)
    assert s.ticks == 3 and s.quiesced_all
    assert s.delta_ops > 0 and s.delta_ops_per_s > 0
    assert s.tick_p95_s >= s.tick_p50_s


def test_device_minmax_insert_matches_cpu():
    def build():
        g = FlowGraph("mm")
        spec = Spec((), np.float32, key_space=32)
        src = g.source("src", spec)
        mx = g.reduce(src, "max", name="mx", spec=spec)
        g.sink(mx, "out")
        return g, src

    rng = np.random.default_rng(0)
    batches = [(rng.integers(0, 32, 40),
                rng.normal(size=40).astype(np.float32)) for _ in range(3)]
    views = {}
    for name in ("cpu", "tpu"):
        g, src = build()
        sched = DirtyScheduler(g, get_executor(name))
        for keys, vals in batches:
            sched.push(src, DeltaBatch(keys, vals))
            sched.tick()
        views[name] = {int(k): float(v)
                       for k, v in sched.view_dict("out").items()}
    assert views["cpu"] == views["tpu"]


def test_device_minmax_retraction_within_buffer_matches_cpu():
    """Scalar min/max retraction is EXACT while the per-key candidate
    buffer covers the churn (SURVEY.md §7 hard part c, bounded form)."""
    def build():
        g = FlowGraph("mm")
        spec = Spec((), np.float32, key_space=32)
        src = g.source("src", spec)
        mx = g.reduce(src, "max", name="mx", spec=spec, candidates=8)
        g.sink(mx, "out")
        return g, src

    rng = np.random.default_rng(5)
    inserted = []
    ticks = []
    for t in range(4):
        rows = []
        for _ in range(20):
            if inserted and rng.random() < 0.4:
                k, v = inserted.pop(int(rng.integers(0, len(inserted))))
                rows.append((k, v, -1))
            else:
                k, v = int(rng.integers(0, 32)), round(
                    float(rng.normal()), 3)
                rows.append((k, v, 1))
                inserted.append((k, v))
        ticks.append(rows)
    views = {}
    for name in ("cpu", "tpu"):
        g, src = build()
        sched = DirtyScheduler(g, get_executor(name))
        for rows in ticks:
            sched.push(src, DeltaBatch(
                np.array([r[0] for r in rows]),
                np.array([r[1] for r in rows], np.float32),
                np.array([r[2] for r in rows])))
            sched.tick()
        views[name] = {int(k): round(float(v), 4)
                       for k, v in sched.view_dict("out").items()}
    assert views["cpu"] == views["tpu"]


def test_device_minmax_buffer_exhaustion_flags_error():
    """Retraction churn beyond the candidate buffer fails loudly (never a
    silently wrong extremum): candidates=1, evict one value, then hollow
    the buffer."""
    g = FlowGraph("mm")
    spec = Spec((), np.float32, key_space=32)
    src = g.source("src", spec)
    mx = g.reduce(src, "max", name="mx", spec=spec, candidates=1)
    g.sink(mx, "out")
    sched = DirtyScheduler(g, get_executor("tpu"))
    sched.push(src, DeltaBatch(np.array([1, 1]),
                               np.array([2.0, 1.0], np.float32)))
    sched.tick()    # buffer holds 2.0; 1.0 evicted to overflow
    sched.push(src, DeltaBatch(np.array([1]), np.array([2.0], np.float32),
                               -np.ones(1, np.int64)))
    # the tick itself fails loudly (scheduler checks the sticky flag), so
    # corrupt deltas never reach sink views
    with pytest.raises(RuntimeError, match="min/max"):
        sched.tick()
    with pytest.raises(RuntimeError, match="min/max"):
        sched.read_table(mx)


def test_checkpoint_restores_arena_occupancy(tmp_path):
    """The arena occupancy counter (rcount) and sticky overflow flag
    travel inside the checkpointed state pytree, so the in-program
    high-water compaction (join_core's lax.cond) resumes against the true
    occupancy after restore — there is no host-side tracker to
    reconstruct (removed with the mid-stream readback it required)."""
    ex = get_executor("tpu")
    sched, pg, web = _pagerank_sched(ex)
    join_ids = [n.id for n in pg.graph.nodes
                if n.kind == "op" and n.op.kind == "join"]
    before = {nid: int(np.max(np.asarray(ex.states[nid]["rcount"])))
              for nid in join_ids}
    assert any(v > 0 for v in before.values())
    save_checkpoint(sched, str(tmp_path / "ck"))

    ex2 = get_executor("tpu")
    sched2 = DirtyScheduler(pg.graph, ex2, max_loop_iters=500)
    load_checkpoint(sched2, str(tmp_path / "ck"))
    for nid in join_ids:
        got = int(np.max(np.asarray(ex2.states[nid]["rcount"])))
        assert got == before[nid]
        assert not bool(np.asarray(ex2.states[nid]["error"]))
    # post-restore churn still ticks through the restored arena
    sched2.push(pg.edges, web.churn(0.2))
    assert sched2.tick().quiesced


def test_device_rejects_oversized_weight_mass():
    """ADVICE r1: a single batch whose |weight| mass reaches 2**24 would
    be folded through an inexact float32 scatter — rejected at upload."""
    from reflow_tpu.delta import Spec
    from reflow_tpu.executors.device_delta import to_device

    spec = Spec((), np.float32, key_space=8)
    b = DeltaBatch(np.zeros(2, np.int64), np.ones(2, np.float32),
                   np.array([1 << 23, 1 << 23], np.int64))
    with pytest.raises(ValueError, match="weight mass"):
        to_device(b, spec)


def test_fixpoint_declines_loop_carried_arena():
    """ADVICE r1: a Join whose right (arena) input is produced inside the
    loop region appends rows every while_loop iteration, invisible to the
    host overflow tracker — analyze() must send such graphs to the
    host-driven loop, which tracks every pass."""
    from reflow_tpu.executors.fixpoint import analyze
    from reflow_tpu.executors.tpu import TpuExecutor

    K = 8
    uniq = Spec((), np.float32, key_space=K, unique=True)
    raw = Spec((), np.float32, key_space=K)
    g = FlowGraph("loop_arena")
    x = g.loop("x", uniq)
    left = g.source("left", uniq)
    j = g.join(left, x, merge=lambda k, a, b: a * b, spec=raw,
               arena_capacity=256, name="j")
    nxt = g.reduce(j, "sum", tol=1e-3, name="nxt", spec=uniq)
    g.close_loop(x, nxt)
    g.validate()
    assert analyze(g) is None


def test_fault_injection_exactly_once():
    """SURVEY.md §5 fault hook: drop/duplicate/reorder source delivery
    under at-least-once retransmission + idempotent push == exactly-once;
    the faulty run's view must equal the clean run's."""
    import numpy as np

    from reflow_tpu import DeltaBatch, DirtyScheduler
    from reflow_tpu.utils.faults import FaultyChannel
    from reflow_tpu.workloads import wordcount

    def batches(rng):
        out = []
        for i in range(30):
            n = int(rng.integers(3, 10))
            words = [f"w{int(x)}" for x in rng.integers(0, 40, n)]
            out.append((f"b{i}", wordcount.ingest_lines([" ".join(words)])))
        return out

    g1, src1, sink1 = wordcount.build_graph()
    clean = DirtyScheduler(g1)
    for bid, b in batches(np.random.default_rng(2)):
        clean.push(src1, b, batch_id=bid)
        clean.tick()

    g2, src2, sink2 = wordcount.build_graph()
    faulty = DirtyScheduler(g2)
    chan = FaultyChannel(faulty, src2, drop_p=0.4, dup_p=0.4,
                         reorder_window=4, seed=7)
    for bid, b in batches(np.random.default_rng(2)):
        chan.send(b, batch_id=bid)
        faulty.tick()
    chan.flush()
    faulty.tick()

    assert chan.stats["dropped"] > 0, "no faults were injected"
    assert chan.stats["duplicated"] > 0
    assert dict(clean.view(sink1.name)) == dict(faulty.view(sink2.name))


def test_config_from_env_and_scheduler():
    """SURVEY.md §5 config/flag system: the executor choice is the
    load-bearing flag; env mapping builds a working scheduler."""
    import numpy as np

    from reflow_tpu import DeltaBatch, FlowGraph, Spec
    from reflow_tpu.utils.config import ReflowConfig

    cfg = ReflowConfig.from_env({"REFLOW_EXECUTOR": "tpu",
                                 "REFLOW_MAX_LOOP_ITERS": "77"})
    assert cfg.executor == "tpu" and cfg.max_loop_iters == 77
    g = FlowGraph()
    src = g.source("s", Spec((), np.float32, key_space=8))
    g.sink(g.reduce(src, "sum"), "out")
    sched = cfg.scheduler(g)
    assert sched.max_loop_iters == 77
    assert sched.executor.name == "tpu"
    assert sched.executor.linear_fixpoint
    sched.push(src, DeltaBatch(np.array([2]), np.array([5.0], np.float32)))
    sched.tick()
    assert sched.view_dict("out") == {2: 5.0}

    sh = ReflowConfig.from_env({"REFLOW_EXECUTOR": "sharded",
                                "REFLOW_MESH_DEVICES": "8"})
    assert sh.make_executor().n == 8


def test_lazy_scalar_composition():
    """LazyScalar defers host ints, device scalars, arrays and thunks
    until int() — the mechanism keeping streaming ticks free of eager
    per-tick scalar dispatches."""
    import jax.numpy as jnp

    from reflow_tpu.scheduler import LazyScalar, lazy_add

    s = LazyScalar(3, jnp.asarray(4, jnp.int32))
    s = s + 5
    s = s + jnp.asarray([1, 2], jnp.int32)      # [K] stack sums
    s = s + (lambda: 10)                        # deferred host thunk
    assert int(s) == 3 + 4 + 5 + 3 + 10
    assert lazy_add(1, 2) == 3                  # pure-host stays plain int
    assert int(lazy_add(1, jnp.asarray(2, jnp.int32))) == 3


def test_tick_many_guards():
    """tick_many refuses pending push()es and non-source feeds."""
    import pytest

    from reflow_tpu.graph import GraphError
    from reflow_tpu.workloads import wordcount

    g, src, sink = wordcount.build_graph()
    sched = DirtyScheduler(g)
    sched.push(src, wordcount.ingest_lines(["a b"]))
    with pytest.raises(GraphError, match="pending"):
        sched.tick_many([{src: wordcount.ingest_lines(["c"])}])
    sched.tick()
    with pytest.raises(GraphError, match="sources"):
        sched.tick_many([{sink: wordcount.ingest_lines(["c"])}])
    # sink-bearing graph on the fallback path: sink deltas aggregate
    agg = sched.tick_many(
        [{src: wordcount.ingest_lines(["c d"])},
         {src: wordcount.ingest_lines(["d"])}]).block()
    assert agg.quiesced
    assert dict(sched.view(sink.name)) and agg.deltas_in == 3


def test_checkpoint_resume_buffered_minmax(tmp_path):
    """The candidate-buffer min/max state round-trips through
    checkpoint/resume — INCLUDING the monotone eviction latches
    (over_lo / over_maybe_pos): key 1 overflows its candidates=2 buffer
    before the save, so a post-restore retraction of the buffered best
    is only safe to refuse if the restored latches carry the eviction
    history. The restored scheduler must replay both the exact tick and
    the loud refusal identically."""
    g = FlowGraph("mm")
    spec = Spec((), np.float32, key_space=32)
    src = g.source("src", spec)
    mx = g.reduce(src, "max", name="mx", spec=spec, candidates=2)
    g.sink(mx, "out")
    sched = DirtyScheduler(g, get_executor("tpu"))
    # key 1: three distinct values -> 3.0 evicted (latches engage);
    # key 2: within buffer
    sched.push(src, DeltaBatch(np.array([1, 1, 1, 2]),
                               np.array([3.0, 5.0, 4.0, 7.0], np.float32)))
    sched.tick()
    save_checkpoint(sched, str(tmp_path / "mm"))

    # exact retraction (4.0 stays buffered, 5.0 remains the max)
    retract_ok = DeltaBatch(np.array([1]), np.array([4.0], np.float32),
                            -np.ones(1, np.int64))
    sched.push(src, retract_ok)
    sched.tick()
    after = {int(k): float(v) for k, v in sched.read_table(mx).items()}
    assert after == {1: 5.0, 2: 7.0}

    sched2 = DirtyScheduler(g, get_executor("tpu"))
    load_checkpoint(sched2, str(tmp_path / "mm"))
    sched2.push(src, retract_ok)
    sched2.tick()
    replay = {int(k): float(v) for k, v in sched2.read_table(mx).items()}
    assert replay == after

    # hollowing the buffer past the eviction watermark must refuse
    # loudly on the RESTORED scheduler too — only true if the latches
    # survived the round-trip
    sched2.push(src, DeltaBatch(np.array([1, 1]),
                                np.array([5.0, 4.0], np.float32),
                                -np.ones(2, np.int64)))
    with pytest.raises(RuntimeError, match="min/max"):
        sched2.tick()


def test_metrics_summary_over_streaming_history():
    """summarize must force streaming ticks' device-resident scalars
    (LazyScalar passes/delta_ops, deferred quiesced) before aggregating."""
    g, src, sink = _wordcountish()
    sched = DirtyScheduler(g, get_executor("tpu"))
    for i in range(3):
        sched.push(src, DeltaBatch(np.array([i]), np.ones(1, np.float32)))
        sched.tick(sync=False)
    s = summarize(sched.history)
    assert s.ticks == 3 and s.quiesced_all
    assert s.delta_ops > 0 and s.passes_mean >= 1.0


def test_minmax_latch_refresh_soak():
    """ROADMAP r3 #3 / VERDICT r3 #7: the over_lo/over_maybe_pos latches
    are one-way, so a long-running high-churn key eventually trips the
    loud error EVEN when the answer stays derivable from a replay.
    refresh_minmax resets the latches from a full-multiset replay: the
    same churn pattern that errors without refresh stays exact across a
    10k-tick soak with it."""
    import numpy as np

    from reflow_tpu import DeltaBatch, DirtyScheduler, FlowGraph, Spec
    from reflow_tpu.executors import get_executor

    spec = Spec((), np.float32, key_space=8)

    def build(candidates=2):
        g = FlowGraph("soak")
        src = g.source("s", spec)
        red = g.reduce(src, "min", name="m", candidates=candidates)
        return g, src, red

    def hollow_cycle(sched, src, lo):
        """insert {lo, lo+1, lo+2} (evicts lo+2 at candidates=2, latching
        the watermark), then retract lo and lo+1: the buffer hollows past
        the watermark -> unknowable from bounded state."""
        vals = np.array([lo, lo + 1.0, lo + 2.0], np.float32)
        sched.push(src, DeltaBatch(np.zeros(3, np.int64), vals,
                                   np.ones(3, np.int64)))
        sched.tick(sync=False)
        sched.push(src, DeltaBatch(np.zeros(2, np.int64), vals[:2],
                                   -np.ones(2, np.int64)))
        sched.tick(sync=False)
        return vals[2]   # the surviving row

    # without refresh: the very first hollow cycle must raise loudly
    g, src, red = build()
    sched = DirtyScheduler(g, get_executor("tpu"))
    hollow_cycle(sched, src, 100.0)
    with pytest.raises(RuntimeError, match="min/max"):
        sched.read_table(red)

    # refresh's real use: latches POLLUTED BY HISTORY over a multiset
    # that fits the buffer again. Epoch (4 ticks): insert {a,b,c} (c
    # evicts -> watermark latches), retract c (the evicted value!),
    # retract a, then refresh replays the true multiset {b} — resetting
    # the stale latches — and retract b empties the key CLEANLY.
    # Without the refresh the final retraction trips unknowable-state.
    def epoch(sched, src, base_v, refresh_red=None):
        vals = np.array([base_v, base_v + 1.0, base_v + 2.0], np.float32)
        k3 = np.zeros(3, np.int64)
        sched.push(src, DeltaBatch(k3, vals, np.ones(3, np.int64)))
        sched.tick(sync=False)
        for v in (vals[2], vals[0]):   # retract c (evicted), then a
            sched.push(src, DeltaBatch(np.zeros(1, np.int64),
                                       np.array([v], np.float32),
                                       -np.ones(1, np.int64)))
            sched.tick(sync=False)
        if refresh_red is not None:    # replay the full live multiset {b}
            sched.refresh_minmax(refresh_red, DeltaBatch(
                np.zeros(1, np.int64), vals[1:2], np.ones(1, np.int64)))
        sched.push(src, DeltaBatch(np.zeros(1, np.int64), vals[1:2],
                                   -np.ones(1, np.int64)))
        sched.tick(sync=False)

    # without refresh: the epoch's last retraction trips the error
    g, src, red = build()
    sched = DirtyScheduler(g, get_executor("tpu"))
    epoch(sched, src, 50.0)
    with pytest.raises(RuntimeError, match="min/max"):
        sched.read_table(red)

    # with refresh: 2500 epochs x 4 ticks = 10k ticks, exact throughout
    g, src, red = build()
    sched = DirtyScheduler(g, get_executor("tpu"))
    epochs = 2_500
    for i in range(epochs):
        epoch(sched, src, float(3 * i), refresh_red=red)
        if i % 500 == 499:
            assert sched.read_table(red) == {}   # sync point: no error
    assert sched.read_table(red) == {}


def test_minmax_latch_refresh_sharded():
    """The routed refresh path: same polluted-latch epoch pattern on the
    8-device mesh — replay rows reach their key's owner, latches reset
    per shard, the final retraction stays clean."""
    import numpy as np

    from reflow_tpu import DeltaBatch, DirtyScheduler, FlowGraph, Spec
    from reflow_tpu.parallel import make_mesh
    from reflow_tpu.parallel.shard import ShardedTpuExecutor

    spec = Spec((), np.float32, key_space=64)
    g = FlowGraph("soak_sh")
    src = g.source("s", spec)
    red = g.reduce(src, "min", name="m", candidates=2)
    sched = DirtyScheduler(g, ShardedTpuExecutor(make_mesh(8)))
    # spread the pattern across keys owned by different shards
    for i in range(6):
        k = np.full(3, 9 * i % 64, np.int64)
        vals = np.array([10.0 * i, 10.0 * i + 1, 10.0 * i + 2], np.float32)
        sched.push(src, DeltaBatch(k, vals, np.ones(3, np.int64)))
        sched.tick(sync=False)
        for v in (vals[2], vals[0]):
            sched.push(src, DeltaBatch(k[:1], np.array([v], np.float32),
                                       -np.ones(1, np.int64)))
            sched.tick(sync=False)
        sched.refresh_minmax(red, DeltaBatch(
            k[:1], vals[1:2], np.ones(1, np.int64)))
        sched.push(src, DeltaBatch(k[:1], vals[1:2],
                                   -np.ones(1, np.int64)))
        sched.tick(sync=False)
    assert sched.read_table(red) == {}


def test_forced_sync_counter():
    """Synchronous ticks / read_table on a device executor count as
    forced syncs (TickResult.forced_sync, MetricsSummary.forced_syncs,
    scheduler.forced_syncs)."""
    from reflow_tpu.utils import summarize as _summarize

    g, src, sink = _wordcountish()
    sched = DirtyScheduler(g, get_executor("tpu"))
    sched.push(src, DeltaBatch(np.array([1]), np.ones(1, np.float32)))
    r = sched.tick()              # sink graph: sync materialization
    assert r.forced_sync and sched.forced_syncs == 1

    sched.push(src, DeltaBatch(np.array([2]), np.ones(1, np.float32)))
    sched.tick()
    assert sched.forced_syncs == 2

    s = _summarize(sched.history)
    assert s.forced_syncs == 2

    # the CPU oracle never forces a device sync
    g2, src2, _ = _wordcountish()
    cp = DirtyScheduler(g2)
    cp.push(src2, DeltaBatch(np.array([1]), np.ones(1, np.float32)))
    assert not cp.tick().forced_sync and cp.forced_syncs == 0


def test_streaming_ticks_do_not_force_sync():
    """A sink-free streaming run (the pipelined fast path) must not flip
    forced_sync until its explicit sync point."""
    pg = pagerank.build_graph(N, tol=1e-5)
    sched = DirtyScheduler(pg.graph, get_executor("tpu"),
                           max_loop_iters=500)
    web = pagerank.WebGraph.random(N, E, seed=2)
    sched.push(pg.teleport, pagerank.teleport_batch(N))
    sched.push(pg.edges, web.initial_batch())
    r = sched.tick(sync=False)
    assert not r.forced_sync and sched.forced_syncs == 0
    sched.read_table(pg.new_rank)     # explicit sync point
    assert sched.forced_syncs == 1


def test_source_cursor_mint_and_resume():
    """SourceCursor mints deterministic '<source>@<seq>' ids (the
    SPMD-identical exactly-once scheme) and resume() re-derives the
    position from a restored dedup window, skipping foreign ids."""
    import numpy as np

    from reflow_tpu.delta import DeltaBatch, Spec
    from reflow_tpu.graph import FlowGraph
    from reflow_tpu.scheduler import DirtyScheduler, SourceCursor

    g = FlowGraph("cur")
    src = g.source("s", Spec((), np.float32, key_space=8))
    g.sink(g.reduce(src, "sum"), "out")
    sched = DirtyScheduler(g)
    cur = SourceCursor(src)
    b = DeltaBatch(np.array([1]), np.array([1.0], np.float32),
                   np.ones(1, np.int64))
    ids = [cur.next_id() for _ in range(3)]
    assert ids == ["s@0", "s@1", "s@2"]
    for bid in ids:
        assert sched.push(src, b, batch_id=bid)
    assert not sched.push(src, b, batch_id="s@1")   # replay dedups
    sched._seen_batch_ids["other@99"] = None        # foreign id ignored
    sched._seen_batch_ids["s@junk"] = None          # malformed ignored
    cur2 = SourceCursor.resume(sched, src)
    assert cur2.seq == 3
    assert cur2.next_id() == "s@3"


def test_checkpoint_meta_digest_order_sensitive():
    """The multi-controller save guard digests the dedup window IN
    ORDER: two processes that accepted the same ids in different orders
    have genuinely diverged (their eviction horizons differ)."""
    from reflow_tpu.utils.checkpoint import meta_digest

    a = meta_digest(5, ["s@0", "s@1"])
    b = meta_digest(5, ["s@1", "s@0"])
    c = meta_digest(6, ["s@0", "s@1"])
    assert a != b and a != c
    assert a == meta_digest(5, ["s@0", "s@1"])


def test_drain_rejects_unreachable_source():
    """drain() must refuse a probe source that cannot structurally reach
    a deferred loop's region (its ticks would report quiescence without
    running the region's program on fallback executors)."""
    import numpy as np
    import pytest

    from reflow_tpu.delta import Spec
    from reflow_tpu.graph import FlowGraph, GraphError
    from reflow_tpu.scheduler import DirtyScheduler
    from reflow_tpu.workloads import pagerank

    pg = pagerank.build_graph(32, defer_passes=2, arena_capacity=1024)
    # an unrelated source grafted onto the same graph, pre-validation
    other = pg.graph.source("unrelated", Spec((), np.float32, key_space=8))
    pg.graph.sink(pg.graph.reduce(other, "sum"), "o")
    sched = DirtyScheduler(pg.graph)
    with pytest.raises(GraphError, match="does not reach"):
        sched.drain(other)
