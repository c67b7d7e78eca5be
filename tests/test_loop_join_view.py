"""The loop join's key-sorted view (``arena.view_*``, ``join_core``'s
``_view_product``): a unique-left join under a loop takes δA ⋈ B_old
through the view where its keys' arena rows fit ``view_budget`` and sweeps
past it, chosen on the device. The probe's rows against the sweep's as a
multiset; the budget's edge; the view after an append, a compaction, a
checkpoint and a rebind against one built from scratch; the counters of
the two forms; and which joins keep a view at all. Small seeded sizes,
CPU."""

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from reflow_tpu import DirtyScheduler
from reflow_tpu.delta import Spec
from reflow_tpu.executors import arena, get_executor
from reflow_tpu.executors import join as jn
from reflow_tpu.executors import lowerings as lw
from reflow_tpu.executors.device_delta import DeviceDelta
from reflow_tpu.graph import FlowGraph
from reflow_tpu.workloads import pagerank, sssp
from reflow_tpu.workloads.sssp import _relax_merge

K, R, C = 128, 512, 128
T = arena.view_budget(K, R)


def _op(k=K, r=R):
    g = FlowGraph("j")
    e = g.source("edges", Spec((2,), np.float32, key_space=k))
    d = g.loop("dist", Spec((), np.float32, key_space=k, unique=True))
    return g.join(d, e, merge=_relax_merge, spec=e.spec, arena_capacity=r,
                  name="relax").op


OP = _op()


def _core(st, da, db, k=K, r=R, op=OP):
    return jn.join_core(op, k, r, np.float32, st, da, db, oshape=(2,))


CORE = jax.jit(_core, static_argnums=(3, 4, 5))


def _states(keys, vals, w, k=K, r=R, op=OP):
    """A plain (swept) and a viewed join state over the same arena rows,
    the view built from scratch."""
    left = Spec((), np.float32, key_space=k, unique=True)
    right = Spec((2,), np.float32, key_space=k)
    out = []
    for viewed in (False, True):
        st = jn.join_state(op, left, right,
                           "viewed" if viewed else "swept")
        n = len(keys)
        st["rkeys"] = st["rkeys"].at[:n].set(jnp.asarray(keys, jnp.int32))
        st["rvals"] = st["rvals"].at[:n].set(jnp.asarray(vals, jnp.float32))
        st["rw"] = st["rw"].at[:n].set(jnp.asarray(w, jnp.int32))
        st["rcount"] = jnp.asarray(n, jnp.int32)
        out.append(_fresh(st, k) if viewed else st)
    return out


def _fresh(st, k=K):
    return dict(st, view_order=arena.view_sort(st["rkeys"], st["rw"], k),
                view_deg=arena.view_count(st["rkeys"], st["rw"], k))


def _delta(rows, cap=C):
    """``rows``: (key, value, weight) triples, dealt over ``cap`` slots
    with dead ones between."""
    dk, dv, dw = (np.zeros(cap, t) for t in (np.int32, np.float32, np.int32))
    at = np.linspace(0, cap - 1, len(rows)).astype(int) if rows else []
    for i, (k, v, w) in zip(at, rows):
        dk[i], dv[i], dw[i] = k, v, w
    return DeviceDelta(jnp.asarray(dk), jnp.asarray(dv), jnp.asarray(dw))


def _live(d):
    m = np.asarray(d.weights) != 0
    rows = np.concatenate([np.asarray(d.keys)[m, None],
                           np.asarray(d.values).reshape(len(m), -1)[m],
                           np.asarray(d.weights)[m, None]], axis=1)
    return rows[np.lexsort(rows.T)]


def _arena(seed, n=150, k=K):
    rng = np.random.default_rng(seed)
    keys = rng.integers(0, k // 2, n)          # the upper keys: no row
    vals = np.stack([rng.integers(0, k, n), rng.integers(1, 64, n) / 64],
                    axis=1)
    return rng, keys, vals, np.ones(n, np.int64)


def _dead(rng, keys, vals, w):
    w[rng.random(len(w)) < 0.3] = 0
    return [(int(k), 0.5, 1) for k in range(0, 32, 3)]


def _duplicates(rng, keys, vals, w):
    keys[40:80], vals[40:80] = keys[:40], vals[:40]
    return [(int(k), 0.25, 1) for k in set(keys[:20].tolist())]


def _negative(rng, keys, vals, w):
    w[rng.random(len(w)) < 0.4] = -1
    w[:5] = 3
    return [(int(k), 1.5, 1) for k in range(0, 32, 2)]


def _unmatched(rng, keys, vals, w):
    return [(int(k), 0.75, 1) for k in range(K // 2 - 4, K // 2 + 12)]


def _retract_insert(rng, keys, vals, w):
    return [r for k in range(0, 24, 5)
            for r in ((k, 2.0, -1), (k, 1.0, 1))] + [(30, 9.0, -1)]


@pytest.mark.parametrize("case", [_dead, _duplicates, _negative, _unmatched,
                                  _retract_insert],
                         ids=lambda f: f.__name__.strip("_"))
@pytest.mark.parametrize("seed", [1, 2])
def test_the_probes_rows_are_the_sweeps_as_a_multiset(seed, case):
    """Seeded arenas with dead rows, duplicate ``(key, value)`` rows,
    rows of negative (and larger) weight, left keys with no arena row,
    and a left delta that holds a retraction and an insert of one key:
    the viewed join probes (``probes`` 1, ``sweeps`` 0), its live rows
    are the swept join's, its capacity too, and so is the state both
    leave (the left table folded, the arena as it was)."""
    rng, keys, vals, w = _arena(seed)
    rows = case(rng, keys, vals, w)
    plain, viewed = _states(keys, vals, w)
    da = _delta(rows)
    want, pst = CORE(plain, da, None, K, R, OP)
    got, vst = CORE(viewed, da, None, K, R, OP)
    assert got.capacity == want.capacity == 2 * R
    assert len(_live(want)) > 0
    np.testing.assert_array_equal(_live(got), _live(want))
    c = dict(zip(lw.OP_COUNTERS["join"], np.asarray(vst["counters"])))
    assert (c["probes"], c["sweeps"]) == (1, 0)
    assert c["pairs"] == c["late_pairs"] == len(_live(want))
    assert c["left_rows"] == len(rows) and c["swept_rows"] == 2 * T
    assert not bool(vst["error"])
    for name in pst:
        np.testing.assert_array_equal(np.asarray(pst[name]),
                                      np.asarray(vst[name]))


@pytest.mark.parametrize("over", [-1, 0, 1], ids=["under", "at", "over"])
def test_a_pass_past_the_pair_budget_sweeps_and_drops_nothing(over):
    """Left rows whose keys hold ``budget - 1``, ``budget`` and ``budget
    + 1`` arena rows between them: the first two probe, the last sweeps
    (chosen on the device: one program), latches no error, and every
    one gives the sweep's rows."""
    n = T + over
    # keys 0..7 share the rows; key 8 holds rows no left row asks for
    keys = np.concatenate([np.arange(n) % 8, np.full(20, 8)])
    vals = np.stack([np.arange(len(keys)) % K,
                     (1 + np.arange(len(keys))) / 512], axis=1)
    plain, viewed = _states(keys, vals, np.ones(len(keys), np.int64))
    da = _delta([(k, 0.5, 1) for k in range(8)] + [(40, 0.5, 1)])
    want, _ = CORE(plain, da, None, K, R, OP)
    got, st = CORE(viewed, da, None, K, R, OP)
    assert len(_live(want)) == n
    np.testing.assert_array_equal(_live(got), _live(want))
    c = dict(zip(lw.OP_COUNTERS["join"], np.asarray(st["counters"])))
    assert (c["probes"], c["sweeps"]) == ((0, 1) if over > 0 else (1, 0))
    assert c["swept_rows"] == (2 * R if over > 0 else 2 * T)
    assert c["pairs"] == c["late_pairs"] == n and c["left_rows"] == 9
    assert not bool(st["error"])


def test_a_key_both_halves_hold_takes_its_slots_once():
    """The budget is in slots: a key the left delta retracts and inserts
    lays its arena rows out once and pairs them with both halves, so
    ``budget`` rows of one such key probe (and emit twice the budget's
    live rows) and ``budget + 1`` sweep, as they do for one half."""
    for n, probes in ((T, 1), (T + 1, 0)):
        keys = np.zeros(n, np.int64)
        vals = np.stack([np.arange(n) % K, np.ones(n)], axis=1)
        plain, viewed = _states(keys, vals, np.ones(n, np.int64))
        for rows in ([(0, 1.0, 1)], [(0, 2.0, -1), (0, 1.0, 1)]):
            da = _delta(rows)
            want, _ = CORE(plain, da, None, K, R, OP)
            got, st = CORE(viewed, da, None, K, R, OP)
            np.testing.assert_array_equal(_live(got), _live(want))
            assert len(_live(want)) == n * len(rows)
            assert int(st["counters"][10]) == probes


# -- the view follows the arena ----------------------------------------------


def _same_view(st, k=K):
    fresh = _fresh(st, k)
    for name in ("view_order", "view_deg"):
        np.testing.assert_array_equal(np.asarray(st[name]),
                                      np.asarray(fresh[name]))


def test_the_view_after_appends_is_the_one_built_from_scratch():
    """Six appends from an empty arena (dead delta rows between the live
    ones, weights of both signs, a left delta in the same pass probing
    the view as it was before the append): ``view_order`` and
    ``view_deg`` are ``view_sort``'s and ``view_count``'s of the arena
    after every one, and the pass's rows are the plain join's."""
    plain, viewed = _states([], np.zeros((0, 2)), [])
    _same_view(viewed)
    rng = np.random.default_rng(3)
    for t in range(6):
        n = 24
        rows = [(int(rng.integers(0, K)), [int(rng.integers(0, K)),
                                           int(rng.integers(1, 9)) / 8],
                 int(rng.choice([-1, 1, 1, 2]))) for _ in range(n)]
        dk, dv, dw = (np.zeros(C, np.int32), np.zeros((C, 2), np.float32),
                      np.zeros(C, np.int32))
        at = rng.choice(C, n, replace=False)
        for i, (k, v, w) in zip(at, rows):
            dk[i], dv[i], dw[i] = k, v, w
        db = DeviceDelta(jnp.asarray(dk), jnp.asarray(dv), jnp.asarray(dw))
        da = _delta([(int(k), float(t), 1)
                     for k in rng.choice(K, 5, replace=False)]) \
            if t % 2 else None
        want, plain = CORE(plain, da, db, K, R, OP)
        got, viewed = CORE(viewed, da, db, K, R, OP)
        np.testing.assert_array_equal(_live(got), _live(want))
        _same_view(viewed)
        assert int(viewed["rcount"]) == int(plain["rcount"]) == 24 * (t + 1)
    assert int(viewed["view_deg"].sum()) == 144


def test_the_view_after_an_in_program_compaction_is_the_one_from_scratch():
    """An append that would cross the arena's end compacts it first,
    inside the program (inserts and their retractions cancel, rows move):
    the view is recounted and re-sorted behind it, and a probe after it
    pairs the compacted arena's rows."""
    k, r = 16, 64
    op = _op(k, r)
    plain, viewed = _states([], np.zeros((0, 2)), [], k, r, op)
    rng = np.random.default_rng(4)
    ins = [(int(rng.integers(0, k)), [float(i), 0.5], 1) for i in range(40)]
    steps = [ins[:20], [(a, v, -1) for a, v, _ in ins[:16]], ins[20:40],
             [(a, v, -1) for a, v, _ in ins[20:30]]]
    for rows in steps:
        db = DeviceDelta(
            jnp.asarray([x[0] for x in rows] + [0] * (32 - len(rows)),
                        jnp.int32),
            jnp.asarray([x[1] for x in rows] + [[0, 0]] * (32 - len(rows)),
                        jnp.float32),
            jnp.asarray([x[2] for x in rows] + [0] * (32 - len(rows)),
                        jnp.int32))
        _, plain = CORE(plain, None, db, k, r, op)
        _, viewed = CORE(viewed, None, db, k, r, op)
        _same_view(viewed, k)
    assert int(viewed["gen"]) >= 1 and not bool(viewed["error"])
    assert int(viewed["counters"][4]) == int(viewed["gen"])
    assert int(viewed["rcount"]) == int(plain["rcount"]) < 46
    deg = np.asarray(viewed["view_deg"])
    few = [a for a in range(k) if deg[:a + 1].sum() <= arena.view_budget(k, r)]
    assert len(few) >= 3 and deg[few].sum() > 0
    da = _delta([(a, 1.0, 1) for a in few], 32)
    want, _ = CORE(plain, da, None, k, r, op)
    got, st = CORE(viewed, da, None, k, r, op)
    np.testing.assert_array_equal(_live(got), _live(want))
    assert int(st["counters"][10]) == 1


def _sssp_run(n=48, seed=7, arena_rows=1 << 9):
    rng = np.random.default_rng(seed)
    m = 200
    src, dst = rng.integers(0, n, m), rng.integers(0, n, m)
    w = rng.integers(1, 64, m) / 64
    sg = sssp.build_graph(n, arena_capacity=arena_rows)
    sched = DirtyScheduler(sg.graph, get_executor("tpu"))
    sched.push(sg.edges, sssp.edge_batch(src[:150], dst[:150], w[:150]))
    sched.push(sg.seeds, sssp.seed_batch(0))
    assert sched.tick().quiesced
    return sg, sched, (src, dst, w)


def test_the_view_survives_a_checkpoint_round_trip_and_a_rebind(tmp_path):
    """The view is state: it travels with the arena through
    ``save_checkpoint`` / ``load_checkpoint`` into a freshly bound
    executor, where it is still the one built from scratch and the next
    tick's distances are Bellman-Ford's; a bind alone gives an empty
    arena's view."""
    from reflow_tpu.utils.checkpoint import load_checkpoint, save_checkpoint

    sg, sched, (src, dst, w) = _sssp_run()
    relax = next(n for n in sg.graph.nodes if n.name == "relax")
    _same_view(sched.executor.states[relax.id], 48)
    save_checkpoint(sched, str(tmp_path / "ck"))
    saved = jax.tree.map(np.asarray, sched.executor.states[relax.id])

    again = DirtyScheduler(sg.graph, get_executor("tpu"))
    bound = again.executor.states[relax.id]
    _same_view(bound, 48)
    assert int(bound["view_deg"].sum()) == 0
    load_checkpoint(again, str(tmp_path / "ck"))
    st = again.executor.states[relax.id]
    for name, x in saved.items():
        np.testing.assert_array_equal(np.asarray(st[name]), x)
    _same_view(st, 48)
    again.push(sg.edges, sssp.edge_batch(src[150:], dst[150:], w[150:]))
    assert again.tick().quiesced
    again.executor.check_errors()
    _same_view(again.executor.states[relax.id], 48)
    got = {int(k): float(v) for k, v in again.read_table(sg.best).items()}
    assert got == sssp.reference_distances(48, src, dst, w, 0)
    assert again.executor.op_counters()["relax"]["probes"] > 0


# -- the counters of the two forms -------------------------------------------


@pytest.mark.parametrize("fused", [True, False], ids=["fused", "host-loop"])
def test_the_two_forms_count_the_same_pairs(monkeypatch, fused):
    """The same ticks with the budget as it is and with none (every
    pass sweeps): ``pairs``, ``late_pairs``, ``left_rows`` and the
    distances are equal, ``sweeps + probes`` is the passes with a left
    delta either way, and ``swept_rows`` is ``2 x arena_capacity`` a
    sweep and twice the budget a probe."""
    seen = {}
    n, arena_rows = 256, 1 << 9
    for form in ("view", "sweep"):
        if form == "sweep":
            monkeypatch.setattr(jn, "view_budget", lambda k, r: 0)
        rng = np.random.default_rng(9)
        m = 240
        src, dst = rng.integers(0, n, m), rng.integers(0, n, m)
        w = rng.integers(1, 64, m) / 64
        sg = sssp.build_graph(n, arena_capacity=arena_rows)
        sched = DirtyScheduler(sg.graph,
                               get_executor("tpu", fixpoint=fused))
        sched.push(sg.seeds, sssp.seed_batch(0))
        passes = []
        for lo in range(0, m, 60):
            sched.push(sg.edges, sssp.edge_batch(
                src[lo:lo + 60], dst[lo:lo + 60], w[lo:lo + 60]))
            r = sched.tick()
            assert r.quiesced
            passes.append(int(r.passes))
        sched.executor.check_errors()
        c = sched.executor.op_counters()["relax"]
        seen[form] = (c, passes, sched.read_table(sg.best))
        assert c["sweeps"] + c["probes"] == sum(passes) - len(passes)
    (cv, pv, dv), (cs, ps, ds) = seen["view"], seen["sweep"]
    assert pv == ps and dv == ds
    for name in ("pairs", "late_pairs", "left_rows", "arena_rows",
                 "retracted"):
        assert cv[name] == cs[name], name
    assert cv["pairs"] > cv["late_pairs"] > 0
    budget = min(n, arena_rows)
    assert cv["sweeps"] == 0 and cv["probes"] > 0
    assert cv["swept_rows"] == cv["probes"] * 2 * budget
    # with no budget only a pass whose left rows pair with nothing probes
    assert cs["sweeps"] > 0
    assert cs["swept_rows"] == cs["sweeps"] * 2 * arena_rows


# -- who keeps a view ----------------------------------------------------------


def _sorts(jaxpr, into=None):
    into = [] if into is None else into
    for eqn in jaxpr.eqns:
        if eqn.primitive.name == "sort":
            into.append(eqn.invars[0].aval.shape[0])
        for sub in jax.core.jaxprs_in_params(eqn.params):
            _sorts(sub, into)
    return into


def _join_states(which):
    """-> (scheduler, join node) of a bound graph whose unique-left join
    is loop-free, declared linear, or on the sharded executor."""
    if which == "loop-free":
        g = FlowGraph("flat")
        a = g.source("a", Spec((), np.float32, key_space=32, unique=True))
        b = g.source("b", Spec((2,), np.float32, key_space=32))
        j = g.join(a, b, merge=_relax_merge, spec=b.spec,
                   arena_capacity=128, name="j")
        g.sink(j, "out")
        ex = get_executor("tpu")
    elif which == "linear-left":
        pg = pagerank.build_graph(32, arena_capacity=128)
        g = pg.graph
        j = next(n for n in g.nodes
                 if n.kind == "op" and n.op.kind == "join")
        assert j.op.linear_left
        ex = get_executor("tpu")
    else:
        from reflow_tpu.parallel.mesh import make_mesh
        from reflow_tpu.parallel.shard import ShardedTpuExecutor

        sg = sssp.build_graph(32, arena_capacity=1024)
        g = sg.graph
        j = next(n for n in g.nodes if n.name == "relax")
        ex = ShardedTpuExecutor(make_mesh(8))
    return DirtyScheduler(g, ex), j


@pytest.mark.parametrize("which", ["loop-free", "linear-left", "sharded"])
def test_only_a_loops_join_keeps_a_view(monkeypatch, which):
    """A loop-free unique-left join keeps the chained index (and every
    counter of ``OP_COUNTERS["join"]``, ``probes`` at 0), a
    declared-linear left and the sharded executor's join the plain
    arena: none has a view leaf, none of their lowerings touches
    ``arena.view_*`` (each raises here), and none sorts anything but a
    compaction's rows."""
    sched, j = _join_states(which)
    ex = sched.executor
    st = ex.states[j.id]
    assert not any(name.startswith("view_") for name in st)
    assert jn.layout_of(st) == ("indexed" if which == "loop-free"
                                else "swept")
    if which == "loop-free":
        assert st["counters"].shape == (len(lw.OP_COUNTERS["join"]),)
        assert ex.counter_names()["j"] == lw.OP_COUNTERS["join"]
    else:
        assert "counters" not in st

    def never(*a, **k):
        raise AssertionError("the view, on a join that keeps none")

    for name in ("view_state", "view_budget", "view_sort", "view_count",
                 "view_probe"):
        monkeypatch.setattr(arena, name, never)
        monkeypatch.setattr(jn, name, never)
    if which == "sharded":
        g = ex.graph
        edges = next(n for n in g.nodes if n.name == "edges")
        seeds = next(n for n in g.nodes if n.name == "seeds")
        sched.push(edges, sssp.edge_batch([0, 1], [1, 2], [0.5, 0.25]))
        sched.push(seeds, sssp.seed_batch(0))
        assert sched.tick().quiesced
        assert sched.read_table(next(n for n in g.nodes
                                     if n.name == "best")) == {
            0: 0.0, 1: 0.5, 2: 0.75}
        return
    k = j.inputs[0].spec.key_space
    r = j.op.arena_capacity
    da = DeviceDelta.empty(j.inputs[0].spec, 64)
    db = DeviceDelta.empty(j.inputs[1].spec, 64)

    def core(s, a, b):
        return jn.join_core(j.op, k, r, j.spec.value_dtype, s, a, b,
                            oshape=tuple(j.spec.value_shape))

    jaxpr = jax.make_jaxpr(core)(st, da, db).jaxpr
    # the indexed append sorts its delta; the plain one only compacts
    assert set(_sorts(jaxpr)) <= ({64} if which == "loop-free" else {r})


def test_a_loops_join_keeps_the_view_and_eleven_counters():
    sg = sssp.build_graph(32, arena_capacity=128)
    ex = get_executor("tpu")
    DirtyScheduler(sg.graph, ex)
    relax = next(n for n in sg.graph.nodes if n.name == "relax")
    st = ex.states[relax.id]
    assert st["view_order"].shape == (128,) and st["view_deg"].shape == (32,)
    assert jn.layout_of(st) == "viewed"
    assert ex.counter_names()["relax"] == lw.OP_COUNTERS["join"]
    assert lw.OP_COUNTERS["join"][10] == "probes"
    assert [lw.OP_COUNTERS["join"].index(n) for n in (
        "pairs", "late_pairs", "sweeps", "swept_rows", "left_rows")] == [
            0, 1, 6, 7, 8]
