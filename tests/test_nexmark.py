"""NEXmark Q3 / Q4 (``workloads/nexmark.py``) on the device executor and
on the CPU oracle against the plain NumPy reference of the benchmark's
configuration (``benchmarks/configs/nexmark-q3q4.py``: its generator,
its reference, its comparison), in every arrival order the served path
can produce and as the records are sent (the source's widths, as rows of
100 bytes); the arena index against the dense product it replaces, and
the executor making room in it between ticks; the bfloat16 control.
Small seeded sizes, CPU."""

import importlib.util
import json
import os
import re
import sys

import numpy as np
import pytest

from reflow_tpu import DirtyScheduler
from reflow_tpu.delta import DeltaBatch, Spec
from reflow_tpu.executors import get_executor
from reflow_tpu.graph import FlowGraph
from reflow_tpu.workloads import nexmark

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
BENCH = os.path.join(ROOT, "benchmarks")


def _config():
    """The benchmark configuration's module and its ``tiny`` sizes."""
    if BENCH not in sys.path:
        sys.path.insert(0, BENCH)
    path = os.path.join(BENCH, "configs", "nexmark-q3q4")
    spec = importlib.util.spec_from_file_location("nexmark_q3q4",
                                                  path + ".py")
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    with open(path + ".json") as f:
        cfg = json.load(f)
    cfg.update(cfg.pop("tiny"))
    return mod, cfg


MOD, CFG = _config()


def _cfg(**over):
    return dict(CFG, **over)


def _build(cfg):
    return nexmark.build_graph(
        persons=cfg["persons"], auctions=cfg["auctions"],
        bid_arena=cfg["bid_arena"], q3_arena=cfg["q3_arena"],
        events=cfg["events_capacity"], candidates=cfg["candidates"],
        product_slack=cfg["product_slack"])


def _reference(cfg, seed, ranges):
    stream = MOD.Stream(cfg, seed, 1)
    ref = MOD.Reference(stream)
    ref.ranges = list(ranges)
    return ref


def _served(dep, sched):
    """The views in the reference's form, from the three Reduce tables
    the sink-free graph serves (as the benchmark's ``read_state``
    does)."""
    q3 = np.array([[k, v[0], v[1], v[2]] for k, v in
                   sched.read_table(dep.q3).items()],
                  np.int64).reshape(-1, 4)
    final = np.array([[k, v[0]] for k, v in
                      sched.read_table(dep.q4_max).items()],
                     np.int64).reshape(-1, 2)
    avg = np.full(MOD.CATEGORIES, np.nan)
    for k, v in sched.read_table(dep.q4).items():
        avg[int(k)] = v
    return {"q3": q3, "final": final, "avg": avg, "errors": 0,
            "maybe_pos": None, "has": None}


def _run(executor, cfg, seed, batches):
    """Push ``batches`` (lists of (first, rows) applied in one tick
    each) and return the served views."""
    dep = _build(cfg)
    sched = DirtyScheduler(dep.graph, get_executor(executor))
    for tick in batches:
        for first, rows in tick:
            sched.push(dep.events, nexmark.events_batch(rows, first))
        sched.tick()
    if executor == "tpu":
        sched.executor.check_errors()
    return dep, sched, _served(dep, sched)


def _check(cfg, seed, got, ranges):
    want = _reference(cfg, seed, ranges).expected()
    checks = MOD.compare(cfg, got, want)
    assert all(c.ok for c in checks), checks
    assert len(want["q3"]) > 0 and len(want["final"]) > 0


def _stream_order(cfg, seed, n_events, per_tick):
    ev = MOD.events(cfg, seed, 0, n_events)
    return [[(a, ev[a:a + per_tick])]
            for a in range(0, n_events, per_tick)]


def _adversarial(cfg, seed, n_events, per_tick):
    """Every bid before its auction, every auction before its seller:
    all the bids first, then the auctions, then the people."""
    ev = MOD.events(cfg, seed, 0, n_events)
    num = np.arange(n_events)
    out = []
    for kind in (MOD.BID, MOD.AUCTION, MOD.PERSON):
        rows, at = ev[ev[:, 0] == kind], num[ev[:, 0] == kind]
        for a in range(0, len(rows), per_tick):
            # keys are event numbers: keep each row's own
            out.append([(int(at[a]), rows[a:a + per_tick])])
    return out


def _push_rows(sched, dep, rows, keys):
    sched.push(dep.events, DeltaBatch(
        np.asarray(keys, np.int64),
        nexmark.events_batch(np.asarray(rows, np.int32)).values,
        np.ones(len(rows), np.int64)))


ORDERS = {"stream": _stream_order, "adversarial": _adversarial}


@pytest.mark.parametrize("executor", ["cpu", "tpu"])
@pytest.mark.parametrize("order", sorted(ORDERS))
def test_views_equal_the_reference(executor, order):
    # the adversarial order's 180 auctions find all 2 760 bids waiting
    # in one tick: the late product's budget has to hold them
    cfg, seed, n = _cfg(product_slack=16), 2**31 + 11, 3000
    ticks = ORDERS[order](cfg, seed, n, 500)
    # events_batch keys a run of rows by consecutive numbers; the
    # adversarial order's rows are not consecutive, and nothing reads a
    # source row's key: the views must still be the reference's
    _dep, _sched, got = _run(executor, cfg, seed, ticks)
    _check(cfg, seed, got, [(0, n)])


@pytest.mark.parametrize("executor", ["cpu", "tpu"])
def test_records_at_the_source_widths_give_the_same_views(executor):
    """The stream as it is sent: every record 200 / 500 / 100 bytes as
    2 / 5 / 1 rows of 100, the opaque words and the continuation rows
    dropped at the filters."""
    cfg, seed, n, per = _cfg(), 2**31 + 3, 3000, 500
    assert nexmark.COLS * 4 == 100 and MOD.COLS == nexmark.COLS
    assert {k: int(v) for k, v in enumerate(MOD.RECORD_ROWS)} == \
        nexmark.RECORD_ROWS
    dep = _build(cfg)
    sched = DirtyScheduler(dep.graph, get_executor(executor))
    sent = 0
    for a in range(0, n, per):
        rows, keys = MOD.records(cfg, seed, a, per)
        assert rows.shape == (per // 50 * 63, 25) and rows.dtype == np.int32
        first = rows[rows[:, 0] != MOD.CONT]
        assert np.array_equal(first[:, :8], MOD.events(cfg, seed, a, per))
        assert np.array_equal(np.unique(keys), np.arange(a, a + per))
        sent += rows.nbytes
        sched.push(dep.events, DeltaBatch(keys, rows,
                                          np.ones(len(rows), np.int64)))
        sched.tick()
    assert sent == n // 50 * 6300
    if executor == "tpu":
        sched.executor.check_errors()
    _check(cfg, seed, _served(dep, sched), [(0, n)])


def test_lanes_interleaved_give_the_same_views():
    """Blocks dealt to two lanes, the second lane running three blocks
    ahead of the first: bids wait in the arena for auctions of blocks
    that come later, and the late product finds them."""
    cfg, seed, blk = _cfg(), 77, 256
    blocks = [(b * blk, MOD.events(cfg, seed, b * blk, blk))
              for b in range(12)]
    order = [1, 3, 5, 0, 7, 2, 9, 4, 11, 6, 8, 10]
    dep, sched, got = _run("tpu", cfg, seed,
                           [[blocks[b]] for b in order])
    _check(cfg, seed, got, [(0, 12 * blk)])
    counters = sched.executor.op_counters()
    assert counters["q4_join"]["late_pairs"] > 0
    assert counters["q3_join"]["late_pairs"] > 0
    assert counters["q4_join"]["pairs"] >= counters["q4_join"]["late_pairs"]
    assert counters["q4_join"]["arena_rows"] == int(
        np.count_nonzero(np.concatenate(
            [r for _, r in blocks])[:, 0] == MOD.BID))
    assert counters["q4_join"]["index_rebuilds"] == 0
    # a late auction walks one segment a block its bids arrived in
    assert counters["q4_join"]["probe_steps"] >= 2
    assert counters["q4_max"]["touched"] > 0


def test_one_auction_takes_more_bids_than_candidates_in_a_tick():
    """A hot auction sends hundreds of distinct prices through one slot
    of the maximum's buffer in one tick: the worse ones are pushed out
    (counted), the maximum is exact."""
    cfg, seed, n = _cfg(candidates=4), 5, 4000
    ev = MOD.events(cfg, seed, 0, n)
    bids = ev[ev[:, 0] == MOD.BID]
    ids, counts = np.unique(bids[:, 1], return_counts=True)
    assert counts.max() > 10 * cfg["candidates"]
    dep, sched, got = _run("tpu", cfg, seed, [[(0, ev)]])
    _check(cfg, seed, got, [(0, n)])
    assert sched.executor.op_counters()["q4_max"]["evicted"] > 0


@pytest.mark.parametrize("executor", ["cpu", "tpu"])
def test_prices_above_2_to_the_24_are_exact(executor):
    """Maxima that differ only below float32's resolution."""
    cfg = _cfg()
    dep = _build(cfg)
    sched = DirtyScheduler(dep.graph, get_executor(executor))
    big = (1 << 26) + 1                       # not a float32
    rows = [[MOD.AUCTION, 1000, 1000, 10, 100, 200, 5, 9],
            [MOD.BID, 1000, 1001, big - 1, 5, 0, 0, 0],
            [MOD.BID, 1000, 1001, big, 6, 0, 0, 0],
            [MOD.BID, 1000, 1001, big - 2, 7, 0, 0, 0],
            [MOD.BID, 1000, 1001, big + 5, 10, 0, 0, 0]]   # too late
    _push_rows(sched, dep, rows, range(len(rows)))
    sched.tick()
    table = sched.read_table(dep.q4_max)
    assert int(table[1000][0]) == big and int(table[1000][1]) == 10
    assert int(np.float32(big)) != big


# -- the index against the product it replaces ------------------------------


def _join_pair(K, R, slack=8):
    """One unique-left join, as the executor builds it indexed and as it
    was (dense)."""
    import jax.numpy as jnp

    from reflow_tpu.executors.join import join_state

    g = FlowGraph("j")
    left = g.source("l", Spec((2,), np.int32, key_space=K, unique=True))
    right = g.source("r", Spec((), np.int32, key_space=K))
    j = g.join(left, right,
               merge=lambda k, va, vb: jnp.concatenate(
                   [va, vb[:, None]], axis=-1),
               spec=Spec((3,), np.int32, key_space=K), arena_capacity=R,
               product_slack=slack)
    states = [join_state(j.op, left.spec, right.spec, layout)
              for layout in ("indexed", "swept")]
    return j, states


def _delta(rng, K, C, n, vshape, w_choices=(1,)):
    from reflow_tpu.executors.device_delta import DeviceDelta

    import jax.numpy as jnp

    keys = np.zeros(C, np.int32)
    vals = np.zeros((C,) + vshape, np.int32)
    w = np.zeros(C, np.int32)
    at = rng.choice(C, n, replace=False)
    keys[at] = rng.integers(0, K, n)
    vals[at] = rng.integers(1, 1000, (n,) + vshape)
    w[at] = rng.choice(w_choices, n)
    return DeviceDelta(jnp.asarray(keys), jnp.asarray(vals), jnp.asarray(w))


def _multiset(d):
    from collections import Counter

    out = Counter()
    for k, v, w in zip(np.asarray(d.keys), np.asarray(d.values),
                       np.asarray(d.weights)):
        if w:
            out[(int(k), tuple(int(x) for x in v))] += int(w)
    return Counter({k: v for k, v in out.items() if v})


@pytest.mark.parametrize("seed", [1, 2, 3])
def test_indexed_product_equals_the_dense_one(seed):
    """Random deltas on both sides, tick after tick, across an arena
    that fills, is compacted (retractions cancel their inserts) and
    re-indexed between ticks as the executor does it: both lowerings
    emit the same multiset every tick and hold the same arena as a
    multiset."""
    import jax

    from reflow_tpu.executors.join import join_core, join_reindex

    K, R, C = 16, 192, 32
    j, (ist, dst) = _join_pair(K, R)
    rng = np.random.default_rng(seed)
    step = jax.jit(lambda st, da, db: join_core(
        j.op, K, R, np.int32, st, da, db, oshape=(3,)))
    import jax.numpy as jnp

    live = []                                 # right rows to retract
    for tick in range(14):
        db = _delta(rng, K, C, 20, ())
        if tick % 3 == 2 and live:
            # retract what an earlier tick inserted: compaction fodder
            old = live.pop(0)
            db = type(db)(old.keys, old.values, -old.weights)
        else:
            live.append(db)
        # unique left: a key that is held is retracted (its held value,
        # weight -1), one that is not is inserted
        da = _delta(rng, K, C, 6, (2,))
        keys, w = np.asarray(da.keys), np.array(da.weights)
        vals = np.array(da.values)
        held, lval = np.asarray(ist["lw"]) > 0, np.asarray(ist["lval"])
        seen = set()
        for i in np.flatnonzero(w):
            k = int(keys[i])
            if k in seen:
                w[i] = 0
            elif held[k]:
                w[i], vals[i] = -1, lval[k]
            seen.add(k)
        da = type(da)(da.keys, jnp.asarray(vals), jnp.asarray(w))
        if int(ist["rcount"]) + C > R:
            ist = jax.jit(join_reindex)(ist)
        out_i, ist = step(ist, da, db)
        out_d, dst = step(dst, da, db)
        assert _multiset(out_i) == _multiset(out_d), tick
        assert not bool(ist["error"]) and not bool(dst["error"])
    assert int(ist["counters"][3]) >= 1       # re-indexed at least once
    assert int(ist["counters"][4]) >= 1       # ... by a compaction
    assert int(ist["counters"][1]) > 0        # late pairs were found
    assert int(ist["counters"][5]) > 0        # ... by walking chains
    from reflow_tpu.executors.device_delta import DeviceDelta
    arena = lambda st: _multiset(DeviceDelta(          # noqa: E731
        st["rkeys"], st["rvals"][:, None], st["rw"]))
    assert arena(ist) == arena(dst)
    # the index describes the arena: every key's chain holds its rows
    rk, n = np.asarray(ist["rkeys"]), int(ist["rcount"])
    deg = np.bincount(rk[:n], minlength=K)
    assert np.array_equal(np.asarray(ist["deg"]), deg)


# -- the append: one block at ``rcount`` --------------------------------------


def _scatter_append(state, keys, vals, w):
    """``arena.index_append`` as it was before the block write: every
    column, keyed or not, by a scatter of the delta's capacity (seven a
    join). The reference the block append is held to, leaf for leaf."""
    import jax.numpy as jnp

    from reflow_tpu.executors.arena import _segments

    R, K, C = state["rkeys"].shape[0], state["head"].shape[0], keys.shape[0]
    live = w != 0
    n_app = jnp.sum(live.astype(jnp.int32))
    skey = jnp.where(live, jnp.clip(keys, 0, K - 1), K)
    order = jnp.argsort(skey, stable=True)
    sk = skey[order]
    first, seg_len = _segments(sk, n_app)
    i = jnp.arange(C, dtype=jnp.int32)
    row = state["rcount"] + i
    pos = jnp.where(i < n_app, row, R)
    fkey = jnp.where(first, sk, K)
    out = dict(state)
    out["rkeys"] = state["rkeys"].at[pos].set(sk, mode="drop")
    out["rvals"] = state["rvals"].at[pos].set(vals[order], mode="drop")
    out["rw"] = state["rw"].at[pos].set(w[order], mode="drop")
    out["rcount"] = state["rcount"] + n_app
    out["seg_len"] = state["seg_len"].at[pos].set(seg_len, mode="drop")
    out["seg_prev"] = state["seg_prev"].at[pos].set(
        jnp.where(first, state["head"][jnp.minimum(sk, K - 1)], -1),
        mode="drop")
    out["head"] = state["head"].at[fkey].set(row, mode="drop")
    out["deg"] = state["deg"].at[fkey].add(seg_len, mode="drop")
    return out, out["rcount"] > R


def _same_leaves(a, b):
    assert a.keys() == b.keys()
    for name in a:
        assert np.array_equal(np.asarray(a[name]), np.asarray(b[name])), name


def _appended(st, deltas):
    """``deltas`` through both appends from the state ``st``, compared
    after every tick: -> (state, overflow of the last tick)."""
    import jax

    from reflow_tpu.executors.arena import index_append

    block, scatter = jax.jit(index_append), jax.jit(_scatter_append)
    ref, ovf = st, False
    for d in deltas:
        st, ovf = block(st, d.keys, d.values, d.weights)
        ref, rovf = scatter(ref, d.keys, d.values, d.weights)
        _same_leaves(st, ref)
        assert bool(ovf) == bool(rovf)
    return st, bool(ovf)


def test_the_append_lowers_to_block_writes_and_two_keyed_scatters():
    """The mechanism in force: of what ``index_append`` writes only
    ``head`` and ``deg`` are keyed, so only they are scatters; the five
    row columns are one ``dynamic_update_slice`` each."""
    import jax

    from reflow_tpu.executors.arena import index_append

    _, (ist, _dst) = _join_pair(16, 192)
    d = _delta(np.random.default_rng(0), 16, 32, 20, ())
    text = jax.jit(index_append).lower(
        ist, d.keys, d.values, d.weights).as_text()
    assert text.count('"stablehlo.scatter"(') == 2
    assert len(re.findall(r"= stablehlo\.dynamic_update_slice ", text)) == 5
    assert "stablehlo.case" not in text and "stablehlo.if" not in text


@pytest.mark.parametrize("live_rows", [0, 1, 20, 32],
                         ids=["none", "one", "interleaved", "all"])
@pytest.mark.parametrize("seed", [1, 2])
def test_block_append_equals_the_scatter_append(seed, live_rows):
    """Six ticks in a row from an empty arena, dead rows interleaved
    with the live ones (or no live row, or no dead one), weights of both
    signs: every leaf of the state is the scatter append's after every
    tick, the rows a block leaves behind its live ones among them."""
    K, R, C = 16, 256, 32
    _, (ist, _dst) = _join_pair(K, R)
    rng = np.random.default_rng(seed)
    deltas = [_delta(rng, K, C, live_rows, (), w_choices=(1, -1, 2))
              for _ in range(6)]
    # one tick of another size between them, as a late left-less tick is
    deltas.insert(3, _delta(rng, K, C, 7, ()))
    st, ovf = _appended(ist, deltas)
    n = int(st["rcount"])
    assert n == 6 * live_rows + 7 and not ovf
    # behind the last live row the arena reads as it was made: dead
    assert not np.asarray(st["rw"])[n:].any()
    assert not np.asarray(st["seg_len"])[n:].any()
    assert (np.asarray(st["seg_prev"])[n:] == -1).all()
    assert not np.asarray(st["rkeys"])[n:].any()
    assert not np.asarray(st["rvals"])[n:].any()


@pytest.mark.parametrize("case", ["fits", "past_the_end", "full",
                                  "delta_wider_than_the_arena"])
def test_block_append_at_the_arenas_end(case):
    """``rcount + C > R``: the block cannot start at ``rcount``
    (``dynamic_update_slice`` would clamp it back over live rows). Live
    rows that fit all land and nothing is reported; those past the end
    are dropped and reported; no row before ``rcount`` changes."""
    K, R, C = 16, 80, 32
    _, (ist, _dst) = _join_pair(K, R)
    rng = np.random.default_rng(5)
    # 64 rows in: rcount + C = 96 > R = 80
    st, ovf = _appended(ist, [_delta(rng, K, C, 32, ()) for _ in range(2)])
    assert int(st["rcount"]) == 64 and not ovf
    before = {k: np.array(v) for k, v in st.items()}
    if case == "delta_wider_than_the_arena":
        # the executor refuses such a graph; the function still drops
        # exactly the rows past the end
        last = _delta(rng, K, 96, 40, ())
    else:
        last = _delta(rng, K, C, {"fits": 16, "past_the_end": 25,
                                  "full": 32}[case], ())
    n_app = int(np.count_nonzero(np.asarray(last.weights)))
    st, ovf = _appended(st, [last])
    assert int(st["rcount"]) == 64 + n_app
    assert ovf == (64 + n_app > R)
    for name in ("rkeys", "rvals", "rw", "seg_len", "seg_prev"):
        assert np.array_equal(np.asarray(st[name])[:64], before[name][:64])
    landed = min(n_app, R - 64)
    order = np.argsort(np.where(np.asarray(last.weights) != 0,
                                np.asarray(last.keys), K), kind="stable")
    assert np.array_equal(np.asarray(st["rvals"])[64:64 + landed],
                          np.asarray(last.values)[order][:landed])
    assert (np.asarray(st["rw"])[64:64 + landed] != 0).all()
    assert not np.asarray(st["rw"])[64 + landed:].any()
    # and once more on the arena that is over its end: nothing lands
    st2, ovf2 = _appended(st, [_delta(rng, K, C, 32, ())])
    if 64 + n_app >= R:
        assert ovf2
        for name in ("rkeys", "rvals", "rw", "seg_len", "seg_prev"):
            assert np.array_equal(np.asarray(st2[name]),
                                  np.asarray(st[name])), name


@pytest.mark.parametrize("seed", [4, 5])
def test_a_blocks_dead_tail_does_not_leak_through_a_reindex(seed):
    """Append (dead rows in every block) -> ``join_reindex`` -> append ->
    probe: the late product of a left delta over every key is the dense
    join's, and so is the arena; the rows behind a block's live ones
    were dead before the compaction and are after it."""
    import jax
    import jax.numpy as jnp

    from reflow_tpu.executors.device_delta import DeviceDelta
    from reflow_tpu.executors.join import join_core, join_reindex

    K, R, C = 16, 192, 32
    j, (ist, dst) = _join_pair(K, R)
    rng = np.random.default_rng(seed)
    step = jax.jit(lambda st, da, db: join_core(
        j.op, K, R, np.int32, st, da, db, oshape=(3,)))
    sent = []
    for tick in range(6):
        db = _delta(rng, K, C, 9 + tick, ())
        if tick == 4:                        # cancels tick 1's rows
            db = DeviceDelta(sent[1].keys, sent[1].values, -sent[1].weights)
        sent.append(db)
        if tick in (2, 5):
            ist = jax.jit(join_reindex)(ist)
            n = int(ist["rcount"])
            assert not np.asarray(ist["rw"])[n:].any()
            assert not np.asarray(ist["seg_len"])[n:].any()
        _, ist = step(ist, None, db)
        _, dst = step(dst, None, db)
    assert int(ist["counters"][3]) == 2
    # tick 1's rows and their retractions went with the second compaction
    assert int(ist["rcount"]) < int(dst["rcount"])
    da = DeviceDelta(jnp.arange(C, dtype=jnp.int32) % K,
                     jnp.asarray(rng.integers(1, 99, (C, 2)), jnp.int32),
                     jnp.asarray((np.arange(C) < K).astype(np.int32)))
    out_i, ist = step(ist, da, None)
    out_d, dst = step(dst, da, None)
    assert _multiset(out_i) == _multiset(out_d) and _multiset(out_i)
    assert not bool(ist["error"]) and not bool(dst["error"])
    arena = lambda st: _multiset(DeviceDelta(          # noqa: E731
        st["rkeys"], st["rvals"][:, None], st["rw"]))
    assert arena(ist) == arena(dst)
    n = int(ist["rcount"])
    assert np.array_equal(np.asarray(ist["deg"]),
                          np.bincount(np.asarray(ist["rkeys"])[:n],
                                      minlength=K))


@pytest.mark.parametrize("ticks_a_window", [1, 4])
def test_executor_makes_room_between_ticks(ticks_a_window):
    """An arena far smaller than what is sent through it, kept from
    overflowing by retractions: the executor's bound of its rows
    reaches the end again and again, it reads the true count, compacts
    and re-indexes between ticks (never in one), and the served table
    stays the CPU oracle's, tick by tick and in fused windows."""
    K, R, C = 32, 512, 64     # two ticks' live rows + a window's appends
    g = FlowGraph("room")
    left = g.source("l", Spec((), np.int32, key_space=K, unique=True))
    right = g.source("r", Spec((), np.int32, key_space=K))
    j = g.join(left, right, merge=lambda k, va, vb: va * vb,
               spec=Spec((), np.int32, key_space=K), arena_capacity=R,
               product_slack=8, name="j")
    total = g.reduce(g.map(j, lambda v: v.astype(np.float32),
                           vectorized=True,
                           spec=Spec((), np.float32, key_space=K)),
                     "sum", name="total")
    rng = np.random.default_rng(ticks_a_window)
    scheds = [DirtyScheduler(g, get_executor(e)) for e in ("cpu", "tpu")]
    for sc in scheds:
        sc.push(left, DeltaBatch(np.arange(0, K, 2), np.arange(1, K // 2 + 1),
                                 np.ones(K // 2, np.int64)))
        sc.tick()
    held, feeds = [], []
    for t in range(48):
        keys = rng.integers(0, K, C - 16)
        vals = rng.integers(1, 50, C - 16)
        w = np.ones(C - 16, np.int64)
        if len(held) >= 2:                    # retract an earlier tick
            k0, v0 = held.pop(0)
            keys, vals = np.concatenate([keys, k0]), np.concatenate([vals, v0])
            w = np.concatenate([w, -np.ones(len(k0), np.int64)])
        held.append((keys[:C - 16], vals[:C - 16]))
        feeds.append({right: DeltaBatch(keys.astype(np.int64),
                                        vals.astype(np.int32), w)})
        if t == 30:                           # the other odd keys arrive late
            feeds[-1][left] = DeltaBatch(
                np.arange(1, K, 2), np.arange(1, K // 2 + 1),
                np.ones(K // 2, np.int64))
    for a in range(0, len(feeds), ticks_a_window):
        for sc in scheds:
            if ticks_a_window == 1:
                for node, b in feeds[a].items():
                    sc.push(node, b)
                sc.tick()
            else:
                sc.tick_many(feeds[a:a + ticks_a_window])
    ex = scheds[1].executor
    ex.check_errors()
    want = {k: float(v) for k, v in scheds[0].read_table(total).items()}
    got = {k: float(v) for k, v in scheds[1].read_table(total).items()}
    assert got == want and len(want) > K // 2
    counters = ex.op_counters()["j"]
    # 48 x 64 rows went through 512 slots
    assert counters["index_rebuilds"] >= 4
    assert counters["compactions"] == counters["index_rebuilds"]
    assert counters["arena_rows"] <= R and counters["late_pairs"] > 0


def test_pair_budget_overflow_still_raises_the_sticky_error():
    import jax.numpy as jnp

    from reflow_tpu.executors.device_delta import DeviceDelta
    from reflow_tpu.executors.join import join_core

    K, R, C = 8, 1024, 64
    j, (ist, _dst) = _join_pair(K, R, slack=1)
    # 200 right rows on key 3, then a left row for key 3: 200 late pairs
    # against a budget of 1 x 64
    for at in range(0, 200, C):
        db = DeviceDelta(jnp.full((C,), 3, jnp.int32),
                         jnp.arange(at, at + C, dtype=jnp.int32),
                         jnp.asarray((np.arange(at, at + C) < 200)
                                     .astype(np.int32)))
        _, ist = join_core(j.op, K, R, np.int32, ist, None, db,
                           oshape=(3,))
    assert not bool(ist["error"])
    da = DeviceDelta(jnp.full((C,), 3, jnp.int32),
                     jnp.ones((C, 2), jnp.int32),
                     jnp.asarray((np.arange(C) == 0).astype(np.int32)))
    out, ist = join_core(j.op, K, R, np.int32, ist, da, None, oshape=(3,))
    assert bool(ist["error"])
    assert int(np.count_nonzero(np.asarray(out.weights))) == C


def test_executor_raises_on_the_sticky_error():
    cfg = _cfg(product_slack=1)
    dep = _build(cfg)
    sched = DirtyScheduler(dep.graph, get_executor("tpu"))
    bids = [[MOD.BID, 1000, 1001, 100 + i, 5, 0, 0, 0] for i in range(200)]
    _push_rows(sched, dep, bids, range(200))
    sched.tick()
    _push_rows(sched, dep, [[MOD.AUCTION, 1000, 1000, 10, 1, 2, 5, 9]],
               [200])
    with pytest.raises(RuntimeError, match="join sticky error"):
        sched.tick()


def test_a_loop_region_keeps_the_dense_join():
    """PageRank's and SSSP's joins see most of the key space change in a
    pass: the executor indexes only joins no loop variable reaches."""
    from reflow_tpu.executors.join import layout_of
    from reflow_tpu.workloads import pagerank, sssp

    def layouts(graph):
        ex = get_executor("tpu")
        ex.bind(graph)
        return sorted(layout_of(ex.states[n.id]) for n in graph.nodes
                      if n.kind == "op" and n.op.kind == "join")

    assert layouts(pagerank.build_graph(64).graph) == ["swept"]
    assert layouts(sssp.build_graph(64).graph) == ["viewed"]
    assert layouts(_build(_cfg()).graph) == ["indexed", "indexed"]


@pytest.mark.parametrize("K", [512, 4096], ids=["dense", "sparse"])
@pytest.mark.parametrize("keys_touched", [20, 300])
@pytest.mark.parametrize("how", ["min", "max"])
def test_minmax_few_and_many_touched_keys(how, keys_touched, K):
    """The buffered min / max merges, writes and (where the key space
    is wider than the delta) emits an eighth of the slots at a time, as
    often as the touched keys need — once when a delta touches few keys,
    five times of the eight when it touches many: both against the CPU
    oracle, with retractions, int32 values past 2^24."""
    C, ticks = 512, 5
    g = FlowGraph("mm")
    src = g.source("s", Spec((2,), np.int32, key_space=K))
    red = g.reduce(src, how, candidates=4, name="m")
    sink = g.sink(red, "out")
    rng = np.random.default_rng(keys_touched)
    scheds = [DirtyScheduler(g, get_executor(e)) for e in ("cpu", "tpu")]
    held, before = [], {"touched": 0, "blocks": 0}
    for t in range(ticks):
        n = C - 40
        keys = rng.integers(0, keys_touched, n)
        vals = rng.integers(1 << 24, (1 << 24) + 50, (n, 2))
        w = np.ones(n, np.int64)
        if held:                      # retract some of an earlier tick
            k0, v0 = held.pop()
            keys = np.concatenate([keys, k0[:40]])
            vals = np.concatenate([vals, v0[:40]])
            w = np.concatenate([w, -np.ones(len(k0[:40]), np.int64)])
        held.append((keys[:n], vals[:n]))
        for sc in scheds:
            sc.push(src, DeltaBatch(keys.astype(np.int64),
                                    vals.astype(np.int32), w))
            sc.tick()
        now = scheds[1].executor.op_counters()["m"]
        # a tick's rows fill the 512-slot bucket: blocks of 64 slots
        assert (now["blocks"] - before["blocks"]
                == -(-(now["touched"] - before["touched"]) // 64))
        before = now
    want = {k: tuple(int(x) for x in v)
            for k, v in scheds[0].view_dict(sink).items()}
    got = {k: tuple(int(x) for x in v)
           for k, v in scheds[1].view_dict(sink).items()}
    assert got == want and len(want) == keys_touched
    assert before["touched"] >= keys_touched
    assert (before["blocks"] == ticks) == (keys_touched <= 64)


# -- the control ---------------------------------------------------------------


@pytest.mark.parametrize("seed", [11, 2**31 + 5, 77])
def test_mean_accumulated_in_bfloat16_is_not_correct(seed):
    cfg = _cfg()
    ref = _reference(cfg, seed, [(0, 60000)])
    want = ref.expected()
    sound = dict(want, avg=want["avg"].astype(np.float32).astype(float))
    assert all(c.ok for c in MOD.compare(cfg, sound, want))
    control = MOD.compare(cfg, ref.expected("bfloat16"), want)
    bad = {c.name: c for c in control if not c.ok}
    assert set(bad) == {"q4_avg_max_rel_err"}, control
    assert bad["q4_avg_max_rel_err"].value > 3 * bad[
        "q4_avg_max_rel_err"].limit
