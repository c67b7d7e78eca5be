"""Join-arena compaction (executors/arena.py): matched insert/retract
pairs cancel on device, so arena_capacity bounds LIVE rows and a
long-running churn stream survives at constant arena size (round-1
VERDICT item 7)."""

import numpy as np
import pytest

from reflow_tpu import DirtyScheduler
from reflow_tpu.executors.device_delta import bucket_capacity
from reflow_tpu.executors.tpu import TpuExecutor
from reflow_tpu.workloads import pagerank


def test_compact_arena_kernel():
    import jax.numpy as jnp

    from reflow_tpu.executors.arena import compact_arena

    R = 16
    # rows: (k=1,v=2.0,+1), (k=1,v=2.0,+1)  -> survives with net weight 2
    #       (k=3,v=5.0,+1), (k=3,v=5.0,-1)  -> cancels
    #       (k=4,v=7.0,-1)                  -> survives (net -1)
    rk = jnp.zeros(R, jnp.int32).at[:5].set(jnp.array([1, 3, 1, 3, 4]))
    rv = jnp.zeros((R, 1), jnp.float32).at[:5, 0].set(
        jnp.array([2.0, 5.0, 2.0, 5.0, 7.0]))
    rw = jnp.zeros(R, jnp.int32).at[:5].set(jnp.array([1, 1, 1, -1, -1]))
    state = {"lval": jnp.zeros((8,)), "lw": jnp.zeros((8,), jnp.int32),
             "rkeys": rk, "rvals": rv, "rw": rw,
             "rcount": jnp.asarray(5, jnp.int32)}
    out = compact_arena(state)
    assert int(out["rcount"]) == 2
    live = np.asarray(out["rw"]) != 0
    rows = sorted(zip(np.asarray(out["rkeys"])[live].tolist(),
                      np.asarray(out["rvals"])[live, 0].tolist(),
                      np.asarray(out["rw"])[live].tolist()))
    assert rows == [(1, 2.0, 2), (4, 7.0, -1)]


@pytest.mark.parametrize("make_ex,arena_mult,ticks", [
    (lambda: TpuExecutor(), 1, 50),
    # the sharded tracker bounds appends by worst-case key skew (every
    # all_gather'd row could land on one shard), so its live-row arena is
    # n_shards x larger — lifetime appends still exceed it several-fold
    pytest.param(lambda: _sharded(), 8, 12, id="sharded"),
])
def test_long_churn_constant_arena(make_ex, arena_mult, ticks):
    """50 churn ticks through an arena sized for LIVE rows only: lifetime
    appends exceed capacity several times over, so this passes only if
    compaction reclaims cancelled pairs."""
    N, E, churn = 48, 200, 0.2
    churn_cap = bucket_capacity(2 * int(churn * E) + 2)
    arena = (bucket_capacity(E) + 2 * churn_cap) * arena_mult
    web = pagerank.WebGraph.random(N, E, seed=4)
    pg = pagerank.build_graph(N, tol=1e-5, arena_capacity=arena)
    ex = make_ex()
    sched = DirtyScheduler(pg.graph, ex, max_loop_iters=500)
    sched.push(pg.teleport, pagerank.teleport_batch(N))
    sched.push(pg.edges, web.initial_batch())
    assert sched.tick().quiesced
    for i in range(ticks):
        sched.push(pg.edges, web.churn(churn))
        assert sched.tick().quiesced, f"tick {i}"
    # GC genuinely required: the lifetime append mass (bucketed ingress
    # capacities per tick) dwarfs the per-shard capacity
    assert bucket_capacity(E) + ticks * churn_cap > arena // arena_mult
    ref = pagerank.reference_ranks(web)
    ranks = sched.read_table(pg.new_rank)
    err = max(abs(float(ranks.get(k, 1 - pagerank.DAMPING)) - ref[k])
              for k in range(N))
    assert err < 5e-3, err


def _sharded():
    from reflow_tpu.parallel import make_mesh
    from reflow_tpu.parallel.shard import ShardedTpuExecutor

    return ShardedTpuExecutor(make_mesh(8))


def test_compact_arena_native_width_bit_identity():
    """ADVICE r2: distinct 64-bit values that alias as float32/int32 must
    NOT be grouped — the bit compare runs at native width."""
    import jax
    import jax.numpy as jnp

    from reflow_tpu.executors.arena import compact_arena

    jax.config.update("jax_enable_x64", True)
    try:
        R = 8
        a, b = 1.0, 1.0 + 2.0**-40        # equal after a float32 cast
        rk = jnp.zeros(R, jnp.int32).at[:2].set(
            jnp.array([5, 5], jnp.int32))
        rv = jnp.zeros((R, 1), jnp.float64).at[:2, 0].set(
            jnp.array([a, b], jnp.float64))
        rw = jnp.zeros(R, jnp.int32).at[:2].set(
            jnp.array([1, -1], jnp.int32))
        state = {"lval": jnp.zeros((4,)), "lw": jnp.zeros((4,), jnp.int32),
                 "rkeys": rk, "rvals": rv, "rw": rw,
                 "rcount": jnp.asarray(2, jnp.int32)}
        out = compact_arena(state)
        # the pair must survive (values differ bitwise), not cancel
        assert int(out["rcount"]) == 2
        live = np.asarray(out["rw"]) != 0
        vals = sorted(np.asarray(out["rvals"])[live, 0].tolist())
        assert vals == [a, b]
    finally:
        jax.config.update("jax_enable_x64", False)


def test_arena_overflow_sets_sticky_error():
    """Genuine overflow — live rows + appends exceed capacity and nothing
    cancels — must raise loudly at the next sync point via the join
    state's sticky error flag (the in-program lax.cond compaction found
    nothing to reclaim). The pre-round-3 host tracker raised *before*
    dispatch but cost a device readback mid-stream; the sticky flag keeps
    the failure loud without ever leaving the device mid-tick."""
    from reflow_tpu import DeltaBatch, FlowGraph, Spec

    K = 16
    uniq = Spec((), np.float32, key_space=K, unique=True)
    raw = Spec((), np.float32, key_space=K)
    g = FlowGraph("overflow")
    vals = g.source("vals", uniq)
    edges = g.source("edges", raw)
    tot = g.reduce(vals, "sum", name="uniq")
    j = g.join(tot, edges, merge=lambda k, va, vb: va + vb, spec=raw,
               arena_capacity=64, name="j")
    out = g.reduce(j, "sum", name="joined")
    g.sink(out, "out")

    sched = DirtyScheduler(g, TpuExecutor())
    sched.push(vals, DeltaBatch(np.arange(K, dtype=np.int64),
                                np.ones(K, np.float32),
                                np.ones(K, np.int64)))
    sched.tick()

    n, v0 = 48, 0
    with pytest.raises(RuntimeError, match="arena overflowed"):
        for _ in range(4):
            keys = (np.arange(n) % K).astype(np.int64)
            vals_b = np.arange(v0, v0 + n).astype(np.float32)  # all distinct
            v0 += n
            sched.push(edges, DeltaBatch(keys, vals_b, np.ones(n, np.int64)))
            sched.tick()


# -- the compaction against a NumPy oracle, bit for bit ---------------------

INT32_MAX = np.iinfo(np.int32).max


def _np_bits(vcols):
    """NumPy's reading of ``arena._value_bits``: the int32 columns the
    compaction orders and compares a value by."""
    R = vcols.shape[0]
    size = vcols.dtype.itemsize
    if size >= 4:
        return np.ascontiguousarray(vcols).view(np.int32).reshape(R, -1)
    if size == 2:
        return np.ascontiguousarray(vcols).view(np.int16).astype(np.int32)
    if vcols.dtype.kind not in "iub":
        return vcols.astype(np.float32).view(np.int32)
    return vcols.astype(np.int32)


def _raw(x):
    """An array's bytes as unsigned words, so NaNs and signed zeros
    compare by their bits."""
    x = np.ascontiguousarray(np.asarray(x))
    return x.view({1: np.uint8, 2: np.uint16, 4: np.uint32,
                   8: np.uint64}[x.dtype.itemsize])


def _oracle(rk, rv, rw, K):
    """What ``compact_arena`` and ``reindex`` must leave: rows in the
    order of (key, value bits), dead rows dropped, a run of equal (key,
    value bits) one row of the run's net weight, runs of net weight 0
    dropped, the survivors packed to the front and zeros behind them;
    then one segment a key."""
    R = rk.shape[0]
    bits = _np_bits(rv.reshape(R, -1))
    skey = np.where(rw != 0, rk, INT32_MAX).astype(np.int32)
    order = np.lexsort(tuple(bits[:, q] for q in
                             range(bits.shape[1] - 1, -1, -1)) + (skey,))
    sk, sb, sv, sw = skey[order], bits[order], rv[order], rw[order]
    nk, nv, nw = np.zeros_like(rk), np.zeros_like(rv), np.zeros_like(rw)
    n = i = 0
    while i < R:
        j = i + 1
        while j < R and sk[j] == sk[i] and np.array_equal(sb[j], sb[i]):
            j += 1
        net = np.int32(sw[i:j].sum(dtype=np.int32))
        if net != 0 and sk[i] != INT32_MAX:
            nk[n], nv[n], nw[n] = sk[i], sv[i], net
            n += 1
        i = j
    head, deg = np.full(K, -1, np.int32), np.zeros(K, np.int32)
    seg_len = np.zeros(R, np.int32)
    for r in range(n):
        if r == 0 or nk[r] != nk[r - 1]:
            head[nk[r]] = r
        deg[nk[r]] += 1
        seg_len[head[nk[r]]] += 1
    return {"rkeys": nk, "rvals": nv, "rw": nw, "rcount": np.int32(n),
            "head": head, "deg": deg, "seg_len": seg_len,
            "seg_prev": np.full(R, -1, np.int32)}


#: (key, value as three indices into the dtype's pool, weight): runs of
#: equal (key, value) of net weight 0, +1, -1 and over 1, a NaN beside
#: its bitwise twins, -0.0 beside +0.0, values that differ only in a
#: later column, a dead row whose key and value a live row shares
_ROWS = [
    (5, (0, 0, 0), 1), (5, (0, 0, 0), -1),              # cancels
    (5, (4, 0, 0), 1),                                  # net +1
    (3, (1, 1, 1), 1), (3, (1, 1, 1), 1), (3, (1, 1, 1), 1),   # NaN x 3
    (3, (2, 2, 2), -1),                                 # -0.0: net -1
    (3, (3, 3, 3), 1),                                  # +0.0: its own run
    (9, (0, 4, 4), 2), (9, (0, 4, 4), -1),              # net +1 of 2 - 1
    (9, (5, 0, 0), -1), (9, (5, 0, 0), 1),              # cancels
    (7, (0, 0, 4), 1), (7, (0, 0, 5), 1), (7, (0, 0, 4), 1),   # last column
    (7, (0, 5, 0), -2),
    (2, (4, 4, 4), 0), (2, (4, 4, 4), 1),               # a dead twin
    (0, (3, 2, 3), 1), (11, (1, 0, 2), -1),
]
_K = 12


def _pool(dtype):
    if np.dtype(dtype).kind in "iu":
        return np.array([17, -5, 0, 1, 100, -128], np.int64).astype(dtype)
    return np.array([1.5, np.nan, -0.0, 0.0, -2.25, 96.0],
                    np.float32).astype(dtype)


def _arena(kind, dtype, vshape):
    """-> (rkeys, rvals, rw, rows in use) of the named arena."""
    rng = np.random.default_rng(len(kind) + 7 * len(vshape))
    pool = _pool(dtype)
    ncol = int(np.prod(vshape, dtype=np.int64))
    if kind == "random":
        keys = rng.integers(0, _K, 48)
        idx = rng.integers(0, len(pool), (48, 3))
        w = rng.choice([-2, -1, 0, 1, 1, 3], 48)
    else:
        rows = [_ROWS[i] for i in rng.permutation(len(_ROWS))]
        keys = np.array([r[0] for r in rows])
        idx = np.array([r[1] for r in rows])
        w = np.array([r[2] for r in rows])
    n = len(keys)
    R = n if kind == "full" else 64
    rk, rw = np.zeros(R, np.int32), np.zeros(R, np.int32)
    rv = np.zeros((R,) + vshape, dtype)
    if kind != "empty":
        rk[:n] = keys
        rv[:n] = pool[idx[:, 3 - ncol:]].reshape((n,) + vshape)
        rw[:n] = {"dead": 0, "live": 1}.get(kind, w)
    return rk, rv, rw, (0 if kind == "empty" else n)


def _value_dtypes():
    import ml_dtypes

    return [np.float32, np.int32, ml_dtypes.bfloat16, np.float16,
            ml_dtypes.float8_e4m3fn, np.int8, np.float64, np.int64]


@pytest.mark.parametrize("kind", ["empty", "dead", "live", "full", "mixed",
                                  "random"])
@pytest.mark.parametrize("vshape", [(), (2,), (3,)], ids=str)
@pytest.mark.parametrize("dtype", _value_dtypes(),
                         ids=lambda d: np.dtype(d).name)
def test_compaction_is_the_oracles_bit_for_bit(dtype, vshape, kind):
    """``compact_arena`` alone (as a tick's ``lax.cond`` runs it) and
    under ``reindex``: every leaf the NumPy oracle's, values by their
    bits at native width."""
    import jax
    import jax.numpy as jnp

    from reflow_tpu.executors.arena import (compact_arena, index_state,
                                            reindex)

    x64 = np.dtype(dtype).itemsize == 8
    jax.config.update("jax_enable_x64", x64)
    try:
        rk, rv, rw, n = _arena(kind, dtype, vshape)
        want = _oracle(rk, rv, rw, _K)
        if kind == "mixed":     # the rows are what their comments say
            assert sorted(want["rw"][:want["rcount"]].tolist()) == [
                -2, -1, -1, 1, 1, 1, 1, 1, 1, 2, 3]
        arena = {"rkeys": jnp.asarray(rk), "rvals": jnp.asarray(rv),
                 "rw": jnp.asarray(rw), "rcount": jnp.asarray(n, jnp.int32),
                 "gen": jnp.asarray(4, jnp.int32)}
        assert arena["rvals"].dtype == np.dtype(dtype)
        got = jax.jit(compact_arena)(arena)
        indexed = jax.jit(reindex)(
            dict(arena, **index_state(_K, rk.shape[0])))
        assert set(got) == set(arena) and int(got["gen"]) == 5
        for out, names in ((got, ["rkeys", "rvals", "rw", "rcount"]),
                           (indexed, list(want))):
            for name in names:
                have = np.asarray(out[name])
                assert have.dtype == want[name].dtype, name
                assert have.shape == want[name].shape, name
                np.testing.assert_array_equal(
                    _raw(have), _raw(want[name]), err_msg=name)
    finally:
        jax.config.update("jax_enable_x64", False)


def _primitives(jaxpr, into=None):
    """Every primitive's name in a jaxpr, those of its inner jaxprs
    (calls, loops, branches) included, counted."""
    import jax

    into = {} if into is None else into
    for eqn in jaxpr.eqns:
        into[eqn.primitive.name] = into.get(eqn.primitive.name, 0) + 1
        for sub in jax.core.jaxprs_in_params(eqn.params):
            _primitives(sub, into)
    return into


def test_the_compaction_moves_the_arena_by_two_sorts_and_no_index():
    """The arena's columns ride the compaction's two sorts: nothing of
    the arena's length is gathered or scattered by index (15 - 65 ns a
    row on a v5e, against a sort's ~7), and ``reindex`` adds exactly its
    two keyed scatters into ``head`` and ``deg``."""
    import jax
    import jax.numpy as jnp

    from reflow_tpu.executors.arena import (compact_arena, index_state,
                                            reindex)

    R = 4096
    arena = {"rkeys": jnp.zeros(R, jnp.int32),
             "rvals": jnp.zeros((R, 2), jnp.int32),
             "rw": jnp.zeros(R, jnp.int32),
             "rcount": jnp.zeros((), jnp.int32)}

    def by_index(fn, state):
        prims = _primitives(jax.make_jaxpr(fn)(state).jaxpr)
        return prims.get("sort", 0), {
            name: n for name, n in prims.items()
            if name.startswith("scatter") or name == "gather"}

    assert by_index(compact_arena, arena) == (2, {})
    assert by_index(reindex, dict(arena, **index_state(100, R))) == (
        2, {"scatter": 2})
