"""A reduce writes its keyed tables a block of ``C / 8`` slots at a time
over the tick's compacted live keys (``lowerings._over_blocks``), where
it wrote them once over the delta's capacity. Held here: the sparse
``sum`` / ``count`` / ``mean`` against the whole-capacity scatter form it
had (kept below as the reference, as ``test_nexmark.py`` keeps the
scatter append), ``min`` / ``max`` against the same program with one
block of ``C`` slots and against a plain NumPy model of the candidate
buffers; every state leaf after every tick, every live output row, and
the ``blocks`` counter. Small seeded sizes, CPU."""

import functools

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from reflow_tpu.delta import Spec
from reflow_tpu.executors import lowerings as lw
from reflow_tpu.executors.device_delta import DeviceDelta
from reflow_tpu.graph import FlowGraph

K, C = 4096, 512
S = lw._block_slots(C)
TOP = np.iinfo(np.int32).max


def test_a_block_is_an_eighth_of_a_delta_that_divides():
    assert S == 64 and S < C
    assert [lw._block_slots(c) for c in (64, 255, 256, 260, 8192)] == [
        64, 255, 32, 260, 1024]


def _reduce_node(how, vshape, dtype, **kw):
    g = FlowGraph("blocks")
    src = g.source("s", Spec(vshape, dtype, key_space=K))
    return g.reduce(src, how, name="r", **kw)


def _device(keys, vals, w, vshape, dtype, cap=C):
    """A delta of capacity ``cap`` with the given rows dealt over its
    slots in order, dead rows between them."""
    n = len(keys)
    at = np.sort(np.random.default_rng(n).choice(cap, n, replace=False))
    k = np.zeros(cap, np.int32)
    v = np.zeros((cap,) + vshape, dtype)
    ww = np.zeros(cap, np.int32)
    k[at], v[at], ww[at] = keys, vals, w
    return DeviceDelta(jnp.asarray(k), jnp.asarray(v), jnp.asarray(ww))


def _same_leaves(a, b):
    assert a.keys() == b.keys()
    for name in a:
        assert np.array_equal(np.asarray(a[name]), np.asarray(b[name])), name


def _same_live_rows(a, b):
    """Same weights slot for slot, same key and value wherever a row is
    live (a dead row's key and value are padding)."""
    wa, wb = np.asarray(a.weights), np.asarray(b.weights)
    assert np.array_equal(wa, wb)
    on = wa != 0
    assert np.array_equal(np.asarray(a.keys)[on], np.asarray(b.keys)[on])
    assert np.array_equal(np.asarray(a.values)[on], np.asarray(b.values)[on])


def _ticks(rng, n_live, vshape, dtype):
    """Four ticks of ``n_live`` rows each: keys that repeat (a run of
    equal keys lies across every block boundary when the rows pass one),
    weights of both signs, and from the second tick on half of the rows
    are the tick before's taken back."""
    n_keys = max(1, n_live // 3)
    pool = rng.choice(K, n_keys, replace=False)
    prev, out = None, []
    for _ in range(4):
        keys = pool[rng.integers(0, n_keys, n_live)]
        vals = rng.integers(1, 50, (n_live,) + vshape)
        w = rng.choice([1, 1, 2, -1], n_live)
        if prev is not None and n_live > 1:
            h = n_live // 2
            keys[:h], vals[:h], w[:h] = prev[0][:h], prev[1][:h], -prev[2][:h]
        prev = (keys.copy(), vals.copy(), w.copy())
        out.append(_device(keys, vals.astype(dtype), w, vshape, dtype))
    return out


# -- sparse sum / count / mean --------------------------------------------------


def _whole_capacity_reduce(op, vdtype, state, d):
    """The sparse branch of ``_lower_reduce`` as it was before the block
    writes: contributions scatter-added over all ``C`` rows, emission
    over all ``C`` sorted rows. The reference, leaf for leaf."""
    emitted, em_has = state["emitted"], state["emitted_has"]
    contrib = lw._masked_contrib(d.weights, d.values).astype(jnp.float32)
    wsum = state["wsum"].at[d.keys].add(contrib)
    wcnt = state["wcnt"].at[d.keys].add(d.weights)
    live = d.weights != 0
    skey = jnp.where(live, d.keys, K)
    sk = skey[jnp.argsort(skey)]
    prev = jnp.concatenate([jnp.full((1,), -1, sk.dtype), sk[:-1]])
    first = (sk != prev) & (sk < K)
    tk = jnp.where(sk < K, sk, 0).astype(jnp.int32)
    agg, exists = lw._agg_tables(op, wsum[tk], wcnt[tk], vdtype)
    em, has = emitted[tk], em_has[tk]
    changed = lw._differs(agg, em, op.tol)
    ins_m = first & exists & (~has | changed)
    ret_m = first & has & (~exists | changed)
    out = DeviceDelta(
        jnp.concatenate([tk, tk]), jnp.concatenate([em, agg]),
        jnp.concatenate([-ret_m.astype(jnp.int32), ins_m.astype(jnp.int32)]))
    set_ins = jnp.where(ins_m, tk, K)
    return out, {
        "wsum": wsum, "wcnt": wcnt,
        "emitted": emitted.at[set_ins].set(agg, mode="drop"),
        "emitted_has": em_has.at[set_ins].set(True, mode="drop").at[
            jnp.where(ret_m & ~exists, tk, K)].set(False, mode="drop")}


def _linear_pair(how, vshape):
    node = _reduce_node(how, vshape, np.float32)
    in_spec = node.inputs[0].spec
    st = lw.reduce_state(node.op, in_spec, node.spec)
    block = jax.jit(lambda s, d: lw._lower_reduce(node.op, node, s, [d]))
    whole = jax.jit(lambda s, d: _whole_capacity_reduce(
        node.op, node.spec.value_dtype, s, d))
    return st, block, whole


@pytest.mark.parametrize("n_live", [0, 1, S - 1, S, S + 1, C],
                         ids=lambda n: f"live{n}")
@pytest.mark.parametrize("how,vshape", [("sum", (3,)), ("count", ()),
                                        ("mean", ())])
def test_sparse_linear_reduce_by_blocks_equals_the_whole_capacity_form(
        how, vshape, n_live):
    st, block, whole = _linear_pair(how, vshape)
    ref = st
    rng = np.random.default_rng(n_live + len(how))
    for d in _ticks(rng, n_live, vshape, np.float32):
        out, st = block(st, d)
        rout, ref = whole(ref, d)
        _same_leaves(st, ref)
        _same_live_rows(out, rout)
    if n_live:
        assert np.asarray(st["emitted_has"]).any()


def test_a_keys_rows_across_a_block_boundary_are_summed_before_it_is_read():
    """Sorted rows ``[S - 2, S + 3)`` are one key's: three in the first
    block, two in the second, one of them a retraction. Its aggregate is
    emitted once, from all five, and a second tick takes it back whole
    (a group that ceases to exist), then below zero (an anti-row)."""
    st, block, whole = _linear_pair("sum", (3,))
    ref = st
    keys = np.concatenate([np.arange(S - 2), np.full(5, 1000),
                           np.arange(2000, 2010)])
    vals = np.arange(1, 1 + 3 * len(keys), dtype=np.float32).reshape(-1, 3)
    w = np.ones(len(keys), np.int64)
    w[S] = -1
    order = np.random.default_rng(0).permutation(len(keys))
    d1 = _device(keys[order], vals[order], w[order], (3,), np.float32)
    at = keys == 1000
    d2 = _device(keys[at], vals[at], -w[at], (3,), np.float32)
    d3 = _device(keys[at][:1], vals[at][:1], [-1], (3,), np.float32)
    want = (vals[at] * w[at, None]).sum(axis=0)
    for d, row, has in ((d1, 1, True), (d2, -1, False), (d3, 1, True)):
        out, st = block(st, d)
        rout, ref = whole(ref, d)
        _same_leaves(st, ref)
        _same_live_rows(out, rout)
        ow = np.asarray(out.weights)
        assert list(ow[(np.asarray(out.keys) == 1000) & (ow != 0)]) == [row]
        assert bool(np.asarray(st["emitted_has"])[1000]) == has
        if d is d1:
            assert np.array_equal(np.asarray(st["emitted"])[1000], want)


# -- min / max ------------------------------------------------------------------

R = 4


def _minmax_ticks(rng, touched):
    """Four ticks that each touch exactly ``touched`` keys: one row a
    key, and as many more as the delta holds up to six a key, over a
    dozen values past 2^24 (equal rows net, a buffer of four overflows),
    weights of both signs (a retraction of a row never inserted is an
    anti-row); from the second tick on half of those further rows are
    the tick before's, taken back."""
    pool = rng.choice(K, touched, replace=False)
    more = min(C, 6 * touched) - touched
    prev, out = None, []
    for _ in range(4):
        keys = np.concatenate([pool, pool[rng.integers(0, max(touched, 1),
                                                       more)]])
        vals = rng.integers(1 << 24, (1 << 24) + 12, (len(keys), 2))
        w = np.concatenate([rng.choice([1, 2], touched),
                            rng.choice([1, 1, 2, -1], more)])
        if prev is not None:
            h = more // 2
            for col, old in zip((keys, vals, w), prev):
                col[touched:touched + h] = old[touched:touched + h]
            w[touched:touched + h] *= -1
        prev = (keys.copy(), vals.copy(), w.copy())
        order = rng.permutation(len(keys))
        out.append(_device(keys[order], vals[order].astype(np.int32),
                           w[order], (2,), np.int32))
    return out


def _minmax_pair(how, monkeypatch):
    node = _reduce_node(how, (2,), np.int32, candidates=R)
    in_spec = node.inputs[0].spec
    st = lw.reduce_state(node.op, in_spec, node.spec)
    block = jax.jit(lambda s, d: lw._lower_reduce(node.op, node, s, [d]))
    out, _ = block(st, _device([], np.zeros((0, 2)), [], (2,), np.int32))
    assert out.weights.shape == (2 * C,)      # traced with blocks of S
    monkeypatch.setattr(lw, "_block_slots", lambda c: c)
    whole = jax.jit(lambda s, d: lw._lower_reduce(node.op, node, s, [d]))
    whole(st, _device([], np.zeros((0, 2)), [], (2,), np.int32))
    monkeypatch.undo()
    return st, block, whole


class _Model:
    """The candidate buffers in plain Python: per key the ``R``
    lex-smallest sign-normalised distinct rows with their net weights,
    the smallest row ever pushed out and whether a positive one was."""

    def __init__(self, how):
        self.sign = 1 if how == "min" else -1
        self.buf, self.lo, self.pos = {}, {}, set()
        self.em, self.error, self.evicted = {}, False, 0

    def tick(self, d):
        rows = {}
        for k, v, w in zip(np.asarray(d.keys), np.asarray(d.values),
                           np.asarray(d.weights)):
            if w:
                rows.setdefault(int(k), []).append(
                    (tuple(int(self.sign * x) for x in v), int(w)))
        out = []
        for k in sorted(rows):
            net = dict(self.buf.get(k, ()))
            for v, w in rows[k]:
                net[v] = net.get(v, 0) + w
            alive = sorted((v, w) for v, w in net.items() if w)
            keep, gone = alive[:R], alive[R:]
            self.buf[k] = keep
            self.evicted += len(gone)
            if gone:
                self.lo[k] = min(self.lo.get(k, (TOP, TOP)), gone[0][0])
                if any(w > 0 for _, w in gone):
                    self.pos.add(k)
            best = next((v for v, w in keep if w > 0), None)
            unknown = (best is None and k in self.pos) or (
                best is not None and not best < self.lo.get(k, (TOP, TOP)))
            self.error |= unknown
            agg = tuple(self.sign * x for x in best) if best else (0, 0)
            had = self.em.get(k)
            changed = had is not None and agg != had
            if best is not None and not unknown and (had is None or changed):
                out.append((k, agg, 1))
            if had is not None and (best is None or changed) and not unknown:
                out.append((k, had, -1))
                if best is None:
                    del self.em[k]
            if best is not None and not unknown and (had is None or changed):
                self.em[k] = agg
        return sorted(out), len(rows)

    def leaves(self):
        cv = np.full((K, R * 2), TOP, np.int32)
        cw = np.zeros((K, R), np.int32)
        lo = np.full((K, 2), TOP, np.int32)
        for k, rows in self.buf.items():
            for r, (v, w) in enumerate(rows):
                cv[k, 2 * r:2 * r + 2], cw[k, r] = v, w
        for k, v in self.lo.items():
            lo[k] = v
        mp = np.zeros(K, bool)
        mp[list(self.pos)] = True
        has = np.zeros(K, bool)
        has[list(self.em)] = True
        em = np.zeros((K, 2), np.int32)
        for k, v in self.em.items():
            em[k] = v
        return {"cand_v": cv, "cand_w": cw, "over_lo": lo,
                "over_maybe_pos": mp, "emitted_has": has}, em


def _live_rows(d):
    return sorted((int(k), tuple(int(x) for x in v), int(w)) for k, v, w in
                  zip(np.asarray(d.keys), np.asarray(d.values),
                      np.asarray(d.weights)) if w)


@pytest.mark.parametrize("touched", [0, 1, S - 1, S, S + 1, C],
                         ids=lambda n: f"keys{n}")
@pytest.mark.parametrize("how", ["min", "max"])
def test_minmax_by_blocks_equals_one_block_of_the_capacity_and_the_model(
        how, touched, monkeypatch):
    """``touched`` keys a tick, as many rows as the delta holds (so a
    key has up to ``C // touched`` rows and a buffer of four overflows),
    half of a tick's rows taken back by the next, a value retracted
    that was never inserted: state, live rows, counters."""
    st, block, whole = _minmax_pair(how, monkeypatch)
    ref, model = st, _Model(how)
    rng = np.random.default_rng(touched)
    want_blocks = 0
    for d in _minmax_ticks(rng, touched):
        out, st = block(st, d)
        rout, ref = whole(ref, d)
        _same_leaves({k: v for k, v in st.items() if k != "counters"},
                     {k: v for k, v in ref.items() if k != "counters"})
        _same_live_rows(out, rout)
        rows, n_t = model.tick(d)
        assert n_t == touched and _live_rows(out) == rows
        leaves, em = model.leaves()
        for name, want in leaves.items():
            assert np.array_equal(np.asarray(st[name]), want), name
        assert np.array_equal(np.asarray(st["emitted"])[leaves["emitted_has"]],
                              em[leaves["emitted_has"]])
        assert bool(st["error"]) == model.error
        want_blocks += -(-n_t // S)
        assert [int(x) for x in st["counters"][:2]] == [
            int(x) for x in ref["counters"][:2]]
    n_t_all, evicted, blocks, merged = (int(x) for x in st["counters"])
    assert blocks == want_blocks and evicted == model.evicted
    assert merged == 4 * C                    # no ladder where C < K
    assert int(ref["counters"][2]) == 4       # one block of C, every tick
    assert n_t_all == 4 * touched
    if 0 < touched <= S:
        assert evicted > 0


def test_the_block_form_lowers_to_loops_over_an_eighth_of_the_slots():
    """The mechanism in force: the maximum's tables and the sparse sum's
    are scattered to inside ``while`` loops, by updates of ``S`` rows,
    and no scatter takes ``C`` rows into a ``K``-row table."""
    import re

    for how, vshape, dtype, kw in (("max", (2,), np.int32,
                                    {"candidates": R}),
                                   ("sum", (3,), np.float32, {})):
        node = _reduce_node(how, vshape, dtype, **kw)
        st = lw.reduce_state(node.op, node.inputs[0].spec, node.spec)
        d = _device([], np.zeros((0,) + vshape), [], vshape, dtype)
        text = jax.jit(lambda s, x: lw._lower_reduce(
            node.op, node, s, [x])).lower(st, d).as_text()
        assert "stablehlo.while" in text
        assert "stablehlo.case" not in text and "stablehlo.if" not in text
        into_table = re.findall(
            r'"stablehlo.scatter"\(([^)]*)\).*?:\s*\((tensor<%dx[^>]*>), '
            r'tensor<(\d+)x' % K, text, re.S)
        assert into_table and {int(n) for _, _, n in into_table} == {S}


# -- a delta many times its key space: the merge follows its live rows -----

KW, CW = 64, 8192                     # a swept join's output over few keys
RUNGS = (64, 256, 1024, 4096, CW)


def test_the_rungs_are_the_key_space_times_four_up_to_the_capacity():
    assert lw._merge_rungs(CW, KW) == RUNGS
    # the sssp-graph500 cell's minimum, and the first capacity that
    # gets a second size at all
    assert lw._merge_rungs(1 << 22, 1 << 16) == (
        1 << 16, 1 << 18, 1 << 20, 1 << 22)
    assert lw._merge_rungs(4 * KW, KW) == (KW, 4 * KW)
    assert lw._merge_rungs(5 * KW, KW - 3) == (KW, 4 * KW, 5 * KW)
    # no ladder under four times the key space (nexmark-q3q4's maximum:
    # 8 192 slots under 2^23 keys), nor where the merge is sparse
    for c, k in ((4 * KW - 8, KW), (2 * KW, KW), (KW, KW), (8192, 1 << 23)):
        assert lw._merge_rungs(c, k) == (c,)


def _rung(n_live):
    return next(r for r in RUNGS if n_live <= r)


def _dealt(keys, vals, w, cap=CW):
    return _device(keys, vals, w, (), np.float32, cap)


def _wide_ticks(rng, n_live):
    """Three ticks of ``n_live`` live rows dealt over ``CW`` slots: a
    third of the keys, values that repeat, half of a tick's rows taken
    back by the next."""
    prev = None
    for _ in range(3):
        keys = rng.integers(0, KW // 3, n_live)
        vals = rng.integers(1, 40, n_live).astype(np.float32) / 8
        w = rng.choice([1, 1, 2], n_live)
        if prev is not None and n_live > 1:
            h = n_live // 2
            keys[:h], vals[:h], w[:h] = prev[0][:h], prev[1][:h], -prev[2][:h]
        prev = (keys.copy(), vals.copy(), w.copy())
        yield _dealt(keys, vals, w)


@functools.lru_cache(maxsize=None)
def _wide_pair(how, k=KW):
    """The minimum (maximum) of ``k`` keys: -> (fresh state,
    ``minmax_core``, ``_minmax_merge`` over the whole delta), compiled
    once a ``how`` and delta shape."""
    g = FlowGraph("wide")
    src = g.source("s", Spec((), np.float32, key_space=k))
    node = g.reduce(src, how, name="r", candidates=4)
    args = (node.op, k, (), np.float32)
    return (lw.reduce_state(node.op, node.inputs[0].spec, node.spec),
            jax.jit(lambda s, d: lw.minmax_core(*args, s, d)),
            jax.jit(lambda s, d: lw._minmax_merge(*args, s, d)))


def _same_but_counters(st, ref):
    _same_leaves({k: v for k, v in st.items() if k != "counters"},
                 {k: v for k, v in ref.items() if k != "counters"})
    assert [int(x) for x in st["counters"][:2]] == [
        int(x) for x in ref["counters"][:2]]


@pytest.mark.parametrize(
    "n_live", [0, 1] + [r + i for r in RUNGS[:-1] for i in (-1, 0, 1)]
    + [4 * KW + 7, CW], ids=lambda n: f"rows{n}")
@pytest.mark.parametrize("how", ["min", "max"])
def test_minmax_over_the_live_prefix_equals_the_merge_of_the_whole_delta(
        how, n_live):
    """``minmax_core`` merges the smallest rung of ``_merge_rungs`` that
    holds the live rows, the whole delta past the last: the same tables,
    the same rows out, the same ``error``, the same keys touched and
    rows evicted as the merge of the whole delta, tick after tick, on,
    one under and one over every rung; ``merged_slots`` says which rung
    each tick took."""
    st, core, whole = _wide_pair(how)
    ref = st
    rung = _rung(n_live)
    S_r = lw._block_slots(rung)
    want_blocks = 0
    for d in _wide_ticks(np.random.default_rng(n_live), n_live):
        touched = -int(st["counters"][0])
        out, st = core(st, d)
        rout, ref = whole(ref, d)
        _same_but_counters(st, ref)
        _same_live_rows(out, rout)
        # trips of the rung's own loop, an eighth of the rung each (one
        # block where the rung is under 256 slots)
        touched += int(st["counters"][0])
        want_blocks += 1 if S_r == rung else -(-touched // S_r)
    assert out.weights.shape == rout.weights.shape == (2 * KW,)
    if n_live >= KW:
        assert int(st["counters"][1]) > 0     # buffers of four overflowed
    assert [int(x) for x in st["counters"][2:]] == [want_blocks, 3 * rung]
    assert int(ref["counters"][3]) == 3 * CW


@pytest.mark.parametrize("edge", RUNGS[:-1], ids=lambda r: f"edge{r}")
@pytest.mark.parametrize("how", ["min", "max"])
def test_a_retraction_and_its_better_insert_across_a_rungs_edge_meet_in_one_merge(
        how, edge):
    """A hub's pass: key 7 holds six values in a buffer of four (two
    pushed out, the watermark set); the next tick takes its four
    buffered ones back in the live rows just under ``edge`` and brings
    a better one as live row ``edge`` itself, the first past the rung.
    Merged together the better row is the answer, strictly inside the
    watermark; a merge of the rung's rows alone would hollow the buffer
    (no positive row left, a positive one evicted before) and latch
    ``unknown``. The ladder takes the next rung whole: tables, rows and
    ``error`` of the whole delta's merge."""
    sign = 1.0 if how == "min" else -1.0
    st, core, whole = _wide_pair(how)
    ref = st
    hub, fill = 7, edge + 9
    first = _dealt(np.full(6, hub), sign * np.arange(1, 7, dtype=np.float32),
                   np.ones(6, np.int32))
    keys = 8 + np.arange(fill) % (KW - 8)
    vals = sign * (10 + np.arange(fill) % 5).astype(np.float32)
    w = np.ones(fill, np.int32)
    keys[edge - 4:edge + 1] = hub
    vals[edge - 4:edge] = sign * np.arange(1, 5, dtype=np.float32)
    w[edge - 4:edge] = -1
    vals[edge] = sign * 0.5
    for d in (first, _dealt(keys, vals, w)):
        out, st = core(st, d)
        rout, ref = whole(ref, d)
        _same_but_counters(st, ref)
        _same_live_rows(out, rout)
    assert not bool(st["error"]) and bool(st["over_maybe_pos"][hub])
    assert float(st["emitted"][hub]) == sign * 0.5
    assert _rung(fill) > edge
    assert int(st["counters"][3]) == KW + _rung(fill)
    # what a split at the edge would have done: the rung's rows alone
    k2, v2, w2 = (np.asarray(x).copy() for x in (d.keys, d.values, d.weights))
    w2[np.flatnonzero(w2)[edge:]] = 0
    _, split = whole(whole(_wide_pair(how)[0], first)[1], DeviceDelta(
        jnp.asarray(k2), jnp.asarray(v2), jnp.asarray(w2)))
    assert bool(split["error"])


def _sorts(jaxpr, into=None):
    """Rows of every ``sort`` in a jaxpr, those of its inner jaxprs
    (calls, loops, branches) included."""
    into = [] if into is None else into
    for eqn in jaxpr.eqns:
        if eqn.primitive.name == "sort":
            into.append(eqn.invars[0].aval.shape[0])
        for sub in jax.core.jaxprs_in_params(eqn.params):
            _sorts(sub, into)
    return into


def _switches(jaxpr):
    out = [e for e in jaxpr.eqns if e.primitive.name == "cond"]
    for eqn in jaxpr.eqns:
        if eqn.primitive.name != "cond":
            for sub in jax.core.jaxprs_in_params(eqn.params):
                out += _switches(sub)
    return out


def test_the_program_holds_one_merge_a_rung_and_none_more_under_four_times_the_keys():
    """One ``lax.switch`` of as many branches as rungs, each with one
    merge over its own rung (its key sort over ``r`` rows, its lexsort
    over ``S_r x R + r``), behind one sort of the ``CW`` live flags; a
    delta of under four times the key space (``nexmark-q3q4``'s shape:
    the capacity under the keys) lowers to the text of ``_minmax_merge``
    itself."""
    st, core, whole = _wide_pair("min")
    d = _dealt([], [], [])
    jaxpr = jax.make_jaxpr(core)(st, d).jaxpr
    (switch,) = _switches(jaxpr)
    branches = switch.params["branches"]
    assert len(branches) == len(RUNGS)
    for r, br in zip(RUNGS, branches):
        assert sorted(_sorts(br.jaxpr)) == sorted(
            [r, lw._block_slots(r) * 4 + r])
    assert sorted(_sorts(jaxpr)) == sorted(
        [CW] + [n for r in RUNGS for n in (r, lw._block_slots(r) * 4 + r)])

    for k, c in ((KW, 4 * KW - 8), (4096, 512)):
        st, as_core, as_merge = _wide_pair("max", k)
        d = _dealt([], [], [], c)
        text = as_core.lower(st, d).as_text()
        assert text == as_merge.lower(st, d).as_text()
        assert "stablehlo.case" not in text and "stablehlo.if" not in text
        _, st = as_core(st, d)
        assert int(st["counters"][3]) == c
