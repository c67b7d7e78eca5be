"""A reduce writes its keyed tables a block of ``C / 8`` slots at a time
over the tick's compacted live keys (``lowerings._over_blocks``), where
it wrote them once over the delta's capacity. Held here: the sparse
``sum`` / ``count`` / ``mean`` against the whole-capacity scatter form it
had (kept below as the reference, as ``test_nexmark.py`` keeps the
scatter append), ``min`` / ``max`` against the same program with one
block of ``C`` slots and against a plain NumPy model of the candidate
buffers; every state leaf after every tick, every live output row, and
the ``blocks`` counter. Small seeded sizes, CPU."""

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


def _device(keys, vals, w, vshape, dtype):
    """A delta of capacity ``C`` with the given rows dealt over its
    slots in order, dead rows between them."""
    n = len(keys)
    at = np.sort(np.random.default_rng(n).choice(C, n, replace=False))
    k = np.zeros(C, np.int32)
    v = np.zeros((C,) + vshape, dtype)
    ww = np.zeros(C, np.int32)
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
    n_t_all, evicted, blocks = (int(x) for x in st["counters"])
    assert blocks == want_blocks and evicted == model.evicted
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


# -- a delta many times its key space: the merge runs over its live rows ----

KW, CW = 64, 8192                     # a swept join's output over few keys


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
        at = np.sort(rng.choice(CW, n_live, replace=False))
        k = np.zeros(CW, np.int32)
        v = np.zeros(CW, np.float32)
        ww = np.zeros(CW, np.int32)
        k[at], v[at], ww[at] = keys, vals, w
        yield DeviceDelta(jnp.asarray(k), jnp.asarray(v), jnp.asarray(ww))


@pytest.mark.parametrize("n_live", [0, 1, KW - 1, KW, KW + 1, 4 * KW, CW],
                         ids=lambda n: f"rows{n}")
@pytest.mark.parametrize("how", ["min", "max"])
def test_minmax_over_the_live_prefix_equals_the_merge_of_the_whole_delta(
        how, n_live):
    """``minmax_core`` merges ``_merge_rows`` slots where the live rows
    fit them and the whole delta where they do not: the same tables,
    the same rows out, the same keys touched and rows evicted as the
    merge of the whole delta, tick after tick, on either side of the
    choice."""
    assert lw._merge_rows(CW, KW) == KW and lw._merge_rows(2 * KW, KW) == 2 * KW
    g = FlowGraph("wide")
    src = g.source("s", Spec((), np.float32, key_space=KW))
    node = g.reduce(src, how, name="r", candidates=4)
    args = (node.op, KW, (), np.float32)
    st = ref = lw.reduce_state(node.op, node.inputs[0].spec, node.spec)
    core = jax.jit(lambda s, d: lw.minmax_core(*args, s, d))
    whole = jax.jit(lambda s, d: lw._minmax_merge(*args, s, d))
    for d in _wide_ticks(np.random.default_rng(n_live), n_live):
        out, st = core(st, d)
        rout, ref = whole(ref, d)
        _same_leaves({k: v for k, v in st.items() if k != "counters"},
                     {k: v for k, v in ref.items() if k != "counters"})
        _same_live_rows(out, rout)
        assert [int(x) for x in st["counters"][:2]] == [
            int(x) for x in ref["counters"][:2]]
    if n_live >= KW:
        assert int(st["counters"][1]) > 0     # buffers of four overflowed
    text = core.lower(st, d).as_text()
    assert "stablehlo.case" in text or "stablehlo.if" in text
