"""The device Join: its state, its four layouts, its lowering, what it
counts and how room is made for its appends.

The left side of a unique-left join is a dense keyed table
(``lval[K,*VA]``, ``lw[K]``); the right side an append-log arena
(``rkeys[R]``, ``rvals[R,*VB]``, ``rw[R]``, ``rcount``; ``arena`` has
its storage, compaction and indexes). δ(A⋈B) = δA⋈B + (A+δA)⋈δB, with
δA split into its retract / insert halves scattered to dense temp tables
so the arena-side product is a pure gather (the SpMV shape).

**Four layouts, by what the graph says of a join's traffic**:
:func:`join_layout` decides once, at bind; :func:`layout_of` reads the
decision back off a state tree, with which it travels through donation,
checkpoints, rebinds and ``shard_map``. They differ in how δA ⋈ B_old
finds its arena rows:

- ``"multiset"``: a left side that is not unique has no dense table:
  both sides are append arenas and both δ-products key-matched pair
  enumerations at a static budget (``_keyed_product``).
- ``"indexed"``, a unique left in a loop-free graph (NEXmark's and
  TPC-H's joins): the arena is appended to in every tick, tens of
  millions of rows, and probed once a tick, so what finds the rows must
  cost by the append — the chained index (``arena.index_*``), which
  never sorts the arena in a tick. Room for the appends is made between
  ticks (:class:`ArenaRoom`).
- ``"viewed"``, a unique left under a loop (SSSP's relaxation): the
  arena is appended to once a tick (phase A of the fixpoint program;
  ``fixpoint.analyze`` refuses a loop-carried right input) and probed by
  every pass of the loop, each time by the frontier, a few hundred keys
  of tens of thousands — the key-sorted view (``arena.view_*``), one
  sort of the arena a tick and a probe with no chain to walk, where a
  chained key would gain a segment in every tick and only a compaction
  would shorten it. A sort a tick over NEXmark's or TPC-H's arenas would
  cost more than their whole tick.
- ``"swept"``: δA ⋈ B_old gathers by every arena row. A declared-linear
  left's (``Join(linear_left=True)``, PageRank's: the fused linear
  fixpoint keeps a CSR cache of its own and the join runs only its
  phase-A append), and every unique-left join of an executor that keeps
  no index (the sharded one).

The indexed and the viewed layouts count on the device
(``JOIN_COUNTERS``).
"""

from __future__ import annotations

import time
from typing import Callable, Dict, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp

from reflow_tpu.delta import Spec
from reflow_tpu.executors.arena import (compact_arena, index_append,
                                        index_probe, index_state, reindex,
                                        view_budget, view_count, view_probe,
                                        view_sort, view_state)
from reflow_tpu.executors.device_delta import DeviceDelta
from reflow_tpu.graph import Node
from reflow_tpu.obs import trace as _trace
from reflow_tpu.ops import Join

__all__ = ["LAYOUTS", "JOIN_COUNTERS", "ERROR_REASON", "join_layout",
           "join_state", "layout_of", "counts", "join_core", "join_reindex",
           "lower_join", "ArenaRoom"]

LAYOUTS = ("multiset", "swept", "viewed", "indexed")

#: what an indexed or a viewed join counts on the device, in the order
#: of its ``counters`` state leaf (``lowerings.OP_COUNTERS["join"]``;
#: int32 each, cumulative since bind; new names are appended: readers go
#: by position). Both layouts keep every name and leave at 0 what they
#: do not do.
#:
#: - ``pairs``: live rows emitted, all products.
#: - ``late_pairs``: those of them that δA ⋈ B_old found (a left row
#:   that arrived after its matches).
#: - ``arena_rows``: the arena's rows now (a level, not a sum).
#: - ``index_rebuilds``, ``compactions``: an indexed join's
#:   :func:`join_reindex` runs (each is both); a viewed join's in-program
#:   compactions (``compactions`` alone).
#: - ``probe_steps``: trips of an indexed probe's chain walk, each a
#:   pass over its pair slots.
#: - ``probes``, ``sweeps``: a viewed join's passes with a left delta
#:   (under a fixpoint every pass but a tick's first) are one of the
#:   two: enumerated through the view, or, where the probed keys hold
#:   more arena rows than ``arena.view_budget``, gathered by every arena
#:   row as a join without a view does.
#: - ``swept_rows``: the slots those passes read, ``2 x arena_capacity``
#:   a sweep, live or not, and twice the budget a probe (wraps after
#:   2^31 slots: a reader differences window by window, modulo 2^32).
#: - ``left_rows``: a viewed join's live left-delta rows folded into its
#:   table (retractions and inserts: under a loop, the frontier).
#: - ``retracted``: rows appended to the arena with a negative weight (a
#:   right-side retraction is a row of the log until a compaction cancels
#:   it against its insert): what fills an arena whose live rows stay
#:   level.
JOIN_COUNTERS = ("pairs", "late_pairs", "arena_rows", "index_rebuilds",
                 "compactions", "probe_steps", "sweeps", "swept_rows",
                 "left_rows", "retracted", "probes")

#: what a join's sticky ``error`` leaf can mean (``check_errors``)
ERROR_REASON = (
    "join sticky error: an arena overflowed (live rows + appends exceeded "
    "capacity even after compaction, in-program or, for an indexed arena, "
    "between windows — raise arena_capacity / left_arena_capacity); or a "
    "multiset-left or indexed delta-by-arena product exceeded its pair "
    "budget of product_slack x delta capacity (raise product_slack); or, "
    "under a sharded executor, sparse routing overflowed its "
    "per-destination budget (key skew — raise delta capacity or rebalance "
    "the key space); or a downstream GroupBy's stable_key=True declaration "
    "was violated (its key_fn read the loop value — the fused fixpoint's "
    "dense tier caught a precomputed/runtime destination mismatch); this "
    "tick's state is invalid")


# -- the layout: decided once, read back off the state ---------------------

def join_layout(op: Join, left_spec: Spec, *, looped: bool,
                index: bool = True) -> str:
    """The layout of a join's device state (module docstring), from the
    left input's ``Spec.unique``, the op's ``linear_left``, whether the
    graph has loops, and whether the executor keeps indexes at all."""
    if not left_spec.unique:
        return "multiset"
    if not index or op.linear_left:
        return "swept"
    return "viewed" if looped else "indexed"


def join_state(op: Join, left_spec: Spec, right_spec: Spec,
               layout: str) -> dict:
    """A join's empty device state in ``layout`` (one of ``LAYOUTS``)."""
    if layout not in LAYOUTS:
        raise ValueError(f"join layout {layout!r}: one of {LAYOUTS}")
    K = left_spec.key_space
    R = op.arena_capacity
    arena = {
        "rkeys": jnp.zeros((R,), jnp.int32),
        "rvals": jnp.zeros((R,) + tuple(right_spec.value_shape),
                           right_spec.value_dtype),
        "rw": jnp.zeros((R,), jnp.int32),
        "rcount": jnp.zeros((), jnp.int32),
        # arena generation: bumped by every compaction (which reorders
        # rows). The linear fixpoint's persistent CSR cache keys its
        # validity on (gen, rcount): a gen mismatch means the base
        # ordering is gone and the CSR must rebuild.
        "gen": jnp.zeros((), jnp.int32),
        # sticky: set when an append overflows the arena even after the
        # compaction pass (checked loudly at the next sync)
        "error": jnp.zeros((), jnp.bool_),
    }
    if layout == "multiset":
        # the left side is a second append arena mirroring the right
        # side's log — a multiset has no per-key value to store densely
        La = op.left_arena_capacity or op.arena_capacity
        return {
            "lkeys": jnp.zeros((La,), jnp.int32),
            "lvals": jnp.zeros((La,) + tuple(left_spec.value_shape),
                               left_spec.value_dtype),
            "lrw": jnp.zeros((La,), jnp.int32),
            "lcount": jnp.zeros((), jnp.int32),
            "lgen": jnp.zeros((), jnp.int32),
            **arena,
        }
    extra = {}
    if layout in ("indexed", "viewed"):
        extra = dict(
            index_state(K, R) if layout == "indexed" else view_state(K, R),
            counters=jnp.zeros((len(JOIN_COUNTERS),), jnp.int32))
    return {
        **extra,
        "lval": jnp.zeros((K,) + tuple(left_spec.value_shape),
                          left_spec.value_dtype),
        "lw": jnp.zeros((K,), jnp.int32),
        **arena,
    }


def layout_of(state) -> str:
    """The layout a join's state tree is in: the only place that tells
    one from its leaves."""
    if "lkeys" in state:
        return "multiset"
    if "head" in state:
        return "indexed"
    if "view_order" in state:
        return "viewed"
    return "swept"


# -- counters, by name -----------------------------------------------------

def counts(**named) -> jax.Array:
    """Named counts as one int32 vector in ``JOIN_COUNTERS`` order, 0
    for every name not given."""
    unknown = set(named) - set(JOIN_COUNTERS)
    if unknown:
        raise KeyError(f"no join counter named {sorted(unknown)}")
    return jnp.stack([jnp.asarray(named.get(name, 0), jnp.int32)
                      for name in JOIN_COUNTERS])


def _counted(counters: jax.Array, arena_rows=None, **added) -> jax.Array:
    """``counters`` with the named counts added and, where given, the
    level ``arena_rows`` set."""
    out = counters + counts(**added)
    if arena_rows is not None:
        out = out.at[JOIN_COUNTERS.index("arena_rows")].set(arena_rows)
    return out


# -- the kernels -----------------------------------------------------------

def lower_join(op: Join, node: Node, state, ins) -> Tuple[DeviceDelta, dict]:
    da, db = ins
    left_spec = node.inputs[0].spec
    return join_core(op, left_spec.key_space, op.arena_capacity,
                     node.spec.value_dtype, state, da, db,
                     oshape=tuple(node.spec.value_shape))


def _append_arena(arena: dict, keys, vals, w, R,
                  recount: Optional[Callable[[dict], dict]] = None
                  ) -> Tuple[dict, jax.Array, jax.Array]:
    """Append live delta rows to an append-log arena (compacted: live
    rows first): the one dense append, the right arena's in every layout
    but the indexed and the multiset-left arena's (which aliases its
    fields to the rkeys/... names). The high-water check is IN-PROGRAM:
    when the append would cross capacity a ``lax.cond`` compacts first,
    so no device value is read back and streaming ticks stay pipelined
    (SURVEY.md §7 hard part d); rows that do not fit even then are
    dropped. ``recount(arena) -> leaves`` recomputes, behind a
    compaction, what the caller keeps beside the rows (the viewed
    layout's ``view_deg``). -> (arena', overflow, the row each delta row
    went to, ``R`` or past it where dropped)."""
    compacted = compact_arena
    if recount is not None:
        def compacted(s):
            s = compact_arena(s)
            return dict(s, **recount(s))

    live = w != 0
    n_app = jnp.sum(live.astype(jnp.int32))
    arena = jax.lax.cond(arena["rcount"] + n_app > R,
                         compacted, lambda s: s, arena)
    rank = jnp.cumsum(live.astype(jnp.int32)) - 1
    pos = jnp.where(live, arena["rcount"] + rank, R)
    out = dict(arena)
    out["rkeys"] = arena["rkeys"].at[pos].set(keys, mode="drop")
    out["rvals"] = arena["rvals"].at[pos].set(vals, mode="drop")
    out["rw"] = arena["rw"].at[pos].set(w, mode="drop")
    out["rcount"] = arena["rcount"] + n_app
    return out, out["rcount"] > R, pos


def _cat_deltas(rows: Sequence[DeviceDelta]) -> DeviceDelta:
    return DeviceDelta(
        jnp.concatenate([o.keys for o in rows]),
        jnp.concatenate([o.values for o in rows]),
        jnp.concatenate([o.weights for o in rows]),
    )


#: an arena's leaves -> the multiset-left arena's names for them
_LEFT_ARENA = {"rkeys": "lkeys", "rvals": "lvals", "rw": "lrw",
               "rcount": "lcount", "gen": "lgen"}


def _join_core_multiset(op: Join, K: int, R: int, state,
                        da: Optional[DeviceDelta],
                        db: Optional[DeviceDelta], merge_v,
                        key_offset) -> Tuple[DeviceDelta, dict]:
    """Two-arena join: both sides are append logs; both δ-products are
    key-matched pair enumerations (δA against the old right arena, δB
    against the post-fold left arena — the bilinear update δA⋈B +
    (A+δA)⋈δB) at static budgets of ``product_slack x delta_capacity``
    pair slots. Sticky error on budget or arena overflow."""
    err = state["error"]
    new_state = dict(state)
    outs = []

    if da is not None:
        out_a, ovf = _keyed_product(
            da.keys, da.values, da.weights,
            state["rkeys"], state["rvals"], state["rw"],
            K, op.product_slack * da.capacity,
            lambda k, vd, va_: merge_v(k - key_offset, vd, va_),
            key_offset)
        err = err | ovf
        outs.append(out_a)
        larena = {r: state[l] for r, l in _LEFT_ARENA.items()}
        La = state["lkeys"].shape[0]
        larena, lovf, _ = _append_arena(larena, da.keys, da.values,
                                        da.weights, La)
        err = err | lovf
        new_state.update({l: larena[r] for r, l in _LEFT_ARENA.items()})

    if db is not None:
        # (A + δA) ⋈ δB : delta is the RIGHT side, arena the LEFT — swap
        # the value argument order back to merge(k, va, vb)
        out_b, ovf = _keyed_product(
            db.keys, db.values, db.weights,
            new_state["lkeys"], new_state["lvals"], new_state["lrw"],
            K, op.product_slack * db.capacity,
            lambda k, vd, va_: merge_v(k - key_offset, va_, vd),
            key_offset)
        err = err | ovf
        outs.append(out_b)
        rarena = {r: state[r] for r in _LEFT_ARENA}
        rarena, rovf, _ = _append_arena(rarena, db.keys, db.values,
                                        db.weights, R)
        err = err | rovf
        new_state.update(rarena)

    out = _cat_deltas(outs)
    new_state["error"] = err
    return out, new_state


def _keyed_product(dk, dv, dw, ak, av, aw, K: int, T: int, emit,
                   key_offset) -> Tuple[DeviceDelta, jax.Array]:
    """Key-matched delta×arena pair enumeration at static budget ``T``.

    For each live delta row i, pair it with every live arena row sharing
    its key; pairs pack into ``T`` slots via the same scatter-of-starts +
    cumsum slot assignment the fused fixpoint's budget tiers use
    (linear_fixpoint.budget_tab — measured ~13x over searchsorted at 1M
    slots). A true pair count beyond ``T`` returns overflow=True (the
    caller sets the sticky error; never silent truncation).
    ``emit(keys_global, v_delta, v_arena)`` -> merged values [T, ...].
    """
    C = dk.shape[0]
    R = ak.shape[0]
    # CSR over the arena by key: the viewed layout's, built per call
    order = view_sort(ak, aw, K)
    deg = view_count(ak, aw, K)
    starts = jnp.cumsum(deg) - deg
    # per-delta-row segment geometry
    k_c = jnp.clip(dk, 0, K - 1)
    di = jnp.where(dw != 0, deg[k_c], 0)
    cum = jnp.cumsum(di)
    total = cum[-1]
    seg0 = cum - di
    overflow = total > T
    # slot -> owning delta ROW INDEX: scatter each segment's row index at
    # its start slot, forward-fill with a running max (row indices rise
    # with slot position, so cummax is exactly last-segment-started; a
    # segment-ORDINAL cumsum would be wrong whenever dead/unmatched delta
    # rows interleave with live ones, e.g. after sharded _localize)
    spos = jnp.where(di > 0, seg0, T)
    marks = jnp.zeros((T,), jnp.int32).at[spos].max(
        jnp.arange(C, dtype=jnp.int32), mode="drop")
    owner = jnp.clip(jax.lax.cummax(marks), 0, C - 1)
    j = jnp.arange(T, dtype=jnp.int32)
    within = j - seg0[owner]
    valid = (j < total) & (di[owner] > 0) & (within < di[owner])
    srow = jnp.clip(starts[k_c[owner]] + within, 0, R - 1)
    row = order[srow]
    k = k_c[owner]
    w = jnp.where(valid, dw[owner] * aw[row], 0)
    vals = emit(k + key_offset, dv[owner], av[row])
    return DeviceDelta(k + key_offset, vals, w), overflow


def _join_core_indexed(op: Join, K: int, R: int, state,
                       da: Optional[DeviceDelta], db: Optional[DeviceDelta],
                       merge_v, key_offset) -> Tuple[DeviceDelta, dict]:
    """Unique-left join over an indexed arena (``arena.index_*``): the
    same bilinear update as the dense path, δA ⋈ B_old + (A+δA) ⋈ δB,
    with the first product a key-matched pair enumeration at a static
    budget of ``product_slack x delta capacity`` slots (sticky error
    past it), so a tick's output and cost follow the delta. Room for the
    appends is made between ticks (:func:`join_reindex`): an append past
    the arena's end latches the sticky error."""
    st = dict(state)
    err = state["error"]
    outs = []
    zero = jnp.zeros((), jnp.int32)
    late = pairs = steps = retracted = zero

    if da is not None:
        with jax.named_scope("join.probe"):
            wa = da.weights
            own, row, valid, ovf, steps = index_probe(
                st, da.keys, wa != 0, op.product_slack * da.capacity)
            err = err | ovf
            k = jnp.clip(da.keys, 0, K - 1)[own]
            w = jnp.where(valid, wa[own] * st["rw"][row], 0)
            vals = merge_v(k, da.values[own], st["rvals"][row])
            outs.append(DeviceDelta(k + key_offset, vals, w))
            late = jnp.sum((w != 0).astype(jnp.int32))
            # fold δA into the left table
            st["lw"] = st["lw"].at[da.keys].add(wa)
            st["lval"] = st["lval"].at[
                jnp.where(wa > 0, da.keys, K)].set(da.values, mode="drop")

    if db is not None:
        with jax.named_scope("join.append"):
            kb, vb, wb = db.keys, db.values, db.weights
            w = st["lw"][kb] * wb
            vals = merge_v(kb, st["lval"][kb], vb)
            outs.append(DeviceDelta(kb + key_offset, vals, w))
            pairs = jnp.sum((w != 0).astype(jnp.int32))
            retracted = jnp.sum((wb < 0).astype(jnp.int32))
            st, ovf = index_append(st, kb, vb, wb)
            err = err | ovf

    st["error"] = err
    st["counters"] = _counted(
        state["counters"], arena_rows=st["rcount"], pairs=pairs + late,
        late_pairs=late, probe_steps=steps, retracted=retracted)
    out = _cat_deltas(outs)
    return out, st


def join_reindex(state: dict) -> dict:
    """Compact an indexed join's arena and rebuild its index
    (``arena.reindex``), counted: the program :class:`ArenaRoom` runs
    between ticks when the arena might not hold a window's appends."""
    st = reindex(state)
    st["counters"] = _counted(st["counters"], index_rebuilds=1,
                              compactions=1)
    return st


def _view_product(state: dict, halves, sweep, merge_v, key_offset
                  ) -> Tuple[DeviceDelta, jax.Array, jax.Array]:
    """δA ⋈ B_old of a unique-left join that keeps the key-sorted view
    of its arena (``arena.view_*``; a join under a loop): -> (its rows,
    how many are live, 1 if the view gave them and 0 if the sweep did).

    ``halves`` are the left delta's retract and insert rows as dense
    ``(value [K], weight [K])`` tables, ``sweep()`` the gather of both
    by every arena row. The view gives the arena rows of the keys either
    half holds, laid into ``view_budget`` slots (``view_probe``), each
    paired with both halves exactly as the sweep pairs it (same tables,
    same ``merge``, dead and negative-weight arena rows alike), behind
    them weight 0 up to the sweep's ``2 R`` slots, so whoever reads the
    rows sees one capacity. What the probe would lay out (those rows,
    each once: a retracted and re-inserted key shares its slots between
    the halves) decides on the device (``lax.cond``): past the budget
    the pass sweeps. Nothing is dropped, nothing latches, and a pass's
    rows are never split between the two."""
    av, aw = state["rvals"], state["rw"]
    K, R = state["view_deg"].shape[0], aw.shape[0]
    T = view_budget(K, R)
    held = halves[0][1] != 0
    for _, dw in halves[1:]:
        held = held | (dw != 0)
    n_slots = jnp.sum(jnp.where(held, state["view_deg"], 0))

    def probe():
        with jax.named_scope("join.view_probe"):
            k, row, valid = view_probe(state, held, T)
            a_v, a_w = av[row], jnp.where(valid, aw[row], 0)
            rows = [DeviceDelta(k + key_offset, merge_v(k, tab[k], a_v),
                                dw[k] * a_w) for tab, dw in halves]
            vals = rows[0].values
            pad = 2 * (R - T)
            rows.append(DeviceDelta(
                jnp.zeros((pad,), rows[0].keys.dtype),
                jnp.zeros((pad,) + vals.shape[1:], vals.dtype),
                jnp.zeros((pad,), jnp.int32)))
            return _cat_deltas(rows)

    probed = n_slots <= T
    out = jax.lax.cond(probed, probe, lambda: _cat_deltas(sweep()))
    return out, out.nonzero(), probed.astype(jnp.int32)


def join_core(op: Join, K: int, R: int, odtype, state,
              da: Optional[DeviceDelta], db: Optional[DeviceDelta],
              key_offset=0, oshape=None) -> Tuple[DeviceDelta, dict]:
    """The join kernel over a (possibly per-shard) key range, in the
    layout ``state`` is in (:func:`layout_of`).

    ``da``/``db`` carry keys LOCAL to this range ``[0, K)``;
    ``key_offset`` maps them back to global ids on emitted rows and in the
    arguments handed to ``merge`` (the sharded path passes the shard base;
    single-device passes 0). A ``None`` side is *statically* absent: the
    corresponding product, fold, and append are not traced at all — a tick
    that only delivers right-side deltas (the steady churn shape) never
    sweeps the arena, and a loop pass with no right deltas never appends.

    The multiset and the indexed layouts have cores of their own; the
    swept and the viewed share the table×arena path below, on which a
    viewed state takes δA ⋈ B_old through its view where the pass's keys
    fit the budget (:func:`_view_product`) and re-sorts it behind every
    append.
    """

    def merge_v(keys, va, vb):
        if op.merge is None:
            # default merge (multiset path): concatenate the flattened
            # value pair — the device encoding of the host oracle's
            # (va, vb) tuple (same flat components, same order)
            n = va.shape[0]
            out = jnp.concatenate(
                [jnp.asarray(va, odtype).reshape(n, -1),
                 jnp.asarray(vb, odtype).reshape(n, -1)], axis=-1)
            return out.reshape((n,) + tuple(oshape))
        out = op.merge(keys + key_offset, va, vb)
        return jnp.asarray(out, odtype)

    layout = layout_of(state)
    if layout == "multiset":
        return _join_core_multiset(op, K, R, state, da, db, merge_v,
                                   key_offset)
    if layout == "indexed":
        return _join_core_indexed(op, K, R, state, da, db, merge_v,
                                  key_offset)

    ak, av, aw = state["rkeys"], state["rvals"], state["rw"]
    lval, lw = state["lval"], state["lw"]
    viewed = layout == "viewed"
    outs = []
    zero = jnp.zeros((), jnp.int32)
    late = probed = zero

    if da is not None:
        # split δA into its retract / insert halves, scattered dense
        wa = da.weights
        ret_keys = jnp.where(wa < 0, da.keys, K)
        ins_keys = jnp.where(wa > 0, da.keys, K)
        zero_val = jnp.zeros((K,) + da.values.shape[1:], da.values.dtype)
        zero_w = jnp.zeros((K,), jnp.int32)
        dval_r = zero_val.at[ret_keys].set(da.values, mode="drop")
        dw_r = zero_w.at[ret_keys].set(wa, mode="drop")
        dval_i = zero_val.at[ins_keys].set(da.values, mode="drop")
        dw_i = zero_w.at[ins_keys].set(wa, mode="drop")
        halves = ((dval_r, dw_r), (dval_i, dw_i))

        def sweep():
            # δA ⋈ B_old : pure gather over the arena (the SpMV)
            rows = []
            for tab, dw in halves:
                w = dw[ak] * aw
                vals = merge_v(ak, tab[ak], av)
                rows.append(DeviceDelta(ak + key_offset, vals, w))
            return rows

        if viewed:
            out_a, late, probed = _view_product(state, halves, sweep,
                                                merge_v, key_offset)
            outs.append(out_a)
        else:
            outs += sweep()

        # fold δA into the left table
        lw = lw.at[da.keys].add(wa)
        lval = lval.at[ins_keys].set(da.values, mode="drop")

    arena = {"rkeys": ak, "rvals": av, "rw": aw, "rcount": state["rcount"],
             "gen": state["gen"]}
    err = state.get("error", jnp.zeros((), jnp.bool_))
    view = ({"view_order": state["view_order"],
             "view_deg": state["view_deg"]} if viewed else {})
    if db is not None:
        # (A + δA) ⋈ δB
        kb, vb, wb = db.keys, db.values, db.weights
        w = lw[kb] * wb
        vals = merge_v(kb, lval[kb], vb)
        db_out = DeviceDelta(kb + key_offset, vals, w)
        outs.append(db_out)

        # append δB to the arena; the view follows it: recounted behind
        # a compaction, else the append's own rows counted in (a scatter
        # of the delta's slots), and the order sorted anew
        recount = None
        if viewed:
            arena["view_deg"] = state["view_deg"]

            def recount(s):
                return {"view_deg": view_count(s["rkeys"], s["rw"], K)}
        arena, ovf, pos = _append_arena(arena, kb, vb, wb, R, recount)
        err = err | ovf
        if viewed:
            with jax.named_scope("join.view_sort"):
                view = {
                    "view_order": view_sort(arena["rkeys"], arena["rw"], K),
                    "view_deg": arena["view_deg"].at[
                        jnp.where(pos < R, jnp.clip(kb, 0, K - 1), K)
                    ].add(1, mode="drop")}

    out = _cat_deltas(outs)
    new_state = {"lval": lval, "lw": lw, **arena, "error": err, **view}
    if viewed:
        # whether a pass has a left delta is static, which way its
        # product went is the device's. A sweep passes over the arena's
        # whole capacity twice (retracted and inserted left rows); a
        # probe lays each of the two into its budget of slots
        swept = (0 if da is None else 1) - probed
        new_state["counters"] = _counted(
            state["counters"], arena_rows=arena["rcount"],
            pairs=late + (db_out.nonzero() if db is not None else zero),
            late_pairs=late, compactions=arena["gen"] - state["gen"],
            sweeps=swept, probes=probed,
            swept_rows=swept * 2 * R + probed * 2 * view_budget(K, R),
            left_rows=da.nonzero() if da is not None else zero,
            retracted=(jnp.sum((db.weights < 0).astype(jnp.int32))
                       if db is not None else zero))
    return out, new_state


# -- room for an indexed arena's appends, made between ticks ---------------

class ArenaRoom:
    """Room for what the indexed joins of one executor's graph append.

    Their tick program only appends; compacting an arena and rebuilding
    its index is :func:`join_reindex`, a program of its own (the sort of
    a whole arena is most of a tick program's code and compile time, and
    a tick never needs it), run from :meth:`make` when an arena might
    not hold the appends. The host keeps an upper bound of each arena's
    rows (every tick adds its right delta's whole capacity) and reads
    the true count from the device only when the bound reaches the end:
    one sync per ``arena_capacity`` rows of capacity dispatched."""

    def __init__(self):
        #: the bound graph's indexed joins, by node id
        self.joins: Dict[int, Node] = {}
        #: node id -> the host's upper bound of its arena's rows
        self._used: Dict[int, int] = {}
        #: state shapes -> ``join_reindex`` compiled ahead of time
        self._programs: Dict[tuple, object] = {}

    def bind(self, graph, states: dict) -> None:
        """Take ``graph``'s joins whose state in ``states`` is indexed."""
        self.joins = {
            n.id: n for n in graph.nodes
            if n.kind == "op" and n.op.kind == "join"
            and layout_of(states[n.id]) == "indexed"}
        self.forget()

    def forget(self) -> None:
        """New states (bind, a restored checkpoint, a snapshot) may
        hold arenas of any fill: the bounds are void."""
        self._used = {}

    def _program(self, state: dict):
        """``join_reindex`` compiled ahead of time for ``state``'s shapes
        (and device) and kept by them: joins of one shape share it."""
        sig = tuple((name, x.shape, str(x.dtype), str(x.sharding))
                    for name, x in sorted(state.items()))
        prog = self._programs.get(sig)
        if prog is None:
            prog = jax.jit(join_reindex, donate_argnums=0).lower(
                state).compile()
            self._programs[sig] = prog
        return prog

    def compile(self, states: dict) -> None:
        """Every join's reindex program, now (beside the first window
        program: making room between two served windows then dispatches
        and never compiles)."""
        for nid in sorted(self.joins):
            self._program(states[nid])

    def _rows(self, node: Node, state: dict) -> int:
        """An arena's true row count, read from the device: it waits for
        every window dispatched so far (``arena_rcount_read``)."""
        tr = _trace.ENABLED
        t0 = time.perf_counter() if tr else 0.0
        used = int(state["rcount"])
        if tr:
            _trace.evt("arena_rcount_read", t0, time.perf_counter() - t0,
                       args=_trace.with_win(
                           {"node": node.name, "rows": used}))
        return used

    def make(self, states: dict, caps: Dict[int, int], ticks: int) -> None:
        """Before ``ticks`` ticks at the per-node capacities ``caps``
        (``arena.propagate_plan_caps``'): every join's arena in ``states`` has room
        for what they can append, reindexed in place where it had not.
        A ``join_reindex`` span runs from the program's dispatch to the
        count read behind it, which is when the device finished it."""
        for nid, node in self.joins.items():
            need = ticks * caps.get(node.inputs[1].id, 0)
            if not need:
                continue
            R = node.op.arena_capacity
            used = self._used.get(nid)
            if used is None or used + need > R:
                used = self._rows(node, states[nid])
            if used + need > R:
                before = used
                t0 = time.perf_counter()
                states[nid] = self._program(states[nid])(states[nid])
                used = int(states[nid]["rcount"])
                _trace.evt("join_reindex", t0, time.perf_counter() - t0,
                           args=_trace.with_win(
                               {"node": node.name, "rows_before": before,
                                "rows_after": used}))
            self._used[nid] = used + need
