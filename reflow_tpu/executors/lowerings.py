"""Per-op XLA lowerings: one tick pass = pure array code (SURVEY.md §7.7).

Each lowering is a pure function ``(op, node, state, in_deltas) ->
(out_delta, state')`` over :class:`DeviceDelta` buffers and dense keyed
state tables. Design rules (tpu-first):

- **No data-dependent shapes.** Emission capacities are static functions of
  input capacities and key-space sizes; dead rows carry weight 0.
- **No host round-trips.** Everything here runs inside one ``jax.jit`` step.
- **NaN hygiene.** Padding rows may hold garbage values; every consumption
  multiplies through a ``where(w == 0, 0, ...)`` guard so garbage never
  reaches live state.

Keyed-state representations:

- Reduce (linear reducers sum/count/mean): dense tables over the key space —
  ``wsum[K,*V]`` (Σ w·v), ``wcnt[K]`` (Σ w), ``emitted[K,*V]`` +
  ``emitted_has[K]`` (the last aggregate actually emitted downstream, for
  retract-correctness under ``tol`` — mirrors the host oracle exactly).
- Join: a dense left table or a second arena, an append-log right arena
  and, by the join's layout, an index over it — ``join`` has the state,
  the layouts and the kernel; this module only sends ``join`` nodes there.

Non-linear reducers (min/max) lower to a bounded per-key candidate buffer
(``minmax_core``) holding the R lex-best distinct value rows per key with
their multiset weights: retractions stay EXACT while the answer is
derivable from the buffer, and cross into a sticky loud error when churn
exhausts it (SURVEY.md §7 hard part c: bounded per-key multisets, loud
failure beyond the bound). Scalar and vector values share the kernel —
rows are ordered lexicographically, the host oracle's tuple order.
"""

from __future__ import annotations

from typing import Optional, Sequence, Tuple

import jax
import jax.numpy as jnp

from reflow_tpu.delta import Spec
from reflow_tpu.executors.device_delta import DeviceDelta
from reflow_tpu.executors.join import JOIN_COUNTERS, lower_join
from reflow_tpu.graph import Node
from reflow_tpu.ops import Filter, GroupBy, Map, Reduce, Union

__all__ = ["lower_node", "reduce_state", "knn_state", "minmax_core",
           "minmax_refresh_core", "DEVICE_REDUCERS", "OP_COUNTERS"]

#: sum/count/mean lower to linear scatter-adds; min/max lower to the
#: bounded candidate-buffer kernel (retraction-exact within the per-key
#: buffer, sticky loud error beyond it — raise Reduce(candidates=...) or
#: run pathological churn on the CPU oracle)
DEVICE_REDUCERS = ("sum", "count", "mean", "min", "max")
LINEAR_DEVICE_REDUCERS = ("sum", "count", "mean")


# -- state builders --------------------------------------------------------

def reduce_state(op: Reduce, in_spec: Spec, out_spec: Spec) -> dict:
    K = in_spec.key_space
    vshape = tuple(in_spec.value_shape)
    oshape = tuple(out_spec.value_shape)
    if op.how not in LINEAR_DEVICE_REDUCERS:
        # min/max, scalar AND vector: retraction-capable candidate buffer
        # with lexicographic row ordering (the host oracle's tuple order)
        return minmax_state(op, K, vshape, oshape, out_spec.value_dtype,
                            in_spec.value_dtype)
    return {
        "wsum": jnp.zeros((K,) + vshape, jnp.float32),
        "wcnt": jnp.zeros((K,), jnp.int32),
        "emitted": jnp.zeros((K,) + oshape, out_spec.value_dtype),
        "emitted_has": jnp.zeros((K,), jnp.bool_),
    }


# -- helpers ---------------------------------------------------------------

def _bcast_w(w: jax.Array, values: jax.Array) -> jax.Array:
    """weights [C] broadcast against values [C, *V]."""
    return w.reshape(w.shape + (1,) * (values.ndim - 1))


def _masked_contrib(w: jax.Array, values: jax.Array) -> jax.Array:
    """w·v with an explicit zero at w==0 so padding NaNs never propagate."""
    wb = _bcast_w(w, values)
    return jnp.where(wb == 0, 0, wb.astype(values.dtype) * values)


def _differs(a: jax.Array, b: jax.Array, tol: float) -> jax.Array:
    """Per-key 'aggregates differ' over trailing value axes."""
    if tol > 0.0:
        d = jnp.abs(a - b) > tol
    else:
        d = a != b
    if d.ndim > 1:
        d = jnp.any(d, axis=tuple(range(1, d.ndim)))
    return d


# -- Map / Filter / GroupBy / Union ----------------------------------------

def _apply_rowfn(fn, vectorized: bool, *cols):
    if vectorized:
        return fn(*cols)
    return jax.vmap(fn)(*cols)


def _lower_map(op: Map, node: Node, state, ins) -> Tuple[DeviceDelta, None]:
    (d,) = ins
    if op.params is not None:
        # params flow in as op STATE (a program argument), never as traced
        # constants — program size stays independent of the model size and
        # params swap without recompiling. State passes through unchanged.
        p = state["params"]
        if op.vectorized:
            vals = op.fn(p, d.values)
        else:
            vals = jax.vmap(op.fn, in_axes=(None, 0))(p, d.values)
        return (DeviceDelta(d.keys, jnp.asarray(vals, node.spec.value_dtype),
                            d.weights), state)
    vals = _apply_rowfn(op.fn, op.vectorized, d.values)
    vals = jnp.asarray(vals, node.spec.value_dtype)
    return DeviceDelta(d.keys, vals, d.weights), None


def _lower_filter(op: Filter, node: Node, state, ins) -> Tuple[DeviceDelta, None]:
    (d,) = ins
    keep = _apply_rowfn(op.pred, op.vectorized, d.values)
    w = jnp.where(jnp.asarray(keep, jnp.bool_), d.weights, 0)
    return DeviceDelta(d.keys, d.values, w), None


def _lower_groupby(op: GroupBy, node: Node, state, ins) -> Tuple[DeviceDelta, None]:
    (d,) = ins
    keys = jnp.asarray(
        _apply_rowfn(op.key_fn, op.vectorized, d.keys, d.values), jnp.int32)
    # keep padding rows at key 0 so downstream scatters stay in range
    keys = jnp.where(d.weights == 0, 0, keys)
    vals = d.values
    if op.value_fn is not None:
        vals = jnp.asarray(
            _apply_rowfn(op.value_fn, op.vectorized, d.keys, d.values),
            node.spec.value_dtype)
    return DeviceDelta(keys, vals, d.weights), None


def _lower_union(op: Union, node: Node, state, ins) -> Tuple[DeviceDelta, None]:
    live = [d for d in ins if d is not None]  # absent streams vanish
    return DeviceDelta(
        jnp.concatenate([d.keys for d in live]),
        jnp.concatenate([d.values for d in live]),
        jnp.concatenate([d.weights for d in live]),
    ), None


# -- Reduce ----------------------------------------------------------------

def _agg_tables(op: Reduce, wsum, wcnt, vdtype):
    """(aggregate, exists) per key from the running linear tables.

    Existence mirrors the host oracle's linear-observable rule (see
    ``Reduce._aggregate``): a group exists iff Σw != 0 or Σw·v != 0. For
    sum with ``tol > 0`` the Σw·v test is tol-guarded, so float scatter-add
    residue after a full retraction doesn't leave a phantom group behind
    (with tol == 0 the contract is exact float equality; use a small tol
    for float workloads on device).
    """
    if op.how == "sum":
        agg = jnp.asarray(wsum, vdtype)
        nz = jnp.abs(wsum) > op.tol if op.tol > 0.0 else wsum != 0
        if nz.ndim > 1:
            nz = jnp.any(nz, axis=tuple(range(1, nz.ndim)))
        exists = (wcnt != 0) | nz
    elif op.how == "count":
        agg = jnp.asarray(wcnt, vdtype)
        exists = wcnt != 0
    elif op.how == "mean":
        denom = jnp.where(wcnt == 0, 1, wcnt)
        agg = jnp.asarray(wsum / _bcast_w(denom, wsum), vdtype)
        exists = wcnt != 0
    else:  # pragma: no cover - validated at bind
        raise NotImplementedError(op.how)
    return agg, exists


def _lex_lt(a: jax.Array, b: jax.Array) -> jax.Array:
    """Lexicographic ``a < b`` over the trailing axis (equal -> False).

    The host oracle's min/max of vector values is the MIN of value
    TUPLES (ops/core.py ``_agg_min``: Python tuple ordering), so the
    device path orders candidate rows lexicographically too — NOT
    elementwise extrema, which would fabricate a vector that is in no
    row of the multiset.
    """
    neq = a != b
    has = jnp.any(neq, axis=-1)
    fi = jnp.argmax(neq, axis=-1)
    av = jnp.take_along_axis(a, fi[..., None], axis=-1)[..., 0]
    bv = jnp.take_along_axis(b, fi[..., None], axis=-1)[..., 0]
    return jnp.where(has, av < bv, False)


def _cand_dtype(in_dtype):
    """Candidates ride in the input's own dtype when that is a 32-bit
    integer (a float32 holds integers exactly only up to 2^24, and a
    maximum must be one of the rows), else in float32."""
    return jnp.int32 if jnp.dtype(in_dtype) == jnp.int32 else jnp.float32


def _cand_top(cdt):
    """The value no candidate can have: +inf, or the largest int32 (an
    integer input therefore lies strictly inside ±(2^31 - 1))."""
    return (jnp.inf if cdt == jnp.float32
            else jnp.iinfo(jnp.int32).max)


def _block_slots(C: int) -> int:
    """Slots of a delta of capacity ``C`` that a reduce takes at a time
    when it writes its keyed tables (``_over_blocks``)."""
    return C // 8 if C >= 256 and C % 8 == 0 else C


def _block(x: jax.Array, lo, S: int) -> jax.Array:
    return jax.lax.dynamic_slice_in_dim(x, lo, S)


def _put_block(x: jax.Array, blk: jax.Array, lo) -> jax.Array:
    return jax.lax.dynamic_update_slice_in_dim(x, blk, lo, 0)


def _over_blocks(C: int, n, body, carry):
    """``carry`` through ``body(lo, carry)`` for every block of
    ``_block_slots(C)`` slots that the prefix ``[0, n)`` of a delta's
    ``C`` compacted slots reaches, in order: -> (carry, trips). A
    scatter into a keyed table costs by the slots of its update, live
    or dropped, and a delta of ``C`` rows rarely holds ``C`` keys (a
    stream join's output is mostly its budget's empty slots, a filter
    keeps its input's capacity), so a reduce writes its tables block by
    block over the slots the tick fills and not once over ``C``. The
    tables ride in ``carry`` and every block reads them there, so they
    are updated in place; a full delta takes 8 blocks over the same
    ``C`` slots. Where a block is the whole delta the body runs once,
    with no loop."""
    S = _block_slots(C)
    zero = jnp.zeros((), jnp.int32)
    if S == C:
        return body(zero, carry), zero + 1
    lo, carry = jax.lax.while_loop(
        lambda c: c[0] < n, lambda c: (c[0] + S, body(*c)), (zero, carry))
    return carry, lo // S


def _no_rows(C: int, vshape, dtype):
    """A reduce's ``2 C`` output rows before any block filled them: the
    retracted and inserted values and their masks, all dead."""
    zrow = jnp.zeros((C,) + tuple(vshape), dtype)
    zm = jnp.zeros((C,), jnp.bool_)
    return zrow, zrow, zm, zm


def _emit_block(emitted, em_has, rows, lo, tk, old, agg, exists, ins_m,
                ret_m):
    """One block's emission: keys ``tk`` (slots from ``lo``) retract
    ``old`` where ``ret_m`` and insert ``agg`` where ``ins_m``. ->
    (emitted', emitted_has', rows with the block's filled in)."""
    K = emitted.shape[0]
    set_ins = jnp.where(ins_m, tk, K)
    return (emitted.at[set_ins].set(agg, mode="drop"),
            em_has.at[set_ins].set(True, mode="drop").at[
                jnp.where(ret_m & ~exists, tk, K)].set(False, mode="drop"),
            tuple(_put_block(p, g, lo)
                  for p, g in zip(rows, (old, agg, ret_m, ins_m))))


def minmax_state(op: Reduce, K: int, in_vshape, out_vshape, odtype,
                 in_dtype=jnp.float32) -> dict:
    """State for the retraction-capable min/max (candidate buffer),
    scalar and vector values alike (a scalar is the V=1 row case).

    Values ride sign-normalized (``sign*v``, sign = +1 for min / -1 for
    max) so one lex-MIN kernel serves both. ``cand_v``/``cand_w`` hold
    the R lex-smallest (normalized) distinct value ROWS per key (flat:
    ``[K, R*V]``) with their multiset weights (any sign: anti-rows are
    legal transients),
    stored in ascending lex order — the kernel's rank-ordered rebuild
    maintains that invariant. ``over_lo`` is a MONOTONE watermark row:
    the lex-smallest value ever evicted; ``over_maybe_pos`` latches
    whether any positive-net row was ever evicted. Together they bound
    what the buffer can prove: the buffered minimum is global only while
    strictly lex-below the watermark, and group existence is decidable
    only while positive support cannot be hiding in the overflow
    (SURVEY.md §7 hard part c: bounded per-key multisets, loud failure
    beyond the bound). Buffer memory is K x R x V floats — the device
    path is meant for modest V; huge-vector extrema belong on the CPU
    oracle.
    """
    R = op.candidates
    V = 1
    for s in in_vshape:
        V *= s
    cdt = _cand_dtype(in_dtype)
    return {
        # [K, R*V], not [K, R, V]: the TPU keeps a 2-D table with the
        # key axis minor and gathers / scatters its rows in place; the
        # 3-D one it copied whole into a padded layout and back every
        # tick (8 GB of temporaries at 2^23 keys)
        "cand_v": jnp.full((K, R * V), _cand_top(cdt), cdt),
        "cand_w": jnp.zeros((K, R), jnp.int32),
        # monotone per-key latches — overflow rows lose their identity,
        # so nothing can ever clear them (see utils refresh for the
        # host-triggered reset path)
        "over_lo": jnp.full((K, V), _cand_top(cdt), cdt),
        "over_maybe_pos": jnp.zeros((K,), jnp.bool_),
        "emitted": jnp.zeros((K,) + tuple(out_vshape), odtype),
        "emitted_has": jnp.zeros((K,), jnp.bool_),
        "error": jnp.zeros((), jnp.bool_),
        "counters": jnp.zeros((len(OP_COUNTERS["reduce"]),), jnp.int32),
    }


def _merge_rungs(C: int, K: int) -> Tuple[int, ...]:
    """The delta sizes the min/max merges a delta of capacity ``C`` over
    ``K`` keys by, ascending: ``K`` slots (the fewest a dense merge,
    ``C >= K``, can take, in whole blocks), then four times the rung
    before while that is under ``C``, then ``C``; ``C`` alone where the
    delta has under four times ``K`` slots."""
    Cs = -(-K // 8) * 8
    if C < 4 * Cs:
        return (C,)
    rungs = []
    while Cs < C:
        rungs.append(Cs)
        Cs *= 4
    return tuple(rungs) + (C,)


def minmax_core(op: Reduce, K: int, out_vshape, odtype, state,
                d: DeviceDelta, key_offset=0
                ) -> Tuple[DeviceDelta, dict]:
    """One tick of the buffered min/max (:func:`_minmax_merge`), over
    as many of the delta's slots as its live rows need. A delta's
    capacity is its producer's worst case: a join under a loop hands
    over ``2 x arena_capacity`` slots a pass (its sweep's; a probe of its
    view fills the first of them) for a frontier's few thousand rows, a
    hub's fan-out for some hundred
    thousand, and the merge's sorts, gathers and scatters cost by the
    slots, live or dead. So where the capacity is several times the key
    space (``_merge_rungs``) the live rows are moved to the front (one
    stable sort of the live mask: their order stays) and the merge runs
    over the smallest rung that holds them all, the whole delta as it
    stands past the last. Every rung is the same merge over a longer
    prefix of the same rows, so each gives the tables and the rows out
    of the whole delta's merge (it nets by key and value, whatever the
    slots between), chosen on the device (``lax.switch`` on the live
    count): no budget, nothing dropped, and one pass's rows never split
    (a retraction and the insert that rides with it meet in one merge).
    A merge over a rung of ``r`` slots adds ``r`` to ``merged_slots``
    and counts its ``blocks`` in slots of ``_block_slots(r)``."""
    rungs = _merge_rungs(d.capacity, K)
    if len(rungs) == 1:
        return _minmax_merge(op, K, out_vshape, odtype, state, d,
                             key_offset)
    with jax.named_scope("minmax.compact"):
        front = jnp.argsort(d.weights == 0, stable=True)

    def over(r):
        def merge(st, dd, at):
            if r < dd.capacity:
                at = at[:r]
                dd = DeviceDelta(dd.keys[at], dd.values[at], dd.weights[at])
            return _minmax_merge(op, K, out_vshape, odtype, st, dd,
                                 key_offset)
        return merge

    n = d.nonzero()
    return jax.lax.switch(
        sum((n > r).astype(jnp.int32) for r in rungs[:-1]),
        [over(r) for r in rungs], state, d, front)


def _minmax_merge(op: Reduce, K: int, out_vshape, odtype, state,
                  d: DeviceDelta, key_offset=0
                  ) -> Tuple[DeviceDelta, dict]:
    """One tick of the buffered min/max over a (per-shard) key range;
    ``d`` carries keys local to ``[0, K)``. Scalar and VECTOR values
    share this kernel: a candidate is a distinct value ROW [V], ordered
    lexicographically (the host oracle's tuple ordering), and a scalar
    is simply V=1.

    Algorithm (all shape-static): compact the tick's touched keys into
    slots, gather their buffers, merge buffer rows + delta rows by
    (slot, normalized value row) with one multi-column lexsort, net
    bit-equal rows' weights, keep the R lex-best nonzero rows per slot
    (rank by running count — the buffer therefore stays rank-SORTED,
    which is what lets the aggregate read the first positive rank),
    evict the rest into the ``over_lo``/``over_maybe_pos`` latches,
    scatter the rebuilt buffers back. Exactness: the buffer's first
    positive entry is the true extremum iff it is strictly lex-below
    ``over_lo`` (everything ever evicted was no better than the buffer's
    worst AT EVICTION TIME, but later retractions can hollow the buffer
    past that point — then the answer is unknowable from bounded state
    and the sticky error raises). Negative-weight entries (retractions
    of evicted or not-yet-inserted values — legal multiset transients)
    occupy buffer slots as anti-rows and cancel against later inserts.
    """
    cdt = state["cand_v"].dtype
    sign = jnp.asarray(1 if op.how == "min" else -1, cdt)
    R = state["cand_w"].shape[1]
    V = state["cand_v"].shape[1] // R
    C = d.capacity
    INF = jnp.asarray(_cand_top(cdt), cdt)

    live = d.weights != 0
    dval = jnp.where(live[:, None],
                     sign * d.values.reshape(C, V).astype(cdt), INF)

    with jax.named_scope("minmax.merge"):
        # touched keys -> dense slots [0, n_t)
        skey = jnp.where(live, d.keys, K)
        order = jnp.argsort(skey)
        sk = skey[order]
        prev = jnp.concatenate([jnp.full((1,), -1, sk.dtype), sk[:-1]])
        first = (sk != prev) & (sk < K)
        slot_sorted = jnp.cumsum(first.astype(jnp.int32)) - 1
        # slot -> key
        tkeys = jnp.full((C,), K, jnp.int32).at[
            jnp.where(first, slot_sorted, C)].set(sk.astype(jnp.int32),
                                                  mode="drop")
        # original row -> slot (dead rows -> C)
        row_slot = jnp.full((C,), C, jnp.int32).at[order].set(
            jnp.where(sk < K, slot_sorted, C))

        tk_c = jnp.minimum(tkeys, K - 1)
        tvalid = tkeys < K
        n_t = jnp.sum(tvalid.astype(jnp.int32))

        # the buffers are rebuilt, and every keyed table written, S
        # slots at a time, as many times as the touched keys need
        # (``_over_blocks``): the sort is over the touched keys' buffers
        # and the scatters over their slots, not over C of either
        S = _block_slots(C)
        dw = jnp.where(live, d.weights, 0)

        def merge(cand_v, cand_w, tk, tv, lo):
            """Rebuild the buffers of slots ``[lo, lo + S)`` (keys
            ``tk``, live where ``tv``) from their buffered rows and the
            delta's: -> (buffers' values [S, R, V] and weights [S, R],
            per slot the lex-smallest evicted row and whether a positive
            row was evicted, rows evicted)."""
            bw = jnp.where(tv[:, None], cand_w[tk], 0)            # [S, R]
            bv = jnp.where((bw != 0)[:, :, None],
                           cand_v[tk].reshape(S, R, V), INF)

            # merged candidate rows: S*R buffer rows + C delta rows
            slot_b = jnp.where(
                bw.reshape(-1) != 0,
                jnp.repeat(jnp.arange(S, dtype=jnp.int32), R), S)
            rs = row_slot - lo
            mslot = jnp.concatenate(
                [slot_b, jnp.where((rs >= 0) & (rs < S), rs, S)])
            mval = jnp.concatenate([bv.reshape(S * R, V), dval])  # [M, V]
            mw = jnp.concatenate([bw.reshape(-1), dw])
            M = mslot.shape[0]

            # lex order: slot primary, then value columns (np.lexsort:
            # LAST key is primary)
            o2 = jnp.lexsort(tuple(mval[:, q] for q in range(V - 1, -1, -1))
                             + (mslot,))
            s2, v2, w2 = mslot[o2], mval[o2], mw[o2]
            pv = jnp.concatenate([jnp.full((1,), -1, s2.dtype), s2[:-1]])
            pval = jnp.concatenate([jnp.full((1, V), -INF), v2[:-1]])
            first2 = ((s2 != pv) | jnp.any(v2 != pval, axis=1)) & (s2 < S)
            gid = jnp.cumsum(first2.astype(jnp.int32)) - 1
            gid_c = jnp.where(s2 < S, gid, M - 1)
            netw = jnp.zeros((M,), jnp.int32).at[gid_c].add(
                jnp.where(s2 < S, w2, 0))
            net_here = netw[gid_c]
            alive = first2 & (net_here != 0)

            # rank among alive rows within each slot
            ca = jnp.cumsum(alive.astype(jnp.int32))
            slot_start = (s2 != pv) & (s2 < S)
            base = jnp.zeros((S + 1,), jnp.int32).at[
                jnp.where(slot_start, s2, S)].set(
                ca - alive.astype(jnp.int32), mode="drop")
            rank = ca - 1 - base[jnp.minimum(s2, S)]
            keep = alive & (rank < R)
            evict = alive & (rank >= R)

            # rebuilt buffers per slot (rank-ordered: ascending lex)
            flat = jnp.where(keep, jnp.minimum(s2, S - 1) * R + rank, S * R)
            nb_v = jnp.full((S * R + 1, V), INF).at[flat].set(
                v2, mode="drop")[:S * R].reshape(S, R, V)
            nb_w = jnp.zeros((S * R + 1,), jnp.int32).at[flat].set(
                net_here, mode="drop")[:S * R].reshape(S, R)

            # evictions: the slot's FIRST evicted row (rank == R) is the
            # lex-smallest evicted (rows are sorted), and it lowers the
            # over_lo watermark; a positive-net eviction latches
            # over_maybe_pos (both monotone — overflow rows lose their
            # identity, so these can never be cleared)
            first_ev = evict & (rank == R)
            ev_lo = jnp.full((S + 1, V), INF).at[
                jnp.where(first_ev, s2, S)].set(v2, mode="drop")[:S]
            ev_pos = jnp.zeros((S + 1,), jnp.bool_).at[
                jnp.where(evict & (net_here > 0), s2, S)].set(
                True, mode="drop")[:S]
            return (nb_v, nb_w, ev_lo, ev_pos,
                    jnp.sum(evict.astype(jnp.int32)))

    def decide(cw, cv, lo, maybe_pos, em, has, n):
        """Aggregate and emission masks of ``n`` keys from their
        buffers. Existence mirrors the host oracle's any(w > 0)
        positive-support rule: provable from the buffer alone unless a
        positive row was ever evicted. Exactness of the buffered minimum
        additionally needs bmin strictly lex-below the eviction
        watermark: at equality an evicted ANTI-row at that very value
        could cancel the buffered positive support."""
        pos = cw > 0                                      # [n, R]
        has_pos = jnp.any(pos, axis=1)
        fi = jnp.argmax(pos, axis=1)
        bmin = jnp.take_along_axis(cv, fi[:, None, None],
                                   axis=1)[:, 0]          # [n, V]
        unknown = ((~has_pos & maybe_pos)
                   | (has_pos & ~_lex_lt(bmin, lo)))
        agg_rows = sign * jnp.where(has_pos[:, None], bmin, 0)
        aggv = jnp.asarray(agg_rows.reshape((n,) + tuple(out_vshape)),
                           odtype)
        changed = _differs(aggv, em, op.tol)
        ins_m = has_pos & ~unknown & (~has | changed)
        ret_m = has & ((~has_pos | changed) & ~unknown)
        return aggv, has_pos, unknown, ins_m, ret_m

    # sparse (C < K): only the touched keys can have moved, so the tick
    # reads and emits 2C rows whatever K is (the linear reducers' rule,
    # ``_lower_reduce``), a block's rows with its tables; a key whose
    # answer became unknowable latches the error in the tick that made
    # it so. Dense: every key's buffer is diffed against what was
    # emitted, once the blocks are in.
    sparse = C < K

    def block(lo, c):
        """Slots ``[lo, lo + S)``: their keys' buffers merged and
        written back with the eviction latches and, when sparse, their
        emission. Blocks hold disjoint keys, so none reads what another
        wrote."""
        t = dict(c["tables"])
        tk, tv = _block(tk_c, lo, S), _block(tvalid, lo, S)
        with jax.named_scope("minmax.merge"):
            nb_v, nb_w, ev_lo, ev_pos, n_ev = merge(
                t["cand_v"], t["cand_w"], tk, tv, lo)
            sidx = jnp.where(tv, tk, K)
            t["cand_v"] = t["cand_v"].at[sidx].set(
                nb_v.reshape(S, R * V), mode="drop")
            t["cand_w"] = t["cand_w"].at[sidx].set(nb_w, mode="drop")
            lo_g = jnp.where(tv[:, None], t["over_lo"][tk], INF)
            new_lo = jnp.where(_lex_lt(ev_lo, lo_g)[:, None], ev_lo, lo_g)
            t["over_lo"] = t["over_lo"].at[sidx].set(new_lo, mode="drop")
            new_mp = tv & (t["over_maybe_pos"][tk] | ev_pos)
            t["over_maybe_pos"] = t["over_maybe_pos"].at[sidx].set(
                new_mp, mode="drop")
        # cand_w accumulates per-(key, value) net weights ACROSS ticks
        # with only the per-batch 2**24 mass guard upstream
        # (check_weight_mass); sustained re-insertion of one value could
        # wrap int32 silently and flip existence/min decisions (ADVICE
        # r3). Latch loudly at 2**30 — far below wrap, with room for any
        # single legal batch on top.
        out = {"tables": t, "evicted": c["evicted"] + n_ev,
               "bad": c["bad"] | jnp.any(jnp.abs(nb_w) > (1 << 30))}
        if sparse:
            old = t["emitted"][tk]
            aggv, exists, unknown, ins_m, ret_m = decide(
                nb_w, nb_v, new_lo, new_mp, old,
                tv & t["emitted_has"][tk], S)
            t["emitted"], t["emitted_has"], out["rows"] = _emit_block(
                t["emitted"], t["emitted_has"], c["rows"], lo, tk, old,
                aggv, exists, ins_m & tv, ret_m & tv)
            out["bad"] = out["bad"] | jnp.any(unknown & tv)
        return out

    tables = ("cand_v", "cand_w", "over_lo", "over_maybe_pos") + (
        ("emitted", "emitted_has") if sparse else ())
    carry = {"tables": {k: state[k] for k in tables},
             "evicted": jnp.zeros((), jnp.int32),
             "bad": jnp.zeros((), jnp.bool_)}
    if sparse:
        carry["rows"] = _no_rows(C, out_vshape, odtype)
    got, blocks = _over_blocks(C, n_t, block, carry)
    new_state = dict(got["tables"])
    error = state["error"] | got["bad"]

    if sparse:
        old, aggv, ret_m, ins_m = got["rows"]
        okeys = key_offset + tk_c
    else:
        aggv, exists, unknown, ins_m, ret_m = decide(
            new_state["cand_w"], new_state["cand_v"].reshape(K, R, V),
            new_state["over_lo"], new_state["over_maybe_pos"],
            state["emitted"], state["emitted_has"], K)
        okeys = key_offset + jnp.arange(K, dtype=jnp.int32)
        old = state["emitted"]
        new_state["emitted"] = jnp.where(_bcast_w(ins_m, aggv), aggv, old)
        new_state["emitted_has"] = jnp.where(
            ins_m, True,
            jnp.where(ret_m & ~exists, False, state["emitted_has"]))
        error = error | jnp.any(unknown)

    out = DeviceDelta(
        keys=jnp.concatenate([okeys, okeys]),
        values=jnp.concatenate([old, aggv]),
        weights=jnp.concatenate(
            [-ret_m.astype(jnp.int32), ins_m.astype(jnp.int32)]),
    )
    new_state["error"] = error
    if "counters" in state:
        new_state["counters"] = state["counters"] + jnp.stack(
            [n_t, got["evicted"], blocks, jnp.asarray(C, jnp.int32)])
    return out, new_state


def _scatter_contribs(d: DeviceDelta, K: int):
    """One fused scatter-add of (w*v, w) into a [K, F+1] table.

    TPU scatter cost scales with update rows, so stacking the weighted
    values and the weights into one update halves the dominant cost of
    large reduce passes vs two separate scatter-adds.
    """
    C = d.capacity
    vflat = _masked_contrib(d.weights, d.values).astype(
        jnp.float32).reshape(C, -1)
    upd = jnp.concatenate(
        [vflat, d.weights.astype(jnp.float32)[:, None]], axis=-1)
    table = jnp.zeros((K, upd.shape[1]), jnp.float32).at[d.keys].add(upd)
    vshape = d.values.shape[1:]
    dws = table[:, :-1].reshape((K,) + vshape)
    # weights are ints; their float32 sum is exact below 2**24 rows/key
    dwc = table[:, -1].astype(jnp.int32)
    return dws, dwc


def minmax_refresh_core(op: Reduce, K: int, out_vshape, odtype, state,
                        d: DeviceDelta, key_offset=0) -> dict:
    """Latch REFRESH (ROADMAP r3 #3): rebuild the candidate buffers of
    every key present in ``d`` from a user-supplied REPLAY of its full
    live multiset, resetting the monotone ``over_lo``/``over_maybe_pos``
    latches — the maintenance path that keeps a long-running
    heavy-churn key exact instead of eventually tripping the loud
    overflow error.

    Contract: for each key it mentions, ``d`` holds EVERY live row of
    that key's current collection (one +w row per multiset entry).
    Because the replay is the same collection the state already
    aggregates, the emitted aggregate cannot change: a live emission
    diff out of the replay means the replay contradicts the state
    (user error, or prior corruption) and sets the sticky error flag
    instead of silently re-emitting.
    """
    live = d.weights != 0
    touched = jnp.zeros((K,), jnp.bool_).at[
        jnp.where(live, d.keys, K)].set(True, mode="drop")
    st = dict(state)
    tb = touched[:, None]
    top = jnp.asarray(_cand_top(state["cand_v"].dtype),
                      state["cand_v"].dtype)
    st["cand_v"] = jnp.where(tb, top, state["cand_v"])
    st["cand_w"] = jnp.where(tb, 0, state["cand_w"])
    st["over_lo"] = jnp.where(tb, top, state["over_lo"])
    st["over_maybe_pos"] = jnp.where(touched, False,
                                     state["over_maybe_pos"])
    out, st2 = minmax_core(op, K, out_vshape, odtype, st, d, key_offset)
    st2["error"] = st2["error"] | jnp.any(out.weights != 0)
    return st2


def _lower_reduce(op: Reduce, node: Node, state, ins) -> Tuple[DeviceDelta, dict]:
    if op.how not in LINEAR_DEVICE_REDUCERS:
        (d,) = ins
        return minmax_core(op, node.inputs[0].spec.key_space,
                           tuple(node.spec.value_shape),
                           node.spec.value_dtype, state, d)
    (d,) = ins
    in_spec = node.inputs[0].spec
    K = in_spec.key_space
    C = d.capacity
    vdtype = node.spec.value_dtype

    emitted, em_has = state["emitted"], state["emitted_has"]

    if C >= K:
        dws, dwc = _scatter_contribs(d, K)
        wsum = state["wsum"] + dws
        wcnt = state["wcnt"] + dwc
        # dense mode: diff the whole aggregate table against what was
        # emitted — no sort, pure vector ops (the PageRank-iteration shape).
        agg, exists = _agg_tables(op, wsum, wcnt, vdtype)
        changed = _differs(agg, emitted, op.tol)
        ins_m = exists & (~em_has | changed)
        ret_m = em_has & (~exists | changed)
        all_keys = jnp.arange(K, dtype=jnp.int32)
        out = DeviceDelta(
            keys=jnp.concatenate([all_keys, all_keys]),
            values=jnp.concatenate([emitted, agg]),
            weights=jnp.concatenate(
                [-ret_m.astype(jnp.int32), ins_m.astype(jnp.int32)]),
        )
        ins_b = _bcast_w(ins_m, agg)
        new_emitted = jnp.where(ins_b, agg, emitted)
        new_has = jnp.where(ins_m, True, jnp.where(ret_m & ~exists, False, em_has))
    else:
        # sparse mode: O(live rows) end to end, never O(K) —
        # contributions scatter-add straight into the persistent tables
        # (no zeros[K] staging table, no full-table add), and
        # aggregation/emission runs only on the gathered touched rows.
        # This is what makes small-edit streaming (config 2: 256-row
        # edits into 2^20-key tables) cost per-edit work instead of
        # per-vocabulary work. The rows are sorted by key first, so the
        # live ones are the prefix [0, n_live), and the tables are
        # written a block of that prefix at a time (``_over_blocks``).
        S = _block_slots(C)
        live = d.weights != 0
        skey = jnp.where(live, d.keys, K)
        order = jnp.argsort(skey)
        sk = skey[order].astype(jnp.int32)       # dead rows: K, dropped
        n_live = jnp.sum(live.astype(jnp.int32))
        prev = jnp.concatenate([jnp.full((1,), -1, sk.dtype), sk[:-1]])
        first = (sk != prev) & (sk < K)
        contrib = _masked_contrib(d.weights, d.values).astype(
            state["wsum"].dtype)

        def add(lo, c):
            wsum, wcnt = c
            at, rows = _block(sk, lo, S), _block(order, lo, S)
            return (wsum.at[at].add(contrib[rows], mode="drop"),
                    wcnt.at[at].add(d.weights[rows], mode="drop"))

        # a key's rows may straddle two blocks: every block's adds land
        # before any block's aggregate is read
        (wsum, wcnt), _ = _over_blocks(
            C, n_live, add, (state["wsum"], state["wcnt"]))

        keys = jnp.where(sk < K, sk, 0)

        def emit(lo, c):
            emitted, em_has, rows = c
            tk, head = _block(keys, lo, S), _block(first, lo, S)
            agg, exists = _agg_tables(op, wsum[tk], wcnt[tk], vdtype)
            em, has = emitted[tk], em_has[tk]
            changed = _differs(agg, em, op.tol)
            return _emit_block(emitted, em_has, rows, lo, tk, em, agg,
                               exists, head & exists & (~has | changed),
                               head & has & (~exists | changed))

        (new_emitted, new_has, (em, agg, ret_m, ins_m)), _ = _over_blocks(
            C, n_live, emit,
            (emitted, em_has, _no_rows(C, emitted.shape[1:], vdtype)))
        out = DeviceDelta(
            keys=jnp.concatenate([keys, keys]),
            values=jnp.concatenate([em, agg]),
            weights=jnp.concatenate(
                [-ret_m.astype(jnp.int32), ins_m.astype(jnp.int32)]),
        )

    new_state = {"wsum": wsum, "wcnt": wcnt,
                 "emitted": new_emitted, "emitted_has": new_has}
    return out, new_state


# -- KnnIndex (SURVEY.md §2 item 14: vmapped cosine + Pallas top-k) --------

#: op kind -> what its nodes count on the device, in the order of their
#: ``counters`` state leaf (int32 each, cumulative since bind). Only
#: nodes whose state has the leaf count. The counters ride in the state,
#: so they cost no program output and no host sync; the executor reads
#: them when a snapshot is taken (``TpuExecutor.op_counters``). New names
#: are appended: readers go by position.
#:
#: - ``"knn"``: ticks that rescanned the corpus, ticks that took the
#:   incremental merge, delta rows folded into its two tables (wraps
#:   after 2^31 rows), and the chunk sweeps its rescans' folds ran
#:   (``kernels.topk.fold_topk``; an incremental tick adds none; wraps,
#:   so a reader takes differences modulo 2^32).
#: - ``"join"``: ``join.JOIN_COUNTERS``, which says what each counts.
#: - ``"reduce"`` (min/max): keys a tick's delta touched, distinct value
#:   rows pushed out of a candidate buffer, blocks of ``_block_slots(C)``
#:   slots its keyed tables were written by (``_over_blocks``: slots
#:   written = blocks x that), and ``merged_slots``, the ``C`` slots of
#:   the delta each merge ran over (wraps: a reader differences modulo
#:   2^32). ``C`` is the merged delta's: where the merge follows the
#:   live rows (``_merge_rungs``) the rung a tick took, so under a ladder
#:   ``merged_slots`` sums the rungs taken and ``blocks`` counts trips of
#:   whichever rung ran, an eighth of that rung each.
#: - ``"loop"`` is no operator: the row fixpoint program
#:   (``fixpoint.FixpointProgram``) counts in the state of its region's
#:   first loop node, where the executor gave it the leaf: ``passes`` as
#:   ``TickResult.passes`` has them (phase A, every trip of the
#:   ``while_loop``, the exit pass), ``ticks``, and ``unquiesced``, the
#:   ticks whose loop stopped at ``max_iters`` with its carry alive.
OP_COUNTERS = {"knn": ("rescans", "incremental", "rows_folded", "sweeps"),
               "join": JOIN_COUNTERS,
               "reduce": ("touched", "evicted", "blocks", "merged_slots"),
               "loop": ("passes", "ticks", "unquiesced")}


def knn_state(op, q_spec: Spec, d_spec: Spec) -> dict:
    Q, D = q_spec.key_space, d_spec.key_space
    dim, k = op.dim, op.k
    # vectors store at the SOURCE spec dtype: bf16 embeddings halve both
    # HBM residency and the host->device transfer per insert tick (the
    # bandwidth-bound cost of config 4) at ~1e-3 relative score error —
    # normalization and the scoring matmuls still accumulate in f32
    return {
        "qvec": jnp.zeros((Q, dim), q_spec.value_dtype),
        "qlive": jnp.zeros((Q,), jnp.bool_),
        "dvec": jnp.zeros((D, dim), d_spec.value_dtype),
        "dlive": jnp.zeros((D,), jnp.bool_),
        "emitted": jnp.zeros((Q, k, 2), jnp.float32),
        "em_has": jnp.zeros((Q,), jnp.bool_),
        "counters": jnp.zeros((len(OP_COUNTERS["knn"]),), jnp.int32),
    }


def _norm_rows(v):
    n = jnp.sqrt(jnp.sum(v * v, axis=-1, keepdims=True))
    return jnp.where(n > 0, v / jnp.maximum(n, 1e-30), 0.0)


def _last_rows(delta, cap: int):
    """Row order within one tick's delta, resolved: per key the LAST
    non-padding row wins (the host oracle applies rows in order, and a
    merged feed can hold insert-then-delete or two updates of one id).
    Returns the (insert, retract) masks of the winning rows; every key
    has at most one winner, so scatters by them never meet a duplicate.
    """
    row = jnp.arange(delta.keys.shape[0], dtype=jnp.int32)
    real = delta.weights != 0
    slot = jnp.where(real, delta.keys, cap)
    last = jnp.full((cap + 1,), -1, jnp.int32).at[slot].max(row)
    wins = real & (last[slot] == row)
    return wins & (delta.weights > 0), wins & (delta.weights < 0)


def _fold_vectors(vec, live, delta):
    """Fold one tick's vector deltas into a dense table in row order:
    per id the last row decides both the vector and liveness
    (insert-then-delete ends dead, delete-then-insert ends live with the
    new vector, two inserts keep the second). Returns the table, the
    live mask and the masks of the rows that won."""
    cap = vec.shape[0]
    ins_m, ret_m = _last_rows(delta, cap)
    ins = jnp.where(ins_m, delta.keys, cap)
    ret = jnp.where(ret_m, delta.keys, cap)
    if vec.dtype == jnp.int8:
        # int8 tables receive PRE-normalized, pre-quantized rows
        # (workloads/knn.quantize_int8): store raw — renormalizing a
        # round(unit*127) row would truncate it to zeros at int8
        vals8 = jnp.asarray(delta.values, jnp.int8)
        vec = vec.at[ins].set(vals8, mode="drop")
    else:
        # normalize in f32 regardless of storage dtype, store at table
        # dtype
        vals = _norm_rows(jnp.asarray(delta.values, jnp.float32))
        vec = vec.at[ins].set(jnp.asarray(vals, vec.dtype), mode="drop")
    live = live.at[ret].set(False, mode="drop").at[ins].set(True, mode="drop")
    return vec, live, ins_m, ret_m


def _knn_incremental(qvec, dvec, emitted, em_has, di, d_ins, k, prec,
                     score_of=None):
    """Incremental merge: the emitted top-k rows stay valid (nothing
    they name was retracted or updated), so merge them with the scores
    of just this tick's inserted docs ``di`` (``d_ins``: the rows that
    won). ``score_of`` lets the sharded lowering score from its slice."""
    from reflow_tpu.kernels.topk import NEG, score_form, topk

    Q = qvec.shape[0]
    em_ids = emitted[:, :, 0].astype(jnp.int32)                # [Q, k]
    em_vals = jnp.where(em_has[:, None] & (em_ids >= 0),
                        emitted[:, :, 1], NEG)
    with jax.named_scope("knn.score"):
        if score_of is not None:
            s_new = score_of(di, d_ins)
        else:
            s_new = jnp.dot(score_form(qvec), score_form(dvec[di]).T,
                            preferred_element_type=jnp.float32,
                            precision=prec)                    # [Q, Cd]
            s_new = jnp.where(d_ins[None, :], s_new, NEG)
    with jax.named_scope("knn.topk"):
        cand_vals = jnp.concatenate([em_vals, s_new], axis=1)
        cand_ids = jnp.concatenate(
            [em_ids, jnp.broadcast_to(di, (Q, di.shape[0]))], axis=1)
        # order candidates by id so topk's first-index tie-break matches
        # the oracle's lowest-doc-id rule on exact score ties
        order = jnp.argsort(cand_ids, axis=1, stable=True)
        cand_ids = jnp.take_along_axis(cand_ids, order, axis=1)
        cand_vals = jnp.take_along_axis(cand_vals, order, axis=1)
        vals, sel = topk(cand_vals, k)
        ids = jnp.take_along_axis(cand_ids, sel, axis=1)
    return vals, ids


def _knn_count(counters, need_full, sweeps, *masks):
    """The node's device counters after this tick (``OP_COUNTERS``)."""
    rows = sum(jnp.sum(m.astype(jnp.int32)) for m in masks)
    full = need_full.astype(jnp.int32)
    return counters + jnp.stack([full, 1 - full, rows, sweeps])


def _lower_knn(op, node: Node, state, ins) -> Tuple[DeviceDelta, dict]:
    from reflow_tpu.kernels.topk import NEG, chunked_corpus_topk

    dq, dd = ins
    if dq is None:
        dq = DeviceDelta.empty(node.inputs[0].spec)
    if dd is None:
        dd = DeviceDelta.empty(node.inputs[1].spec)
    Q = node.inputs[0].spec.key_space
    k = op.k

    with jax.named_scope("knn.fold"):
        qvec, qlive, q_ins, q_ret = _fold_vectors(
            state["qvec"], state["qlive"], dq)
        dvec, dlive, d_ins, d_ret = _fold_vectors(
            state["dvec"], state["dlive"], dd)
        # what the winning doc rows do to ids that were live BEFORE the
        # fold: an insert is an in-place update (its stale score may sit
        # in a query's emitted top-k, and the incremental merge would
        # keep treating it as a valid candidate), a retraction takes a
        # candidate away. Either rescans; a row that only touches a
        # fresh id (insert, or insert-then-delete within the tick) does
        # not. Padding rows have weight 0 and never win.
        was_live = state["dlive"][dd.keys]
        doc_change = jnp.any((d_ins | d_ret) & was_live)
    emitted, em_has = state["emitted"], state["em_has"]
    prec = (jax.lax.Precision.HIGHEST if op.precision == "highest"
            else jax.lax.Precision.DEFAULT)

    # fresh doc-insert and query-retract ticks take the incremental
    # merge (a retracted query just stops emitting); query
    # inserts/updates, doc retractions and doc UPDATES rescan the
    # corpus (chunked, MXU)
    need_full = jnp.any(q_ins) | doc_change

    def full_path(_):
        return chunked_corpus_topk(qvec, dvec, dlive, k, op.scan_chunk,
                                   precision=prec)

    def incr_path(_):
        return *_knn_incremental(qvec, dvec, emitted, em_has, dd.keys,
                                 d_ins, k, prec), jnp.int32(0)

    vals, ids, sweeps = jax.lax.cond(need_full, full_path, incr_path, None)
    ids = jnp.where(vals <= NEG, -1, ids)
    new_row = jnp.stack([ids.astype(jnp.float32), vals], axis=-1)  # [Q,k,2]

    changed = jnp.any(new_row != emitted, axis=(1, 2))
    ins_m = qlive & (~em_has | changed)
    ret_m = em_has & (~qlive | changed)
    qkeys = jnp.arange(Q, dtype=jnp.int32)
    out = DeviceDelta(
        keys=jnp.concatenate([qkeys, qkeys]),
        values=jnp.concatenate([emitted, new_row]),
        weights=jnp.concatenate(
            [-ret_m.astype(jnp.int32), ins_m.astype(jnp.int32)]),
    )
    new_emitted = jnp.where(ins_m[:, None, None], new_row, emitted)
    new_has = jnp.where(ins_m, True, jnp.where(ret_m & ~qlive, False, em_has))
    return out, {"qvec": qvec, "qlive": qlive, "dvec": dvec, "dlive": dlive,
                 "emitted": new_emitted, "em_has": new_has,
                 "counters": _knn_count(state["counters"], need_full, sweeps,
                                        q_ins, q_ret, d_ins, d_ret)}


# -- dispatch --------------------------------------------------------------

_LOWERINGS = {
    "map": _lower_map,
    "filter": _lower_filter,
    "groupby": _lower_groupby,
    "union": _lower_union,
    "reduce": _lower_reduce,
    "join": lower_join,
    "knn": _lower_knn,
}


def lower_node(node: Node, state, ins: Sequence[DeviceDelta]
               ) -> Tuple[DeviceDelta, Optional[dict]]:
    return _LOWERINGS[node.op.kind](node.op, node, state, ins)
