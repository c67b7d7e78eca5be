"""Join-arena compaction (GC): bound the arena by LIVE rows, not lifetime.

The device Join stores its right side as an append-only log: retractions
append negative-weight rows rather than freeing their match, so without
reclamation ``arena_capacity`` must cover the *lifetime* append count and
a long-running stream eventually dies on the overflow check (round-1
VERDICT item 7).

``compact_arena`` cancels matched pairs on device: rows are lex-sorted by
(key, value bytes), equal (key, value) runs are weight-summed, and groups
with net weight 0 vanish; survivors are repacked to the front with their
net weight. The columns move by two sorts and a scan: key, value bits
and weights ride the lex sort as its operands, a run's net weight is a
segmented running sum, and the survivors are packed by a second sort on
their rank — nothing of the arena's length is gathered or scattered by
index, which on a TPU costs several sorts' worth a column
(``tests/test_arena_gc.py`` holds the jaxpr to it). Exactness contract:
a retraction carries the SAME value bytes as the insert it cancels (true
by construction for host-driven deltas — the retract batch replays the
original row with weight -1; float values are compared bitwise, so NaNs
and signed zeros cancel only their bit-identical twins).

Compaction triggers IN-PROGRAM: ``join._append_arena`` wraps this kernel
in a ``lax.cond`` guarded by ``rcount + appends > capacity`` (no value is
read back to the host); a genuine overflow (live + appends > capacity
even after compaction) sets the join state's sticky ``error`` flag,
raised at the next sync point. Sharded executors reach this through the
same path: ``join_core`` runs per shard under ``shard_map`` (rows never
migrate; each shard compacts its slice and its slot of ``rcount``).

``propagate_plan_caps`` is the host-side static counterpart: the
pre-dispatch capacity walk that rejects statically impossible ingress
sizes and sizes the mega-tick ingress queue against the arenas.

Beside the log a unique-left join may keep one of two indexes, which let
δA ⋈ B_old cost by the delta's matches and not by ``arena_capacity``.
Which join keeps which, and why, is ``join.join_layout``'s to say; this
module holds how each is stored, appended to and probed.

**The arena index** (``index_state`` / ``index_probe`` / ``index_append``
/ ``reindex``; the ``"indexed"`` layout's): every tick's appends land
key-SORTED, so the rows one
tick gave one key are one contiguous *segment*, and the segments of a key
are chained newest to oldest (``head[K]`` -> first row of the newest
segment; at a segment's first row ``seg_len`` rows and ``seg_prev`` the
first row of the one before; ``deg[K]`` rows in all). An append writes
O(delta) entries: the rows as one block at ``rcount``, ``head`` and
``deg`` by key. A probe reads ``deg`` to lay every delta row's pairs
into a static budget of slots, then walks the chains one segment of
every probed key per step: as many steps as the most *ticks* any probed
key was appended in, whatever the rows. A compaction re-sorts the log,
after which every key is one segment again and the index is derived from
the sorted log in one pass (``reindex``). That is a program of its own,
run between ticks when a window's appends might not fit
(``join.ArenaRoom``). The index is part of the join's state: it travels
with the arena through donation, checkpoints and rebinds, only
``reindex`` compacts an indexed arena, and so the two are never out of
step.

**The key-sorted view** (``view_state`` / ``view_sort`` / ``view_count``
/ ``view_probe``; the ``"viewed"`` layout's): ``view_order[R]``, the
arena's rows by
key (a key's rows in arena order, dead rows last), and ``view_deg[K]``,
the live rows a key has; where a key's rows start is a running sum of
``view_deg``. ``join_core`` rebuilds it in the pass that appends, which
is a static fact of the traced pass as the product's presence is:
``view_deg`` by the delta's rows (a scatter of its slots; a recount of
the arena, one slot a row, only behind an in-program compaction) and
``view_order`` by one stable sort of the arena's keys. A pass with a
left delta lays the rows of the keys it holds into ``view_budget`` slots
(``view_probe``) and pairs them with both halves of the delta; a pass
whose keys hold more arena rows than that sweeps the arena as a join
without a view does, chosen on the device, so no budget errs and nothing
is dropped (``join._view_product``). This is the CSR the multiset
layout's ``join._keyed_product`` builds on every call (``view_sort``,
``view_count``) and ``linear_fixpoint``'s ``(gen, rcount)``-keyed cache
keeps beside the executor's state, here as state of the join itself:
whoever appends re-sorts, so the two are never out of step and no
validity key is needed.
"""

from __future__ import annotations

from typing import Container, Dict, Tuple

import jax
import jax.numpy as jnp

from reflow_tpu.graph import GraphError

__all__ = ["compact_arena", "propagate_plan_caps", "index_state",
           "index_probe", "index_append", "reindex", "view_state",
           "view_budget", "view_sort", "view_count", "view_probe"]


def propagate_plan_caps(plan, ingress_caps: Dict[int, int],
                        divisor: int = 1,
                        indexed: Container[int] = ()) -> Dict[int, int]:
    """Static per-tick capacity propagation against the Join arenas.

    Walks ``plan`` in topo order carrying worst-case per-node egress row
    counts from the seeded ``ingress_caps`` (sources, loops, fixpoint
    boundary producers), and raises :class:`GraphError` for the
    statically impossible case: one tick's delta capacity exceeding the
    whole (per-shard, via ``divisor``) arena. The *dynamic* high-water
    check stays inside the compiled program (``lax.cond`` compaction +
    sticky error flag) — nothing here reads a device value back.

    This is both the per-tick executor's pre-dispatch sanity check and
    the mega-tick ingress queue's capacity negotiation: queue slots are
    only allocated for capacities this propagation accepts.
    ``indexed``: ids of the joins that keep an arena index, whose δA
    product is a budgeted enumeration like the multiset joins' and not a
    sweep of the arena.
    """
    outs_cap: Dict[int, int] = dict(ingress_caps)
    for node in plan:
        if node.kind in ("source", "loop") or node.id in ingress_caps:
            continue
        if node.kind == "sink":
            continue
        caps = [outs_cap.get(i.id, 0) for i in node.inputs]
        if all(c == 0 for c in caps):
            continue
        if node.op.kind == "join":
            cap = node.op.arena_capacity // divisor
            if caps[1] > cap:
                raise GraphError(
                    f"{node}: a single tick's right-delta capacity "
                    f"({caps[1]} rows) exceeds the per-shard arena "
                    f"capacity {cap}; raise arena_capacity")
            if node.id in indexed:
                outs_cap[node.id] = (node.op.product_slack * caps[0]
                                     + caps[1])
                continue
            if not node.inputs[0].spec.unique:
                La = ((node.op.left_arena_capacity
                       or node.op.arena_capacity) // divisor)
                if caps[0] > La:
                    raise GraphError(
                        f"{node}: a single tick's left-delta capacity "
                        f"({caps[0]} rows) exceeds the per-shard left "
                        f"arena capacity {La}; raise "
                        f"left_arena_capacity")
                # both products are budget-bounded pair enumerations
                outs_cap[node.id] = (node.op.product_slack
                                     * (caps[0] + caps[1]) * divisor)
                continue
            # an absent left delta skips the arena sweep entirely;
            # sharded: each of the n shards emits 2*R/n + caps[1] rows
            # (the right delta is all_gather'd), so global egress is
            # 2*R + n*caps[1]
            outs_cap[node.id] = (
                (2 * node.op.arena_capacity if caps[0] else 0) +
                divisor * caps[1])
        elif node.op.kind == "reduce":
            K = node.inputs[0].spec.key_space
            outs_cap[node.id] = 2 * K if caps[0] >= K else 2 * caps[0]
        elif node.op.kind == "knn":
            outs_cap[node.id] = 2 * node.inputs[0].spec.key_space
        elif node.op.kind == "union":
            outs_cap[node.id] = sum(caps)
        else:
            outs_cap[node.id] = caps[0]
    return outs_cap


def _value_bits(vcols: jax.Array) -> jax.Array:
    """``vcols [R, n]`` -> int32 ``[R, q]``: the columns the compaction
    sorts and compares values by. Bitwise value identity at NATIVE width
    (ADVICE r2: narrowing 64-bit payloads to 32 bits before the compare
    can alias distinct values and corrupt non-matching rows): 64-bit
    dtypes bitcast to two int32 columns, 32-bit to one, 16-bit through
    int16; sub-4-byte ints widen losslessly."""
    R = vcols.shape[0]
    itemsize = jnp.dtype(vcols.dtype).itemsize
    if itemsize >= 4:
        return jax.lax.bitcast_convert_type(vcols, jnp.int32).reshape(R, -1)
    if itemsize == 2:
        return jax.lax.bitcast_convert_type(
            vcols, jnp.int16).astype(jnp.int32)
    if jnp.issubdtype(vcols.dtype, jnp.floating):
        # 1-byte floats (f8 variants): widen losslessly, then bitcast —
        # a numeric int cast would truncate distinct values to one bucket
        return jax.lax.bitcast_convert_type(
            vcols.astype(jnp.float32), jnp.int32)
    return vcols.astype(jnp.int32)


def _bits_value(bits: jax.Array, dtype, shape) -> jax.Array:
    """``_value_bits``'s inverse: the values the bit columns came from,
    in ``shape``, bit for bit: every widening there narrows back
    losslessly (but an 8-bit float's NaN, whose payload XLA's conversions
    do not keep in either direction: its sign and its being a NaN
    stay)."""
    R = bits.shape[0]
    itemsize = jnp.dtype(dtype).itemsize
    if itemsize >= 4:
        words = itemsize // 4
        vals = jax.lax.bitcast_convert_type(
            bits.reshape((R, -1, words) if words > 1 else (R, -1)), dtype)
    elif itemsize == 2:
        vals = jax.lax.bitcast_convert_type(bits.astype(jnp.int16), dtype)
    elif jnp.issubdtype(dtype, jnp.floating):
        vals = jax.lax.bitcast_convert_type(bits, jnp.float32).astype(dtype)
    else:
        vals = bits.astype(dtype)
    return vals.reshape(shape)


def _run_sums(w: jax.Array, first: jax.Array) -> jax.Array:
    """The running sum of ``w`` within each run of rows (``first`` marks
    a run's first row): at a run's last row, the run's total. A scan by
    doubling: after the step at distance ``d`` a row holds the sum of
    the ``2 d`` rows up to it, or of its run up to it once that is
    shorter (``reached``) — ``log2 R`` shifted adds, each one fused pass
    over the column. int32 sums wrap as a scatter-add's would."""
    def shifted(x, d, fill):
        return jnp.concatenate([jnp.full((d,), fill, x.dtype), x[:-d]])

    reached, d = first, 1
    while d < w.shape[0]:
        w = w + jnp.where(reached, 0, shifted(w, d, 0))
        reached = reached | shifted(reached, d, True)
        d *= 2
    return w


def compact_arena(state: dict) -> dict:
    """Pure kernel: (join state) -> (join state with arena compacted).

    Only the arena fields (rkeys/rvals/rw/rcount) change; the left table
    passes through untouched. Shapes are static; runs under jit or as a
    shard_map body.

    How the columns move: they ride two sorts as operands, and nothing of
    the arena's length is gathered or scattered by index. The first sort
    orders the rows by (key, value bits) — dead rows behind every key —
    and hands back the sorted key, bits and weights; a run of equal
    (key, value bits) is one group, its net weight a running sum within
    the run (``_run_sums``), read at the run's last row (any row of a
    run carries the run's key and bits). The second sort packs the
    survivors — groups of net weight other than 0 — to the front by
    their rank, which is unique, so neither sort need be stable; every
    other row rides it as zeros, which is what lies behind the
    survivors. The values are their bits, bitcast back at native width.
    """
    rk, rv, rw = state["rkeys"], state["rvals"], state["rw"]
    R = rk.shape[0]
    imax = jnp.iinfo(jnp.int32).max
    bits = _value_bits(rv.reshape(R, -1))
    cols = [bits[:, q] for q in range(bits.shape[1])]

    # lex order: key primary, then the value's bit columns
    sk, *sb, sw = jax.lax.sort(
        (jnp.where(rw != 0, rk, imax), *cols, rw),
        num_keys=1 + len(cols), is_stable=False)

    same = sk[1:] == sk[:-1]
    for col in sb:
        same = same & (col[1:] == col[:-1])
    edge = jnp.ones((1,), jnp.bool_)
    first = jnp.concatenate([edge, ~same])
    last = jnp.concatenate([~same, edge])
    netw = _run_sums(sw, first)
    keep = last & (netw != 0) & (sk != imax)

    # the survivors' ranks rise with the sorted row: packing them is a
    # sort by rank, everyone else behind
    pos = jnp.cumsum(keep, dtype=jnp.int32) - 1
    _, nk, *nbits, nw = jax.lax.sort(
        (jnp.where(keep, pos, imax),
         *(jnp.where(keep, col, 0) for col in (sk, *sb, netw))),
        num_keys=1, is_stable=False)
    nv = _bits_value(jnp.stack(nbits, axis=1), rv.dtype, rv.shape)
    ncount = pos[-1] + 1

    out = dict(state)
    out.update(rkeys=nk, rvals=nv, rw=nw,
               rcount=jnp.broadcast_to(ncount, state["rcount"].shape
                                       ).astype(state["rcount"].dtype))
    if "gen" in state:
        # compaction reorders rows: bump the generation so any persistent
        # CSR cache over the old ordering invalidates (linear_fixpoint)
        out["gen"] = state["gen"] + 1
    return out


# -- the arena index (see the module docstring) ----------------------------

def index_state(K: int, R: int) -> dict:
    """The index leaves of a join's state over ``K`` keys and an arena
    of ``R`` rows, for an empty arena."""
    return {
        "seg_len": jnp.zeros((R,), jnp.int32),
        "seg_prev": jnp.full((R,), -1, jnp.int32),
        "head": jnp.full((K,), -1, jnp.int32),
        "deg": jnp.zeros((K,), jnp.int32),
    }


def _segments(sk: jax.Array, n) -> Tuple[jax.Array, jax.Array]:
    """Rows ``[0, n)`` of ``sk`` are key-sorted: -> (first-of-segment
    mask, segment length at each first row and 0 elsewhere)."""
    C = sk.shape[0]
    i = jnp.arange(C, dtype=jnp.int32)
    inside = i < n
    first = inside & ((i == 0) | (sk != jnp.roll(sk, 1)))
    # the next boundary strictly after each row: a later first row, or
    # the end of the sorted stretch
    edge = jnp.where(first | ~inside, i, C)
    after = jax.lax.cummin(
        jnp.concatenate([edge[1:], jnp.full((1,), C, jnp.int32)]),
        reverse=True)
    return first, jnp.where(first, jnp.minimum(after, n) - i, 0)


def index_probe(state: dict, dk: jax.Array, dlive: jax.Array, T: int):
    """Every (delta row, arena row) pair that shares a key, laid into
    ``T`` slots: -> (owner delta row [T], arena row [T], valid [T],
    overflow, steps). ``dk`` are keys local to the index; a true pair
    count beyond ``T`` returns overflow (the caller latches the sticky
    error) and the pairs that fit. ``steps``: the chain walk's trips,
    each a pass over the ``T`` slots: the segments of the probed key
    that has most (one a tick it was appended in since the last
    ``reindex``: only a compaction shortens a chain)."""
    C = dk.shape[0]
    K = state["head"].shape[0]
    k_c = jnp.clip(dk, 0, K - 1)
    d = jnp.where(dlive, state["deg"][k_c], 0)
    cum = jnp.cumsum(d)
    start = cum - d
    j = jnp.arange(T, dtype=jnp.int32)
    rows = jnp.arange(C, dtype=jnp.int32)

    def owner_at(pos, active):
        # slot -> the last active row whose ``pos`` is at or before it
        # (positions rise with the row index, so a running max of the
        # scattered row indices is exactly that)
        marks = jnp.full((T,), -1, jnp.int32).at[
            jnp.where(active, pos, T)].max(rows, mode="drop")
        return jax.lax.cummax(marks)

    own = owner_at(start, d > 0)
    own_c = jnp.maximum(own, 0)
    valid = (own >= 0) & (j < start[own_c] + d[own_c])

    def live(c):
        return jnp.any(c[0] >= 0)

    def step(c):
        cur, at, src, steps = c
        act = cur >= 0
        cur_c = jnp.maximum(cur, 0)
        n = jnp.where(act, state["seg_len"][cur_c], 0)
        o = owner_at(at, act)
        o_c = jnp.maximum(o, 0)
        hit = (o >= 0) & (j < at[o_c] + n[o_c])
        src = jnp.where(hit, cur_c[o_c] + (j - at[o_c]), src)
        return (jnp.where(act, state["seg_prev"][cur_c], -1), at + n, src,
                steps + 1)

    cur0 = jnp.where(d > 0, state["head"][k_c], -1)
    _, _, src, steps = jax.lax.while_loop(
        live, step, (cur0, start, jnp.zeros((T,), jnp.int32),
                     jnp.zeros((), jnp.int32)))
    return own_c, src, valid, cum[-1] > T, steps


def _write_block(col: jax.Array, at, new: jax.Array, n) -> jax.Array:
    """``col`` with rows ``[at, at + n)`` taken from ``new[:n]`` and every
    other row as it was; a row past ``col``'s end is dropped. One
    contiguous read and one contiguous write of ``new``'s rows.
    ``dynamic_update_slice`` clamps a start whose block would pass the
    end, so the block is placed where it fits, ``back`` rows early, and
    the new rows enter it ``back`` rows late (rolled: the rows that wrap
    are not taken) over what the block held."""
    R = col.shape[0]
    new = new[:R]              # rows [R, C) of a wider delta never fit
    C = new.shape[0]
    lo = jnp.clip(at, 0, R - C)
    back = jnp.minimum(at - lo, C)
    i = jnp.arange(C, dtype=jnp.int32) - back
    take = ((i >= 0) & (i < n)).reshape((C,) + (1,) * (new.ndim - 1))
    held = jax.lax.dynamic_slice_in_dim(col, lo, C)
    return jax.lax.dynamic_update_slice_in_dim(
        col, jnp.where(take, jnp.roll(new, back, axis=0), held), lo, 0)


def index_append(state: dict, keys, vals, w) -> Tuple[dict, jax.Array]:
    """Append the live rows of one tick's right delta, key-sorted, as one
    segment a key, and chain them in. The rows land at ``rcount``, one
    after the other, so the arena's and the index's row columns are each
    written as one block (``_write_block``); only ``head`` and ``deg``,
    which are keyed, are scatters. Whoever runs the ticks has made room
    (``reindex``); rows past the arena's end are dropped and reported,
    and no row before ``rcount`` or from ``rcount + n_app`` on changes.
    -> (state', overflow)."""
    R = state["rkeys"].shape[0]
    K = state["head"].shape[0]
    live = w != 0
    n_app = jnp.sum(live.astype(jnp.int32))
    skey = jnp.where(live, jnp.clip(keys, 0, K - 1), K)
    order = jnp.argsort(skey, stable=True)
    sk = skey[order]
    first, seg_len = _segments(sk, n_app)
    rc = state["rcount"]
    row = rc + jnp.arange(sk.shape[0], dtype=jnp.int32)
    fkey = jnp.where(first, sk, K)
    seg_prev = jnp.where(first, state["head"][jnp.minimum(sk, K - 1)], -1)
    out = dict(state)
    for name, new in (("rkeys", sk), ("rvals", vals[order]),
                      ("rw", w[order]), ("seg_len", seg_len),
                      ("seg_prev", seg_prev)):
        out[name] = _write_block(state[name], rc, new, n_app)
    out["rcount"] = rc + n_app
    out["head"] = state["head"].at[fkey].set(row, mode="drop")
    out["deg"] = state["deg"].at[fkey].add(seg_len, mode="drop")
    return out, out["rcount"] > R


def reindex(state: dict) -> dict:
    """Compact the arena (which leaves it sorted by key) and derive the
    index from it: one segment a key."""
    st = compact_arena(state)
    rk, n = st["rkeys"], st["rcount"]
    R = rk.shape[0]
    K = st["head"].shape[0]
    first, seg_len = _segments(rk, n)
    fkey = jnp.where(first, rk, K)
    st["seg_len"] = seg_len
    st["seg_prev"] = jnp.full((R,), -1, jnp.int32)
    st["head"] = jnp.full((K,), -1, jnp.int32).at[fkey].set(
        jnp.arange(R, dtype=jnp.int32), mode="drop")
    st["deg"] = jnp.zeros((K,), jnp.int32).at[fkey].set(seg_len,
                                                        mode="drop")
    return st


# -- the key-sorted view (see the module docstring) ------------------------

def view_state(K: int, R: int) -> dict:
    """The view leaves of a join's state over ``K`` keys and an arena of
    ``R`` rows, for an empty arena (what ``view_sort`` and ``view_count``
    give for one: every row dead, so every row in its own place)."""
    return {
        "view_order": jnp.arange(R, dtype=jnp.int32),
        "view_deg": jnp.zeros((K,), jnp.int32),
    }


def view_budget(K: int, R: int) -> int:
    """Arena rows a probe of the view lays out (slots: the rows of the
    probed keys, each once) before a pass sweeps instead: the key
    space, or the arena where that is smaller (which then always
    fits). A probe of ``T`` slots emits up to ``2 T`` live rows (a key
    both retracted and inserted pairs twice), so it does not bind the
    min/max's rung (``_merge_rungs`` goes by the live rows it is
    handed)."""
    return min(K, R)


def _view_key(rkeys: jax.Array, rw: jax.Array, K: int) -> jax.Array:
    """The key the view sorts a row by: its own, clipped as a gather
    clips it, and ``K`` for a dead row, which so sorts behind every
    key."""
    return jnp.where(rw != 0, jnp.clip(rkeys, 0, K - 1), K)


def view_sort(rkeys: jax.Array, rw: jax.Array, K: int) -> jax.Array:
    """``view_order``: the arena's rows by key, a key's rows in arena
    order, dead rows last (one stable sort of ``R`` keys)."""
    return jnp.argsort(_view_key(rkeys, rw, K), stable=True
                       ).astype(jnp.int32)


def view_count(rkeys: jax.Array, rw: jax.Array, K: int) -> jax.Array:
    """``view_deg`` recounted over the whole arena: one scatter of ``R``
    slots, for an arena a compaction has just rewritten (an append
    counts its own rows in)."""
    return jnp.zeros((K + 1,), jnp.int32).at[
        _view_key(rkeys, rw, K)].add(1)[:K]


def view_probe(state: dict, probed: jax.Array, T: int):
    """Every live arena row whose key is ``probed`` (bool ``[K]``), laid
    into ``T`` slots, key after key and a key's rows in arena order:
    -> (key [T], arena row [T], valid [T]). Where there are more than
    ``T`` the slots hold the first ``T`` of them: the caller counts
    first and takes another way then (a loop join sweeps: nothing
    latches). The slot assignment is ``_keyed_product``'s (each key's
    first slot scattered, a running maximum fills the rest), over the
    ``K`` keys and not over a delta's slots."""
    deg, order = state["view_deg"], state["view_order"]
    K, R = deg.shape[0], order.shape[0]
    d = jnp.where(probed, deg, 0)
    cum = jnp.cumsum(d)
    start = cum - d
    marks = jnp.full((T,), -1, jnp.int32).at[
        jnp.where(d > 0, start, T)].max(
            jnp.arange(K, dtype=jnp.int32), mode="drop")
    key = jnp.maximum(jax.lax.cummax(marks), 0)
    j = jnp.arange(T, dtype=jnp.int32)
    # a key's rows start at (rows of the keys before it) in the view and
    # at ``start`` in the slots: one table of the difference
    shift = (jnp.cumsum(deg) - deg) - start
    row = order[jnp.clip(shift[key] + j, 0, R - 1)]
    return key, row, j < cum[-1]
