"""Fused delta-vector fixpoint: frontier-proportional loop passes.

The row-based on-device fixpoint (``fixpoint.py``) does O(arena) work per
loop pass: the Join sweeps its whole append arena and the Reduce
scatter-adds the full product, regardless of how many keys actually
changed. Profiling the north-star PageRank churn tick (100k nodes / 1M
edges / 1% churn, real chip) shows why that hurts: the live frontier is
160k-900k edges for the first ~6 passes and then collapses to a few
thousand, while the row-based program pays for ~4.9M product rows on
every one of its ~17 passes.

This module exploits a *declared-linear* loop region to make per-pass cost
proportional to the live frontier:

    loop L -> Join(left=L, linear_left) -> [GroupBy] -> [linear Maps]
           -> [Union with region-external streams] -> Reduce('sum', tol)
           -> close_loop(L, ...)

For such a region the per-pass delta stream through the chain is fully
determined by its *linear observables* per key — ``dval[k] = Σ w·v`` and
``dw[k] = Σ w`` of the loop delta — because every operator maps weighted
sums to weighted sums. The loop carry therefore collapses from padded
delta rows to one dense [K, P+1] array (``dval`` flattened + ``dw``), and
one pass becomes:

    1. frontier = keys with any nonzero observable and out-degree > 0
    2. gather exactly the frontier's arena rows (CSR over the arena) and
       push ``merge/key_fn/value_fn/maps`` through them —
       ``Σ_j sw_j·φ_j(dval[k])`` per consumed edge j
    3. one fused scatter-add of (value, weight) contributions into the
       Reduce's dense tables
    4. the Reduce's dense emission diff (tol-gated) becomes the next
       observables directly — no rows are ever materialized

Step 2's gather capacity adapts per pass: the exact frontier edge count
(a dot of the frontier mask with the degree vector) selects one of a few
static budget tiers via ``lax.switch``, with a full-arena dense branch as
the always-correct top tier. TPU random access runs at a few tens of
million rows/s, so everything row-shaped is fused into stacked-column
single gathers, and the ragged segment->slot mapping uses a
scatter-of-starts + cumsum (a measured ~13x over ``searchsorted``'s
binary-search loop at 1M slots).

**Persistent CSR (round 4).** The CSR over the arena used to be rebuilt
from scratch every tick (~25-30ms device at a 1.31M-row arena,
argsort-dominated — VERDICT r3 #2). The arena is an append-only log
between compactions, so the sorted base is now a cache that PERSISTS
across ticks on the program object: rows ``[0, count)`` stay sorted in
``svalw`` with their ``geo`` (start, degree) table, and each tick only
sorts the small append TAIL ``[count, rcount)`` into its own window CSR
(capacity ``Ft``, a fraction of the arena). A loop pass then pushes the
frontier through BOTH segments (two tier-switched gathers whose dense
contribution tables sum before one fold), which costs O(tail frontier)
extra instead of O(arena log arena) fixed. The cache self-invalidates:
compaction bumps the arena's ``gen`` counter, and a gen mismatch, a
shrunken ``rcount``, or a tail overflowing ``Ft`` forces an in-program
full rebuild (``lax.cond``). The cache is pure derived state — never
checkpointed, safe across rebinds, correct under program interleaving —
because validity is decided only against the live arena's (gen, rcount).

State transitions stay exactly the row-program's: the Reduce's
wsum/wcnt/emitted tables evolve identically (the linear observables are
all the row program ever folds into them), and the Join's left table is
patched densely at loop exit (``lval = emitted where live``,
``lw += has_final - has_entry`` — per-pass retract/insert pairs cancel;
``has_entry`` is the PRE-tick table because the loop folds phase A's
emission too). Boundary telescoping and the exit pass are inherited
unchanged from ``FixpointProgram``'s host structure.
"""

from __future__ import annotations

import dataclasses
from typing import Callable, Dict, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp

from reflow_tpu.executors.device_delta import DeviceDelta
from reflow_tpu.executors.fixpoint import (FixpointStructure,
                                           _MacroTickMixin, _emitted_diff)
from reflow_tpu.executors.lowerings import (_agg_tables, _bcast_w, _differs,
                                            _masked_contrib)
from reflow_tpu.graph import FlowGraph, Node

__all__ = ["LinearFixpointProgram", "LinearStructure", "analyze_linear"]

#: offsets/degrees/keys ride in f32 columns of fused gathers; they must be
#: exactly representable
_F32_EXACT = 1 << 24


def _f32_roundtrip_safe(dtype) -> bool:
    """Whether every value of ``dtype`` survives a cast through float32.

    The budget tiers stack arena/loop values into f32 gather columns
    (ADVICE r2: int32 >= 2**24, int64, and f64 payloads would silently
    lose precision there and disagree with the dense tier).
    """
    dt = jnp.dtype(dtype)
    if jnp.issubdtype(dt, jnp.floating):
        return dt.itemsize <= 4   # f32 exact; bf16/f16 widen losslessly
    if jnp.issubdtype(dt, jnp.integer) or dt == jnp.bool_:
        return dt.itemsize <= 2   # int8/int16/uint* fit in f32's mantissa
    return False


@dataclasses.dataclass(frozen=True)
class LinearStructure:
    """A loop region matching the fused delta-vector pattern."""

    loop: Node                    # the loop variable (unique-keyed)
    join: Node                    # Join(left=loop, right external, linear)
    groupby: Optional[Node]       # optional re-key after the join
    maps: Tuple[Node, ...]        # linear Maps after the (re-keyed) join
    union: Optional[Node]         # optional Union with external streams
    reduce: Node                  # Reduce('sum'), closes the loop


def analyze_linear(graph: FlowGraph,
                   structure: FixpointStructure) -> Optional[LinearStructure]:
    """Match the region against the linear-chain pattern; None = no match."""
    if len(structure.loops) != 1:
        return None
    (loop,) = structure.loops
    region = {n.id: n for n in structure.loop_plan}

    # the loop's only region consumer must be a declared-linear Join with
    # the loop variable on the (unique-keyed) left and an external right
    consumers = [c for c, _ in graph.consumers(loop)]
    if len(consumers) != 1:
        return None
    join = consumers[0]
    if (join.kind != "op" or join.op.kind != "join"
            or not join.op.linear_left or join.op.merge is None
            or join.id not in region):
        return None
    if join.inputs[0] is not loop or not join.inputs[0].spec.unique:
        return None
    if join.inputs[1].id in region:
        return None  # arena must be static during the loop

    # walk the single-consumer chain join -> [groupby] -> maps* -> [union]
    # -> reduce
    groupby: Optional[Node] = None
    maps: List[Node] = []
    union: Optional[Node] = None
    node = join
    red: Optional[Node] = None
    while red is None:
        cons = [c for c, _ in graph.consumers(node) if c.id in region]
        if len(cons) != 1:
            return None
        prev, node = node, cons[0]
        if node.kind != "op":
            return None
        k = node.op.kind
        if k == "groupby":
            if groupby is not None or maps or union is not None:
                return None  # at most one, directly after the join
            groupby = node
        elif k == "map":
            if not node.op.linear or union is not None:
                return None
            maps.append(node)
        elif k == "union":
            if union is not None:
                return None
            # every other Union input must be region-external (quiet
            # during the loop)
            for inp in node.inputs:
                if inp is not prev and inp.id in region:
                    return None
            union = node
        elif k == "reduce":
            red = node
        else:
            return None

    if red.op.how != "sum" or loop.back_input is not red:
        return None
    # the Reduce must be the region's only boundary node (telescoping)
    if any(b is not red for b in structure.boundary):
        return None
    # every region node must be on the recognized chain
    chain_ids = {loop.id, join.id, red.id}
    chain_ids.update(m.id for m in maps)
    if groupby is not None:
        chain_ids.add(groupby.id)
    if union is not None:
        chain_ids.add(union.id)
    if set(region) != chain_ids:
        return None
    # the loop variable and the Reduce emission are the same collection
    if (loop.spec.key_space != red.spec.key_space
            or tuple(loop.spec.value_shape) != tuple(red.spec.value_shape)):
        return None
    return LinearStructure(loop=loop, join=join, groupby=groupby,
                           maps=tuple(maps), union=union, reduce=red)


def _rowfn(fn: Callable, vectorized: bool) -> Callable:
    if vectorized:
        return fn
    return jax.vmap(fn)


def _edge_budget_tiers(arena_capacity: int) -> List[int]:
    """Static gather budgets, large to small; the dense full-arena branch
    sits above the largest. Measured regime (v5e, 1.31M-row arena,
    round-4 microbench): a budget pass costs ~2ms of O(K) machinery +
    ~55ns/slot of gathers+scatter (17.5ms at EB=262144, 3.6ms at 8192);
    the dense branch costs ~23-25ms destination-sorted (segment_sum
    16.2ms vs scatter-add 24.3ms for the fold alone) and ~34ms raw.
    Crossover is therefore near arena/3; the ladder starts at arena/4
    (clear budget win) and steps by ratio 2, bounding wasted gather
    slots to 2x the live frontier. Six tiers keep the lax.switch small;
    frontiers below the floor ride the smallest tier cheaply."""
    tiers = []
    c = 1 << (max(arena_capacity // 4, 1).bit_length() - 1)
    while c >= 2048 and len(tiers) < 6:
        tiers.append(c)
        c //= 2
    return tiers


def _tail_tiers(Ft: int) -> List[int]:
    """Budget ladder for the tail segment. The top tier is ``Ft`` itself
    (the tail's frontier edge count can never exceed its row count, so a
    dense fallback is unnecessary); smaller tiers halve down like the
    base ladder."""
    tiers = [Ft]
    c = Ft // 2
    while c >= 2048 and len(tiers) < 6:
        tiers.append(c)
        c //= 2
    return tiers


class LinearFixpointProgram(_MacroTickMixin):
    """One compiled tick for a linear loop region: row-based phase A +
    fused delta-vector while_loop + row-based exit pass.

    Drop-in alternative to ``FixpointProgram`` (same call contract);
    built by the executor when :func:`analyze_linear` matches. Raises
    ValueError when shapes don't fit the fused path's representation
    (caller falls back to the row program).
    """

    def __init__(self, executor, plan: Sequence[Node],
                 ingress_caps: Dict[int, int], max_iters: int, *,
                 structure: FixpointStructure,
                 linear: LinearStructure):
        graph = executor.graph
        self.structure = structure
        self.linear = linear
        self.max_iters = max_iters
        self.sink_ids = [s.id for s in graph.sinks]

        L, J, R = linear.loop, linear.join, linear.reduce
        if (L.spec.key_space >= _F32_EXACT
                or J.op.arena_capacity >= _F32_EXACT
                or R.inputs[0].spec.key_space >= _F32_EXACT):
            raise ValueError("key space / arena too large for fused-f32 "
                             "index columns")
        for what, dt in (("arena value", J.inputs[1].spec.value_dtype),
                         ("join output value", J.spec.value_dtype),
                         ("loop value", L.spec.value_dtype),
                         ("reduce value", R.spec.value_dtype)):
            if not _f32_roundtrip_safe(dt):
                raise ValueError(
                    f"{what} dtype {jnp.dtype(dt).name} does not round-trip "
                    f"exactly through the fused loop's float32 columns; "
                    f"using the row-based fixpoint")

        full_pass = executor.build_pass_fn(list(plan))
        exit_pass = (executor.build_pass_fn(list(structure.exit_plan))
                     if structure.exit_plan else None)

        gb = linear.groupby
        K = L.spec.key_space                   # loop/left key space
        KR = R.inputs[0].spec.key_space        # reduce key space
        odtype = J.spec.value_dtype
        rdtype = R.spec.value_dtype
        vdtype = J.inputs[1].spec.value_dtype  # arena value dtype
        tol = R.op.tol
        loop_vshape = tuple(L.spec.value_shape)
        P = 1
        for s in loop_vshape:
            P *= s
        arena_vshape = tuple(J.inputs[1].spec.value_shape)
        Q = 1
        for s in arena_vshape:
            Q *= s
        #: cross-tick residual deferral (close_loop defer_passes): cap the
        #: while_loop at ``defer`` passes per tick and carry the live
        #: observables ``xw`` across ticks in the loop node's ``resid``
        #: state leaf instead of iterating to quiescence. The left-table
        #: patch then tracks the FOLDED collection A = emitted - resid
        #: (in-flight emission rows have not passed through the Join yet),
        #: which keeps the schedule exactly equal to a host loop that
        #: stops after the same passes. Accuracy contract: docs/guide.md.
        defer = L.defer_passes
        mi = min(max_iters, defer) if defer else max_iters
        # shard context: under a ShardedTpuExecutor the whole loop runs
        # inside ONE shard_map region — per-shard CSR over the local arena
        # slice (arena keys are shard-local by construction of the routed
        # Join), a GLOBAL-domain contribution scatter combined with one
        # psum_scatter per pass onto the owned key slice, and globally
        # uniform tier selection so the collectives inside lax.switch
        # branches can never diverge across devices (VERDICT r2 item 5)
        mesh = getattr(executor, "mesh", None)
        axis = getattr(executor, "axis", None) if mesh is not None else None
        nsh = executor.n if axis is not None else 1
        if K % nsh or J.op.arena_capacity % nsh:
            raise ValueError("key space / arena not divisible by mesh size")
        Rl = J.op.arena_capacity // nsh
        tiers = _edge_budget_tiers(Rl)
        #: tail window capacity: appends since the last full CSR rebuild
        #: accumulate here; overflow forces a rebuild. Rl/8 amortizes the
        #: rebuild over ~8 windows of appends while keeping the per-tick
        #: tail sort small.
        Ft = min(Rl, max(2048, Rl // 8))
        tail_tiers = _tail_tiers(Ft)
        merge = J.op.merge
        #: destination-sorted dense tier: available when every arena row's
        #: output key is loop-value-independent (GroupBy(stable_key=True),
        #: or no re-key at all — then the destination IS the join key).
        #: The dense sweep's contribution scatter becomes a sorted
        #: segment_sum (measured 16.2ms vs 24.3ms scatter-add at 1.31M
        #: rows, v5e), with per-row destinations precomputed at CSR build.
        stable_dst = gb is None or gb.op.stable_key
        key_fn = _rowfn(gb.op.key_fn, gb.op.vectorized) if gb else None
        value_fn = (_rowfn(gb.op.value_fn, gb.op.vectorized)
                    if gb is not None and gb.op.value_fn is not None else None)
        map_fns = [_rowfn(m.op.fn, m.op.vectorized) for m in linear.maps]
        boundary = structure.boundary
        loop_id, join_id, red_id = L.id, J.id, R.id

        def push(src_keys, x, dwx, vb, ew):
            """Per-edge contributions of the frontier push.

            src_keys [E'] global join keys; x [E', *loop_vshape] per-key
            dval gathered per edge; dwx [E'] per-key net weight; vb
            [E', *arena_vshape] arena values; ew [E'] arena row weights
            (0 = dead or out-of-budget). -> (okey, wsum_c, wcnt_c).
            """
            merged = jnp.asarray(merge(src_keys, x, vb), odtype)
            if key_fn is not None:
                okey = jnp.asarray(key_fn(src_keys, merged), jnp.int32)
            else:
                okey = src_keys
            okey = jnp.where(ew == 0, 0, okey)
            val = merged
            if value_fn is not None:
                val = value_fn(src_keys, merged)
            for fn in map_fns:
                val = fn(val)
            wv = _masked_contrib(ew, jnp.asarray(val, jnp.float32))
            return okey, wv, (dwx * ew).astype(jnp.float32)

        def scatter_tab(okey, wv, wc):
            """One fused scatter-add of a push's contributions into a
            GLOBAL-key-domain [KR, P+1] table (okey is a global dst id).
            Segments (base/tail) each produce a table; the tables SUM
            before the single fold + psum_scatter of the pass."""
            flat = wv.reshape(wv.shape[0], -1)
            upd = jnp.concatenate([flat, wc[:, None]], axis=-1)
            return jnp.zeros((KR, upd.shape[1]), jnp.float32
                             ).at[okey].add(upd, mode="drop")

        def fold(rstate, tab):
            """Fold one pass's summed contribution table into the Reduce's
            running tables, then the dense emission diff (exactly
            _lower_reduce's dense mode, expressed on the vectors).

            Sharded: one tiled psum_scatter both sums cross-shard
            contributions and hands each shard its owned slice — the
            fold, diff, and next observables are then local.
            """
            if axis is not None:
                tab = jax.lax.psum_scatter(tab, axis, scatter_dimension=0,
                                           tiled=True)
            Ko = tab.shape[0]              # owned key rows (KR / nsh)
            vshape = loop_vshape
            wsum = rstate["wsum"] + tab[:, :-1].reshape((Ko,) + vshape)
            wcnt = rstate["wcnt"] + tab[:, -1].astype(jnp.int32)

            emitted, em_has = rstate["emitted"], rstate["emitted_has"]
            agg, exists = _agg_tables(R.op, wsum, wcnt, rdtype)
            changed = _differs(agg, emitted, tol)
            ins_m = exists & (~em_has | changed)
            ret_m = em_has & (~exists | changed)
            new_emitted = jnp.where(_bcast_w(ins_m, agg), agg, emitted)
            new_has = jnp.where(ins_m, True,
                                jnp.where(ret_m & ~exists, False, em_has))
            # next-pass linear observables of the emission delta:
            # rows are (emitted_old, -1)[ret] + (agg, +1)[ins]
            dval = (jnp.where(_bcast_w(ins_m, agg), agg.astype(jnp.float32),
                              0.0)
                    - jnp.where(_bcast_w(ret_m, emitted),
                                emitted.astype(jnp.float32), 0.0))
            dwv = (ins_m.astype(jnp.float32) - ret_m.astype(jnp.float32))
            xw = jnp.concatenate([dval.reshape(Ko, P), dwv[:, None]], axis=1)
            rows = jnp.sum(ins_m.astype(jnp.int32) + ret_m.astype(jnp.int32))
            if axis is not None:
                rows = jax.lax.psum(rows, axis)
            new_rstate = dict(rstate)
            new_rstate.update(wsum=wsum, wcnt=wcnt, emitted=new_emitted,
                              emitted_has=new_has)
            return new_rstate, xw, rows

        def budget_tab(EB, geo, svalw, xw, base):
            """Frontier-compacted push at static gather budget EB over one
            CSR segment (base or tail) -> contribution table.

            One gather builds the compacted frontier table, a
            scatter-of-starts + cumsum assigns segment slots to frontier
            segments, one gather expands the frontier table per slot, one
            gather fetches the segment's sorted rows, one scatter applies
            contributions. All indices are LOCAL to this shard's key
            slice; ``base`` rebases them to global ids for merge/key_fn.
            """
            Klc = geo.shape[0]
            deg = geo[:, 1]
            mask = jnp.any(xw != 0, axis=1) & (deg > 0)
            # compact frontier keys; count <= frontier edge count <= EB
            # because every compacted key has deg >= 1
            pos = jnp.cumsum(mask.astype(jnp.int32)) - 1
            tgt = jnp.where(mask, pos, EB)
            ids = jnp.full((EB,), Klc, jnp.int32).at[tgt].set(
                jnp.arange(Klc, dtype=jnp.int32), mode="drop")
            ids_c = jnp.minimum(ids, Klc - 1)
            # one fused gather: offsets, deg, key, observables per frontier
            ftab = jnp.concatenate(
                [geo, jnp.arange(Klc, dtype=jnp.float32)[:, None], xw],
                axis=1)
            fr = ftab[ids_c]                   # [EB, 3 + P + 1]
            fdeg = jnp.where(ids < Klc, fr[:, 1], 0.0)
            cum = jnp.cumsum(fdeg)
            total = cum[-1]
            start = cum - fdeg
            # slot j belongs to the frontier entry whose segment starts at
            # or before j: scatter segment starts, running-sum them
            spos = jnp.where(fdeg > 0, start.astype(jnp.int32), EB)
            marks = jnp.zeros((EB,), jnp.int32).at[spos].add(1, mode="drop")
            owner = jnp.cumsum(marks) - 1
            owner = jnp.clip(owner, 0, EB - 1)
            # expand the frontier table per slot (one gather), with the
            # segment start appended so each slot finds its sorted row
            frs = jnp.concatenate([fr, start[:, None]], axis=1)[owner]
            j = jnp.arange(EB, dtype=jnp.float32)
            valid = (j < total) & (frs[:, 1] > 0)
            eidx = (frs[:, 0] + (j - frs[:, -1])).astype(jnp.int32)
            eidx = jnp.where(valid, eidx, 0)
            src = frs[:, 2].astype(jnp.int32)
            src = jnp.clip(src, 0, Klc - 1)
            x = frs[:, 3:3 + P].reshape((EB,) + loop_vshape)
            dwx = frs[:, 3 + P]
            sv = svalw[eidx]                   # [EB, Q+1]
            vb = jnp.asarray(sv[:, :Q], vdtype).reshape((EB,) + arena_vshape)
            ew = jnp.where(valid, sv[:, Q].astype(jnp.int32), 0)
            okey, wv, wc = push(src + base, jnp.asarray(x, jnp.float32),
                                dwx, vb, ew)
            return scatter_tab(okey, wv, wc), jnp.zeros((), jnp.bool_)

        def dense_tab(arena, xw, base):
            """Full-arena push — the always-correct top tier. Sweeps the
            RAW arena rows (base and tail alike), so when this branch is
            selected the tail switch must contribute zeros."""
            rk, rv, rw = arena
            g = xw[rk]                          # [Rl, P+1] one gather
            x = g[:, :P].reshape((rk.shape[0],) + loop_vshape)
            okey, wv, wc = push(rk + base, x, g[:, P], rv, rw)
            return scatter_tab(okey, wv, wc), jnp.zeros((), jnp.bool_)

        def dense_sorted_tab(dokey, dsrc, dvalw, xw, base):
            """Base-rows dense push over the destination-SORTED copy: the
            contribution fold is a sorted segment_sum instead of a random
            scatter-add. Covers only rows [0, count) — the tail switch
            must run alongside (tail rows are not in the sorted copy)."""
            Rl_ = dsrc.shape[0]
            src_c = jnp.clip(dsrc, 0, xw.shape[0] - 1)
            g = xw[src_c]                       # [Rl, P+1] one gather
            x = g[:, :P].reshape((Rl_,) + loop_vshape)
            vb = jnp.asarray(dvalw[:, :Q], vdtype).reshape(
                (Rl_,) + arena_vshape)
            ew = dvalw[:, Q].astype(jnp.int32)
            # stable_key declares the runtime okey equals the precomputed
            # (sorted) destination. The declaration is near-free to CHECK
            # here (okey is already computed): a key_fn that actually
            # reads the loop value would otherwise corrupt ranks
            # tier-selection-dependently (ADVICE r4) — route the mismatch
            # into the join's sticky error instead.
            okey, wv, wc = push(src_c + base, x, g[:, P], vb, ew)
            bad = jnp.any((okey != dokey) & (ew != 0))
            upd = jnp.concatenate([wv.reshape(Rl_, -1), wc[:, None]],
                                  axis=-1)
            return jax.ops.segment_sum(upd, dokey, num_segments=KR,
                                       indices_are_sorted=True), bad

        def loop_region(jstate, rstate, csr, ld, has_entry, resid):
            """Phase B on one shard's slices (the whole mesh's arrays when
            single-device): observables from the loop delta, CSR cache
            validation + tail build, the while_loop, and the Join
            left-table patch. ``ld`` rows are owner-aligned by
            construction (loop deltas are always Reduce emissions, which
            each shard emits over its owned key range). ``resid`` (defer
            mode only, else None) is the carried [Klc, P+1] observable
            block from the previous tick; the final ``xw`` is returned as
            the next tick's carry."""
            Klc = rstate["emitted_has"].shape[0]   # local loop/key rows
            if axis is not None:
                base = (jax.lax.axis_index(axis) * Klc).astype(jnp.int32)
            else:
                base = jnp.zeros((), jnp.int32)

            # loop delta rows -> dense linear observables (local keys)
            dval = jnp.zeros((Klc,) + loop_vshape, jnp.float32)
            dw = jnp.zeros((Klc,), jnp.int32)
            lk = ld.keys - base
            contrib = _masked_contrib(ld.weights, ld.values.astype(jnp.float32))
            dval = dval.at[lk].add(contrib, mode="drop")
            dw = dw.at[lk].add(ld.weights, mode="drop")
            xw = jnp.concatenate(
                [dval.reshape(Klc, P), dw.astype(jnp.float32)[:, None]],
                axis=1)
            if resid is not None:
                # carried residue joins the loop-delta stream at the FIRST
                # loop pass (pushed against the post-churn arena) — the
                # exact schedule a host loop resuming its stashed back-edge
                # rows would run, since the region is linear and the Join
                # bilinear (phase A already joined deltas against the
                # folded A, which excludes the in-flight rows)
                xw = xw + resid

            rk, rv, rw = jstate["rkeys"], jstate["rvals"], jstate["rw"]
            Rcap = rk.shape[0]
            rc = jnp.reshape(jstate["rcount"], (-1,))[0]
            gen = jnp.reshape(jstate["gen"], (-1,))[0]
            c_count = csr["count"][0]
            c_gen = csr["gen"][0]

            # CSR cache validity: the base ordering survives only while
            # the arena is append-only past ``c_count`` under the same
            # generation, and the un-sorted tail must fit its window
            rebuild = ((c_gen != gen) | (c_count > rc)
                       | (rc - c_count > Ft))

            def do_rebuild(_):
                # full rebuild: argsort the whole (per-shard) arena slice,
                # dead rows to the sentinel; bounds via scatter-count +
                # cumsum (identical to searchsorted over the sorted keys
                # at a third of the cost — tools/profile_tick.py)
                skey = jnp.where(rw != 0, rk, Klc)
                order = jnp.argsort(skey)
                svalw = jnp.concatenate(
                    [rv[order].reshape(Rcap, Q).astype(jnp.float32),
                     rw[order].astype(jnp.float32)[:, None]], axis=1)
                deg_i = jnp.zeros((Klc + 1,), jnp.int32).at[skey].add(
                    1, mode="drop")[:Klc]
                starts = jnp.cumsum(deg_i) - deg_i
                geo = jnp.stack([starts, deg_i], axis=1).astype(jnp.float32)
                out = (geo, svalw, rc)
                if stable_dst:
                    # per-row output keys with the loop value zeroed (the
                    # stable_key contract makes them loop-independent);
                    # live rows outside [0, KR) mirror scatter_tab's drop
                    gk = jnp.clip(rk, 0, Klc - 1) + base
                    x0 = jnp.zeros((Rcap,) + loop_vshape, jnp.float32)
                    merged0 = jnp.asarray(merge(gk, x0, rv), odtype)
                    if key_fn is not None:
                        ok0 = jnp.asarray(key_fn(gk, merged0), jnp.int32)
                    else:
                        ok0 = gk
                    ok_valid = (rw != 0) & (ok0 >= 0) & (ok0 < KR)
                    ok0 = jnp.where(ok_valid, ok0, 0)
                    dorder = jnp.argsort(ok0)
                    dokey = ok0[dorder]
                    dsrc = rk[dorder]
                    dvalw = jnp.concatenate(
                        [rv[dorder].reshape(Rcap, Q).astype(jnp.float32),
                         jnp.where(ok_valid[dorder], rw[dorder], 0
                                   ).astype(jnp.float32)[:, None]], axis=1)
                    out = out + (dokey, dsrc, dvalw)
                return out

            def keep(_):
                out = (csr["geo"], csr["svalw"], c_count)
                if stable_dst:
                    out = out + (csr["dokey"], csr["dsrc"], csr["dvalw"])
                return out

            built = jax.lax.cond(rebuild, do_rebuild, keep, None)
            geo_b, svalw_b, bcount = built[:3]
            if stable_dst:
                dokey_b, dsrc_b, dvalw_b = built[3:]

            # tail CSR over the fresh rows [bcount, rc): a small argsort
            # window (appends are live-compacted by join_core, so the
            # window holds only live rows below rc). Append-free ticks
            # (rc == bcount — e.g. pure left-side deltas) skip the build
            # entirely via lax.cond instead of sorting Ft sentinels.
            def build_tail(_):
                fidx = bcount + jnp.arange(Ft, dtype=jnp.int32)
                fvalid = fidx < rc
                fi_c = jnp.minimum(fidx, Rcap - 1)
                tk = jnp.where(fvalid & (rw[fi_c] != 0), rk[fi_c], Klc)
                torder = jnp.argsort(tk)
                stk = tk[torder]
                fi_s = fi_c[torder]
                svalw_t = jnp.concatenate(
                    [rv[fi_s].reshape(Ft, Q).astype(jnp.float32),
                     jnp.where(stk < Klc, rw[fi_s].astype(jnp.float32), 0.0
                               )[:, None]], axis=1)
                deg_t_i = jnp.zeros((Klc + 1,), jnp.int32).at[tk].add(
                    1, mode="drop")[:Klc]
                starts_t = jnp.cumsum(deg_t_i) - deg_t_i
                geo_t = jnp.stack([starts_t, deg_t_i], axis=1
                                  ).astype(jnp.float32)
                return geo_t, svalw_t, deg_t_i

            def empty_tail(_):
                return (jnp.zeros((Klc, 2), jnp.float32),
                        jnp.zeros((Ft, Q + 1), jnp.float32),
                        jnp.zeros((Klc,), jnp.int32))

            geo_t, svalw_t, deg_t_i = jax.lax.cond(
                rc > bcount, build_tail, empty_tail, None)

            deg_b_i = geo_b[:, 1].astype(jnp.int32)
            arena = (jnp.minimum(rk, Klc - 1), rv, rw)

            branches_b = [
                (lambda xw, EB=EB: budget_tab(EB, geo_b, svalw_b, xw, base))
                for EB in tiers
            ]
            if stable_dst:
                branches_b.append(
                    lambda xw: dense_sorted_tab(dokey_b, dsrc_b, dvalw_b,
                                                xw, base))
            else:
                branches_b.append(lambda xw: dense_tab(arena, xw, base))
            dense_ix = len(tiers)
            branches_t = [
                (lambda xw, EB=EB: budget_tab(EB, geo_t, svalw_t, xw, base))
                for EB in tail_tiers
            ]
            branches_t.append(
                lambda xw: (jnp.zeros((KR, P + 1), jnp.float32),
                            jnp.zeros((), jnp.bool_)))
            zero_ix = len(tail_tiers)

            def live(xw):
                l = jnp.any(xw != 0)
                if axis is not None:
                    # globally uniform predicate: every shard must agree
                    # on the trip count (collectives inside the body)
                    l = jax.lax.psum(l.astype(jnp.int32), axis) > 0
                return l

            def cond(c):
                rst, xw, it, rows, err = c
                return jnp.logical_and(it < mi, live(xw))

            def body(c):
                rst, xw, it, rows, err = c
                fmask = jnp.any(xw != 0, axis=1)
                if tiers:
                    nedges = jnp.sum(jnp.where(fmask, deg_b_i, 0))
                    if axis is not None:
                        # uniform tier: the worst shard picks for everyone,
                        # so lax.switch branches (which contain collectives
                        # downstream) never diverge across devices
                        nedges = jax.lax.pmax(nedges, axis)
                    # descending budgets; pick the smallest that fits.
                    # Scalar compares over the static tier list — never a
                    # materialized s32[k] literal: the remote-device runtime
                    # drops into a degraded dispatch mode (~88ms/dispatch,
                    # process-wide, permanent) after executing any program
                    # whose HLO carries a multi-element constant.
                    n_fits = sum(((jnp.int32(t) >= nedges).astype(jnp.int32)
                                  for t in tiers), jnp.zeros((), jnp.int32))
                    ix_b = jnp.where(n_fits > 0, n_fits - 1, dense_ix)
                else:
                    ix_b = jnp.full((), dense_ix, jnp.int32)
                tab, bad_b = jax.lax.switch(ix_b, branches_b, xw)
                # tail segment: skipped when the frontier doesn't touch
                # any tail source (nt == 0 — the common late-pass case
                # once the wave moves past the churned keys). The RAW
                # dense branch also sweeps tail rows, so it skips the
                # tail too; the destination-sorted dense branch covers
                # only base rows and needs the tail alongside.
                nt = jnp.sum(jnp.where(fmask, deg_t_i, 0))
                if axis is not None:
                    nt = jax.lax.pmax(nt, axis)
                nt_fits = sum(((jnp.int32(t) >= nt).astype(jnp.int32)
                               for t in tail_tiers),
                              jnp.zeros((), jnp.int32))
                # the top tail tier is Ft itself, so nt always fits
                skip_t = (nt == 0) if stable_dst else (
                    (ix_b == dense_ix) | (nt == 0))
                ix_t = jnp.where(skip_t, zero_ix,
                                 jnp.maximum(nt_fits - 1, 0))
                tab_t, bad_t = jax.lax.switch(ix_t, branches_t, xw)
                tab = tab + tab_t
                rst2, xw2, prows = fold(rst, tab)
                return (rst2, xw2, it + 1, rows + prows,
                        err | bad_b | bad_t)

            rstate, xw, iters, rows, skerr = jax.lax.while_loop(
                cond, body, (rstate, xw, jnp.zeros((), jnp.int32),
                             jnp.zeros((), jnp.int32),
                             jnp.zeros((), jnp.bool_)))
            converged = ~live(xw)
            if axis is not None:
                skerr = jax.lax.pmax(skerr.astype(jnp.int32), axis) > 0

            # patch the Join's left table densely (per-pass retract/insert
            # pairs cancel; only entry-vs-exit existence and value matter)
            has_f = rstate["emitted_has"]
            em_f = rstate["emitted"]
            new_jstate = dict(jstate)
            # a violated stable_key declaration surfaces as the join's
            # sticky error at the next sync — loudly, before corrupt
            # ranks reach any view (ADVICE r4)
            new_jstate["error"] = jstate["error"] | skerr
            if resid is None:
                new_jstate["lval"] = jnp.where(
                    _bcast_w(has_f, em_f),
                    jnp.asarray(em_f, jstate["lval"].dtype), jstate["lval"])
                new_jstate["lw"] = (jstate["lw"] + has_f.astype(jnp.int32)
                                    - has_entry.astype(jnp.int32))
            else:
                # defer mode: the final xw is still in flight, so the
                # FOLDED collection lags the emitted table by exactly its
                # observables: A = emitted - xw. Invariant at entry was
                # lw = has_entry - resid_dw (same formula, last tick), so
                # the weight delta nets the two residues. lval for keys
                # without an emission (pure retraction in flight) keeps
                # its old folded value — the where() leaves it alone.
                rout_dval = xw[:, :P].reshape((Klc,) + loop_vshape)
                lval_t = em_f.astype(jnp.float32) - rout_dval
                new_jstate["lval"] = jnp.where(
                    _bcast_w(has_f, em_f),
                    jnp.asarray(lval_t, jstate["lval"].dtype),
                    jstate["lval"])
                ddw = jnp.round(xw[:, P] - resid[:, P]).astype(jnp.int32)
                new_jstate["lw"] = (jstate["lw"] + has_f.astype(jnp.int32)
                                    - has_entry.astype(jnp.int32) - ddw)
            new_csr = {"geo": geo_b, "svalw": svalw_b,
                       "count": bcount[None], "gen": gen[None]}
            if stable_dst:
                new_csr.update(dokey=dokey_b, dsrc=dsrc_b, dvalw=dvalw_b)
            if resid is None:
                return new_jstate, rstate, new_csr, iters, rows, converged
            return new_jstate, rstate, new_csr, iters, rows, converged, xw

        def run_loop(jstate, rstate, csr, ld, has_entry, resid):
            if axis is None:
                return loop_region(jstate, rstate, csr, ld, has_entry, resid)
            from jax.sharding import PartitionSpec as PS

            jspec = executor._state_tree_specs({join_id: jstate})[join_id]
            rspec = executor._state_tree_specs({red_id: rstate})[red_id]
            cspec = {k: PS(axis) for k in csr}
            dspec = DeviceDelta(PS(axis), PS(axis), PS(axis))
            # resid (defer mode) adds one key-sharded operand and the
            # carried-out observables; None is spec'd as a leafless pytree
            rs_in = PS(axis) if resid is not None else None
            out_specs = (jspec, rspec, cspec, PS(), PS(), PS())
            if resid is not None:
                out_specs = out_specs + (PS(axis),)
            fn = jax.shard_map(
                loop_region, mesh=mesh,
                in_specs=(jspec, rspec, cspec, dspec, PS(axis), rs_in),
                out_specs=out_specs, check_vma=False)
            return fn(jstate, rstate, csr, ld, has_entry, resid)

        def tick_fn(op_states, csr, ingress):
            # the loop folds every emission from phase A's onward into the
            # join's left table, so the exit patch diffs existence against
            # the PRE-tick table, not the post-phase-A one
            has_entry = op_states[red_id]["emitted_has"]
            states, eg_a = full_pass(op_states, ingress)
            snaps = {n.id: (states[n.id]["emitted"],
                            states[n.id]["emitted_has"]) for n in boundary}

            ld = eg_a.get(loop_id)
            if defer and ld is None:
                # carried residue may still be live even when phase A
                # emitted no loop delta: run the loop with an empty delta
                # (trace-static shape; weight-0 rows are no-ops)
                from reflow_tpu.executors.device_delta import MIN_CAPACITY
                ld = DeviceDelta.empty(L.spec, MIN_CAPACITY)
            if ld is not None:
                resid = states[loop_id]["resid"] if defer else None
                out = run_loop(states[join_id], states[red_id], csr, ld,
                               has_entry, resid)
                states = dict(states)
                if defer:
                    (new_jstate, rstate, csr, iters, rows, converged,
                     resid_out) = out
                    states[loop_id] = {"resid": resid_out}
                else:
                    new_jstate, rstate, csr, iters, rows, converged = out
                states[join_id] = new_jstate
                states[red_id] = rstate
            else:
                # phase A emitted no loop delta: the region is already
                # quiescent and the left-table patch would be an identity.
                # The CSR cache passes through; any phase-A appends land
                # in the next loop tick's tail via the count delta.
                iters = jnp.zeros((), jnp.int32)
                rows = jnp.zeros((), jnp.int32)
                converged = jnp.ones((), jnp.bool_)

            eg_b = {}
            if exit_pass is not None:
                diffs = {n.id: _emitted_diff(snaps[n.id], states[n.id], n)
                         for n in boundary}
                states, eg_b = exit_pass(states, diffs)

            sink_egress = {}
            for sid in self.sink_ids:
                batches = []
                if sid in eg_a:
                    batches.append(eg_a[sid])
                if sid in eg_b:
                    batches.append(eg_b[sid])
                if batches:
                    sink_egress[sid] = tuple(batches)
            return states, csr, sink_egress, iters, rows, converged

        # donate the state pytree AND the CSR cache: the arena, dense
        # tables, and sorted base update in place instead of being copied
        # every tick
        self.tick_fn = tick_fn
        self._fn = jax.jit(tick_fn, donate_argnums=(0, 1))
        self._executor = executor
        self._join_id = join_id
        self._csr_shape = (K, J.op.arena_capacity, Q, nsh, KR, stable_dst)

    def _take_csr(self):
        """Fetch (or lazily build) the ONE sorted-arena cache this join
        shares across every program signature — held on the EXECUTOR, so
        alternating ingress buckets advance one copy instead of each
        re-sorting appends the other already covered. Pure derived state:
        never part of the durable state tree, never checkpointed
        (restore/rebind drop it via the executor hooks). count=0 / gen=-1
        forces a rebuild on the first loop tick."""
        csr = self._executor._csr_cache.pop(self._join_id, None)
        if csr is not None:
            return csr
        K, R, Q, nsh, KR, stable_dst = self._csr_shape
        csr0 = {
            "geo": jnp.zeros((K, 2), jnp.float32),
            "svalw": jnp.zeros((R, Q + 1), jnp.float32),
            "count": jnp.zeros((nsh,), jnp.int32),
            "gen": jnp.full((nsh,), -1, jnp.int32),
        }
        if stable_dst:
            csr0.update(
                dokey=jnp.zeros((R,), jnp.int32),
                dsrc=jnp.zeros((R,), jnp.int32),
                dvalw=jnp.zeros((R, Q + 1), jnp.float32),
            )
        mesh = getattr(self._executor, "mesh", None)
        if mesh is not None:
            from jax.sharding import NamedSharding, PartitionSpec as PS

            axis = self._executor.axis
            csr0 = {k: jax.device_put(v, NamedSharding(mesh, PS(axis)))
                    for k, v in csr0.items()}
        return csr0

    def __call__(self, op_states, dev_ingress):
        """-> (states', {sink_id: (DeviceDelta, ...)}, carry, iters,
        loop_rows, converged) — the FixpointProgram call contract. The
        CSR cache threads through invisibly (held on the executor,
        donated here). carry is None: this program's in-flight loop
        state is dense observables, carried in the loop node's ``resid``
        state under defer_passes (resumable by construction); a
        max_iters halt WITHOUT defer_passes is non-resumable here
        (use defer_passes when halting mid-fixpoint is expected)."""
        states, csr, eg, iters, rows, conv = self._fn(
            op_states, self._take_csr(), dev_ingress)
        self._executor._csr_cache[self._join_id] = csr
        return states, eg, None, iters, rows, conv

    def call_many(self, op_states, ing_stack, n_ticks: int):
        """K ticks in ONE device execution, CSR cache carried through the
        scan. -> (states', (iters[K], rows[K], converged[K]),
        fresh_stack) — the ingress stack is donated (mega-tick queue
        buffers stop living across the dispatch) and the zeroed
        replacement rides back for the queue to re-bind."""
        cache = getattr(self, "_many_cache", None)
        if cache is None:
            cache = self._many_cache = {}
        prog = cache.get(n_ticks)
        if prog is None:
            tick_fn = self.tick_fn

            def scan_fn(op_states, csr, ing_stack):
                def body(carry, ing):
                    st, c = carry
                    st2, c2, sink_eg, iters, rows, conv = tick_fn(st, c, ing)
                    if sink_eg:  # trace-time structural check
                        raise RuntimeError(
                            "macro-tick requires a sink-free graph")
                    return (st2, c2), (iters, rows, conv)

                (states, csr), ys = jax.lax.scan(body, (op_states, csr),
                                                 ing_stack)
                return states, csr, ys, jax.tree.map(jnp.zeros_like,
                                                     ing_stack)

            prog = cache[n_ticks] = jax.jit(scan_fn,
                                            donate_argnums=(0, 1, 2))
        states, csr, ys, fresh = prog(op_states, self._take_csr(),
                                      ing_stack)
        self._executor._csr_cache[self._join_id] = csr
        return states, ys, fresh
