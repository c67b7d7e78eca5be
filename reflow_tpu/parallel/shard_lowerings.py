"""Shard-aware op lowerings: the per-shard kernels under ``shard_map``.

Design (the scaling-book recipe — route rows to their key's owner, shard
what's big):

- **Map / Filter / GroupBy / Union** are local on row-sharded delta
  buffers: no communication. A GroupBy re-key leaves rows in place; routing
  happens where a *keyed* op consumes them.
- **Row routing** (:func:`route_rows`): one ``all_to_all`` on
  shard-of-key delivers every live delta row to the shard owning its key
  range — traffic O(slack x delta rows), independent of both the mesh
  size (vs all_gather's O(n x rows)) and the key space (vs a dense
  reduce-scatter's O(K)). Static shapes force a per-destination budget
  (``ROUTE_SLACK`` x balanced share); overflow beyond the budget sets a
  sticky per-node error flag surfaced by ``check_errors`` — loud, never
  silent truncation.
- **Reduce**: sparse regime (delta capacity well under K) routes rows to
  their owners and scatter-adds locally — per-pass comms scale with the
  delta, not the key space. Dense regime (delta ~ K, e.g. full rebuild
  passes) keeps the full-K contribution table + one ``psum_scatter``
  (reduce-scatter), which is optimal when most keys are touched. State
  tables (``wsum``/``wcnt``/``emitted``) live key-sharded; emission covers
  the owned range with global key ids.
- **Join**: both delta sides are routed to key owners (``all_to_all``)
  and fed to the shared :func:`join_core` over the shard's slice of the
  left table and append arena; meshes too small for routing to win
  (n <= ROUTE_SLACK) and deltas whose per-destination budget would fall
  under ``_MIN_ROUTE_BUDGET`` rows keep the tiled ``all_gather`` + mask.
  Output rows stay on the owning shard (row-sharded), keys global. Arena
  rows therefore always carry shard-LOCAL keys — the invariant the
  sharded linear fixpoint's per-shard CSR relies on.

Keyed state is range-sharded: shard ``i`` of ``n`` owns keys
``[i*K/n, (i+1)*K/n)``. Range (not hash) sharding keeps key<->shard
arithmetic trivial and lets emission use a contiguous ``arange``.
"""

from __future__ import annotations

from typing import Optional, Sequence, Tuple

import jax
import jax.numpy as jnp

from reflow_tpu.executors.device_delta import DeviceDelta
from reflow_tpu.executors.join import join_core, layout_of
from reflow_tpu.executors.lowerings import (_LOWERINGS, LINEAR_DEVICE_REDUCERS,
                                            _agg_tables, _bcast_w, _differs,
                                            _scatter_contribs)
from reflow_tpu.graph import Node

__all__ = ["lower_node_sharded", "route_rows", "deliver_to_owner",
           "ROUTE_SLACK"]

#: per-destination row budget = ROUTE_SLACK x the perfectly-balanced
#: share. 4x absorbs realistic key skew; pathological skew trips the
#: sticky overflow flag instead of truncating.
ROUTE_SLACK = 4
#: the Join routes a delta side only when its per-destination budget is at
#: least this many rows — thin budgets trip on ordinary randomness, and
#: replicating a small delta costs next to nothing
_MIN_ROUTE_BUDGET = 64


def _should_route(n: int, Cl: int) -> bool:
    """The shared routed-vs-replicated comm policy (Join, min/max):
    route when the mesh is big enough for all_to_all to beat all_gather
    AND the per-destination budget is thick enough not to trip on
    ordinary key randomness."""
    return n > ROUTE_SLACK and ROUTE_SLACK * Cl >= _MIN_ROUTE_BUDGET * n


def deliver_to_owner(d: DeviceDelta, axis, n: int, Kl: int,
                     sizes: Optional[Tuple[int, ...]] = None
                     ) -> Tuple[DeviceDelta, jax.Array]:
    """Deliver every live row of a row-sharded delta to the shard owning
    its key range, returning a LOCAL-keyed delta plus the (pmax-combined)
    route-overflow flag. ONE definition of the routed-vs-replicated
    policy, shared by every keyed consumer (Reduce, Join, min/max, the
    latch refresh) so no path can drift to a different policy.

    On a 2-axis (dcn, ici) mesh (``axis`` a tuple, ``sizes`` its per-axis
    extents) the routed path is HIERARCHICAL: an intra-slice ICI leg
    delivers each row to its destination's ICI column, then ONE DCN
    exchange crosses slices — each row crosses the slow network exactly
    once, in per-slice aggregated messages, instead of the flat product
    ``all_to_all`` treating every DCN link like an ICI link
    (ROADMAP r4 #1 / VERDICT r4 #4)."""
    Cl = d.keys.shape[0]
    if _should_route(n, Cl):
        if isinstance(axis, tuple) and sizes is not None:
            dl, route_err = _route_rows_hier(d, axis, sizes, Kl)
        else:
            dl, route_err = route_rows(d, axis, n, Kl)
        return dl, jax.lax.pmax(route_err.astype(jnp.int32), axis) > 0
    base = (jax.lax.axis_index(axis) * Kl).astype(jnp.int32)
    g = jax.tree.map(lambda x: jax.lax.all_gather(x, axis, tiled=True), d)
    return _localize(g, base, Kl), jnp.zeros((), jnp.bool_)


def _bucket_exchange(d: DeviceDelta, dest: jax.Array, n_sub: int, B: int,
                     axis_name: str) -> Tuple[DeviceDelta, jax.Array]:
    """One bucketed ``all_to_all`` leg: rows with ``dest`` in
    ``[0, n_sub)`` pack into per-destination buckets of ``B`` slots
    (``dest == n_sub`` drops — dead rows), exchange along ``axis_name``,
    and return the received ``n_sub * B`` rows (keys untouched — global)
    plus this shard's overflow flag."""
    Cl = d.keys.shape[0]
    order = jnp.argsort(dest, stable=True)
    so = dest[order]
    sk, sv, sw = d.keys[order], d.values[order], d.weights[order]
    start = jnp.searchsorted(so, jnp.arange(n_sub, dtype=so.dtype))
    slot = (jnp.arange(Cl, dtype=jnp.int32)
            - start[jnp.minimum(so, n_sub - 1)])
    ok = (so < n_sub) & (slot < B)
    err = jnp.any((so < n_sub) & (slot >= B))
    pos = jnp.where(ok, so.astype(jnp.int32) * B + slot, n_sub * B)
    send_k = jnp.zeros((n_sub * B,), jnp.int32).at[pos].set(sk, mode="drop")
    send_v = jnp.zeros((n_sub * B,) + d.values.shape[1:],
                       d.values.dtype).at[pos].set(sv, mode="drop")
    send_w = jnp.zeros((n_sub * B,), jnp.int32).at[pos].set(sw, mode="drop")

    def xchg(a):
        trail = a.shape[1:]
        out = jax.lax.all_to_all(a.reshape((n_sub, B) + trail), axis_name,
                                 0, 0)
        return out.reshape((n_sub * B,) + trail)

    return DeviceDelta(xchg(send_k), xchg(send_v), xchg(send_w)), err


def _route_rows_hier(d: DeviceDelta, axes: Tuple[str, str],
                     sizes: Tuple[int, int], Kl: int,
                     slack: int = ROUTE_SLACK
                     ) -> Tuple[DeviceDelta, jax.Array]:
    """Two-stage owner delivery on a (dcn, ici) mesh: ICI leg to the
    destination's ici column (intra-slice), then ONE DCN exchange to the
    destination slice. Flat owner ids are dcn-major (the executor's
    product-axis order), so ``owner = key // Kl``,
    ``(own_dcn, own_ici) = divmod(owner, n_ici)``."""
    dcn_ax, ici_ax = axes
    n_dcn, n_ici = sizes
    n = n_dcn * n_ici
    Cl = d.keys.shape[0]
    live = d.weights != 0
    owner = jnp.where(live, jnp.clip(d.keys // Kl, 0, n - 1), n)
    own_ici = jnp.where(owner < n, owner % n_ici, n_ici)
    # stage 1 (ICI): to my slice's device in the destination's column
    B1 = max(1, -(-slack * Cl // n_ici))
    d1, err1 = _bucket_exchange(d, own_ici, n_ici, B1, ici_ax)
    # stage 2 (DCN): to the destination slice (column now correct).
    # Bucket size derives from the ORIGINAL live-row bound Cl, not the
    # padded stage-1 capacity (which is already slack-inflated): the
    # balanced per-device share after stage 1 is ~Cl rows split over
    # n_dcn destinations, so slack*Cl/n_dcn gives the same skew headroom
    # as the flat route at the same total capacity (~slack*Cl).
    live1 = d1.weights != 0
    owner1 = jnp.where(live1, jnp.clip(d1.keys // Kl, 0, n - 1), n)
    own_dcn = jnp.where(owner1 < n, owner1 // n_ici, n_dcn)
    B2 = max(1, -(-slack * Cl // n_dcn))
    d2, err2 = _bucket_exchange(d1, own_dcn, n_dcn, B2, dcn_ax)
    base = (jax.lax.axis_index(axes) * Kl).astype(jnp.int32)
    lk = jnp.where(d2.weights != 0, d2.keys - base, 0)
    return DeviceDelta(lk, d2.values, d2.weights), err1 | err2


def route_rows(d: DeviceDelta, axis: str, n: int, Kl: int,
               slack: int = ROUTE_SLACK
               ) -> Tuple[DeviceDelta, jax.Array]:
    """Deliver each live row to the shard owning its key (one all_to_all).

    ``d`` is this shard's local slice (capacity Cl) of a row-sharded
    delta. Rows are bucketed by owner shard (``key // Kl``), each bucket
    padded to the static budget ``B = ceil(slack*Cl/n)``, exchanged, and
    returned as a local-keyed delta of capacity ``n*B`` (re-based keys,
    weight-0 padding). Second return is the per-shard overflow flag (any
    live row beyond its bucket's budget was NOT sent).
    """
    Cl = d.keys.shape[0]
    B = max(1, -(-slack * Cl // n))
    live = d.weights != 0
    owner = jnp.where(live, jnp.clip(d.keys // Kl, 0, n - 1), n)
    order = jnp.argsort(owner, stable=True)
    so = owner[order]
    sk, sv, sw = d.keys[order], d.values[order], d.weights[order]
    start = jnp.searchsorted(so, jnp.arange(n, dtype=so.dtype))
    slot = jnp.arange(Cl, dtype=jnp.int32) - start[jnp.minimum(so, n - 1)]
    ok = (so < n) & (slot < B)
    err = jnp.any((so < n) & (slot >= B))
    pos = jnp.where(ok, so.astype(jnp.int32) * B + slot, n * B)
    send_k = jnp.zeros((n * B,), jnp.int32).at[pos].set(sk, mode="drop")
    send_v = jnp.zeros((n * B,) + d.values.shape[1:],
                       d.values.dtype).at[pos].set(sv, mode="drop")
    send_w = jnp.zeros((n * B,), jnp.int32).at[pos].set(sw, mode="drop")

    def xchg(a):
        trail = a.shape[1:]
        out = jax.lax.all_to_all(a.reshape((n, B) + trail), axis, 0, 0)
        return out.reshape((n * B,) + trail)

    rk, rv, rw = xchg(send_k), xchg(send_v), xchg(send_w)
    base = (jax.lax.axis_index(axis) * Kl).astype(jnp.int32)
    lk = jnp.where(rw != 0, rk - base, 0)
    return DeviceDelta(lk, rv, rw), err


def _localize(d: DeviceDelta, base, Kl: int) -> DeviceDelta:
    """Mask a gathered delta to this shard's key range and re-base keys.

    Non-owned rows become weight-0 padding at local key 0 — no-ops of the
    multiset algebra, so the downstream kernel needs no other masking.
    """
    own = (d.keys >= base) & (d.keys < base + Kl)
    return DeviceDelta(
        keys=jnp.where(own, d.keys - base, 0),
        values=d.values,
        weights=jnp.where(own, d.weights, 0),
    )


def _lower_reduce_sharded(op, node: Node, state, ins, axis, n: int,
                          sizes=None) -> Tuple[DeviceDelta, dict]:
    (d,) = ins                      # local delta rows [Cl]
    in_spec = node.inputs[0].spec
    K = in_spec.key_space
    Kl = K // n
    Cl = d.keys.shape[0]
    vdtype = node.spec.value_dtype
    base = (jax.lax.axis_index(axis) * Kl).astype(jnp.int32)
    vshape = d.values.shape[1:]
    # linear reducers get their error scalar at sharded bind time, so the
    # route-overflow flag below is never silently dropped (ADVICE r2 high)
    err = state.get("error", jnp.zeros((), jnp.bool_))

    if ROUTE_SLACK * Cl < Kl:
        # sparse regime: route rows to their key's owner and fold locally
        # — comms O(slack*Cl), independent of K (hierarchical two-stage
        # on a 2-axis mesh: one DCN crossing per row)
        if isinstance(axis, tuple) and sizes is not None:
            dl, route_err = _route_rows_hier(d, axis, sizes, Kl)
        else:
            dl, route_err = route_rows(d, axis, n, Kl)
        dws, dwc = _scatter_contribs(dl, Kl)
        wsum = state["wsum"] + dws
        wcnt = state["wcnt"] + dwc
        err = err | (jax.lax.pmax(route_err.astype(jnp.int32), axis) > 0)
    else:
        # dense regime (most keys touched, e.g. rebuild passes): full-K
        # local contributions + one reduce-scatter
        dws, dwc = _scatter_contribs(d, K)
        stacked = jnp.concatenate(
            [dws.reshape(K, -1), dwc.astype(jnp.float32)[:, None]], axis=-1)
        combined = jax.lax.psum_scatter(stacked, axis, scatter_dimension=0,
                                        tiled=True)
        wsum = state["wsum"] + combined[:, :-1].reshape((Kl,) + vshape)
        wcnt = state["wcnt"] + combined[:, -1].astype(jnp.int32)

    # dense diff over the owned slice (mirrors _lower_reduce dense mode)
    emitted, em_has = state["emitted"], state["emitted_has"]
    agg, exists = _agg_tables(op, wsum, wcnt, vdtype)
    changed = _differs(agg, emitted, op.tol)
    ins_m = exists & (~em_has | changed)
    ret_m = em_has & (~exists | changed)
    gkeys = base + jnp.arange(Kl, dtype=jnp.int32)
    out = DeviceDelta(
        keys=jnp.concatenate([gkeys, gkeys]),
        values=jnp.concatenate([emitted, agg]),
        weights=jnp.concatenate(
            [-ret_m.astype(jnp.int32), ins_m.astype(jnp.int32)]),
    )
    ins_b = _bcast_w(ins_m, agg)
    new_emitted = jnp.where(ins_b, agg, emitted)
    new_has = jnp.where(ins_m, True, jnp.where(ret_m & ~exists, False, em_has))
    new_state = {"wsum": wsum, "wcnt": wcnt,
                 "emitted": new_emitted, "emitted_has": new_has,
                 "error": err}
    return out, new_state


def _lower_reduce_minmax_sharded(op, node: Node, state, ins,
                                 axis, n: int, sizes=None
                                 ) -> Tuple[DeviceDelta, dict]:
    """Retraction-capable min/max (scalar AND vector rows), key-sharded:
    delta rows reach their key's owner (routed ``all_to_all`` on large
    meshes, tiled ``all_gather`` + mask on small ones — the Join's comm
    policy), then the shared candidate-buffer kernel (``minmax_core``)
    runs on the owned key slice. Error flags (route overflow, buffer
    exhaustion) combine with ``pmax``."""
    from reflow_tpu.executors.lowerings import minmax_core

    (d,) = ins
    K = node.inputs[0].spec.key_space
    Kl = K // n
    base = (jax.lax.axis_index(axis) * Kl).astype(jnp.int32)
    dl, route_err = deliver_to_owner(d, axis, n, Kl, sizes=sizes)
    err = state["error"] | route_err

    core_state = dict(state)
    core_state["error"] = err
    out, new_state = minmax_core(op, Kl, tuple(node.spec.value_shape),
                                 node.spec.value_dtype, core_state, dl,
                                 key_offset=base)
    new_state["error"] = (jax.lax.pmax(
        new_state["error"].astype(jnp.int32), axis) > 0)
    return out, new_state


def _lower_join_sharded(op, node: Node, state, ins, axis, n: int,
                        sizes=None) -> Tuple[DeviceDelta, dict]:
    da, db = ins                    # local delta rows
    K = node.inputs[0].spec.key_space
    Kl = K // n
    Rl = op.arena_capacity // n
    base = (jax.lax.axis_index(axis) * Kl).astype(jnp.int32)
    err = state.get("error", jnp.zeros((), jnp.bool_))

    # both delta sides reach their key's owner: routed (one all_to_all,
    # O(slack x rows) traffic) on meshes where routing beats replication;
    # small meshes (n <= ROUTE_SLACK) and small deltas (per-destination
    # budget under _MIN_ROUTE_BUDGET rows — skew trips a thin budget far
    # too easily, and tiny batches are cheap to replicate) keep the tiled
    # all_gather + mask, whose O(n x rows) traffic is then no worse
    def _route(d):
        nonlocal err
        if d is None:
            return None
        dl, route_err = deliver_to_owner(d, axis, n, Kl, sizes=sizes)
        err = err | route_err
        return dl

    da_l = _route(da)
    db_l = _route(db)

    # per-shard scalar append counter / arena generation are stored as
    # length-1 slices of mesh-length vectors; the core kernel wants scalars
    core_state = dict(state)
    core_state["rcount"] = state["rcount"][0]
    core_state["gen"] = state["gen"][0]
    multiset = layout_of(state) == "multiset"
    if multiset:
        core_state["lcount"] = state["lcount"][0]
        core_state["lgen"] = state["lgen"][0]
    out, new_state = join_core(op, Kl, Rl, node.spec.value_dtype,
                               core_state, da_l, db_l, key_offset=base,
                               oshape=tuple(node.spec.value_shape))
    new_state["rcount"] = new_state["rcount"][None]
    new_state["gen"] = new_state["gen"][None]
    if multiset:
        new_state["lcount"] = new_state["lcount"][None]
        new_state["lgen"] = new_state["lgen"][None]
    # join_core's arena-overflow flag is per-shard; the state leaf is
    # replicated, so fold it with pmax before OR-ing the route error in
    new_state["error"] = err | (jax.lax.pmax(
        new_state["error"].astype(jnp.int32), axis) > 0)
    return out, new_state


def _lower_knn_sharded(op, node: Node, state, ins, axis: str, n: int
                       ) -> Tuple[DeviceDelta, dict]:
    """Corpus row-sharded k-NN: each shard scans its corpus slice, one
    all_gather merges k candidates per query (SURVEY.md §2 item 14,
    'sharded' aspiration of BASELINE config 4).

    Layout: ``dvec``/``dlive`` sharded over the corpus axis; queries and
    the emitted table replicated (every shard needs every query against
    its slice, and the merged result is identical everywhere). Emission is
    partitioned by query range so the egress delta stays row-sharded.
    """
    from reflow_tpu.executors.lowerings import (_fold_vectors,
                                                _knn_count,
                                                _knn_incremental,
                                                _last_rows)
    from reflow_tpu.kernels.topk import (NEG, chunked_corpus_topk,
                                         score_form, topk)

    dq, dd = ins
    if dq is None:
        dq = DeviceDelta.empty(node.inputs[0].spec)
    if dd is None:
        dd = DeviceDelta.empty(node.inputs[1].spec)
    Q = node.inputs[0].spec.key_space
    D = node.inputs[1].spec.key_space
    Ql, Dl = Q // n, D // n
    k = op.k
    base_q = (jax.lax.axis_index(axis) * Ql).astype(jnp.int32)
    base_d = (jax.lax.axis_index(axis) * Dl).astype(jnp.int32)

    # deltas are replicated by one gather: queries fold everywhere (the
    # query table is replicated); docs fold only into the owned slice
    gq = jax.tree.map(lambda x: jax.lax.all_gather(x, axis, tiled=True), dq)
    gd = jax.tree.map(lambda x: jax.lax.all_gather(x, axis, tiled=True), dd)
    gd_l = _localize(gd, base_d, Dl)

    with jax.named_scope("knn.fold"):
        qvec, qlive, q_ins, q_ret = _fold_vectors(
            state["qvec"], state["qlive"], gq)
        dvec, dlive, l_ins, l_ret = _fold_vectors(
            state["dvec"], state["dlive"], gd_l)
        # the winning rows of the whole gathered delta, the same on every
        # shard; what they do to ids live before the fold is known only
        # to the owner, so that is folded with one pmax
        d_ins, d_ret = _last_rows(gd, D)
        hit = jnp.any((l_ins | l_ret) & state["dlive"][gd_l.keys])
        doc_change = jax.lax.pmax(hit.astype(jnp.int32), axis) > 0
    emitted, em_has = state["emitted"], state["em_has"]
    prec = (jax.lax.Precision.HIGHEST if op.precision == "highest"
            else jax.lax.Precision.DEFAULT)

    # uniform across shards (the gathered deltas and one pmax), so every
    # device takes the same lax.cond branch and collectives line up
    need_full = jnp.any(q_ins) | doc_change

    def _merge2(av, ai, bv, bi):
        """Merge two [Q, k] candidate sets; ties break to the lowest id.

        (score desc, id asc) is a total order, so pairwise merging is
        associative and the ring result matches a flat n*k sort."""
        cv = jnp.concatenate([av, bv], axis=1)
        ci = jnp.concatenate([ai, bi], axis=1)
        order = jnp.argsort(jnp.where(ci < 0, jnp.iinfo(jnp.int32).max, ci),
                            axis=1, stable=True)
        ci = jnp.take_along_axis(ci, order, axis=1)
        cv = jnp.take_along_axis(cv, order, axis=1)
        vals, sel = topk(cv, k)
        return vals, jnp.take_along_axis(ci, sel, axis=1)

    def full_path(_):
        chunk = min(op.scan_chunk, Dl)
        vals_l, ids_l, sweeps = chunked_corpus_topk(
            qvec, dvec, dlive, k, chunk, precision=prec)
        ids_g = jnp.where(vals_l <= NEG, -1, ids_l + base_d)
        # ring merge over ICI neighbors (ppermute): n-1 hops, each passing
        # a [Q, k] candidate window and merging into the local best —
        # peak buffer [Q, 2k] vs an all_gather's [Q, n*k]
        perm = [(i, (i + 1) % n) for i in range(n)]
        acc_v, acc_i = vals_l, ids_g
        cur_v, cur_i = vals_l, ids_g
        for _ in range(n - 1):
            cur_v = jax.lax.ppermute(cur_v, axis, perm)
            cur_i = jax.lax.ppermute(cur_i, axis, perm)
            acc_v, acc_i = _merge2(acc_v, acc_i, cur_v, cur_i)
        # the counters are replicated: every shard's sweeps, summed
        return acc_v, acc_i, jax.lax.psum(sweeps, axis)

    def _score_owned(di, won):
        # per-entry scores from the OWNED folded vectors (exactly the
        # single-device dvec[di] semantics), combined with one pmax —
        # non-owned entries contribute NEG
        own = (di >= base_d) & (di < base_d + Dl)
        di_l = jnp.where(own, di - base_d, 0)
        s_loc = jnp.dot(score_form(qvec), score_form(dvec[di_l]).T,
                        preferred_element_type=jnp.float32,
                        precision=prec)                        # [Q, Cd]
        s_loc = jnp.where((own & won)[None, :], s_loc, NEG)
        return jax.lax.pmax(s_loc, axis)

    def incr_path(_):
        return *_knn_incremental(qvec, dvec, emitted, em_has, gd.keys,
                                 d_ins, k, prec,
                                 score_of=_score_owned), jnp.int32(0)

    vals, ids, sweeps = jax.lax.cond(need_full, full_path, incr_path, None)
    ids = jnp.where(vals <= NEG, -1, ids)
    new_row = jnp.stack([ids.astype(jnp.float32), vals], axis=-1)  # [Q,k,2]

    changed = jnp.any(new_row != emitted, axis=(1, 2))
    ins_m = qlive & (~em_has | changed)
    ret_m = em_has & (~qlive | changed)
    # replicated masks/table; each shard EMITS its owned query range so
    # the egress delta is row-sharded like every other op's
    sl = lambda x: jax.lax.dynamic_slice_in_dim(x, base_q, Ql, 0)
    qkeys = base_q + jnp.arange(Ql, dtype=jnp.int32)
    out = DeviceDelta(
        keys=jnp.concatenate([qkeys, qkeys]),
        values=jnp.concatenate([sl(emitted), sl(new_row)]),
        weights=jnp.concatenate(
            [-sl(ret_m).astype(jnp.int32), sl(ins_m).astype(jnp.int32)]),
    )
    new_emitted = jnp.where(ins_m[:, None, None], new_row, emitted)
    new_has = jnp.where(ins_m, True, jnp.where(ret_m & ~qlive, False, em_has))
    return out, {"qvec": qvec, "qlive": qlive, "dvec": dvec, "dlive": dlive,
                 "emitted": new_emitted, "em_has": new_has,
                 "counters": _knn_count(state["counters"], need_full, sweeps,
                                        q_ins, q_ret, d_ins, d_ret)}


#: per-leaf shard_map specs for the knn state: corpus sharded, queries +
#: emitted table replicated (consumed by ShardedTpuExecutor)
def knn_state_specs(axis: str):
    return {"qvec": None, "qlive": None, "dvec": axis, "dlive": axis,
            "emitted": None, "em_has": None, "counters": None}


def lower_node_sharded(node: Node, state, ins: Sequence[DeviceDelta],
                       axis, n: int, sizes=None
                       ) -> Tuple[DeviceDelta, dict]:
    kind = node.op.kind
    if kind == "reduce":
        if node.op.how in LINEAR_DEVICE_REDUCERS:
            return _lower_reduce_sharded(node.op, node, state, ins, axis,
                                         n, sizes=sizes)
        return _lower_reduce_minmax_sharded(node.op, node, state, ins,
                                            axis, n, sizes=sizes)
    if kind == "join":
        return _lower_join_sharded(node.op, node, state, ins, axis, n,
                                   sizes=sizes)
    if kind == "knn":
        return _lower_knn_sharded(node.op, node, state, ins, axis, n)
    # stateless row ops are shard-local
    return _LOWERINGS[kind](node.op, node, state, ins)
