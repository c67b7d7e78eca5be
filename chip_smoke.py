#!/usr/bin/env python3
"""chip_smoke.py — the quickest proof that the system still starts on the chip.

One process, one chip, four phases, every check fatal:

1. **The served device path, end to end** at the repo's flagship size
   (BASELINE config 3: incremental PageRank, 100 000 nodes / 1 000 000
   edges, ``WebGraph.random(seed=7)``, tol 1e-4). A
   ``DurableScheduler(get_executor("tpu"), fsync="tick",
   committer="thread")`` loads the graph, then serves 1 % churn through
   ``RemoteProducer`` -> ``RpcIngestServer`` (loopback transport, threads)
   -> ``IngestFrontend(depth=2)`` -> WAL -> fused 16-tick windows. It
   answers: every ticket ``applied``; ranks within tol / (1 - damping) of
   ``pagerank.reference_ranks`` (the bound two tol-converged fixpoints can
   differ by — ``__graft_entry__._dryrun_body``); the fused linear
   fixpoint engine ran, in fused windows, with zero fallbacks. Then the
   guarantee: the scheduler is dropped and a fresh one on a fresh executor
   ``recover()``s from the same WAL — an acked write is read back through
   the device path (same tick horizon, every acked batch id, ranks inside
   the same bound). That second build of the same programs also shows the
   persistent compile cache working.
2. **Kernels that compile**: k-NN at BASELINE config 4 widths (Q 256,
   dim 768, k 16, scan chunk 8192, int8 corpus of 2^20 slots preloaded on
   device), one insert tick and one retraction tick with the Pallas top-k
   in the tick program, its result equal to ``jax.lax.top_k`` on the same
   scores.
3. **The indexed join** (``executors/arena.py``) at the kernel level:
   skewed right rows over several ticks, then the left rows they waited
   for; its late pairs equal the dense sweep's and NumPy's.

4. **The row fixpoint** (``executors/fixpoint.py``) at the size of the
   benchmark's ``sssp-graph500`` cell: incremental SSSP over a Kronecker
   graph, half loaded with the root in one tick (a fixpoint from
   scratch), then a few insert batches each a one-tick window
   (``tick_many``): every tick ``converged``, no sticky error, state
   resident, distances equal to Bellman-Ford's to the bit, the program's
   own counters say the same; the loop's join took every pass's left
   delta through its key-sorted view of the arena or, past the pair
   budget, by the sweep (``probes`` + ``sweeps`` = the passes but each
   tick's first).

The default invocation needs a TPU and never finishes on anything else.
``--tiny`` is the small CPU form tier-1 drives; it is reached only by
asking for it AND stating ``JAX_PLATFORMS=cpu``, never by finding no chip.

stdout: progress lines, one JSON summary (``"claim": null`` — this script
measures nothing it would defend as a performance number), and as the
LAST line ``{"ok": true, "device": {"platform", "kind", "count"}}``.
"""

from __future__ import annotations

import argparse
import json
import os
import shutil
import sys
import tempfile
import time

FULL = {
    "nodes": 100_000, "edges": 1_000_000, "churn": 0.01, "tol": 1e-4,
    "window_ticks": 16, "steady_windows": 2,
    "knn": {"Q": 256, "D": 1 << 20, "dim": 768, "k": 16, "chunk": 8192,
            "preload_rows": 1 << 16, "preload_chunks": 15,
            "insert_rows": 8192, "retract_rows": 1024},
    "join": {"keys": 1 << 16, "arena": 1 << 20, "rows": 1 << 15,
             "ticks": 6, "left_rows": 2048},
    "sssp": {"scale": 16, "edgefactor": 16, "ticks": 3},
}
TINY = {
    "nodes": 256, "edges": 2048, "churn": 0.01, "tol": 1e-4,
    "window_ticks": 4, "steady_windows": 2,
    "knn": {"Q": 16, "D": 2048, "dim": 32, "k": 4, "chunk": 512,
            "preload_rows": 512, "preload_chunks": 3,
            "insert_rows": 256, "retract_rows": 64},
    "join": {"keys": 64, "arena": 4096, "rows": 256, "ticks": 4,
             "left_rows": 32},
    "sssp": {"scale": 8, "edgefactor": 16, "ticks": 3},
}

#: generous wall bounds on each blocking wait, so a wedged pump or link
#: fails the smoke instead of hanging the chip
WAIT_S = 600.0


def say(msg: str) -> None:
    print(f"chip_smoke: {msg}", flush=True)


def require(cond, msg: str) -> None:
    """A failed check ends the run: non-zero exit, no result line."""
    if not cond:
        raise SystemExit(f"chip_smoke: FAILED: {msg}")


def require_device(tiny: bool):
    """The device this run is allowed to finish on — checked before any
    work. Default: a TPU, whatever the environment says. ``--tiny``: the
    CPU, and only because the caller said so."""
    if tiny:
        require(os.environ.get("JAX_PLATFORMS") == "cpu",
                "--tiny is the CPU form: state JAX_PLATFORMS=cpu")
    import jax

    dev = jax.devices()[0]
    want = "cpu" if tiny else "tpu"
    require(dev.platform == want,
            f"needs platform {want!r}: JAX resolved {dev.platform!r} "
            f"(kind {dev.device_kind!r}); there is no fallback")
    return dev


def require_resident(states, devices, what: str) -> None:
    """Every state leaf lives on ``devices``, and together they use all
    of them — nothing strayed to another device (or stayed on device 0
    of a mesh)."""
    import jax

    leaves = [x for x in jax.tree.leaves(states) if isinstance(x, jax.Array)]
    require(leaves, f"{what}: no device state was built")
    used = set().union(*(x.devices() for x in leaves))
    require(used == set(devices),
            f"{what}: state leaves live on {sorted(map(str, used))}, "
            f"want exactly {sorted(map(str, devices))}")


def rel_err(got, ref) -> float:
    """max |got - ref| / max(|ref|, 1): ``__graft_entry__``'s measure."""
    import numpy as np

    return float((np.abs(got - ref) / np.maximum(np.abs(ref), 1.0)).max())


# -- phase 1: served PageRank + recovery from the WAL ----------------------

def pagerank_phase(cfg: dict, devices, root: str, *, make_executor=None,
                   shards: int = 1) -> dict:
    """``devices`` is where the executor's state must live: the one chip
    here; ``tools/chip_checks.py mesh`` passes a 4-chip mesh's devices
    with a ``ShardedTpuExecutor`` factory and ``shards=4``."""
    import jax

    from reflow_tpu.executors import get_executor
    from reflow_tpu.net import LoopbackTransport
    from reflow_tpu.serve import (APPLIED, CoalesceWindow, IngestFrontend,
                                  RemoteProducer, RpcIngestServer)
    from reflow_tpu.wal import DurableScheduler, recover
    from reflow_tpu.workloads import pagerank

    n, e, tol, k = cfg["nodes"], cfg["edges"], cfg["tol"], cfg["window_ticks"]
    bound = tol / (1.0 - pagerank.DAMPING)
    n_churn = 2 * max(1, int(cfg["churn"] * e))
    wal_dir = os.path.join(root, "wal")

    # every batch is minted up front (WebGraph.churn mutates its edge
    # set), padded to ONE capacity bucket; afterwards `web` IS the final
    # graph, so reference_ranks(web) is what the served state must equal
    if make_executor is None:
        make_executor = lambda: get_executor("tpu")     # noqa: E731
    arena = pagerank.churn_arena_capacity(e, cfg["churn"], shards)
    pr = pagerank.build_graph(n, tol=tol, arena_capacity=arena)
    web = pagerank.WebGraph.random(n, e, seed=7)
    init = web.initial_batch()
    waves = [[web.churn(cfg["churn"]).padded(n_churn)
              for _ in range(w * k)]
             for w in (1, cfg["steady_windows"])]   # warm (compiles), steady
    ref = pagerank.reference_ranks(web)

    # -- load: the first build of every program (compile included) -----
    t0 = time.perf_counter()
    ex = make_executor()
    sched = DurableScheduler(pr.graph, ex, wal_dir=wal_dir, fsync="tick",
                             committer="thread")
    require_resident(ex.states, devices, "pagerank bind")
    sched.push(pr.teleport, pagerank.teleport_batch(n), batch_id="load/tp")
    sched.push(pr.edges, init, batch_id="load/edges")
    built = sched.tick()
    setup_s = time.perf_counter() - t0
    require(built.quiesced, "initial build did not quiesce")
    require(ex.fixpoint_engine == "LinearFixpointProgram",
            f"build ran {ex.fixpoint_engine}, not the fused linear loop")
    say(f"pagerank load {n} nodes / {e} edges: {setup_s:.2f}s "
        f"(build tick {built.wall_s:.2f}s, engine {ex.fixpoint_engine})")

    # -- serve: churn through producer -> rpc -> frontend -> WAL -> windows
    fe = IngestFrontend(
        sched, max_bytes=1 << 30, depth=2,
        window=CoalesceWindow(max_rows=n_churn, max_ticks=k,
                              max_latency_s=0.005))
    srv = prod = None
    acked = []
    try:
        lt = LoopbackTransport()
        srv = RpcIngestServer(fe, lt).start()
        prod = RemoteProducer(lt, srv.address, name="smoke")
        walls = []
        for batches in waves:
            # pause -> submit -> resume: the wave drains as ONE backlog,
            # so its windows stage back to back and depth 2 can pipeline
            fe.pause()
            tickets = [prod.submit(pr.edges, b) for b in batches]
            t0 = time.perf_counter()
            fe.resume()
            prod.flush(timeout=WAIT_S)
            fe.flush(timeout=WAIT_S)
            jax.block_until_ready(ex.states)
            walls.append(time.perf_counter() - t0)
            for t in tickets:
                res = t.result(timeout=WAIT_S)
                require(res.status == APPLIED,
                        f"ticket {t.batch_id} resolved {res.status!r}")
                acked.append(t.batch_id)
        warm_s, steady_s = walls
        steady_ticks = cfg["steady_windows"] * k
        say(f"served {len(acked)} churn batches of {n_churn} rows: warm "
            f"window {warm_s:.2f}s (compiles), {steady_ticks} steady ticks "
            f"{steady_s:.3f}s")

        sched.executor.check_errors()
        require(all(r.block().quiesced for r in sched.history),
                "a churn window did not quiesce")
        counters = {
            "fixpoint_engine": ex.fixpoint_engine,
            "megatick_windows": sched.megatick_windows,
            "megatick_fallbacks": sched.megatick_fallbacks,
            "windows_staged": fe.windows_staged,
            "windows_pipelined": fe.windows_pipelined,
            "forced_syncs": sched.forced_syncs,
            "wal_log_readbacks": sched.log_readbacks,
        }
        require(ex.fixpoint_engine == "LinearFixpointProgram",
                f"churn ran {ex.fixpoint_engine}, not the fused linear loop")
        require(sched.megatick_windows >= 1 + cfg["steady_windows"],
                f"only {sched.megatick_windows} fused windows")
        require(sched.megatick_fallbacks == 0,
                f"{sched.megatick_fallbacks} windows fell back per-tick")
        require(fe.windows_pipelined >= 1, "depth 2 never pipelined")
        require_resident(ex.states, devices, "pagerank after churn")

        ranks = pagerank.ranks_to_array(sched.read_table(pr.new_rank), n)
        err = rel_err(ranks, ref)
        require(err < bound, f"served ranks off the reference by {err:.3e} "
                             f"(bound {bound:.3e})")
        horizon = sched._tick
        say(f"ranks vs reference_ranks: max rel err {err:.3e} < "
            f"{bound:.3e}; horizon tick {horizon}; {counters}")
    finally:
        if prod is not None:
            prod.close()
        if srv is not None:
            srv.close()
        fe.close()
        sched.close()
    del sched, ex, fe

    # -- the guarantee: recover a fresh scheduler from the same WAL ----
    pr2 = pagerank.build_graph(n, tol=tol, arena_capacity=arena)
    t0 = time.perf_counter()
    ex2 = make_executor()
    sched2 = DurableScheduler(pr2.graph, ex2, wal_dir=wal_dir, fsync="tick",
                              committer="thread")
    try:
        report = recover(sched2, wal_dir)
        jax.block_until_ready(ex2.states)
        recover_s = time.perf_counter() - t0
        rebuilt_s = sched2.history[0].wall_s
        require(sched2._tick == horizon,
                f"recovered horizon {sched2._tick} != {horizon}")
        lost = [b for b in acked if b not in sched2._seen_batch_ids]
        require(not lost, f"{len(lost)} acked batches missing after "
                          f"recovery (first: {lost[:3]})")
        require(ex2.fixpoint_engine == "LinearFixpointProgram",
                f"recovery ran {ex2.fixpoint_engine}")
        require_resident(ex2.states, devices, "pagerank recovered")
        ranks2 = pagerank.ranks_to_array(
            sched2.read_table(pr2.new_rank), n)
        err2 = rel_err(ranks2, ref)
        drift = rel_err(ranks2, ranks)
        require(err2 < bound, f"recovered ranks off the reference by "
                              f"{err2:.3e} (bound {bound:.3e})")
        require(drift < bound, f"recovered ranks differ from the served "
                               f"ones by {drift:.3e} (bound {bound:.3e})")
    finally:
        sched2.close()
    say(f"recovered from the WAL in {recover_s:.2f}s: {report.replayed_pushes}"
        f" pushes, {report.replayed_ticks} ticks, horizon {sched2._tick}, "
        f"rel err {err2:.3e}, vs served {drift:.3e}; build tick "
        f"{rebuilt_s:.2f}s (first build {built.wall_s:.2f}s)")

    return {
        "nodes": n, "edges": e, "churn_rows_per_batch": n_churn,
        "window_ticks": k, "tickets_applied": len(acked),
        "max_rel_err": err, "rel_err_bound": bound,
        **counters,
        "setup_s": round(setup_s, 3),
        "build_tick_s": round(built.wall_s, 3),
        "warm_window_s": round(warm_s, 3),
        "steady_s": round(steady_s, 4), "steady_ticks": steady_ticks,
        "recover_s": round(recover_s, 3),
        "recovered_build_tick_s": round(rebuilt_s, 3),
        "recovered_horizon": sched2._tick,
        "recovered_max_rel_err": err2,
        "recovered_vs_served_rel": drift,
        "replayed_pushes": report.replayed_pushes,
        "replayed_ticks": report.replayed_ticks,
    }


# -- phase 2: k-NN with the compiled Pallas top-k --------------------------

def knn_phase(cfg: dict, dev) -> dict:
    import jax
    import jax.numpy as jnp
    import numpy as np

    from reflow_tpu.delta import DeltaBatch
    from reflow_tpu.executors import get_executor
    from reflow_tpu.kernels.topk import (NEG, chunked_corpus_topk,
                                         fold_topk, score_form,
                                         sweep_blocks, topk)
    from reflow_tpu.scheduler import DirtyScheduler
    from reflow_tpu.workloads import knn

    c = cfg["knn"]
    Q, D, dim, k, chunk = c["Q"], c["D"], c["dim"], c["k"], c["chunk"]
    kg = knn.build_graph(Q, D, dim, k, scan_chunk=chunk, dtype=jnp.bfloat16,
                         doc_dtype=jnp.int8, precision="default")
    ex = get_executor("tpu")
    sched = DirtyScheduler(kg.graph, ex)
    require_resident(ex.states, [dev], "knn bind")

    t0 = time.perf_counter()
    store = knn.EmbeddingStore.create(dim, seed=3)
    sched.push(kg.queries, DeltaBatch(
        np.arange(Q, dtype=np.int64), store._random(Q),
        np.ones(Q, np.int64)))
    gen = knn.preload_chunk(c["preload_rows"], dim, D, jnp.int8)
    next_id = 0
    for ix in range(c["preload_chunks"]):       # corpus made on the device
        sched.push(kg.docs, gen(np.int32(ix), np.int32(next_id)))
        sched.tick(sync=False)
        next_id += c["preload_rows"]
    jax.block_until_ready(ex.states)
    preload_s = time.perf_counter() - t0

    # one insert tick (host int8 rows, the real ingest boundary) ...
    t0 = time.perf_counter()
    ins_ids = np.arange(next_id, next_id + c["insert_rows"])
    sched.push(kg.docs, store.insert_batch(ins_ids, quantize=True))
    sched.tick()
    insert_s = time.perf_counter() - t0
    # ... and one retraction tick: forces the chunked full-corpus rescan,
    # i.e. the fold kernel at [Q, k] + [Q, chunk]. A device retraction
    # only clears the id's live bit, so zero rows stand in for the
    # device-made vectors.
    t0 = time.perf_counter()
    ret_ids = np.arange(c["retract_rows"], dtype=np.int64)
    sched.push(kg.docs, DeltaBatch(
        ret_ids, np.zeros((len(ret_ids), dim), np.int8),
        -np.ones(len(ret_ids), np.int64)))
    sched.tick()
    rescan_s = time.perf_counter() - t0

    st = ex.states[kg.index.id]
    live = int(np.asarray(st["dlive"]).sum())
    want_live = next_id + c["insert_rows"] - c["retract_rows"]
    require(live == want_live, f"{live} live corpus rows, want {want_live}")
    table = sched.read_table(kg.index)
    require(len(table) == Q, f"{len(table)} query rows, want {Q}")
    rows = np.stack([table[q] for q in range(Q)])          # [Q, k, 2]
    require(rows.shape == (Q, k, 2) and np.isfinite(rows).all(),
            f"top-k table shape {rows.shape} / non-finite entries")
    got_ids, got_vals = rows[:, :, 0].astype(np.int64), rows[:, :, 1]
    require((got_ids >= c["retract_rows"]).all() and (got_ids < D).all(),
            "a retracted or out-of-range doc id is in a query's top-k")

    # the served table (Pallas inside the tick program on a TPU) against
    # the same rescan selected by lax.top_k, from the executor's own state
    prec = jax.lax.Precision.DEFAULT
    ref_vals, ref_ids, _ = jax.jit(
        lambda q, d, l: chunked_corpus_topk(q, d, l, k, chunk,
                                            use_pallas=False,
                                            precision=prec)
    )(st["qvec"], st["dvec"], st["dlive"])
    require(np.array_equal(got_ids, np.asarray(ref_ids))
            and np.array_equal(got_vals, np.asarray(ref_vals)),
            "served top-k table != lax.top_k rescan of the same state")

    # the kernels alone, on literally the same scores. The generic entry:
    # one [Q, k + chunk] candidate matrix, Pallas vs lax.top_k
    @jax.jit
    def chunk_scores(q, d, l, at):
        blk = jax.lax.dynamic_slice_in_dim(d, at, chunk, 0)
        s = jnp.dot(score_form(q), score_form(blk).T,
                    preferred_element_type=jnp.float32, precision=prec)
        return jnp.where(
            jax.lax.dynamic_slice_in_dim(l, at, chunk, 0)[None, :], s, NEG)

    def scores_at(at):
        return chunk_scores(st["qvec"], st["dvec"], st["dlive"], at)

    no_vals = jnp.full((Q, k), NEG, jnp.float32)
    s = jnp.concatenate([no_vals, scores_at(0)], 1)
    pv, pi = jax.jit(lambda x: topk(x, k, use_pallas=True))(s)
    lv, li = jax.lax.top_k(s, k)
    require(np.array_equal(np.asarray(pi), np.asarray(li))
            and np.array_equal(np.asarray(pv), np.asarray(lv)),
            f"Pallas top-k != lax.top_k on the same {s.shape} scores")

    # the rescan's fold step, mid-scan: the carry [Q, k] the chunk before
    # `lo` leaves and the chunk at `lo` [Q, chunk], as the scan hands
    # them over — the fold kernel vs its XLA body vs one candidate matrix
    # with an id block beside it (lax.top_k for columns, a gather for
    # ids). The kernel sweeps a block as often as a row of it has
    # columns above its carry's k-th score, so the same comparison is
    # made where no row takes anything (the chunk pushed under the
    # carry: every block's gate stays shut) and where every row takes k
    # (pushed over it: what an ascending-score corpus hands the fold at
    # every chunk); the sweeps it counts must be its XLA body's, 0 and
    # k a block there.
    lo = D // chunk // 2 * chunk
    fold = jax.jit(fold_topk, static_argnums=(5, 6))
    none = jnp.zeros((sweep_blocks(Q),), jnp.int32)
    carry = fold(no_vals, jnp.full((Q, k), -1, jnp.int32), none,
                 scores_at(lo - chunk), jnp.int32(lo - chunk), k, False)[:2]
    sc = scores_at(lo)
    swept = {}
    for case, shift in (("mid-scan", 0.0), ("no entrant", -4.0),
                        ("every row takes k", 4.0)):
        x = jnp.where(sc > NEG, sc + shift, NEG)
        fv, fi, fn = fold(*carry, none, x, jnp.int32(lo), k, True)
        xv, xi, xn = fold(*carry, none, x, jnp.int32(lo), k, False)
        bv, sel = jax.lax.top_k(jnp.concatenate([carry[0], x], 1), k)
        bi = jnp.take_along_axis(jnp.concatenate(
            [carry[1], jnp.broadcast_to(
                lo + jnp.arange(chunk, dtype=jnp.int32), (Q, chunk))], 1),
            sel, axis=1)
        for what, v, i in (("its XLA body", xv, xi),
                           ("the id block", bv, bi)):
            require(np.array_equal(np.asarray(fi), np.asarray(i))
                    and np.array_equal(np.asarray(fv), np.asarray(v)),
                    f"fold kernel != {what} ({case}) on the same carry "
                    f"{carry[0].shape} + chunk {sc.shape} at lo {lo}")
        require(np.array_equal(np.asarray(fn), np.asarray(xn)),
                f"fold kernel counts {np.asarray(fn).tolist()} sweeps, its "
                f"XLA body {np.asarray(xn).tolist()} ({case})")
        swept[case] = int(jnp.sum(fn))
    require(swept["no entrant"] == 0
            and swept["every row takes k"] == k * sweep_blocks(Q),
            f"sweeps {swept}: want 0 where nothing enters and k a block "
            f"where every row takes k")
    pallas_in_tick = jax.default_backend() == "tpu"
    say(f"knn Q {Q} dim {dim} k {k} chunk {chunk}, {live} live of {D} "
        f"slots (cut: {D - next_id - c['insert_rows']} slots left empty): "
        f"preload {preload_s:.2f}s, insert tick {insert_s:.3f}s, "
        f"retraction rescan {rescan_s:.3f}s; Pallas kernel "
        f"{'compiled' if pallas_in_tick else 'interpreted'} at {s.shape} "
        f"== lax.top_k; fold kernel at {carry[0].shape} + {sc.shape}, lo "
        f"{lo} == XLA body == id block (sweeps {swept}); served table == "
        f"lax.top_k rescan")
    return {
        "Q": Q, "dim": dim, "k": k, "scan_chunk": chunk,
        "corpus_slots": D, "corpus_live": live,
        "pallas_compiled": pallas_in_tick,
        "kernel_scores_shape": list(s.shape),
        "fold_kernel_shapes": [list(carry[0].shape), list(sc.shape)],
        "fold_kernel_sweeps": swept,
        "preload_s": round(preload_s, 3),
        "insert_tick_s": round(insert_s, 4),
        "rescan_tick_s": round(rescan_s, 4),
    }


# -- phase 3: the indexed join against the sweep it replaces ------------------

def join_phase(cfg: dict, dev) -> dict:
    """One unique-left join at the kernel level: right rows arrive for
    ``ticks`` ticks (skewed: one key takes a quarter of them), then the
    left rows they were waiting for, all late. The indexed lowering's
    output (``executors/arena.py``: key-sorted appends, segment chains,
    budgeted probe) == the dense table-by-arena sweep's == NumPy's, as
    multisets of (key, left value, right value, weight)."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from reflow_tpu.delta import Spec
    from reflow_tpu.executors.device_delta import DeviceDelta
    from reflow_tpu.executors.join import (JOIN_COUNTERS, join_core,
                                           join_reindex, join_state)
    from reflow_tpu.graph import FlowGraph

    c = cfg["join"]
    K, R, C, L = c["keys"], c["arena"], c["rows"], c["left_rows"]
    g = FlowGraph("smoke_join")
    left = g.source("l", Spec((), np.int32, key_space=K, unique=True))
    right = g.source("r", Spec((), np.int32, key_space=K))
    j = g.join(left, right,
               merge=lambda k, va, vb: jnp.stack([va, vb], axis=-1),
               spec=Spec((2,), np.int32, key_space=K), arena_capacity=R,
               product_slack=c["ticks"] * C // L + 1)
    rng = np.random.default_rng(7)
    step = jax.jit(lambda st, da, db: join_core(
        j.op, K, R, np.int32, st, da, db, oshape=(2,)))
    states = {ix: jax.device_put(
        join_state(j.op, left.spec, right.spec,
                   "indexed" if ix else "swept"), dev)
        for ix in (True, False)}
    rk, rv = [], []
    for _ in range(c["ticks"]):
        keys = np.where(rng.random(C) < 0.25, 3,
                        rng.integers(0, K, C)).astype(np.int32)
        vals = rng.integers(1, 1 << 30, C).astype(np.int32)
        rk.append(keys)
        rv.append(vals)
        db = DeviceDelta(jnp.asarray(keys), jnp.asarray(vals),
                         jnp.ones((C,), jnp.int32))
        for ix in states:
            _, states[ix] = step(states[ix], None, db)
    rk, rv = np.concatenate(rk), np.concatenate(rv)
    lk = np.concatenate([[3], rng.choice(
        np.setdiff1d(np.arange(K), [3]), L - 1, replace=False)]
    ).astype(np.int32)
    lv = rng.integers(1, 1 << 30, L).astype(np.int32)
    da = DeviceDelta(jnp.asarray(lk), jnp.asarray(lv),
                     jnp.ones((L,), jnp.int32))
    t0 = time.perf_counter()
    outs = {}
    for ix in states:
        out, states[ix] = step(states[ix], da, None)
        require(not bool(states[ix]["error"]),
                f"join ({'indexed' if ix else 'dense'}) latched its error")
        w = np.asarray(out.weights)
        rows = np.concatenate([np.asarray(out.keys)[:, None],
                               np.asarray(out.values)], axis=1)[w != 0]
        require((w[w != 0] == 1).all(), "a late pair's weight is not 1")
        outs[ix] = rows[np.lexsort(rows.T[::-1])]
    late_s = time.perf_counter() - t0
    lval = np.zeros(K, np.int64)
    held = np.zeros(K, bool)
    lval[lk], held[lk] = lv, True
    want = np.stack([rk, lval[rk], rv], axis=1)[held[rk]]
    want = want[np.lexsort(want.T[::-1])]
    require(np.array_equal(outs[True], outs[False]),
            "indexed join != dense join on the same arena and delta")
    require(np.array_equal(outs[True], want), "join != NumPy")
    counters = dict(zip(JOIN_COUNTERS,
                        np.asarray(states[True]["counters"]).tolist()))
    require(counters["late_pairs"] == len(want)
            and counters["arena_rows"] == len(rk),
            f"join counters {counters}: want {len(want)} late pairs and "
            f"{len(rk)} arena rows")
    say(f"join K {K} arena {R}: {len(rk)} right rows in {c['ticks']} ticks "
        f"(key 3 holds {int((rk == 3).sum())}), then {L} left rows: "
        f"{len(want)} late pairs, indexed == dense == NumPy; counters "
        f"{counters}")

    # retract the first tick's right rows, compact and re-index the
    # indexed arena as a program of its own (``join_reindex``: what the
    # executor runs between two windows), then retract the left rows:
    # the probe over the rebuilt index finds what the dense sweep finds
    # in its uncompacted log, every pair of the rows that are left
    gone = DeviceDelta(jnp.asarray(rk[:C]), jnp.asarray(rv[:C]),
                       -jnp.ones((C,), jnp.int32))
    for ix in states:
        _, states[ix] = step(states[ix], None, gone)
    t0 = time.perf_counter()
    states[True] = jax.jit(join_reindex, donate_argnums=0)(states[True])
    rows_left = int(states[True]["rcount"])
    reindex_s = time.perf_counter() - t0
    require(rows_left == len(rk) - C,
            f"reindex left {rows_left} rows of {len(rk)} less {C} retracted")
    back = DeviceDelta(da.keys, da.values, -jnp.ones((L,), jnp.int32))
    nets = {}
    for ix in states:
        out, states[ix] = step(states[ix], back, None)
        require(not bool(states[ix]["error"]),
                f"join ({'indexed' if ix else 'dense'}) latched its error "
                f"on the retraction")
        rows = np.concatenate([np.asarray(out.keys)[:, None],
                               np.asarray(out.values)], axis=1)
        uniq, inv = np.unique(rows, axis=0, return_inverse=True)
        net = np.bincount(inv.ravel(), weights=np.asarray(out.weights),
                          minlength=len(uniq)).astype(np.int64)
        nets[ix] = np.concatenate([uniq, net[:, None]], axis=1)[net != 0]
    want2 = np.stack([rk[C:], lval[rk[C:]], rv[C:]], axis=1)[held[rk[C:]]]
    uniq, n = np.unique(want2, axis=0, return_counts=True)
    want2 = np.concatenate([uniq, -n[:, None]], axis=1)
    require(np.array_equal(nets[True], nets[False]),
            "after join_reindex: indexed join != dense join on a left "
            "retraction")
    require(np.array_equal(nets[True], want2),
            "after join_reindex: retracted pairs != NumPy")
    counters = dict(zip(JOIN_COUNTERS,
                        np.asarray(states[True]["counters"]).tolist()))
    require(counters["index_rebuilds"] == 1 and counters["compactions"] == 1
            and counters["retracted"] == C,
            f"join counters {counters}: want 1 rebuild, 1 compaction and "
            f"{C} retracted rows")
    say(f"join: {C} right rows retracted, join_reindex in {reindex_s:.3f} s "
        f"(first call: compiled in it) leaves {rows_left} rows; {L} left "
        f"rows retracted: {len(want2)} distinct pairs taken back, "
        f"indexed == dense == NumPy")
    return {"keys": K, "arena": R, "right_rows": int(len(rk)),
            "late_pairs": int(len(want)),
            "late_ticks_s": round(late_s, 4),
            "reindex_rows_left": rows_left,
            "retracted_pairs": int(n.sum())}


def _sssp_config():
    """The benchmark configuration's module, for its Kronecker
    generator: the one copy there is
    (``benchmarks/configs/sssp-graph500.py``)."""
    import importlib.util

    bench = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                         "benchmarks")
    if bench not in sys.path:
        sys.path.insert(0, bench)       # the module's own ``common``
    spec = importlib.util.spec_from_file_location(
        "sssp_graph500", os.path.join(bench, "configs", "sssp-graph500.py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def sssp_phase(cfg: dict, dev) -> dict:
    """The row fixpoint (``FixpointProgram``: phase A, a ``while_loop``
    over Join -> GroupBy -> min-Reduce, no exit pass) at the benchmark
    cell's scale: half of a Kronecker graph and the root in one tick,
    then ``ticks`` insert batches of 1 / 512 of the dataset, each a
    one-tick fused window."""
    import numpy as np

    from reflow_tpu import DirtyScheduler
    from reflow_tpu.executors import get_executor
    from reflow_tpu.workloads import sssp

    def both_ways(u, v, w):
        return sssp.edge_batch(np.concatenate([u, v]),
                               np.concatenate([v, u]),
                               np.concatenate([w, w]))

    c = cfg["sssp"]
    n = 1 << c["scale"]
    u, v, w = _sssp_config().kronecker(c["scale"], c["edgefactor"],
                                       (0.57, 0.19, 0.19, 0.05), 7, 16)
    half, batch = len(u) // 2, len(u) // 512
    root = int(np.argmax(np.bincount(u[:half], minlength=n)
                         + np.bincount(v[:half], minlength=n)))
    sg = sssp.build_graph(n, arena_capacity=2 * len(u))
    ex = get_executor("tpu")
    sched = DirtyScheduler(sg.graph, ex)
    t0 = time.perf_counter()
    sched.push(sg.edges, both_ways(u[:half], v[:half], w[:half]))
    sched.push(sg.seeds, sssp.seed_batch(root))
    first = sched.tick()
    load_s = time.perf_counter() - t0
    require(first.quiesced, "sssp: the load tick did not quiesce")
    require(ex.fixpoint_engine == "FixpointProgram",
            f"sssp: engine {ex.fixpoint_engine!r}, want the row program")
    passes, tick_s = [], []
    for i in range(c["ticks"]):
        lo, hi = half + i * batch, half + (i + 1) * batch
        t0 = time.perf_counter()
        r = sched.tick_many([{sg.edges: both_ways(u[lo:hi], v[lo:hi],
                                                  w[lo:hi])}])
        r.block()
        tick_s.append(time.perf_counter() - t0)
        require(bool(np.all(np.asarray(r.quiesced))),
                f"sssp: window {i} did not converge")
        passes.append(int(r.passes))
    require(sched.megatick_windows == c["ticks"]
            and sched.megatick_fallbacks == 0,
            f"sssp: {sched.megatick_windows} fused windows, "
            f"{sched.megatick_fallbacks} fallbacks")
    ex.check_errors()
    require_resident(ex.states, [dev], "sssp")
    counters = ex.op_counters()
    require(counters["dist"]["unquiesced"] == 0
            and counters["dist"]["ticks"] == 1 + c["ticks"]
            and counters["dist"]["passes"] == int(first.passes)
            + sum(passes), f"sssp: loop counters {counters['dist']}")
    # every pass with a left delta went through the join's key-sorted
    # view or swept, and a window's small frontiers took the view
    relax = counters["relax"]
    require(relax["sweeps"] + relax["probes"]
            == counters["dist"]["passes"] - counters["dist"]["ticks"]
            and relax["probes"] > 0, f"sssp: join counters {relax}")
    hi = half + c["ticks"] * batch
    want = sssp.reference_distances(
        n, np.concatenate([u[:hi], v[:hi]]),
        np.concatenate([v[:hi], u[:hi]]), np.concatenate([w[:hi], w[:hi]]),
        root)
    got = {int(k): float(x) for k, x in sched.read_table(sg.best).items()}
    require(got == want,
            f"sssp: {len(got)} served distances != Bellman-Ford's "
            f"{len(want)}")
    # the first window compiles its program; the later ones say what a
    # pass costs with the host waiting on each
    say(f"sssp scale {c['scale']}: {half} tuples + root in one tick, "
        f"{int(first.passes)} passes, {load_s:.1f}s with its compile; "
        f"{c['ticks']} windows of {batch} tuples: passes {passes}, "
        f"{[round(t, 3) for t in tick_s]} s; {len(got)} of {n} vertices "
        f"reached, distances == Bellman-Ford; counters {counters}")
    return {"scale": c["scale"], "reached": len(got),
            "load_passes": int(first.passes), "window_passes": passes,
            "window_s": [round(t, 3) for t in tick_s],
            "evicted": counters["best"]["evicted"]}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--tiny", action="store_true",
                    help="small CPU form (tier-1); needs JAX_PLATFORMS=cpu")
    args = ap.parse_args(argv)
    cfg = TINY if args.tiny else FULL

    t_start = time.perf_counter()
    dev = require_device(args.tiny)

    from reflow_tpu.utils.runtime import device_record, place_compile_cache

    device = device_record()
    say(f"platform={device['platform']} device_kind={device['kind']} "
        f"devices={device['count']}")
    cache_dir = place_compile_cache()
    cached = len(os.listdir(cache_dir)) if os.path.isdir(cache_dir) else 0
    say(f"compile cache {cache_dir} ({cached} entries at start)")

    root = tempfile.mkdtemp(prefix="reflow-chip-smoke-")
    try:
        pr = pagerank_phase(cfg, [dev], root)
        kn = knn_phase(cfg, dev)
        jn = join_phase(cfg, dev)
        sp = sssp_phase(cfg, dev)
    finally:
        shutil.rmtree(root, ignore_errors=True)

    print(json.dumps({
        "schema": "reflow.chip_smoke/1", "form": "tiny" if args.tiny
        else "full", "device": device,
        "compile_cache": {"dir": cache_dir, "entries_at_start": cached},
        "pagerank": pr, "knn": kn, "join": jn, "sssp": sp,
        "total_s": round(time.perf_counter() - t_start, 2),
        "claim": None,
    }), flush=True)
    print(json.dumps({"ok": True, "device": device}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
