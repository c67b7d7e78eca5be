"""Per-config benchmark records for BASELINE.md configs 1, 2, 4, 5.

Config 3 (incremental PageRank) is the headline and lives in bench.py;
this module measures the remaining four and emits one JSON record each on
stderr (via the passed ``log``), so the driver's BENCH tail carries all
five per-config records while stdout keeps the single headline line.

Each config is wrapped so a failure records an error line before it
propagates: the config's child process exits non-zero, and so does
``python bench.py``.
"""

from __future__ import annotations

import json
import time

import numpy as np

from reflow_tpu.utils.config import env_int, env_str


#: peak dense bf16 FLOP/s of one chip, keyed by ``device_kind`` as JAX
#: reports it. Source: Google Cloud documentation, "TPU v5e" system
#: architecture page (197 TFLOP/s bf16 per chip).
PEAK_BF16_FLOPS = {
    "TPU v5 lite": 197e12,
}


def peak_bf16_flops(device):
    """Published bf16 peak of ``device`` (a ``jax.Device``). None on the
    CPU backend — a CPU run reports no utilization; an accelerator that
    is not in the table is an error, never given another chip's peak."""
    if device.platform == "cpu":
        return None
    if device.device_kind not in PEAK_BF16_FLOPS:
        raise KeyError(
            f"no published bf16 peak on record for device_kind "
            f"{device.device_kind!r} (known: {sorted(PEAK_BF16_FLOPS)}); "
            f"add it to bench_configs.PEAK_BF16_FLOPS with its source")
    return PEAK_BF16_FLOPS[device.device_kind]


def _record(log, name: str, rec: dict) -> None:
    rec = {"config": name, **rec}
    log(json.dumps(rec))


def _barrier(executor) -> None:
    """Wait until everything dispatched so far has run. The device
    stream is in-order, so blocking on the final state tree covers every
    program enqueued before it. (Measured on a directly attached v5e:
    ``block_until_ready`` returns when the device is done, within a
    millisecond of a host readback of the same result — CHANGES.md PR
    21.)"""
    import jax

    jax.block_until_ready(getattr(executor, "states", None))


def _timed_tick(sched, **kw):
    """One tick measured to device completion via ``_barrier`` (the CPU
    oracle is synchronous by construction and its states are giant host
    Counters — pytree traversal there costs hundreds of ms and would
    inflate the baseline's walls, so only device executors barrier)."""
    t0 = time.perf_counter()
    r = sched.tick(**kw)
    if getattr(sched.executor, "name", "") != "cpu":
        _barrier(sched.executor)
    return time.perf_counter() - t0, r


def _median_window(run_once, log, tag: str, n: int = 3):
    """Run ``n`` measurement windows, return ``(wall, dispatch_wall,
    delta_ops)`` of the MEDIAN-throughput window, so one outlier window
    on a shared host does not set the record.

    ``run_once() -> (wall_s, dispatch_wall_s, delta_ops)``. Returns
    ``(median_wall, median_dispatch_wall, median_delta_ops, windows)``
    with ``windows`` the full per-window list for diagnostics.
    """
    windows = []
    for ix in range(n):
        wall, dwall, dops = run_once()
        windows.append((wall, dwall, dops))
        log(f"{tag} window {ix}: {wall:.2f}s "
            f"({dops / wall:,.0f} delta-ops/s)")
    ordered = sorted(windows, key=lambda w: w[2] / w[0])
    wall, dwall, dops = ordered[len(ordered) // 2]
    return wall, dwall, dops, windows


def _stream_window(sched, feed, n: int):
    """Pipelined measurement window: dispatch ``n`` streaming ticks
    back-to-back with ZERO host readbacks (the device runs the ticks
    shoulder to shoulder), then wait for completion once. Returns ``(wall, dispatch_wall,
    results)`` — ``wall`` covers dispatch + all device compute;
    ``dispatch_wall`` shows the host enqueue cost (its smallness is the
    evidence the window was device-bound). Error checks and TickResult
    scalar conversion run after the clock stops."""
    t0 = time.perf_counter()
    results = []
    for i in range(n):
        feed(i)
        results.append(sched.tick(sync=False))
    dispatch_wall = time.perf_counter() - t0
    _barrier(sched.executor)
    wall = time.perf_counter() - t0
    sched.executor.check_errors()
    for r in results:
        r.block()
    return wall, dispatch_wall, results


def _pad_batch(batch, rows: int):
    """Pad a host DeltaBatch to a fixed row count with weight-0 rows so
    every edit tick hits ONE capacity bucket (VERDICT r2 weak #5: batches
    wandering across buckets kept recompiling in steady state)."""
    from reflow_tpu.delta import DeltaBatch

    n = len(batch)
    if n >= rows:
        return batch
    pad = rows - n
    vals = np.zeros((pad,) + batch.values.shape[1:], batch.values.dtype)
    return DeltaBatch.concat([batch, DeltaBatch(
        np.zeros(pad, np.int64), vals, np.zeros(pad, np.int64))])


def control_scenario(smoke: bool) -> dict:
    """Step-load knobs for bench.py's ``REFLOW_BENCH_CONTROL`` mode
    (hot-tenant surge + pump-crash storm under a live ControlPlane).

    One place for the scenario's shape so the bench and the tier-1
    smoke assert against the same numbers. The budget is sized so the
    hot tenant genuinely saturates its byte ceiling (wordcount
    micro-batches are tiny); the control interval is fast enough that
    recovery-in-intervals is measured in tens of milliseconds, not
    seconds. ``recovery_slack_ticks`` pads the analytic recovery bound
    (ladder rungs x recover_intervals) with the ticks the pool needs to
    drain in-flight bytes after the surge stops."""
    return {
        "budget_bytes": 8 << 10,
        "pump_threads": 2,
        "interval_s": 0.005,
        # hot tenant's SLO: occupancy of its ceiling, 2-interval breach
        # confirm, 2-interval per-rung recovery hysteresis
        "occupancy_slo": 0.6,
        "breach_intervals": 2,
        "recover_intervals": 2,
        "hammers": 3,
        "quiet_batches": 60 if smoke else 200,
        # quiet tenant's admission p99 bound during the surge (same
        # bound phase C of the tier bench enforces without a controller)
        "quiet_p99_bound_s": 0.05,
        "recovery_slack_ticks": 12,
        # crash-storm breaker knobs (fast cooldowns: the bench proves
        # the open -> half-open -> closed arc, not production pacing)
        "max_crashes": 3,
        "crash_window_s": 30.0,
        "respawn_backoff_s": 0.0,
        "respawn_backoff_max_s": 0.01,
        "breaker_cooldown_s": 0.02,
        "breaker_cooldown_max_s": 0.1,
        "probe_intervals": 2,
    }


def _guard(log, name: str):
    """Record a failing config's error line, then let the failure
    propagate (no config finishes "ok" over an exception)."""
    def deco(fn):
        def wrapped(*a, **k):
            try:
                return fn(*a, **k)
            except Exception as e:
                _record(log, name, {"error": f"{type(e).__name__}: {e}"})
                raise
        return wrapped
    return deco


# -- config 1: incremental word-count, CPU executor ------------------------

def cfg1_wordcount(smoke: bool, log) -> None:
    @_guard(log, "1_wordcount")
    def run():
        from reflow_tpu.scheduler import DirtyScheduler
        from reflow_tpu.workloads import wordcount

        n_lines = 2_000 if smoke else 100_000
        per_tick = 500 if smoke else 10_000
        rng = np.random.default_rng(0)
        vocab_words = [f"w{i}" for i in range(5_000)]
        lines = [" ".join(rng.choice(vocab_words,
                                     size=rng.integers(5, 15)))
                 for _ in range(n_lines)]

        g, src, sink = wordcount.build_graph()
        sched = DirtyScheduler(g)  # CpuExecutor: the default path
        walls, dops = [], []
        for i in range(0, n_lines, per_tick):
            sched.push(src, wordcount.ingest_lines(lines[i:i + per_tick]))
            r = sched.tick()
            walls.append(r.wall_s)
            dops.append(r.delta_ops)
        # one retraction tick (incremental un-count)
        sched.push(src, wordcount.ingest_lines(lines[:per_tick], weight=-1))
        r = sched.tick()
        walls.append(r.wall_s)
        dops.append(r.delta_ops)
        _record(log, "1_wordcount", {
            "executor": "cpu",
            "lines": n_lines,
            "delta_ops_per_s": round(sum(dops) / sum(walls)),
            "ticks": len(walls),
        })
    run()


# -- config 2: streaming TF-IDF, CPU + TPU ---------------------------------

def cfg2_tfidf(smoke: bool, log) -> None:
    from reflow_tpu.executors import get_executor
    from reflow_tpu.scheduler import DirtyScheduler
    from reflow_tpu.workloads import tfidf

    n_docs = 64 if smoke else 4_096
    # 2^20-term vocabulary (a real Wikipedia-scale vocab is ~10^6; the
    # radix-split presence path is exact to 2^24 — workloads/tfidf.py)
    n_terms = 1 << (10 if smoke else 20)
    # pair capacity covers the full run: initial corpus plus the per-edit
    # AND micro-batched phases at 1 warm + 3 measured windows each (every
    # edit interns ~45 fresh (doc,term) pairs; real scale ~540k total)
    n_pairs = 1 << (15 if smoke else 20)
    edits = 32 if smoke else 512
    vocab = 1_000 if smoke else 250_000  # drawn words (ids intern densely)
    # np array, not list: rng.choice over a list re-converts all 250k
    # strings per call (~20ms x thousands of edits)
    words = np.array([f"t{i}" for i in range(vocab)])

    for ex_name in ("cpu", "tpu"):
        @_guard(log, f"2_tfidf_{ex_name}")
        def run(ex_name=ex_name):
            rng = np.random.default_rng(1)
            corpus = tfidf.Corpus(n_pairs, n_terms)
            tg = tfidf.build_graph(n_pairs, n_terms, n_docs)
            sched = DirtyScheduler(tg.graph, get_executor(ex_name))

            def text():
                return " ".join(rng.choice(words, size=rng.integers(20, 60)))

            # initial corpus load (streaming on the device path: nothing
            # needs the host to wait for it)
            batches = [corpus.edit(d, text()) for d in range(n_docs // 2)]
            from reflow_tpu.delta import DeltaBatch
            sched.push(tg.tokens, DeltaBatch.concat(batches))
            sched.tick(sync=ex_name == "cpu")
            # device path: every edit batch is padded to ONE fixed
            # capacity bucket so steady state compiles exactly one churn
            # program. The CPU oracle pays per-row cost for pad rows, so
            # it gets the raw batches; pad rows are excluded from BOTH
            # executors' delta-ops numerators (they are no-ops)
            edit_rows = 256 if ex_name != "cpu" else 0

            def _push_edit(batch):
                pad = max(0, edit_rows - len(batch))
                sched.push(tg.tokens, _pad_batch(batch, edit_rows)
                           if edit_rows else batch)
                return pad

            if ex_name == "cpu":
                _push_edit(corpus.edit(0, text()))  # warm the churn shape
                _timed_tick(sched)
                walls, dops = [], []
                for i in range(edits):
                    d = int(rng.integers(0, n_docs))
                    pad = _push_edit(corpus.edit(d, text()))
                    wall, r = _timed_tick(sched)
                    walls.append(wall)
                    dops.append(r.delta_ops - pad)
                _record(log, f"2_tfidf_{ex_name}", {
                    "executor": ex_name,
                    "docs": n_docs, "terms": n_terms,
                    "edits": edits,
                    "delta_ops_per_s": round(sum(dops) / sum(walls)),
                    "tick_ms_median": round(1e3 * float(np.median(walls)), 2),
                })
            else:
                # device path: ALL edits of a window scan-fuse into ONE
                # device execution (tick_many on the loop-free graph),
                # amortizing the per-dispatch overhead across the whole
                # window; zero readbacks before the barrier
                pads = []

                def make_feed():
                    d = int(rng.integers(0, n_docs))
                    b = corpus.edit(d, text())
                    pads.append(max(0, edit_rows - len(b)))
                    return {tg.tokens: _pad_batch(b, edit_rows)}

                sched.tick_many([make_feed() for _ in range(edits)])  # warm
                pads.clear()
                _barrier(sched.executor)   # drain initial load + warm window

                def run_edit_window():
                    feeds = [make_feed() for _ in range(edits)]
                    t0 = time.perf_counter()
                    agg = sched.tick_many(feeds)
                    dwall = time.perf_counter() - t0
                    _barrier(sched.executor)
                    wall = time.perf_counter() - t0
                    sched.executor.check_errors()
                    agg.block()
                    dops = agg.delta_ops - sum(pads)
                    pads.clear()
                    return wall, dwall, dops

                wall, dwall, dops, _ = _median_window(
                    run_edit_window, log, "2_tfidf edit")
                _record(log, f"2_tfidf_{ex_name}", {
                    "executor": ex_name,
                    "docs": n_docs, "terms": n_terms,
                    "edits": edits,
                    "delta_ops_per_s": round(dops / wall),
                    "tick_ms_amortized": round(1e3 * wall / edits, 2),
                    "dispatch_ms_total": round(1e3 * dwall, 1),
                })

                # micro-batched streaming: a realistic ingestion buffer
                # groups edits per tick — a 256-row single edit cannot
                # fill the chip, a few-thousand-row micro-batch can
                group = 8 if smoke else 64
                ticks2 = 4 if smoke else 32
                # one bucket above any group's worst case (~80 rows/edit:
                # retract+insert per touched term), so every window tick
                # pads to ONE capacity and the measured window can never
                # compile a fresh scan program mid-measurement
                cap2 = 1024 if smoke else 8192
                pads2 = []

                def make_group():
                    bs = []
                    for _ in range(group):
                        d = int(rng.integers(0, n_docs))
                        bs.append(corpus.edit(d, text()))
                    b = DeltaBatch.concat(bs)
                    pads2.append(max(0, cap2 - len(b)))
                    return {tg.tokens: _pad_batch(b, cap2)}

                sched.tick_many([make_group() for _ in range(ticks2)])
                pads2.clear()
                _barrier(sched.executor)   # drain the batched warm window

                def run_batched_window():
                    feeds2 = [make_group() for _ in range(ticks2)]
                    t0 = time.perf_counter()
                    agg2 = sched.tick_many(feeds2)
                    dwall2 = time.perf_counter() - t0
                    _barrier(sched.executor)
                    wall2 = time.perf_counter() - t0
                    sched.executor.check_errors()
                    agg2.block()
                    dops2 = agg2.delta_ops - sum(pads2)
                    pads2.clear()
                    return wall2, dwall2, dops2

                wall2, _, dops2, _ = _median_window(
                    run_batched_window, log, "2_tfidf batched")
                _record(log, "2_tfidf_tpu_batched", {
                    "executor": ex_name,
                    "docs": n_docs, "terms": n_terms,
                    "edits_per_tick": group, "ticks": ticks2,
                    "delta_ops_per_s": round(dops2 / wall2),
                    "edits_per_s": round(group * ticks2 / wall2, 1),
                    "tick_ms_amortized": round(1e3 * wall2 / ticks2, 2),
                })
        run()


# -- config 4: k-NN re-index on 1Mx768 embedding deltas, TPU ---------------

def knn_preload_chunk(rows: int, dim: int, n_docs: int, doc_dtype):
    """Jitted ``(seed, base) -> DeviceDelta`` minting one ``rows``-row
    corpus insert batch with the on-chip RNG (ids ``base..base+rows``
    mod ``n_docs``): the device-resident corpus preload shared by config
    4 and ``chip_smoke.py``."""
    import jax
    import jax.numpy as jnp

    from reflow_tpu.executors.device_delta import DeviceDelta

    @jax.jit
    def gen_chunk(seed, base):
        kk = jax.random.fold_in(jax.random.PRNGKey(3), seed)
        vals = jax.random.normal(kk, (rows, dim), jnp.float32)
        keys = (base + jnp.arange(rows, dtype=jnp.int32)) % n_docs
        if doc_dtype == jnp.int8:
            # device-side form of workloads.knn.quantize_int8
            nrm = jnp.sqrt(jnp.sum(vals * vals, axis=1, keepdims=True))
            unit = vals / jnp.maximum(nrm, 1e-30)
            out = jnp.clip(jnp.round(unit * 127.0), -127, 127
                           ).astype(jnp.int8)
        else:
            out = jnp.asarray(vals, doc_dtype)
        return DeviceDelta(keys, out, jnp.ones((rows,), jnp.int32))

    return gen_chunk


def cfg4_knn(smoke: bool, log) -> None:
    @_guard(log, "4_knn")
    def run():
        from reflow_tpu.executors import get_executor
        from reflow_tpu.scheduler import DirtyScheduler
        from reflow_tpu.workloads import knn

        import os

        if smoke:
            Q, D, dim, k, chunk = 64, 4096, 64, 8, 1024
            per_tick, preload = 256, 1024
        else:
            Q, D, dim, k, chunk = 256, 1 << 20, 768, 16, 8192
            per_tick = 8192
            # the BASELINE scale is a 1Mx768 corpus; the preload is
            # env-tunable but clamped to leave headroom for every
            # measured insert tick (absorb + 3 windows x 6 x per_tick):
            # an id wrap during measurement would turn inserts into
            # in-place updates (which rescan) and also break the
            # wrap-aware live-row accounting at the record step
            cap_preload = (1 << 20) - 24 * 8192
            preload = min(env_int("REFLOW_BENCH_KNN_PRELOAD", cap_preload), cap_preload)

        # int8 quantized corpus ingest (VERDICT r4 #3a): round(unit*127)
        # on the wire — 1 byte/dim, HALF the bf16 wire+HBM cost that was
        # the measured binding constraint of this config — dequantized to
        # bf16 at score time on chip (kernels.topk.score_form; recall
        # bound tested in tests/test_knn.py). Queries stay bf16 (their
        # upload is negligible). REFLOW_BENCH_KNN_DTYPE=bf16 restores
        # the previous wire format for A/B runs.
        import jax.numpy as jnp
        wire = env_str("REFLOW_BENCH_KNN_DTYPE", "int8")
        doc_dtype = jnp.int8 if wire == "int8" else jnp.bfloat16
        kg = knn.build_graph(Q, D, dim, k, scan_chunk=chunk,
                             dtype=jnp.bfloat16, doc_dtype=doc_dtype,
                             precision="default")
        # generator-only here: the corpus preload below is device-made, so
        # store.vecs mirrors ONLY the measured host-boundary inserts (never
        # use store.reference_topk / len(store.vecs) in this config)
        store = knn.EmbeddingStore.create(dim, seed=3)
        sched = DirtyScheduler(kg.graph, get_executor("tpu"))
        qvecs = store._random(Q)
        from reflow_tpu.delta import DeltaBatch
        sched.push(kg.queries, DeltaBatch(
            np.arange(Q, dtype=np.int64), qvecs, np.ones(Q, np.int64)))
        next_id = 0

        def insert(n):
            nonlocal next_id
            # wrap into the corpus key space: once the id range is
            # exhausted, inserts become embedding UPDATES of existing
            # ids (the steady re-index regime) instead of out-of-range
            # keys the device would silently drop
            ids = np.arange(next_id, next_id + n) % D
            next_id += n
            return store.insert_batch(ids, quantize=(wire == "int8"))

        # corpus preload GENERATED ON DEVICE: the preload is bench
        # fixture setup (the measured flow is the insert windows below,
        # which still cross the host boundary as real ingestion), and
        # synthesizing it with the on-chip RNG replaces a ~0.7GB
        # host->device upload with a dozen device executions
        # smoke keeps the chunk small so the device-generated preload
        # path runs under CI too, not just on real-chip runs
        big = 512 if smoke else 1 << 16
        gen_chunk = knn_preload_chunk(big, dim, D, doc_dtype)

        def retract(ids):
            # device knn retraction clears the id's live bit and never
            # consults the value (lowerings._fold_vectors), so zero rows
            # stand in for the device-generated preload vectors
            return DeltaBatch(np.asarray(ids, np.int64),
                              np.zeros((len(ids), dim), np.dtype(doc_dtype)),
                              -np.ones(len(ids), np.int64))

        t0 = time.perf_counter()
        chunk_ix = 0
        while next_id + big <= preload:
            sched.push(kg.docs, gen_chunk(np.int32(chunk_ix),
                                          np.int32(next_id % D)))
            sched.tick(sync=False)
            next_id += big
            chunk_ix += 1
        preload_s = time.perf_counter() - t0   # dispatch wall (pipelined)
        sched.push(kg.docs, insert(per_tick))
        sched.tick(sync=False)
        sched.push(kg.docs, retract(np.arange(per_tick // 8)))
        sched.tick(sync=False)
        _barrier(sched.executor)   # drain the preload + absorb ticks

        # insert-heavy re-index flow (median-of-3 windows, _stream_window).
        # Per-tick streaming, not a macro-tick: with ~6MB of upload per
        # tick it keeps the uploads pipelined against compute. (Not
        # re-measured against the scan-fused form on the current code.)
        def run_insert_window():
            wall, dwall, results = _stream_window(
                sched, lambda i: sched.push(kg.docs, insert(per_tick)), 6)
            return wall, dwall, sum(r.delta_ops for r in results)

        wall, dwall, dops, _ = _median_window(
            run_insert_window, log, "4_knn insert")

        # one retraction tick: triggers the chunked full-corpus rescan,
        # timed to device completion (never an enqueue time)
        retract_ids = np.arange(per_tick // 8, per_tick // 4)
        sched.push(kg.docs, retract(retract_ids))
        rescan_wall, r = _timed_tick(sched)

        # the rescan is one [Q, D_cap] x [D_cap, dim] similarity matmul:
        # report achieved TFLOP/s so the wall defends itself
        rescan_gflop = 2.0 * Q * D * dim / 1e9
        # live rows, wrap-aware: ids retracted in the absorb tick
        # (0..per_tick//8) are re-enlivened by wrapped inserts once
        # next_id passes D + id; the post-window retract never is
        re_ins = min(max(next_id - D, 0), per_tick // 8)
        live_rows = (min(next_id, D) - (per_tick // 8 - re_ins)
                     - per_tick // 8)
        wire_bytes = 1 if doc_dtype == jnp.int8 else 2
        _record(log, "4_knn", {
            "executor": "tpu",
            "queries": Q,
            "corpus": live_rows,
            "corpus_capacity": D,
            "dim": dim, "k": k,
            "embed_wire_dtype": wire,
            "upload_mb_per_tick": round(
                per_tick * dim * wire_bytes / 1e6, 2),
            "preload_dispatch_s": round(preload_s, 1),
            "delta_ops_per_s": round(dops / wall),
            "insert_tick_ms_amortized": round(1e3 * wall / 6, 1),
            "dispatch_ms_total": round(1e3 * dwall, 1),
            "rescan_tick_ms": round(1e3 * rescan_wall, 1),
            "rescan_achieved_tflops": round(
                rescan_gflop / max(rescan_wall, 1e-9) / 1e3, 1),
        })
    run()


# -- config 5: image-embed ETL (ViT feature extract), sharded --------------

def cfg5_image_embed(smoke: bool, log) -> None:
    @_guard(log, "5_image_embed")
    def run():
        import jax

        from reflow_tpu.models import VIT_B_16, VIT_TINY, init_vit
        from reflow_tpu.parallel import make_mesh
        from reflow_tpu.parallel.shard import ShardedTpuExecutor
        from reflow_tpu.scheduler import DirtyScheduler
        from reflow_tpu.workloads import image_embed

        import os as _os

        cfg = VIT_TINY if smoke else VIT_B_16
        # 256-image batches: a 16-image tick leaves the chip mostly idle
        # and even 64 images pay mostly fixed overhead. 256 uint8 images
        # = ~38MB of upload per tick — the record carries
        # upload_mb_per_tick + mfu so whichever ceiling binds is visible
        # in the data
        per_tick = 8 if smoke else env_int("REFLOW_BENCH_IMG_PER_TICK", 256)
        ticks = 2 if smoke else 4
        n_groups = 64
        n_images = 1 << 14
        params = init_vit(0, **cfg)
        params["_cfg"] = cfg

        # REFLOW_BENCH_MODEL_AXIS=m: tensor-parallel the ViT over an
        # m-way model axis (2-D delta x model mesh, VERDICT r4 #8) —
        # params shard 1/m per device; needs >= m local devices. The
        # default is the 1-D data mesh over every local device.
        m_tp = env_int("REFLOW_BENCH_MODEL_AXIS", 0)
        n_dev = len(jax.devices())
        if m_tp >= 2 and n_dev >= m_tp and n_dev % m_tp == 0:
            from reflow_tpu.parallel.mesh import make_model_mesh
            mesh = make_model_mesh(n_dev // m_tp, m_tp)
            ex = ShardedTpuExecutor(mesh, model_axis="model")
            ig = image_embed.build_graph(n_images, n_groups, params,
                                         model_axis="model")
        else:
            mesh = make_mesh()  # all local devices (1 on the real chip)
            ex = ShardedTpuExecutor(mesh)
            ig = image_embed.build_graph(n_images, n_groups, params)
        sched = DirtyScheduler(ig.graph, ex)
        embed_node = next(n for n in ig.graph.nodes if n.name == "embed")
        param_mb_dev = sum(
            s.data.nbytes for leaf in jax.tree.leaves(
                ex.states[embed_node.id]["params"])
            for s in leaf.addressable_shards[:1]) / 1e6
        stream = image_embed.ImageStream(params, seed=5)
        next_id = 0

        def insert(n):
            nonlocal next_id
            ids = np.arange(next_id, next_id + n)
            groups = ids % n_groups
            next_id += n
            return stream.insert(ids, groups)

        # macro-tick window: all K image ticks scan-fuse into ONE device
        # execution (the graph is sink-free and loop-free), amortizing
        # the fixed per-dispatch overhead — the same shape as
        # config 2's micro-batched path. Absorption runs the SAME K as
        # the measured windows (the scan program's shape includes K) plus
        # one single-tick move shape, so nothing compiles mid-measurement
        sched.tick_many([{ig.images: insert(per_tick)} for _ in range(ticks)])
        sched.push(ig.images, stream.move(0, 1))
        sched.tick(sync=False)
        _barrier(sched.executor)   # drain the absorption window

        def run_image_window():
            feeds = [{ig.images: insert(per_tick)} for _ in range(ticks)]
            t0 = time.perf_counter()
            agg = sched.tick_many(feeds)
            dwall = time.perf_counter() - t0
            _barrier(sched.executor)
            wall = time.perf_counter() - t0
            sched.executor.check_errors()
            agg.block()
            return wall, dwall, agg.delta_ops

        wall, dwall, dops, _ = _median_window(
            run_image_window, log, "5_image_embed")

        # DEVICE-BOUND window (VERDICT r4 #3b): the same ingestion flow
        # with pixel batches GENERATED ON CHIP (the cfg4 preload trick),
        # so the record separates the model-compute ceiling from the
        # host-upload ceiling — upload per tick drops from ~38MB to
        # the dispatch bytes of one seed scalar
        import jax.numpy as jnp
        from functools import partial

        from jax.sharding import NamedSharding, PartitionSpec as P

        from reflow_tpu.executors.device_delta import DeviceDelta

        flat = cfg["img"] * cfg["img"] * cfg["chans"]
        row_sh = NamedSharding(
            mesh, P(mesh.axis_names if len(mesh.axis_names) > 1
                    else mesh.axis_names[0]))

        @partial(jax.jit,
                 out_shardings=DeviceDelta(row_sh, row_sh, row_sh))
        def gen_imgs(seed, base):
            kk = jax.random.fold_in(jax.random.PRNGKey(11), seed)
            pix = jax.random.randint(kk, (per_tick, flat), 0, 256,
                                     jnp.int32).astype(jnp.uint8)
            ids = base + jnp.arange(per_tick, dtype=jnp.int32)
            grp = (ids % n_groups).astype(jnp.uint8)
            vals = jnp.concatenate([grp[:, None], pix], axis=1)
            return DeviceDelta(ids % n_images, vals,
                               jnp.ones((per_tick,), jnp.int32))

        dev_seed = 0

        def dev_tick():
            nonlocal dev_seed, next_id
            sched.push(ig.images, gen_imgs(np.int32(dev_seed),
                                           np.int32(next_id % n_images)))
            dev_seed += 1
            next_id += per_tick
            sched.tick(sync=False)

        dev_tick()                      # absorb the device-gen shape
        _barrier(sched.executor)
        t0 = time.perf_counter()
        for _ in range(ticks):
            dev_tick()
        _barrier(sched.executor)
        dev_wall = time.perf_counter() - t0
        sched.executor.check_errors()

        # a group move: retract/insert pair through the model, timed to
        # device completion. Group 2 (absorption already moved image 0
        # to 1): a same-group move would cancel to a no-op tick
        sched.push(ig.images, stream.move(0, 2))
        move_wall, r = _timed_tick(sched)

        # achieved model FLOP/s + MFU: images/s x the model's matmul
        # FLOPs per image (FMA=2 convention) against the device's bf16
        # peak — alongside the per-tick upload volume, so the record
        # itself shows which wall binds
        from reflow_tpu.models.vit import vit_flops

        img_per_s = per_tick * ticks / wall
        flops = vit_flops(**cfg)
        n_mesh = len(mesh.devices.ravel())
        dev0 = mesh.devices.ravel()[0]
        peak = peak_bf16_flops(dev0)

        def mfu_pct(images_per_s):
            # aggregate mesh throughput against the AGGREGATE mesh peak
            if peak is None:
                return None
            return round(100 * images_per_s * flops / (peak * n_mesh), 2)

        upload_mb = per_tick * cfg["img"] * cfg["img"] * cfg["chans"] / 1e6
        _record(log, "5_image_embed", {
            "executor": "sharded",
            "mesh_devices": n_mesh,
            "model_axis": m_tp if m_tp >= 2 else None,
            "param_mb_per_device": round(param_mb_dev, 1),
            "model": "vit_tiny" if smoke else "vit_b_16",
            "images_per_tick": per_tick,
            "delta_ops_per_s": round(dops / wall, 1),
            "images_per_s": round(img_per_s, 2),
            "model_gflop_per_image": round(flops / 1e9, 1),
            "achieved_tflops": round(img_per_s * flops / 1e12, 2),
            "device_kind": dev0.device_kind,
            "mfu_pct_vs_bf16_peak": mfu_pct(img_per_s),
            "upload_mb_per_tick": round(upload_mb, 1),
            "dispatch_ms_total": round(1e3 * dwall, 1),
            "move_tick_ms": round(1e3 * move_wall, 1),
            # upload factored out: on-chip-generated pixels, ~0MB upload
            "images_per_s_device_bound": round(
                per_tick * ticks / dev_wall, 2),
            "mfu_pct_device_bound": mfu_pct(per_tick * ticks / dev_wall),
        })
    run()
