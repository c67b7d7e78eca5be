#!/usr/bin/env python3
"""Benchmark harness: the BASELINE.md configs, headline = config 3.

Headline (ONE JSON line on stdout): incremental PageRank under per-tick
edge churn (BASELINE.md config 3, the north-star workload) on the
TpuExecutor vs the CpuExecutor (the default path / baseline)::

    {"metric": ..., "value": <speedup>, "unit": "x", "vs_baseline": <v/20>}

``value`` is the delta-ops/sec throughput ratio TPU/CPU on churn ticks.

Measurement model. The device is directly attached: dispatch is
asynchronous and ``jax.block_until_ready`` on the state tree is the
barrier (``bench_configs._barrier``; measured against a host readback
on a v5e in CHANGES.md PR 21). Each device config measures PIPELINED
WINDOWS — N streaming ticks dispatched back-to-back with zero
readbacks, then one barrier on the in-order device stream
(``bench_configs._stream_window``). The wall covers dispatch + all
device compute; the dispatch-only wall is reported alongside as
evidence the window was device-bound.

One process per chip. An accelerator belongs to the one process that
initialised it, so this parent never imports JAX: every device-touching
config runs in its own child process, one after another, and the
backend line comes from the first child's result. Children share one
persistent compile cache (``utils.runtime.place_compile_cache``). A
child that fails makes this command exit non-zero.

The CPU baseline measures the same graph shape scaled to
``REFLOW_BENCH_CPU_EDGES_CAP`` edges (default 200k) plus a scaling sweep
over smaller sizes (stderr) showing the per-row rate is flat-to-declining
in graph size, so extrapolating the 200k-edge rate to 1M edges is
conservative for the speedup claim. ``REFLOW_BENCH_CPU_FULL=1`` instead
measures the CPU executor at the full 1M-edge config (cold build alone
costs ~15 minutes of pure-Python fixpoint — 921s measured offline; see
README's benchmark notes).

Env knobs::

    REFLOW_BENCH_SMOKE=1          tiny scale (local sanity check)
    REFLOW_BENCH_NODES/EDGES      graph size        (default 100k / 1M)
    REFLOW_BENCH_CHURN            churn fraction    (default 0.01)
    REFLOW_BENCH_STREAM_TICKS     pipelined window length    (default 16)
    REFLOW_BENCH_CPU_EDGES_CAP    CPU measured at <= this many edges
    REFLOW_BENCH_CPU_FULL=1       CPU at full scale (overrides cap; slow)
    REFLOW_BENCH_ALL=0            skip configs 1/2/4/5 (default: run them)
    REFLOW_BENCH_TRACE=<dir>      xprof device trace of one churn tick
    REFLOW_BENCH_RECOVERY=1       WAL mode instead: ingestion overhead per
                                  fsync policy + time-to-first-tick after a
                                  simulated crash (CPU-only)
    REFLOW_BENCH_RECOVERY_TICKS   crash-backlog size  (default 1000)
    REFLOW_BENCH_RECOVERY_TPU_TICKS  device-path crash backlog
                                  (default backlog/10; the recovery mode
                                  also replays over TpuExecutor to price
                                  recompile-on-replay)
    REFLOW_BENCH_MEGATICK=1       mega-tick mode instead: the PageRank
                                  churn window fused into ONE compiled
                                  dispatch (tick_many -> run_window over
                                  the device-resident ingress queue),
                                  reporting tick_s_amortized vs
                                  window_dispatch_s plus view parity
                                  against an identically-fed per-tick
                                  twin (runs on the selected device)
    REFLOW_BENCH_PIPELINE=1       pipelined-window mode instead: the
                                  PageRank churn waves through an
                                  IngestFrontend at window depth 1 vs 2
                                  on identical batches — amortized tick,
                                  stage_overlap_frac, EXACT depth parity
                                  (max_abs_diff == 0), zero fallbacks
    REFLOW_BENCH_SERVE=1          serve mode instead: IngestFrontend
                                  sustained throughput at 1/4/16 concurrent
                                  producers vs the bare push+tick loop,
                                  coalesce factor, zero forced syncs
                                  (CPU-only)
    REFLOW_BENCH_SERVE_BATCHES    micro-batches per producer (default 250)
    REFLOW_BENCH_TIER=1           tier mode instead: ServeTier hosting 4
                                  graphs x 4 producers on a 2-thread pump
                                  pool vs 4 independent frontends, plus
                                  pump-crash isolation (exactly-once after
                                  recover) and hot/quiet-tenant QoS
                                  isolation (CPU-only)
    REFLOW_BENCH_TIER_BATCHES     micro-batches per producer (default 200)
    REFLOW_BENCH_SHARDSERVE=1     pod-scale serving mode instead: the
                                  same mega-tick tier load three ways —
                                  8 tenants on one device, 8 tenants
                                  spread one-per-device (placement=
                                  "spread", shared window programs via
                                  the plan-signature cache), and ONE
                                  sharded hot tenant spanning the mesh —
                                  with exact view parity vs a CPU oracle
                                  and zero fallbacks (cpu runs force 8
                                  host devices; real meshes use theirs)
    REFLOW_BENCH_SHARDSERVE_BATCHES  batches per producer (default 48)
    REFLOW_BENCH_CONTROL=1        control mode instead: self-healing
                                  ControlPlane under step load — a
                                  hot-tenant surge browned out per-graph
                                  (quiet sibling's admission p99 bounded,
                                  recovery within the configured control
                                  intervals after the surge ends) and a
                                  pump-crash storm tripping the circuit
                                  breaker then healing through half-open
                                  unattended (CPU-only)
    REFLOW_BENCH_OBS=1            obs mode instead: tracing + telemetry
                                  overhead on the 16-producer serve
                                  protocol over a durable scheduler, obs
                                  disabled vs enabled, plus the chrome
                                  trace export and the per-ticket stage
                                  decomposition check (CPU-only)
    REFLOW_BENCH_OBS_BATCHES      micro-batches per producer (default 250)
    REFLOW_BENCH_WALPIPE=1        durability-pipeline mode instead:
                                  device-resident pre-imaged submissions
                                  over fsync="record", inline (frame+
                                  write+fsync on the dispatch path) vs
                                  pipelined committer at 1/16 producers,
                                  asserting zero log readbacks, LSN-
                                  stamped tickets, and inline==pipelined
                                  ==replayed sink views (CPU-only)
    REFLOW_BENCH_WALPIPE_BATCHES  batches per producer at 16p (default 4)
    REFLOW_BENCH_REPLICA=1        read-replica mode instead: WAL shipping
                                  to N ReplicaSchedulers under sustained
                                  16-producer writes; aggregate ReadTier
                                  top-k QPS vs the single-leader
                                  baseline, bounded replay lag, and
                                  exact leader-vs-replica view parity at
                                  the published horizon (CPU-only)
    REFLOW_BENCH_REPLICA_N        follower count            (default 4)
    REFLOW_BENCH_REPLICA_READ_S   per-leg read window (s)   (default 2.0)
    REFLOW_BENCH_SUBS=1           reactive-reads mode instead: one
                                  replica's SubscriptionHub fans
                                  per-window deltas to N simulated
                                  subscribers (plus real wire
                                  subscribers through a mid-run
                                  partition + heal) under sustained
                                  16-producer writes; asserts exact
                                  push-vs-pull parity at equal
                                  horizons, zero gaps / zero duplicate
                                  applies on resume, and write-path
                                  admission p99 within 2x the
                                  no-subscriber baseline (CPU-only)
    REFLOW_BENCH_SUBS_N           simulated subscriber count
                                  (default 100_000, smoke 2000)
    REFLOW_BENCH_SUBS_RUN_S       per-leg write window (s)
                                  (default 2.0, smoke 0.6)
    REFLOW_BENCH_FAILOVER=1       failover mode instead: kill the leader
                                  (committer crash seam) under sustained
                                  16-producer writes; a
                                  FailoverCoordinator detects, fences the
                                  old epoch, elects + promotes a replica
                                  and re-binds ingestion; reports
                                  detection/promotion/first-window walls,
                                  asserts ZERO acked-write loss (final
                                  view == a fold of every acked batch)
                                  and exact old-vs-new view parity at the
                                  promotion horizon (CPU-only)
    REFLOW_BENCH_FAILOVER_N       follower count            (default 2)
    REFLOW_BENCH_FAILOVER_RUN_S   per-phase write window (s) (default 1.0)
    REFLOW_BENCH_COMPACT=1        bounded-history mode instead: two
                                  identically-fed 16-producer legs
                                  (unbounded oracle vs checkpoint chain
                                  + key-level WAL compaction); asserts
                                  history >= 10x live state, >= 5x
                                  faster leader crash-recovery AND
                                  fresh-replica bootstrap vs full-
                                  history replay, both within 2x of a
                                  fresh-full-checkpoint restore, exact
                                  view parity, zero acked-write loss,
                                  bounded on-disk footprint (CPU-only)
    REFLOW_BENCH_COMPACT_TICKS    batches per producer (default 480)
    REFLOW_BENCH_TILES=1          tiled-maintenance mode instead: two
                                  identically-fed bounded legs (chain +
                                  compactor), one monolithic and one
                                  with REFLOW_TILE_BYTES set at state
                                  >= 8x the budget; asserts compactor
                                  and checkpoint writer/reader peaks
                                  under 2x budget, exact recover /
                                  bootstrap parity, per-tile crash-seam
                                  survival, tile-unit bootstrap, top_k
                                  and lookup parity vs an untiled
                                  snapshot oracle, and tiled restore /
                                  bootstrap wall within 1.2x untiled
    REFLOW_BENCH_TILES_TICKS      batches per producer (default 320)
    REFLOW_BENCH_CHAOS=1          chaos-soak mode instead: ship the WAL
                                  to N replicas over REAL TCP links, each
                                  wrapped in a seeded fault injector
                                  (drop/dup/reorder/corrupt/delay, a
                                  scripted one-way partition + reset),
                                  then quiesce and kill the leader;
                                  asserts zero acked-write loss, exact
                                  view parity at equal horizons, lag <=
                                  one commit window after faults stop,
                                  and that the fenced ex-leader's
                                  post-fence shipments are all NACKed
                                  (CPU-only)
    REFLOW_BENCH_CHAOS_N          follower count            (default 3)
    REFLOW_BENCH_CHAOS_RUN_S      write window (s)          (default 1.2)
    REFLOW_BENCH_FLEETOBS=1       fleet-telemetry mode instead: the
                                  replicated TCP topology with a
                                  TelemetryShipper per node streaming
                                  registry snapshots to a live
                                  FleetAggregator; reports the write-
                                  path overhead (off vs on, best-of-2,
                                  <3% on an uncontended host), asserts
                                  aggregator horizons == ground truth
                                  at quiesce, >= 1 post-heal causal
                                  chain ship_segment->net_send->
                                  replica_replay, and that the fleet
                                  view serves stale-marked through a
                                  telemetry-link partition (CPU-only)
    REFLOW_BENCH_FLEETOBS_BATCHES fixed-work batches per producer for
                                  the A/B legs (default 320, smoke 160)
    REFLOW_BENCH_MULTIPROC=1      multi-process mode instead: a leader
                                  + N replica + M producer fleet of
                                  real OS processes (python -m
                                  reflow_tpu.proc) pumping over the
                                  ingestion RPC; a kill -9 storm takes
                                  every replica (respawn + WAL
                                  recovery + horizon-barrier rejoin)
                                  and then the leader (cross-process
                                  promotion; producers reconnect and
                                  resubmit exactly-once); asserts zero
                                  acked-write loss vs a deterministic
                                  oracle, exact parity at equal
                                  horizons on the survivors, an empty
                                  in-doubt set on every producer, and
                                  full fleet-telemetry coverage
                                  (CPU-only)
    REFLOW_BENCH_MULTIPROC_N      replica-process count     (default 3)
    REFLOW_BENCH_MULTIPROC_PRODUCERS  producer-process count
                                  (default 4)
    REFLOW_BENCH_MULTIPROC_RUN_S  per-phase write window (s)
                                  (default 1.5, smoke 0.6)
    REFLOW_TRACE_OUT              obs-mode chrome trace path
                                  (default /tmp/reflow_obs_trace.json;
                                  fleetobs default
                                  /tmp/reflow_fleet_trace.json)

Every mode also accepts ``--json-out PATH``: the final result object is
written there (pretty-printed) in addition to the stdout JSON line.
"""

from __future__ import annotations

import argparse
import json
import os
import subprocess
import sys
import time

import numpy as np

from reflow_tpu.utils.config import (env_flag, env_float, env_int, env_str)


def log(*a) -> None:
    print(*a, file=sys.stderr, flush=True)


def _build_pagerank(n_nodes: int, n_edges: int, churn: float,
                    tol: float, seed: int = 7, defer=None, shards: int = 1):
    from reflow_tpu.executors.device_delta import bucket_capacity
    from reflow_tpu.workloads import pagerank

    # arena sized for LIVE rows plus churn headroom — in-program
    # compaction (executors/arena.py via join_core's lax.cond) reclaims
    # cancelled pairs at high water, so capacity doesn't scale with ticks.
    # A sharded executor bounds every tick against the PER-SHARD slice
    # under worst-case key skew (one shard owning every row), so a mesh
    # of ``shards`` needs that many times the single-device arena.
    churn_cap = bucket_capacity(2 * int(churn * n_edges) + 2)
    arena = shards * (bucket_capacity(n_edges) + 8 * churn_cap)
    pr = pagerank.build_graph(n_nodes, tol=tol, arena_capacity=arena,
                              defer_passes=defer)
    web = pagerank.WebGraph.random(n_nodes, n_edges, seed=seed)
    return pr, web


def _synced_tick(sched):
    from bench_configs import _timed_tick

    return _timed_tick(sched)


def _params():
    smoke = env_flag("REFLOW_BENCH_SMOKE")
    return {
        "smoke": smoke,
        "n_nodes": env_int("REFLOW_BENCH_NODES", 1_000 if smoke else 100_000),
        "n_edges": env_int("REFLOW_BENCH_EDGES", 10_000 if smoke else 1_000_000),
        "churn": env_float("REFLOW_BENCH_CHURN", 0.01),
        "stream_ticks": env_int("REFLOW_BENCH_STREAM_TICKS", 4 if smoke else 16),
        "cpu_cap": env_int("REFLOW_BENCH_CPU_EDGES_CAP", 10_000 if smoke else 200_000),
        "cpu_full": env_flag("REFLOW_BENCH_CPU_FULL"),
        "tol": 1e-4,
        # cross-tick residual deferral (close_loop defer_passes) for the
        # pr_tpu_defer child — the incr_vs_full lever (VERDICT r4 #1);
        # accuracy verified in-record against reference_ranks. Unset
        # defaults to defer=1 (the measured-dominant mode); set to 0,
        # empty, or a non-integer to skip the deferred child.
        "defer": _defer_env(),
    }


def _defer_env():
    # defer=1 dominates defer=2 on this workload: same worst-key
    # mid-stream rel lag (0.352 vs 0.367 measured) and the same drained
    # band (rel ~1.4e-4), at 74.5 vs 92 ms per tick
    raw = env_str("REFLOW_BENCH_DEFER", "1").strip()
    try:
        v = int(raw)
    except ValueError:
        return None
    return v if v > 0 else None


# -- WAL / crash-recovery mode (REFLOW_BENCH_RECOVERY=1) -------------------

def run_recovery_bench() -> dict:
    """Durable-ingestion numbers (docs/guide.md "Write-ahead delta log"):

    1. WAL append overhead: the same wordcount drive with no WAL vs each
       fsync policy (``os`` / ``tick`` / ``record``) — the per-tick
       policy is the default, so its overhead is the headline cost of
       durability.
    2. Recovery: abandon the per-tick run mid-flight with the full
       backlog in the log (the simulated kill, final record torn), then
       time ``recover()`` + the first post-recovery tick on a fresh
       scheduler — time-to-first-tick after a crash at N ticks of
       backlog.

    Host-side end to end (the WAL is host-boundary machinery); runs on
    the CPU executor."""
    import shutil
    import tempfile

    from reflow_tpu.scheduler import DirtyScheduler
    from reflow_tpu.utils.faults import tear_wal_tail
    from reflow_tpu.utils.metrics import summarize_wal
    from reflow_tpu.wal import DurableScheduler, recover
    from reflow_tpu.workloads import wordcount

    backlog = env_int("REFLOW_BENCH_RECOVERY_TICKS", "1000")
    rows_per_tick = 8

    def drive(sched, src):
        rng = np.random.default_rng(11)
        t0 = time.perf_counter()
        for t in range(backlog):
            words = " ".join(f"w{int(x)}"
                             for x in rng.integers(0, 1000, rows_per_tick))
            sched.push(src, wordcount.ingest_lines([words]),
                       batch_id=f"t{t}")
            sched.tick()
        return time.perf_counter() - t0

    out = {"backlog_ticks": backlog, "rows_per_tick": rows_per_tick}
    g, src, _sink = wordcount.build_graph()
    base_s = drive(DirtyScheduler(g), src)
    out["no_wal_s"] = round(base_s, 3)
    tmp = tempfile.mkdtemp(prefix="reflow_wal_bench_")
    try:
        crash_dir = None
        for policy in ("os", "tick", "record"):
            wal_dir = os.path.join(tmp, policy)
            g, src, _sink = wordcount.build_graph()
            sched = DurableScheduler(g, wal_dir=wal_dir, fsync=policy)
            wall = drive(sched, src)
            wm = summarize_wal(sched.wal)
            out[f"wal_{policy}_s"] = round(wall, 3)
            out[f"wal_{policy}_overhead_x"] = round(wall / base_s, 3)
            out[f"wal_{policy}_append_p50_us"] = round(
                wm.append_p50_s * 1e6, 1)
            out[f"wal_{policy}_fsync_p50_us"] = round(
                wm.fsync_p50_s * 1e6, 1)
            log(f"wal[{policy}]: {wall:.3f}s "
                f"({out[f'wal_{policy}_overhead_x']}x of no-WAL "
                f"{base_s:.3f}s; append p50 "
                f"{out[f'wal_{policy}_append_p50_us']}us)")
            if policy == "tick":
                crash_dir = wal_dir  # the default policy's log is the
                # crash corpus; the writer is simply abandoned (killed)
        tear_wal_tail(crash_dir, 7)   # the kill also tore a record
        g, src, _sink = wordcount.build_graph()
        fresh = DirtyScheduler(g)
        t0 = time.perf_counter()
        report = recover(fresh, crash_dir)
        recover_s = time.perf_counter() - t0
        words = " ".join(f"w{i}" for i in range(rows_per_tick))
        fresh.push(src, wordcount.ingest_lines([words]),
                   batch_id="post-crash")
        t1 = time.perf_counter()
        fresh.tick()
        first_tick_s = time.perf_counter() - t1
        out.update({
            "recover_s": round(recover_s, 3),
            "recovered_ticks_per_s": round(report.replayed_ticks
                                           / max(recover_s, 1e-9)),
            "replayed_pushes": report.replayed_pushes,
            "replayed_ticks": report.replayed_ticks,
            "torn_tail_tolerated": report.torn_tail is not None,
            "first_tick_s": round(first_tick_s, 4),
            "time_to_first_tick_s": round(recover_s + first_tick_s, 3),
        })
        log("recovery:", json.dumps(report.as_dict()))
    finally:
        shutil.rmtree(tmp, ignore_errors=True)

    # 3. Device-path recovery: the same crash protocol over the jit
    #    executor (TpuExecutor), where replay re-executes through compiled
    #    programs — the first replayed tick pays the recompile, the rest
    #    stream. Records the post-crash first-tick and the backlog-drain
    #    (replay) wall on the device path, next to the host-oracle numbers
    #    above. Runs on whatever backend JAX_PLATFORMS selects (the mode
    #    defaults to cpu), so by default this measures the jit/recompile
    #    cost.
    from reflow_tpu import FlowGraph
    from reflow_tpu.delta import DeltaBatch, Spec
    from reflow_tpu.executors import get_executor

    tpu_backlog = env_int(
        "REFLOW_BENCH_RECOVERY_TPU_TICKS", max(8, backlog // 10))

    def build_dev():
        g = FlowGraph("recovery_dev")
        src = g.source("s", Spec((), np.float32, key_space=64))
        red = g.reduce(src, "sum", tol=0.0)
        return g, src, red

    def dev_batch(rng):
        return DeltaBatch(
            rng.integers(0, 64, rows_per_tick).astype(np.int64),
            rng.integers(0, 8, rows_per_tick).astype(np.float32),
            np.ones(rows_per_tick, np.int64))

    tmp = tempfile.mkdtemp(prefix="reflow_wal_bench_tpu_")
    try:
        wal_dir = os.path.join(tmp, "tick")
        g, src, _red = build_dev()
        sched = DurableScheduler(g, get_executor("tpu"), wal_dir=wal_dir,
                                 fsync="tick")
        rng = np.random.default_rng(23)
        t0 = time.perf_counter()
        for t in range(tpu_backlog):
            sched.push(src, dev_batch(rng), batch_id=f"d{t}")
            sched.tick(sync=False)
        tpu_ingest_s = time.perf_counter() - t0
        # abandon mid-flight (the simulated kill also tore a record)
        tear_wal_tail(wal_dir, 7)
        g2, src2, _red2 = build_dev()
        fresh = DirtyScheduler(g2, get_executor("tpu"))
        t0 = time.perf_counter()
        report = recover(fresh, wal_dir)
        tpu_recover_s = time.perf_counter() - t0
        fresh.push(src2, dev_batch(np.random.default_rng(99)),
                   batch_id="post-crash")
        t1 = time.perf_counter()
        fresh.tick()
        tpu_first_tick_s = time.perf_counter() - t1
        out.update({
            "tpu_backlog_ticks": tpu_backlog,
            "tpu_ingest_s": round(tpu_ingest_s, 3),
            "tpu_recover_s": round(tpu_recover_s, 3),
            "tpu_replayed_ticks": report.replayed_ticks,
            "tpu_recovered_ticks_per_s": round(
                report.replayed_ticks / max(tpu_recover_s, 1e-9)),
            "tpu_first_tick_s": round(tpu_first_tick_s, 4),
            "tpu_time_to_first_tick_s": round(
                tpu_recover_s + tpu_first_tick_s, 3),
        })
        log(f"recovery[tpu]: replay {report.replayed_ticks} ticks in "
            f"{tpu_recover_s:.3f}s, first tick {tpu_first_tick_s:.4f}s")
    finally:
        shutil.rmtree(tmp, ignore_errors=True)
    return out


# -- compiled mega-tick mode (REFLOW_BENCH_MEGATICK=1) ---------------------

def run_megatick_bench() -> dict:
    """Compiled mega-tick numbers (docs/guide.md "Compiled mega-ticks").

    The PageRank churn-window protocol with the whole K-tick commit
    window fused into ONE jit'd dispatch: ``tick_many`` routes through
    ``TpuExecutor.run_window``, whose scan body consumes slots of the
    device-resident ingress queue. The reported pair is the acceptance
    metric: ``tick_s_amortized`` — full window wall including the
    closing readback barrier, divided by K — vs ``window_dispatch_s`` —
    the host-side cost of dispatching the entire window (queue slot
    writes + one program enqueue). Dispatch-bound means the ratio stays
    small: the host pays per-WINDOW cost, not per-tick cost.

    Parity is asserted in-record: a twin scheduler is driven per-tick
    (push + tick(sync=False)) with the IDENTICAL pre-generated churn
    batches, and both drained rank tables must agree."""
    from bench_configs import _barrier, _median_window, _pad_batch
    from reflow_tpu.executors import get_executor
    from reflow_tpu.scheduler import DirtyScheduler
    from reflow_tpu.workloads import pagerank

    p = _params()
    k = p["stream_ticks"]
    n_windows = 3
    n_churn = 2 * max(1, int(p["churn"] * p["n_edges"]))

    pr, web = _build_pagerank(p["n_nodes"], p["n_edges"], p["churn"],
                              p["tol"])
    # pre-generate EVERYTHING before building the twin: WebGraph.churn
    # mutates its edge set, so the batches are minted once and both
    # drives consume the same list (and the same initial batch). Padding
    # to a fixed row count keeps every window on ONE queue/program
    # signature (weight-0 rows are semantic no-ops).
    init = web.initial_batch()
    churn = [_pad_batch(web.churn(p["churn"]), n_churn)
             for _ in range((1 + n_windows) * k)]   # 1 warm + measured

    sched = DirtyScheduler(pr.graph, get_executor("tpu"))
    sched.push(pr.teleport, pagerank.teleport_batch(p["n_nodes"]))
    sched.push(pr.edges, init)
    sched.tick(sync=False)                       # cold build (compile)
    warm = sched.tick_many([{pr.edges: b} for b in churn[:k]])
    _barrier(sched.executor)        # drain the build + warm window

    win_ix = [0]

    def run_window_once():
        lo = (1 + win_ix[0]) * k
        feeds = [{pr.edges: b} for b in churn[lo:lo + k]]
        win_ix[0] += 1
        t0 = time.perf_counter()
        res = sched.tick_many(feeds)
        dwall = time.perf_counter() - t0    # host released: window queued
        _barrier(sched.executor)
        wall = time.perf_counter() - t0
        res.block()
        assert res.quiesced
        return wall, dwall, res.delta_ops

    wall, dwall, dops, windows = _median_window(
        run_window_once, log, f"megatick churn x{k}", n=n_windows)
    warm.block()
    assert sched.megatick_fallbacks == 0, (
        f"window path fell back {sched.megatick_fallbacks}x — the bench "
        f"must measure the fused path")
    assert sched.megatick_windows == 1 + n_windows, sched.megatick_windows

    # twin drive: identical batches through the per-tick streaming crank;
    # table parity is the assertion here, its wall a reference point.
    pr2, _ = _build_pagerank(p["n_nodes"], p["n_edges"], p["churn"],
                             p["tol"])
    per = DirtyScheduler(pr2.graph, get_executor("tpu"))
    per.push(pr2.teleport, pagerank.teleport_batch(p["n_nodes"]))
    per.push(pr2.edges, init)
    per.tick(sync=False)
    t0 = time.perf_counter()
    results = []
    for b in churn:
        per.push(pr2.edges, b)
        results.append(per.tick(sync=False))
    _barrier(per.executor)
    pertick_wall_s = time.perf_counter() - t0
    for r in results:
        r.block()

    ranks_m = pagerank.ranks_to_array(sched.read_table(pr.new_rank),
                                      p["n_nodes"])
    ranks_p = pagerank.ranks_to_array(per.read_table(pr2.new_rank),
                                      p["n_nodes"])
    max_abs_diff = float(np.abs(ranks_m - ranks_p).max())
    out = {
        "executor": "tpu", "nodes": p["n_nodes"], "edges": p["n_edges"],
        "window_ticks": k,
        "window_wall_s": round(wall, 4),
        "window_dispatch_s": round(dwall, 4),
        "tick_s_amortized": round(wall / k, 5),
        "amortized_over_dispatch_x": round(
            (wall / k) / max(dwall, 1e-9), 3),
        "delta_ops_per_s": round(dops / wall),
        "pertick_wall_s": round(pertick_wall_s, 4),
        "megatick_windows": sched.megatick_windows,
        "megatick_fallbacks": sched.megatick_fallbacks,
        "window_dispatches": getattr(sched.executor,
                                     "window_dispatches", 0),
        "views_match": bool(max_abs_diff <= 1e-6),
        "max_abs_diff": max_abs_diff,
        "windows": [{"wall_s": round(w, 4), "dispatch_s": round(d, 4),
                     "delta_ops": o} for w, d, o in windows],
    }
    log("megatick:", json.dumps(out))
    return out


# -- pipelined-window mode (REFLOW_BENCH_PIPELINE=1) -----------------------

def run_pipeline_bench() -> dict:
    """Pipelined window execution numbers (docs/guide.md "Pipelined
    windows"): the PageRank churn workload driven through a standalone
    ``IngestFrontend`` at window depth 1 (stage and execute strictly
    alternating — the serial pump) vs depth 2 (stage(N+1) overlaps the
    in-flight dispatch of window N), on IDENTICAL pre-generated
    batches. The pause → submit wave → resume → flush protocol forces
    each wave to drain as one multi-chunk backlog, so consecutive
    window chunks actually pipeline.

    Per depth: amortized tick wall (flush + device sync over total
    ticks) and ``stage_overlap_frac``. Across depths: EXACT table
    parity (``max_abs_diff`` must be 0.0 — same fused program, same
    slot contents, same dispatch order), zero mega-tick fallbacks, and
    the not-slower check (depth 2 within 5% of depth 1; on real
    accelerators the overlap is the win, on CPU it must at least not
    regress). A per-tick twin on the same executor bounds both drives
    the way the mega-tick bench does."""
    from bench_configs import _barrier, _pad_batch
    from reflow_tpu.executors import get_executor
    from reflow_tpu.scheduler import DirtyScheduler
    from reflow_tpu.serve import CoalesceWindow, IngestFrontend
    from reflow_tpu.workloads import pagerank

    p = _params()
    k = p["stream_ticks"]
    n_windows = 3     # chunks per measured wave (>= 2 so chunks overlap)
    n_waves = 2       # measured waves per depth; best wall wins (noise)
    n_churn = 2 * max(1, int(p["churn"] * p["n_edges"]))

    _, web = _build_pagerank(p["n_nodes"], p["n_edges"], p["churn"],
                             p["tol"])
    # mint every batch once (WebGraph.churn mutates its edge set): both
    # depths and the per-tick twin consume the same list; fixed-row
    # padding keeps every window on one queue/program signature
    init = web.initial_batch()
    churn = [_pad_batch(web.churn(p["churn"]), n_churn)
             for _ in range((1 + n_waves * n_windows) * k)]
    warm, measured = churn[:k], churn[k:]

    out = {"executor": "tpu", "nodes": p["n_nodes"],
           "edges": p["n_edges"], "window_ticks": k,
           "windows_per_wave": n_windows, "waves": n_waves}
    tables = {}
    for d in (1, 2):
        pr, _ = _build_pagerank(p["n_nodes"], p["n_edges"], p["churn"],
                                p["tol"])
        sched = DirtyScheduler(pr.graph, get_executor("tpu"))
        sched.push(pr.teleport, pagerank.teleport_batch(p["n_nodes"]))
        sched.push(pr.edges, init)
        sched.tick(sync=False)                   # cold build (compile)
        fe = IngestFrontend(
            sched, max_bytes=1 << 30, depth=d,
            window=CoalesceWindow(max_rows=n_churn, max_ticks=k,
                                  max_latency_s=0.005))

        def wave(batches, fe=fe, src=pr.edges, sched=sched):
            fe.pause()
            tks = [fe.submit(src, b) for b in batches]
            t0 = time.perf_counter()
            fe.resume()
            fe.flush(timeout=600)
            _barrier(sched.executor)
            wall = time.perf_counter() - t0
            assert all(t.result(timeout=60).applied for t in tks)
            return wall

        wave(warm)      # returns after its own barrier: nothing to drain
        walls = []
        for w in range(n_waves):
            lo = w * n_windows * k
            walls.append(wave(measured[lo:lo + n_windows * k]))
        wall = min(walls)
        ticks = n_windows * k
        out[f"depth{d}_tick_s_amortized"] = round(wall / ticks, 5)
        out[f"depth{d}_wave_walls_s"] = [round(w, 4) for w in walls]
        out[f"depth{d}_windows_staged"] = fe.windows_staged
        out[f"depth{d}_windows_pipelined"] = fe.windows_pipelined
        out[f"depth{d}_stage_overlap_frac"] = round(
            fe.stage_overlap_frac, 4)
        out[f"depth{d}_megatick_windows"] = sched.megatick_windows
        out[f"depth{d}_megatick_fallbacks"] = sched.megatick_fallbacks
        log(f"pipeline[depth {d}]: {wall:.3f}s best wave "
            f"({out[f'depth{d}_tick_s_amortized']}s/tick; "
            f"staged {fe.windows_staged}, pipelined "
            f"{fe.windows_pipelined}, overlap "
            f"{out[f'depth{d}_stage_overlap_frac']:.0%}, fallbacks "
            f"{sched.megatick_fallbacks})")
        fe.close()
        tables[d] = pagerank.ranks_to_array(
            sched.read_table(pr.new_rank), p["n_nodes"])

    # per-tick twin on the same executor: the proven-parity reference
    pr2, _ = _build_pagerank(p["n_nodes"], p["n_edges"], p["churn"],
                             p["tol"])
    per = DirtyScheduler(pr2.graph, get_executor("tpu"))
    per.push(pr2.teleport, pagerank.teleport_batch(p["n_nodes"]))
    per.push(pr2.edges, init)
    per.tick(sync=False)
    results = []
    for b in churn:
        per.push(pr2.edges, b)
        results.append(per.tick(sync=False))
    _barrier(per.executor)
    for r in results:
        r.block()
    ranks_t = pagerank.ranks_to_array(per.read_table(pr2.new_rank),
                                      p["n_nodes"])

    max_abs_diff = float(np.abs(tables[2] - tables[1]).max())
    twin_diff = float(np.abs(tables[1] - ranks_t).max())
    out.update({
        # the acceptance set: depth parity is EXACT, the twin is the
        # usual float-tolerance check, the pipeline never fell back,
        # depth 2 genuinely overlapped, and it paid no throughput tax
        "max_abs_diff": max_abs_diff,
        "views_match": bool(max_abs_diff == 0.0),
        "twin_max_abs_diff": twin_diff,
        "twin_views_match": bool(twin_diff <= 1e-6),
        "zero_fallbacks": bool(
            out["depth1_megatick_fallbacks"] == 0
            and out["depth2_megatick_fallbacks"] == 0),
        "overlap_at_depth2": bool(
            out["depth2_stage_overlap_frac"] > 0.0),
        "depth2_not_slower": bool(
            out["depth2_tick_s_amortized"]
            <= 1.05 * out["depth1_tick_s_amortized"]),
        "depth2_vs_depth1_x": round(
            out["depth1_tick_s_amortized"]
            / max(out["depth2_tick_s_amortized"], 1e-9), 3),
    })
    log("pipeline:", json.dumps(out))
    return out


# -- serve / ingestion-frontend mode (REFLOW_BENCH_SERVE=1) ----------------

def run_serve_bench() -> dict:
    """Ingestion-frontend numbers (docs/guide.md "Serving ingestion"):
    sustained micro-batch throughput through ``IngestFrontend`` at
    1 / 4 / 16 concurrent producers vs the bare single-threaded
    ``push()+tick()`` loop on the same workload, plus the coalescing
    factor (micro-batches folded per scheduler tick) and the
    zero-forced-syncs check (the pump only ever calls ``tick_many``).

    Host-side end to end (admission/coalescing are host-boundary
    machinery); runs on the CPU executor.
    """
    import threading

    from reflow_tpu.scheduler import DirtyScheduler
    from reflow_tpu.serve import CoalesceWindow, IngestFrontend
    from reflow_tpu.utils.metrics import summarize, summarize_serve
    from reflow_tpu.workloads import wordcount

    smoke = env_flag("REFLOW_BENCH_SMOKE")
    per_producer = env_int("REFLOW_BENCH_SERVE_BATCHES", "40" if smoke else "250")
    rows_per_batch = 8

    def make_lines(producer: int, j: int) -> list:
        rng = np.random.default_rng(producer * 100_003 + j)
        return [" ".join(f"w{int(x)}"
                         for x in rng.integers(0, 1000, rows_per_batch))]

    out = {"per_producer_batches": per_producer,
           "rows_per_batch": rows_per_batch}

    # bare-loop baseline: one thread, one tick per micro-batch
    g, src, _sink = wordcount.build_graph()
    sched = DirtyScheduler(g)
    t0 = time.perf_counter()
    for j in range(per_producer):
        sched.push(src, wordcount.ingest_lines(make_lines(0, j)))
        sched.tick()
    bare_s = time.perf_counter() - t0
    bare_rate = per_producer * rows_per_batch / bare_s
    out["bare_loop_rows_per_s"] = round(bare_rate)
    log(f"bare loop: {per_producer} batches in {bare_s:.3f}s "
        f"({bare_rate:.0f} rows/s)")

    for n_prod in (1, 4, 16):
        g, src, _sink = wordcount.build_graph()
        sched = DirtyScheduler(g)
        fe = IngestFrontend(sched, window=CoalesceWindow(
            max_rows=4096, max_ticks=8, max_latency_s=0.005))
        tickets = []
        tk_lock = threading.Lock()

        def produce(pid, fe=fe, src=src):
            mine = [fe.submit(src, wordcount.ingest_lines(
                make_lines(pid, j))) for j in range(per_producer)]
            with tk_lock:
                tickets.extend(mine)

        threads = [threading.Thread(target=produce, args=(pid,))
                   for pid in range(n_prod)]
        t0 = time.perf_counter()
        for t in threads:
            t.start()
        for t in threads:
            t.join()
        fe.flush()
        wall = time.perf_counter() - t0
        assert all(t.result(timeout=10).applied for t in tickets)
        sm = summarize_serve(fe)
        ms = summarize(sched.history)
        fe.close()
        n_batches = n_prod * per_producer
        rate = n_batches * rows_per_batch / wall
        out[f"serve_{n_prod}p_rows_per_s"] = round(rate)
        out[f"serve_{n_prod}p_vs_bare_x"] = round(rate / bare_rate, 3)
        out[f"serve_{n_prod}p_coalesce_factor"] = round(
            sm.coalesce_factor, 2)
        out[f"serve_{n_prod}p_ticks"] = sm.ticks
        out[f"serve_{n_prod}p_admission_p95_us"] = round(
            sm.admission_p95_s * 1e6, 1)
        out[f"serve_{n_prod}p_forced_syncs"] = ms.forced_syncs
        log(f"serve[{n_prod}p]: {n_batches} batches in {wall:.3f}s "
            f"({rate:.0f} rows/s, {out[f'serve_{n_prod}p_vs_bare_x']}x "
            f"bare; coalesce {sm.coalesce_factor:.2f} over {sm.ticks} "
            f"ticks; forced_syncs={ms.forced_syncs})")
    # the acceptance pair: heavy concurrency must actually coalesce, and
    # the pump must never have forced a mid-stream sync
    out["coalesce_gt_1_at_16p"] = out["serve_16p_coalesce_factor"] > 1.0
    out["zero_forced_syncs"] = all(
        out[f"serve_{n}p_forced_syncs"] == 0 for n in (1, 4, 16))
    from reflow_tpu import obs
    if obs.enabled():
        # REFLOW_TRACE=1 at bench time: export what the run recorded
        out["trace_file"] = obs.export_chrome_trace()
        log(f"serve: chrome trace -> {out['trace_file']}")
    return out


# -- obs / tracing-overhead mode (REFLOW_BENCH_OBS=1) ----------------------

def run_obs_bench() -> dict:
    """Observability-overhead numbers (docs/guide.md "Observability"):
    the 16-producer serve protocol from ``run_serve_bench`` driven over
    a ``DurableScheduler`` (``fsync="record"``, so the per-ticket fsync
    stage is real work), run twice — obs fully disabled, then with
    tracing enabled plus a live ``MetricsRegistry`` and a fast-interval
    ``SnapshotEmitter``. Reports the throughput overhead fraction
    (acceptance: <3% enabled, <1% merely importable), exports the
    chrome trace, and checks the per-ticket stage decomposition: each
    sampled ticket's six stage durations must sum to within 10% of its
    measured end-to-end latency.

    Host-side CPU work.
    """
    import shutil
    import tempfile
    import threading

    from reflow_tpu import obs
    from reflow_tpu.serve import CoalesceWindow, IngestFrontend
    from reflow_tpu.wal import DurableScheduler
    from reflow_tpu.workloads import wordcount

    smoke = env_flag("REFLOW_BENCH_SMOKE")
    per_producer = env_int("REFLOW_BENCH_OBS_BATCHES", "40" if smoke else "250")
    rows_per_batch = 8
    n_prod = 16

    def make_lines(producer: int, j: int) -> list:
        rng = np.random.default_rng(producer * 100_003 + j)
        return [" ".join(f"w{int(x)}"
                         for x in rng.integers(0, 1000, rows_per_batch))]

    def run_once(wal_dir: str, registry=None) -> float:
        g, src, _sink = wordcount.build_graph()
        sched = DurableScheduler(g, wal_dir=wal_dir, fsync="record")
        fe = IngestFrontend(sched, window=CoalesceWindow(
            max_rows=4096, max_ticks=8, max_latency_s=0.005))
        if registry is not None:
            fe.publish_metrics(registry)
            sched.publish_metrics(registry)
            sched.wal.publish_metrics(registry)
        tickets = []
        tk_lock = threading.Lock()

        def produce(pid, fe=fe, src=src):
            mine = [fe.submit(src, wordcount.ingest_lines(
                make_lines(pid, j))) for j in range(per_producer)]
            with tk_lock:
                tickets.extend(mine)

        threads = [threading.Thread(target=produce, args=(pid,))
                   for pid in range(n_prod)]
        t0 = time.perf_counter()
        for t in threads:
            t.start()
        for t in threads:
            t.join()
        fe.flush()
        wall = time.perf_counter() - t0
        assert all(t.result(timeout=30).applied for t in tickets)
        fe.close()
        sched.wal.close()
        return n_prod * per_producer * rows_per_batch / wall

    out = {"per_producer_batches": per_producer,
           "rows_per_batch": rows_per_batch, "producers": n_prod}
    tmp = tempfile.mkdtemp(prefix="reflow-obs-bench-")
    try:
        obs.disable()
        obs.trace.reset()
        rate_off = run_once(os.path.join(tmp, "wal-off"))
        out["disabled_rows_per_s"] = round(rate_off)
        log(f"obs[off]: {rate_off:.0f} rows/s")

        obs.trace.reset()
        obs.enable()
        reg = obs.MetricsRegistry()
        snap_path = os.path.join(tmp, "snapshots.jsonl")
        emitter = obs.SnapshotEmitter(snap_path, interval_s=0.2,
                                      registry=reg)
        emitter.start()
        try:
            rate_on = run_once(os.path.join(tmp, "wal-on"), registry=reg)
        finally:
            emitter.stop()
            obs.disable()
        out["enabled_rows_per_s"] = round(rate_on)
        overhead = 1.0 - rate_on / rate_off
        out["obs_overhead_frac"] = round(overhead, 4)
        out["obs_overhead_lt_3pct"] = overhead < 0.03
        log(f"obs[on]: {rate_on:.0f} rows/s "
            f"(overhead {100 * overhead:.2f}%)")

        with open(snap_path) as f:
            snaps = [json.loads(ln) for ln in f if ln.strip()]
        out["snapshot_lines"] = len(snaps)
        out["snapshot_schema_ok"] = bool(snaps) and all(
            s.get("schema") == obs.SNAPSHOT_SCHEMA for s in snaps)

        # export + decomposition check on the enabled run's rings
        events = obs.chrome_events()
        trace_path = env_str("REFLOW_TRACE_OUT", "/tmp/reflow_obs_trace.json")
        obs.export_chrome_trace(trace_path)
        out["trace_file"] = trace_path
        out["trace_events"] = sum(1 for e in events if e.get("ph") == "X")
        timelines = obs.ticket_timelines(events)
        out["sampled_tickets"] = len(timelines)
        max_dev = 0.0
        for t in timelines.values():
            if t["e2e_us"] > 0:
                max_dev = max(max_dev, abs(t["sum_us"] - t["e2e_us"])
                              / t["e2e_us"])
        out["decomposition_max_dev_frac"] = round(max_dev, 4)
        out["decomposition_ok"] = bool(timelines) and max_dev <= 0.10
        log(f"obs: {out['trace_events']} spans, "
            f"{len(timelines)} sampled tickets, stage-sum deviation max "
            f"{100 * max_dev:.2f}% -> {trace_path}")
        obs.trace.reset()
    finally:
        shutil.rmtree(tmp, ignore_errors=True)
    return out


# -- walpipe / asynchronous-durability mode (REFLOW_BENCH_WALPIPE=1) -------

def run_walpipe_bench() -> dict:
    """Durability-pipeline numbers (docs/guide.md "Durability pipeline"):
    the serve protocol over a ``DurableScheduler`` with
    ``fsync="record"`` — every window's WAL barrier must reach the disk
    before its tickets resolve — comparing ``committer="inline"`` (the
    pre-pipeline behavior: frame+write+fsync all on the pump, on the
    dispatch path) against ``committer="thread"`` (the pump only
    pickles and enqueues; a dedicated committer frames, writes and
    fsyncs while the pump merges and dispatches the next window,
    tickets resolving at the durable watermark via ``when_durable``).

    The workload is the streaming ingest path end to end: 16 producers
    submit **device-resident** 8192-row batches of ``(64,)``-vector
    values with ingest-time pre-images (``submit(..., preimage=host)``)
    into a sum-reduce graph on a real device executor; every batch
    fills one coalescing window, so each window is one ~2 MB WAL group
    commit + one fsync. Payloads are pre-generated and pre-uploaded —
    the timed region contains only submit/merge/dispatch/durability.

    Property checks ride along:

    - **zero-readback logging** — ``DurableScheduler.log_readbacks``
      stays 0 on every leg (no forced materialize on the logging path);
    - **committed evidence** — every pipelined ticket resolves with its
      covering LSN;
    - **view equality** — inline and pipelined legs reach the same sink
      view (pipelining changed the *when* of durability, not the math);
    - **replay equality** — the pipelined 16-producer log replays
      through ``recover()`` into a fresh host scheduler that reaches
      the same sink view (durability was never traded for throughput).

    Host-side CPU work; runs on the CPU executor/platform."""
    import shutil
    import tempfile
    import threading

    from reflow_tpu import FlowGraph
    from reflow_tpu.delta import DeltaBatch, Spec
    from reflow_tpu.executors import get_executor
    from reflow_tpu.executors.device_delta import to_device
    from reflow_tpu.scheduler import DirtyScheduler
    from reflow_tpu.serve import CoalesceWindow, IngestFrontend
    from reflow_tpu.wal import DurableScheduler, recover

    smoke = env_flag("REFLOW_BENCH_SMOKE")
    key_space, feat = 64, 64
    rows = 8192  # one batch == one window == one ~2 MB group commit

    def build():
        spec = Spec((feat,), np.float32, key_space=key_space)
        g = FlowGraph()
        src = g.source("in", spec)
        total = g.reduce(g.map(src, lambda v: v * 2.0, vectorized=True),
                         "sum", name="sum")
        sink = g.sink(total, "out")
        return g, src, sink, spec

    def pregen(spec, n_prod, per_prod):
        # pre-generated + pre-uploaded: data creation never pollutes the
        # timed region, and both committer legs replay identical bytes
        payloads = {}
        for pid in range(n_prod):
            rng = np.random.default_rng(1000 + pid)
            payloads[pid] = []
            for j in range(per_prod):
                host = DeltaBatch(
                    rng.integers(0, key_space, rows).astype(np.int64),
                    rng.random((rows, feat)).astype(np.float32),
                    np.ones(rows, np.int64))
                payloads[pid].append(
                    (f"p{pid}-{j}", host, to_device(host, spec)))
        return payloads

    def views_equal(a, b):
        # sink views are row multisets keyed by (key, value-tuple);
        # device and host float32 sums differ in the last ulp, so
        # compare per-key aggregates with tolerance instead of exact
        # row identity
        def as_map(view):
            m = {}
            for (k, v), w in view.items():
                if w:
                    m[int(k)] = np.asarray(v)
            return m

        ma, mb = as_map(a), as_map(b)
        return (set(ma) == set(mb)
                and all(np.allclose(ma[k], mb[k], rtol=1e-3, atol=1e-4)
                        for k in ma))

    def run_once(wal_dir, committer, payloads, n_prod, per_prod, spec):
        g, src, sink, _ = build()
        sched = DurableScheduler(g, get_executor("tpu"), wal_dir=wal_dir,
                                 fsync="record", committer=committer)
        fe = IngestFrontend(sched, window=CoalesceWindow(
            max_rows=rows, max_ticks=1, max_latency_s=0.001))
        # warmup window outside the timed region compiles the jit path;
        # os.sync() flushes unrelated dirty pages so the timed fsyncs
        # pay only for their own bytes
        warm = DeltaBatch(np.zeros(4, np.int64),
                          np.zeros((4, feat), np.float32),
                          np.ones(4, np.int64))
        fe.submit(src, to_device(warm, spec), batch_id="warm",
                  preimage=warm).result(timeout=60)
        os.sync()
        tickets, tk_lock = [], threading.Lock()

        def produce(pid):
            mine = [fe.submit(src, dev, batch_id=bid, preimage=host)
                    for bid, host, dev in payloads[pid]]
            with tk_lock:
                tickets.extend(mine)

        threads = [threading.Thread(target=produce, args=(pid,))
                   for pid in range(n_prod)]
        t0 = time.perf_counter()
        for t in threads:
            t.start()
        for t in threads:
            t.join()
        fe.flush()
        results = [t.result(timeout=120) for t in tickets]
        wall = time.perf_counter() - t0
        assert all(r.applied for r in results)
        rate = n_prod * per_prod * rows / wall
        view = dict(sched.view(sink))
        fsyncs = sched.wal.fsyncs
        readbacks = sched.log_readbacks
        fe.close()
        return rate, view, fsyncs, readbacks, results

    # (n_producers, batches_per_producer, paired trials): the 16p point
    # is the acceptance number, so it gets best-of-N paired trials to
    # shave ext4 writeback noise; smoke keeps the same window shape
    # (the speedup comes from the shape) but trims the run
    per16 = env_int("REFLOW_BENCH_WALPIPE_BATCHES", "2" if smoke else "4")
    legs = [(16, per16, 1 if smoke else 2)]
    if not smoke:
        legs.insert(0, (4, 8, 1))
        legs.insert(0, (1, 16, 1))

    out = {"rows_per_batch": rows, "value_shape": [feat],
           "key_space": key_space, "fsync": "record"}
    tmp = tempfile.mkdtemp(prefix="reflow-walpipe-")
    all_zero_readbacks = True
    try:
        pipelined_dir_16p = None
        view_16p = None
        for n_prod, per_prod, trials in legs:
            spec = build()[3]
            payloads = pregen(spec, n_prod, per_prod)
            best = None
            for trial in range(trials):
                rates, views = {}, {}
                for committer in ("inline", "thread"):
                    wal_dir = os.path.join(
                        tmp, f"{committer}-{n_prod}p-{trial}")
                    rate, view, fsyncs, readbacks, results = run_once(
                        wal_dir, committer, payloads, n_prod, per_prod,
                        spec)
                    rates[committer] = rate
                    views[committer] = view
                    all_zero_readbacks &= readbacks == 0
                    assert readbacks == 0  # pre-imaged: no materialize
                    if committer == "thread":
                        # pipelined resolution still carries the commit
                        # evidence: every APPLIED ticket names its LSN
                        assert all(r.lsn for r in results)
                    if committer == "thread" and n_prod == 16:
                        if pipelined_dir_16p is not None:
                            shutil.rmtree(pipelined_dir_16p,
                                          ignore_errors=True)
                        pipelined_dir_16p = wal_dir
                        view_16p = view
                    else:
                        # drop the leg's WAL right away: ~136 MB of
                        # stale log per leg left on the bench disk
                        # perturbs the next leg's fsync latencies
                        shutil.rmtree(wal_dir, ignore_errors=True)
                    tag = ("pipelined" if committer == "thread"
                           else "inline")
                    out[f"walpipe_{n_prod}p_{tag}_rows_per_s"] = round(
                        rate)
                    out[f"walpipe_{n_prod}p_{tag}_fsyncs"] = fsyncs
                    log(f"walpipe[{n_prod}p/{tag}#{trial}]: "
                        f"{rate:.0f} rows/s ({fsyncs} fsyncs)")
                assert views_equal(views["inline"], views["thread"])
                sp = rates["thread"] / rates["inline"]
                if best is None or sp > best:
                    best = sp
            out[f"walpipe_speedup_{n_prod}p"] = round(best, 3)
        out["pipelined_ge_inline"] = out["walpipe_speedup_16p"] >= 1.0
        out["zero_materialize_readbacks"] = all_zero_readbacks

        # replay equality: the pipelined 16p log (host pre-images of
        # every device batch) drives a fresh host scheduler to the same
        # sink view
        g, _src, sink, _spec = build()
        fresh = DirtyScheduler(g)
        report = recover(fresh, pipelined_dir_16p)
        out["replayed_pushes"] = report.replayed_pushes
        out["replay_view_matches"] = views_equal(
            dict(fresh.view(sink)), view_16p)
        log(f"walpipe[replay]: {report.replayed_pushes} pushes, "
            f"matches={out['replay_view_matches']}")
    finally:
        shutil.rmtree(tmp, ignore_errors=True)
    return out


# -- WAL shipping / read-replica mode (REFLOW_BENCH_REPLICA=1) -------------

def run_replica_bench() -> dict:
    """Read-replica scaling (docs/guide.md "Read replicas"): a
    wordcount leader (``DurableScheduler`` + ``IngestFrontend``) under
    sustained 16-producer writes, with a ``SegmentShipper`` streaming
    its synced WAL prefix to N ``ReplicaScheduler`` followers and a
    ``ReadTier`` fanning top-k reads across them.

    Two read legs run back to back under the SAME write load:

    - **leader baseline**: 4 reader threads on the
      ``LeaderReadAdapter`` — every read copies the live, mutable sink
      view under one lock (the leader's views have no other consistent
      read point), then ranks in Python;
    - **replica aggregate**: the same 4 reader threads through the
      ``ReadTier`` — each replica serves immutable per-horizon snapshot
      arrays, so the hot path is a lock-free ``np.argpartition``.

    Property checks ride along:

    - **exact parity** — after quiesce (flush + sync + catch-up) every
      replica's view at the published horizon equals the leader's with
      ``max_abs_diff == 0`` (replicas replay the same WAL bytes through
      the same idempotent machinery; there is nothing to be off by);
    - **bounded lag** — final replica lag is 0 ticks and never exceeded
      one commit window (``window_ticks``) at any sampled steady-state
      point except transient shipping bursts (max sampled lag is
      reported);
    - **read-your-writes** — a writer that observed its tick can read
      it back through the tier at ``min_horizon=`` without error.

    Host-side CPU work; runs on the CPU executor/platform."""
    import shutil
    import tempfile
    import threading

    from reflow_tpu.obs import REGISTRY
    from reflow_tpu.serve import (CoalesceWindow, IngestFrontend,
                                  LeaderReadAdapter, ReadTier,
                                  ReplicaScheduler)
    from reflow_tpu.wal import DurableScheduler, SegmentShipper
    from reflow_tpu.workloads import wordcount

    smoke = env_flag("REFLOW_BENCH_SMOKE")
    n_replicas = env_int("REFLOW_BENCH_REPLICA_N", "4")
    n_producers = 16
    n_readers = 4
    window_ticks = 4
    vocab = 2_000 if smoke else 20_000
    read_s = env_float("REFLOW_BENCH_REPLICA_READ_S", "0.6" if smoke else "2.0")
    topk = 10

    tmp = tempfile.mkdtemp(prefix="reflow-replica-")
    out = {"replicas": n_replicas, "producers": n_producers,
           "readers": n_readers, "window_ticks": window_ticks,
           "read_s": read_s, "vocab": vocab}
    fe = ship = None
    replicas = []
    try:
        g, src, sink = wordcount.build_graph()
        sched = DurableScheduler(g, wal_dir=os.path.join(tmp, "wal"),
                                 fsync="tick", committer="thread",
                                 segment_bytes=1 << 20)
        fe = IngestFrontend(sched, window=CoalesceWindow(
            max_rows=65536, max_ticks=window_ticks, max_latency_s=0.002))
        ship = SegmentShipper(sched.wal, leader_tick=lambda: sched._tick,
                              poll_s=0.001)
        for i in range(n_replicas):
            gr, _s, _k = wordcount.build_graph()
            r = ReplicaScheduler(gr, os.path.join(tmp, f"r{i}"),
                                 name=f"r{i}")
            ship.attach(r)
            r.publish_metrics()
            replicas.append(r)
        leader = LeaderReadAdapter(sched)
        tier = ReadTier(replicas, leader=leader)
        ship.publish_metrics()
        tier.publish_metrics()
        ship.start()

        # -- sustained 16-producer writes for the whole measured region
        stop = threading.Event()
        submitted = [0] * n_producers

        def produce(pid):
            rng = np.random.default_rng(1000 + pid)
            seq = 0
            while not stop.is_set():
                words = " ".join(
                    f"w{int(x)}" for x in rng.integers(0, vocab, 24))
                try:
                    fe.submit(src, wordcount.ingest_lines([words]),
                              batch_id=f"p{pid}-{seq}")
                except Exception:
                    break
                seq += 1
            submitted[pid] = seq

        producers = [threading.Thread(target=produce, args=(pid,))
                     for pid in range(n_producers)]
        for t in producers:
            t.start()

        lag_samples: list = []
        lag_stop = threading.Event()

        def sample_lag():
            while not lag_stop.is_set():
                lag_samples.append(max(r.lag_ticks() for r in replicas))
                lag_stop.wait(0.02)

        lag_thread = threading.Thread(target=sample_lag)
        lag_thread.start()
        time.sleep(0.5)  # build up a real view before measuring reads

        def read_qps(fn) -> float:
            counts = [0] * n_readers

            def reader(i):
                end = time.perf_counter() + read_s
                c = 0
                while time.perf_counter() < end:
                    fn()
                    c += 1
                counts[i] = c

            threads = [threading.Thread(target=reader, args=(i,))
                       for i in range(n_readers)]
            for t in threads:
                t.start()
            for t in threads:
                t.join()
            return sum(counts) / read_s

        # warm both read paths before measuring (first replica reads
        # pay one-off snapshot builds; first leader read pays the view
        # copy's allocator warmup) so short smoke legs compare steady
        # states, not cold starts
        for _ in range(8):
            leader.top_k(sink.name, topk, by="value")
            tier.top_k(sink.name, topk, by="value")

        leader_qps = read_qps(
            lambda: leader.top_k(sink.name, topk, by="value"))
        log(f"replica[leader-baseline]: {leader_qps:.0f} reads/s "
            f"under {n_producers}p writes")
        replica_qps = read_qps(
            lambda: tier.top_k(sink.name, topk, by="value"))
        log(f"replica[{n_replicas}-replica tier]: {replica_qps:.0f} "
            f"reads/s under {n_producers}p writes")

        # read-your-writes: a writer that saw its window land can pin
        # the tier to at least that horizon
        fe.submit(src, wordcount.ingest_lines(["ryw probe words"]),
                  batch_id="ryw-1").result(timeout=60)
        h = sched._tick
        res = tier.top_k(sink.name, topk, min_horizon=h, by="value")
        out["ryw_min_horizon"] = h
        out["ryw_horizon"] = res.horizon
        out["ryw_source"] = res.source
        assert res.horizon >= h

        # -- quiesce: stop writers, land everything, let replicas catch up
        stop.set()
        for t in producers:
            t.join()
        fe.flush()
        sched.wal.sync()
        deadline = time.monotonic() + 60
        while (any(r.published_horizon() != sched._tick
                   for r in replicas)
               and time.monotonic() < deadline):
            time.sleep(0.005)
        lag_stop.set()
        lag_thread.join()
        ship.stop()
        ship.pump_once()  # final deterministic pass (thread is down)

        final_lag = max(r.lag_ticks() for r in replicas)
        out["final_lag_ticks"] = final_lag
        out["max_sampled_lag_ticks"] = max(lag_samples, default=0)
        out["lag_bound_ok"] = final_lag <= window_ticks
        assert all(r.published_horizon() == sched._tick
                   for r in replicas), \
            (sched._tick, [r.published_horizon() for r in replicas])

        # -- exact parity at the shared horizon
        leader_view = {kv: w for kv, w in sched.view(sink.name).items()
                       if w != 0}
        max_abs_diff = 0
        for r in replicas:
            rh, rv = r.view_at(sink.name)
            assert rh == sched._tick, (r.name, rh, sched._tick)
            for kv in set(leader_view) | set(rv):
                max_abs_diff = max(
                    max_abs_diff,
                    abs(leader_view.get(kv, 0) - rv.get(kv, 0)))
        out["parity_max_abs_diff"] = max_abs_diff
        assert max_abs_diff == 0

        out["total_batches"] = sum(submitted)
        out["leader_ticks"] = sched._tick
        out["leader_read_qps"] = round(leader_qps, 1)
        out["replica_read_qps"] = round(replica_qps, 1)
        out["read_scaling_x"] = round(replica_qps / leader_qps, 3) \
            if leader_qps else 0.0
        out["ship_bytes_total"] = ship.bytes_total
        out["ship_nacks"] = ship.nacks
        out["ship_backlog_segments"] = ship.backlog_segments()
        out["lag_gauge"] = REGISTRY.value("replica.lag_ticks", -1)
        log(f"replica[scaling]: {out['read_scaling_x']}x "
            f"({n_replicas} replicas vs leader), parity diff "
            f"{max_abs_diff}, final lag {final_lag} tick(s), "
            f"{ship.bytes_total} bytes shipped, {ship.nacks} nacks")
    finally:
        if fe is not None:
            fe.close()
        if ship is not None:
            ship.close()
        for r in replicas:
            r.close()
        shutil.rmtree(tmp, ignore_errors=True)
    return out


# -- reactive-reads mode (REFLOW_BENCH_SUBS=1) ------------------------------


def _subs_query_pool(sink_name: str, vocab: int, n: int) -> list:
    """``n`` distinct standing queries mixing the three kinds. Lookups
    dominate (they are what 100k real subscribers look like: each
    watching its own key); topk/view ride along so every fan-out round
    exercises the expensive paths too. The hub keys fan-out state by
    *distinct* query, so subscriber count and query diversity are
    independent axes — the bench stresses both."""
    pool = []
    for i in range(n):
        m = i % 8
        if m < 5:
            pool.append((sink_name, "lookup", ((f"w{i % vocab}", 1.0),)))
        elif m < 7:
            pool.append((sink_name, "topk", (5 + 5 * (m - 4), "weight")))
        else:
            pool.append((sink_name, "view", ()))
    return pool


def run_subs_bench() -> dict:
    """Reactive reads (docs/guide.md "Reactive reads"): one replica's
    :class:`~reflow_tpu.subs.hub.SubscriptionHub` fanning per-window
    deltas to ``REFLOW_BENCH_SUBS_N`` simulated subscribers (in-process
    :class:`SubHandle`\\ s — the same state machine the wire client
    wraps) while 16 producers write through the durable leader.

    Two identically-loaded write legs run back to back:

    - **baseline**: leader + shipper + replica, no hub — the write
      path's admission p99 with nobody watching;
    - **subs**: the same topology with the hub attached, N in-process
      subscribers standing on a mixed query pool, and a few real wire
      subscribers over loopback that live through a mid-run
      partition + heal of their endpoint.

    Property checks, each a hard assert:

    - **push == pull**: sampled subscribers' delta-reconstructed
      answers equal ``view_at``/``lookup``/top-k at the same horizon
      with ``max_abs_diff == 0``, and reach it with zero gaps and zero
      duplicate applies;
    - **partition/heal**: every wire subscriber resumes (``mode ==
      "resume"`` — cursor, not re-snapshot) with ``gaps_total == 0``
      and ``dups_skipped_total == 0``;
    - **write path immune**: the subs leg's admission p99 stays within
      2x the no-subscriber baseline (plus a small absolute floor so a
      sub-millisecond baseline doesn't turn timer noise into a fail).

    Host-side CPU work; runs on the CPU executor/platform."""
    import shutil
    import tempfile
    import threading

    from reflow_tpu.net import LoopbackTransport, ReconnectPolicy
    from reflow_tpu.serve import (CoalesceWindow, IngestFrontend,
                                  ReplicaScheduler)
    from reflow_tpu.subs import (Subscriber, SubscriptionHub,
                                 SubscriptionServer)
    from reflow_tpu.subs.query import topk_rows
    from reflow_tpu.wal import DurableScheduler, SegmentShipper
    from reflow_tpu.workloads import wordcount

    smoke = env_flag("REFLOW_BENCH_SMOKE")
    n_subs = env_int("REFLOW_BENCH_SUBS_N") or (2_000 if smoke
                                                else 100_000)
    run_s = env_float("REFLOW_BENCH_SUBS_RUN_S") or (0.6 if smoke
                                                     else 2.0)
    n_producers = 16
    n_wire = 3
    window_ticks = 4
    vocab = 2_000 if smoke else 20_000
    n_distinct = min(n_subs, 64 if smoke else 512)
    n_sampled = min(n_subs, 32)

    out = {"subscribers": n_subs, "distinct_queries": n_distinct,
           "wire_subscribers": n_wire, "producers": n_producers,
           "run_s": run_s, "vocab": vocab}

    def write_leg(tag: str, with_subs: bool) -> dict:
        tmp = tempfile.mkdtemp(prefix=f"reflow-subs-{tag}-")
        fe = ship = rep = hub = srv = srv2 = None
        wire_subs = []
        pumpers = []
        pump_stop = threading.Event()
        leg = {}
        try:
            g, src, sink = wordcount.build_graph()
            sched = DurableScheduler(g, wal_dir=os.path.join(tmp, "wal"),
                                     fsync="tick", committer="thread",
                                     segment_bytes=1 << 20)
            fe = IngestFrontend(sched, window=CoalesceWindow(
                max_rows=65536, max_ticks=window_ticks,
                max_latency_s=0.002))
            ship = SegmentShipper(sched.wal,
                                  leader_tick=lambda: sched._tick,
                                  poll_s=0.001)
            gr, _s, _k = wordcount.build_graph()
            rep = ReplicaScheduler(gr, os.path.join(tmp, "r0"),
                                   name="r0")
            ship.attach(rep)
            ship.start()

            handles = []
            sampled = []
            pool = _subs_query_pool(sink.name, vocab, n_distinct)
            if with_subs:
                hub = SubscriptionHub(rep, name="r0")
                rep.attach_hub(hub)
                t0 = time.perf_counter()
                for i in range(n_subs):
                    q = pool[i % len(pool)]
                    handles.append((hub.open(q[0], q[1], q[2]), q))
                leg["open_s"] = round(time.perf_counter() - t0, 3)
                step = max(1, n_subs // n_sampled)
                sampled = handles[::step][:n_sampled]
                lt = LoopbackTransport()
                srv = SubscriptionServer(hub, lt).start()
                for i in range(n_wire):
                    q = pool[i % len(pool)]
                    wire_subs.append(Subscriber(
                        lt, srv.address, q[0], kind=q[1], params=q[2],
                        name=f"bench-wire-{i}",
                        policy=ReconnectPolicy(f"bench-wire-{i}",
                                               base_s=0.01, cap_s=0.05,
                                               jitter=0.0)))

                def pump_forever(sub):
                    # never raises while the link is down — the whole
                    # point of the partition leg
                    while not pump_stop.is_set():
                        sub.pump(wait_s=0.05)

                pumpers = [threading.Thread(target=pump_forever,
                                            args=(s,))
                           for s in wire_subs]
                for t in pumpers:
                    t.start()

            # -- sustained 16-producer writes for the measured window
            stop = threading.Event()
            submitted = [0] * n_producers

            def produce(pid):
                rng = np.random.default_rng(1000 + pid)
                seq = 0
                while not stop.is_set():
                    words = " ".join(
                        f"w{int(x)}" for x in rng.integers(0, vocab, 24))
                    try:
                        fe.submit(src, wordcount.ingest_lines([words]),
                                  batch_id=f"p{pid}-{seq}")
                    except Exception:
                        break
                    seq += 1
                submitted[pid] = seq

            producers = [threading.Thread(target=produce, args=(pid,))
                         for pid in range(n_producers)]
            for t in producers:
                t.start()

            if with_subs:
                # partition the subscription endpoint mid-run, heal it
                # while writes are still flowing — the resume contract
                # has to hold under load, not at quiesce
                time.sleep(run_s * 0.5)
                srv.close()
                time.sleep(run_s * 0.25)
                srv2 = SubscriptionServer(hub, lt).start()
                for s in wire_subs:
                    s.retarget(srv2.address)
                time.sleep(run_s * 0.25)
            else:
                time.sleep(run_s)

            # -- quiesce: land everything, replica catches up
            stop.set()
            for t in producers:
                t.join()
            p99 = float(np.percentile(list(fe.admission_s), 99)) \
                if fe.admission_s else 0.0
            fe.flush()
            sched.wal.sync()
            deadline = time.monotonic() + 60
            while (rep.published_horizon() != sched._tick
                   and time.monotonic() < deadline):
                time.sleep(0.005)
            ship.stop()
            ship.pump_once()
            assert rep.published_horizon() == sched._tick, \
                (rep.published_horizon(), sched._tick)
            horizon = sched._tick
            leg["admission_p99_us"] = round(p99 * 1e6, 1)
            leg["total_batches"] = sum(submitted)
            leg["leader_ticks"] = horizon

            if with_subs:
                # fan-out settles to the replica's published horizon
                deadline = time.monotonic() + 30
                while (hub.fanout_horizon < horizon
                       and time.monotonic() < deadline):
                    time.sleep(0.005)
                assert hub.fanout_horizon == horizon, \
                    (hub.fanout_horizon, horizon)

                # push == pull, zero gaps, zero duplicate applies
                view = rep.view_at(sink.name)[1]
                max_abs_diff = 0.0
                gaps = dups = 0
                for h, q in sampled:
                    assert h.wait_horizon(horizon, timeout_s=10.0), \
                        (q, h.horizon, horizon)
                    got = h.value()
                    if q[1] == "view":
                        for kv in set(got) | set(view):
                            max_abs_diff = max(
                                max_abs_diff,
                                abs(got.get(kv, 0) - view.get(kv, 0)))
                    elif q[1] == "lookup":
                        max_abs_diff = max(
                            max_abs_diff,
                            abs(got - view.get(q[2][0], 0)))
                    else:
                        k, by = q[2]
                        assert got == topk_rows(view, k, by), (q, got)
                    gaps += h.state.gaps
                    dups += h.state.dups_skipped
                assert max_abs_diff == 0, max_abs_diff
                assert gaps == 0 and dups == 0, (gaps, dups)

                # wire subscribers: gap-free, dup-free resume through
                # the partition/heal
                pump_stop.set()
                for t in pumpers:
                    t.join()
                for s in wire_subs:
                    deadline = time.monotonic() + 10
                    while (s.horizon < horizon
                           and time.monotonic() < deadline):
                        s.pump(wait_s=0.05)
                    assert s.horizon >= horizon, (s.name, s.horizon,
                                                  horizon)
                    assert s.mode == "resume", (s.name, s.mode)
                    assert s.gaps_total == 0, s.name
                    assert s.dups_skipped_total == 0, s.name
                    assert s.reconnects_total >= 1, s.name
                    if s.query.kind == "view":
                        assert s.value() == view
                    elif s.query.kind == "lookup":
                        assert s.value() == view.get(s.query.params[0],
                                                     0)
                    else:
                        k, by = s.query.params
                        assert s.value() == topk_rows(view, k, by)

                leg["sampled_subscribers"] = len(sampled)
                leg["parity_max_abs_diff"] = max_abs_diff
                leg["frames_total"] = hub.frames_total
                leg["fanout_rows_total"] = hub.fanout_rows_total
                leg["fanout_rows_per_s"] = round(
                    hub.fanout_rows_total / run_s, 1)
                leg["conflations_total"] = hub.conflations_total
                leg["sheds_total"] = hub.sheds_total
                leg["active_subs"] = hub.active_subs()
                leg["slowest_lag"] = hub.slowest_lag()
                leg["wire_reconnects"] = sum(s.reconnects_total
                                             for s in wire_subs)
        finally:
            pump_stop.set()
            for t in pumpers:
                t.join(timeout=5.0)
            for s in wire_subs:
                s.close()
            for s in (srv, srv2):
                if s is not None:
                    s.close()
            if hub is not None:
                hub.close()
            if fe is not None:
                fe.close()
            if ship is not None:
                ship.close()
            if rep is not None:
                rep.close()
            shutil.rmtree(tmp, ignore_errors=True)
        return leg

    base = write_leg("base", with_subs=False)
    log(f"subs[baseline]: admission p99 "
        f"{base['admission_p99_us']:.0f}us, "
        f"{base['total_batches']} batches, no subscribers")
    subs = write_leg("subs", with_subs=True)
    log(f"subs[{n_subs}-subscriber leg]: admission p99 "
        f"{subs['admission_p99_us']:.0f}us, "
        f"{subs['total_batches']} batches, "
        f"{subs['fanout_rows_per_s']} fan-out rows/s, "
        f"{subs['conflations_total']} conflations, "
        f"{subs['sheds_total']} sheds, parity diff "
        f"{subs['parity_max_abs_diff']}, "
        f"{subs['wire_reconnects']} wire reconnects")

    p99_base = base["admission_p99_us"]
    p99_subs = subs["admission_p99_us"]
    out["baseline"] = base
    out["subs"] = subs
    out["write_p99_overhead_x"] = round(p99_subs / p99_base, 3) \
        if p99_base else 0.0
    # the bound: 2x the baseline, with an absolute floor so a
    # microsecond-scale baseline doesn't turn scheduler jitter into a
    # spurious fail on a loaded host
    bound_us = max(2.0 * p99_base, p99_base + 5_000.0)
    out["write_p99_bound_us"] = round(bound_us, 1)
    out["write_p99_bounded"] = p99_subs <= bound_us
    assert p99_subs <= bound_us, (p99_subs, bound_us)
    log(f"subs[overhead]: write p99 {out['write_p99_overhead_x']}x "
        f"baseline (bounded={out['write_p99_bounded']})")
    return out


# -- bounded-history mode (REFLOW_BENCH_COMPACT=1) -------------------------

def run_compact_bench() -> dict:
    """Bounded history (docs/guide.md "Bounded history"): incremental
    checkpoint chains + key-level WAL compaction must buy O(state)
    recovery and fast replica bootstrap without giving up a byte of
    exactly-once.

    Two identically-fed legs run back to back — 16 producer threads
    each submit a fixed, deterministic batch stream (every odd batch
    retracts its predecessor, so live state stays tiny while history
    grows without bound) through an ``IngestFrontend`` into a durable
    wordcount leader:

    - **unbounded oracle**: no checkpoints, no compaction — the WAL
      keeps the full history (the "before" condition);
    - **bounded**: a ``CheckpointChain`` element every ``save_every``
      leader ticks (full every ``delta_every``-th save, lag-one WAL
      truncation) with a ``WalCompactor`` folding the sealed replay
      tail between saves.

    Then four cold starts are timed:

    1. leader crash-recovery by full-history replay (oracle WAL);
    2. leader crash-recovery from {chain + compacted tail};
    3. fresh-replica bootstrap streaming the full oracle WAL;
    4. fresh-replica bootstrap from {chain + compacted tail};

    plus the floor everything is measured against: restoring a fresh
    full checkpoint of the final state (the O(state) lower bound).

    Acceptance: WAL history >= 10x live-state bytes; (2) and (4) each
    >= 5x faster than their full-history twin AND within 2x (+ a fixed
    50ms epsilon for fsync/transport constants) of the fresh-full
    floor; EXACT view parity (max_abs_diff == 0) between every
    recovered/bootstrapped view and its leg's leader view, and between
    the two legs' quiesced final views (identical batch multiset ->
    identical fold); zero acked-write loss; the reclaimable-bytes gauge
    settles near zero after the final pass (bounded footprint).

    Host-side CPU work; runs on the CPU executor/platform."""
    import shutil
    import tempfile
    import threading

    from reflow_tpu.obs import MetricsRegistry
    from reflow_tpu.scheduler import DirtyScheduler
    from reflow_tpu.serve import (CoalesceWindow, IngestFrontend,
                                  ReplicaScheduler)
    from reflow_tpu.utils.checkpoint import (CheckpointChain,
                                             load_checkpoint,
                                             save_checkpoint)
    from reflow_tpu.wal import (DurableScheduler, SegmentShipper,
                                WalCompactor, recover)
    from reflow_tpu.workloads import wordcount

    smoke = env_flag("REFLOW_BENCH_SMOKE")
    per_prod = env_int("REFLOW_BENCH_COMPACT_TICKS") \
        or (160 if smoke else 480)
    n_producers = 16
    vocab = 300
    save_every = 24          # leader ticks between chain elements
    delta_every = 6          # full checkpoint every 6th element
    eps_s = 0.05             # fixed epsilon on the within-2x floors
    out = {"producers": n_producers, "per_producer_batches": per_prod,
           "vocab": vocab, "save_every": save_every,
           "delta_every": delta_every}

    def words_for(pid, seq):
        rng = np.random.default_rng(pid * 100_000 + seq)
        return " ".join(f"w{int(x)}" for x in rng.integers(0, vocab, 24))

    def batch_for(pid, seq):
        if seq % 2 == 1:
            # retract the predecessor: live state stays O(recent),
            # history keeps both records — the compactor's whole case
            return wordcount.ingest_lines([words_for(pid, seq - 1)],
                                          weight=-1)
        return wordcount.ingest_lines([words_for(pid, seq)])

    def du(path):
        total = 0
        for base, _dirs, files in os.walk(path):
            for f in files:
                total += os.path.getsize(os.path.join(base, f))
        return total

    def run_leg(tmp, bounded):
        wal_dir = os.path.join(tmp, "wal-bounded" if bounded
                               else "wal-full")
        root = os.path.join(tmp, "ckpt") if bounded else None
        g, src, sink = wordcount.build_graph()
        sched = DurableScheduler(g, wal_dir=wal_dir, fsync="tick",
                                 committer="thread",
                                 segment_bytes=1 << 15)
        fe = IngestFrontend(sched, window=CoalesceWindow(
            max_rows=65536, max_ticks=4, max_latency_s=0.002))
        chain = comp = None
        if bounded:
            chain = CheckpointChain(root, delta_every=delta_every)
            comp = WalCompactor(sched.wal, ckpt_dir=root,
                                min_segments=2, keep_segments=1)
        acked = [0] * n_producers
        n_saves = 0
        last_save = 0

        def produce(pid, lo, hi):
            n = 0
            tickets = []

            def resolve():
                nonlocal n
                for t in tickets:
                    if t.result(timeout=120).applied:
                        n += 1
                tickets.clear()

            for seq in range(lo, hi):
                tickets.append(fe.submit(src, batch_for(pid, seq),
                                         batch_id=f"p{pid}-{seq}"))
                if len(tickets) >= 64:
                    resolve()
            resolve()
            acked[pid] += n

        def save_and_compact():
            nonlocal n_saves, last_save
            fe.pause()
            try:
                chain.save(sched)
            finally:
                fe.resume()
            n_saves += 1
            last_save = sched._tick
            comp.compact_once()

        def drive(lo, hi):
            threads = [threading.Thread(target=produce,
                                        args=(pid, lo, hi))
                       for pid in range(n_producers)]
            for t in threads:
                t.start()
            while any(t.is_alive() for t in threads):
                if bounded and sched._tick - last_save >= save_every:
                    save_and_compact()
                time.sleep(0.002)
            for t in threads:
                t.join()

        # two write phases around a guaranteed chain save: heavy
        # coalescing can finish a smoke run in fewer leader ticks than
        # ``save_every``, and the bounded leg MUST exercise {chain +
        # compacted tail}, not compaction alone — phase 2's records are
        # the replay tail past the last anchor
        split = (4 * per_prod) // 5
        drive(0, split)
        if bounded:
            fe.flush()
            save_and_compact()
        drive(split, per_prod)
        fe.flush()
        sched.wal.sync()
        if bounded:
            while comp.compact_once() is not None:
                pass  # drain: fold the sealed tail completely
        view = {kv: w for kv, w in sched.view(sink.name).items()
                if w != 0}
        tick = sched._tick
        fe.close()
        sched.close()
        return {"wal_dir": wal_dir, "root": root, "view": view,
                "tick": tick, "acked": sum(acked), "chain": chain,
                "comp": comp, "sink": sink.name, "saves": n_saves}

    def diff(a, b):
        return max((abs(a.get(kv, 0) - b.get(kv, 0))
                    for kv in set(a) | set(b)), default=0)

    def timed_recover(wal_dir, root):
        g, _s, sink = wordcount.build_graph()
        sched = DirtyScheduler(g)
        t0 = time.perf_counter()
        recover(sched, wal_dir, root)
        dt = time.perf_counter() - t0
        view = {kv: w for kv, w in sched.view(sink.name).items()
                if w != 0}
        return dt, view, sched._tick, sched

    def timed_bootstrap(tmp, wal_dir, root, target_tick, name):
        ship = SegmentShipper(wal_dir=wal_dir, ckpt_dir=root)
        g, _s, sink = wordcount.build_graph()
        r = ReplicaScheduler(g, os.path.join(tmp, name), name=name)
        t0 = time.perf_counter()
        ship.attach(r)
        stalls = 0
        while r.published_horizon() < target_tick:
            if ship.pump_once() == 0:
                stalls += 1
                if stalls > 3:
                    break
            else:
                stalls = 0
        dt = time.perf_counter() - t0
        assert r.published_horizon() == target_tick, \
            (name, r.published_horizon(), target_tick)
        _h, view = r.view_at(sink)
        ship.close()
        r.close()
        return dt, view

    tmp = tempfile.mkdtemp(prefix="reflow-compact-")
    try:
        full = run_leg(tmp, bounded=False)
        bounded = run_leg(tmp, bounded=True)
        assert full["acked"] == bounded["acked"] \
            == n_producers * per_prod, "acked-write loss at submit time"
        out["acked_batches"] = bounded["acked"]
        # identical batch multiset -> identical final fold, exactly
        out["legs_parity_max_abs_diff"] = diff(full["view"],
                                               bounded["view"])
        assert out["legs_parity_max_abs_diff"] == 0

        comp = bounded["comp"]
        reg = MetricsRegistry()
        comp.publish_metrics(reg)
        full_bytes = du(full["wal_dir"])
        bounded_bytes = du(bounded["wal_dir"]) + du(bounded["root"])
        out["wal_full_bytes"] = full_bytes
        out["wal_bounded_bytes"] = du(bounded["wal_dir"])
        out["ckpt_chain_bytes"] = du(bounded["root"])
        out["chain_saves"] = bounded["saves"]
        out["leader_ticks"] = bounded["tick"]
        assert bounded["saves"] >= 1 and out["ckpt_chain_bytes"] > 0, \
            "bounded leg never cut a checkpoint chain element"
        out["compact_folds"] = comp.folds
        out["compact_reclaimed_bytes"] = comp.reclaimed_bytes
        out["reclaimable_bytes_final"] = reg.value(
            "compact.reclaimable_bytes", comp.reclaimable_bytes())

        # -- leader crash-recovery ------------------------------------
        t_full, v_full, tick_full, _ = timed_recover(
            full["wal_dir"], None)
        assert tick_full == full["tick"]
        assert diff(v_full, full["view"]) == 0
        t_bounded, v_bounded, tick_b, sched_b = timed_recover(
            bounded["wal_dir"], bounded["root"])
        assert tick_b == bounded["tick"]
        assert diff(v_bounded, bounded["view"]) == 0
        log(f"compact[recover]: full replay {t_full:.3f}s vs "
            f"chain+tail {t_bounded:.3f}s")

        # -- the O(state) floor: a fresh full checkpoint --------------
        fresh_dir = os.path.join(tmp, "fresh-full")
        save_checkpoint(sched_b, fresh_dir)
        g2, _s2, _k2 = wordcount.build_graph()
        t0 = time.perf_counter()
        load_checkpoint(DirtyScheduler(g2), fresh_dir)
        t_fresh = time.perf_counter() - t0
        state_bytes = du(fresh_dir)
        out["state_bytes"] = state_bytes
        out["history_ratio"] = round(full_bytes / max(1, state_bytes), 2)

        # -- fresh-replica bootstrap ----------------------------------
        tb_full, rv_full = timed_bootstrap(
            tmp, full["wal_dir"], None, full["tick"], "boot-full")
        assert diff(rv_full, full["view"]) == 0
        tb_bounded, rv_bounded = timed_bootstrap(
            tmp, bounded["wal_dir"], bounded["root"], bounded["tick"],
            "boot-bounded")
        assert diff(rv_bounded, bounded["view"]) == 0
        log(f"compact[bootstrap]: full stream {tb_full:.3f}s vs "
            f"chain+tail {tb_bounded:.3f}s (fresh-full floor "
            f"{t_fresh:.3f}s)")

        out["recover_full_s"] = round(t_full, 4)
        out["recover_bounded_s"] = round(t_bounded, 4)
        out["bootstrap_full_s"] = round(tb_full, 4)
        out["bootstrap_bounded_s"] = round(tb_bounded, 4)
        out["fresh_full_restore_s"] = round(t_fresh, 4)
        out["recover_speedup_x"] = round(t_full / max(t_bounded, 1e-9), 2)
        out["bootstrap_speedup_x"] = round(
            tb_full / max(tb_bounded, 1e-9), 2)
        out["parity_max_abs_diff"] = max(
            out["legs_parity_max_abs_diff"], diff(v_full, full["view"]),
            diff(v_bounded, bounded["view"]),
            diff(rv_full, full["view"]),
            diff(rv_bounded, bounded["view"]))
        out["history_ratio_ok"] = out["history_ratio"] >= 10
        out["recover_speedup_ok"] = out["recover_speedup_x"] >= 5
        out["bootstrap_speedup_ok"] = out["bootstrap_speedup_x"] >= 5
        out["recover_near_floor_ok"] = \
            t_bounded <= 2 * t_fresh + eps_s
        out["bootstrap_near_floor_ok"] = \
            tb_bounded <= 2 * t_fresh + eps_s
        out["footprint_bounded_ok"] = bounded_bytes * 3 <= full_bytes
        out["zero_acked_loss"] = (out["parity_max_abs_diff"] == 0
                                  and out["acked_batches"]
                                  == n_producers * per_prod)
        log(f"compact[summary]: history {out['history_ratio']}x state, "
            f"recover {out['recover_speedup_x']}x, bootstrap "
            f"{out['bootstrap_speedup_x']}x, footprint "
            f"{bounded_bytes}/{full_bytes} bytes, "
            f"{comp.folds} fold(s), reclaimed "
            f"{comp.reclaimed_bytes} bytes")
        comp.close()
    finally:
        shutil.rmtree(tmp, ignore_errors=True)
    return out


# -- tiled-maintenance mode (REFLOW_BENCH_TILES=1) -------------------------

def run_tiles_bench() -> dict:
    """Tiled maintenance (docs/guide.md "Tiled maintenance"): with
    ``REFLOW_TILE_BYTES`` set, every O(state) maintenance path —
    compaction folds, checkpoint base/delta elements, published replica
    snapshots, and bootstrap shipping — must bound its peak resident
    bytes by the tile budget (enforced 2x) without giving up a byte of
    parity or exactly-once.

    Two identically-fed legs run back to back, BOTH bounded (checkpoint
    chain + compactor, the REFLOW_BENCH_COMPACT shape) and differing
    only in the tile budget: the **untiled** leg runs the monolithic
    paths (budget 0), the **tiled** leg runs with a budget the final
    state exceeds by >= 8x (so no maintenance step may ever hold the
    whole state). Then:

    1. both legs' final views must agree exactly (identical batch
       multiset -> identical fold, ``max_abs_diff == 0``);
    2. the tiled leg's ``compact.peak_tile_bytes`` and the checkpoint
       writer/reader peak frame bytes must stay under 2x the budget;
    3. crashed-leader recovery from {chain + compacted tail} and a
       fresh-replica bootstrap must hit exact parity on both legs,
       with the tiled leg's bootstrap going through the per-file
       tile-unit protocol (``tile_bootstraps >= 1``);
    4. a per-tile crash-seam sweep kills a maintenance pass at every
       new seam (``compact_tile_before_progress`` /
       ``compact_tile_after_progress`` / ``ckpt_tile_full_append`` /
       ``ckpt_tile_append``) and proves the next pass resumes to exact
       parity — zero acked-write loss at every seam;
    5. the tiled replica's ``top_k`` / ``lookup`` answers must match an
       untiled snapshot oracle bootstrapped from the same leg;
    6. a dedicated small-state pair — identical direct-push feeds, no
       coalescing, so both legs' WAL shapes are byte-identical and the
       walls compare tiled-vs-monolithic work and nothing else — must
       show tiled restore and bootstrap within 1.2x of untiled (+ a
       fixed 50ms epsilon): the bound costs sequential passes, not a
       slowdown where tiling barely engages.

    Host-side CPU work; runs on the CPU executor/platform."""
    import shutil
    import tempfile
    import threading

    from reflow_tpu.obs import MetricsRegistry
    from reflow_tpu.scheduler import DirtyScheduler
    from reflow_tpu.serve import (CoalesceWindow, IngestFrontend,
                                  ReplicaScheduler)
    from reflow_tpu.utils import tiles as _tiles
    from reflow_tpu.utils.checkpoint import (TILE_IO_STATS, CheckpointChain,
                                             reset_tile_io_stats)
    from reflow_tpu.utils.faults import CrashInjector, CrashPoint
    from reflow_tpu.wal import (DurableScheduler, SegmentShipper,
                                WalCompactor, recover)
    from reflow_tpu.workloads import wordcount

    smoke = env_flag("REFLOW_BENCH_SMOKE")
    per_prod = env_int("REFLOW_BENCH_TILES_TICKS") \
        or (120 if smoke else 320)
    n_producers = 16
    vocab = 4000             # wide key space: live state >> tile budget
    tile_b = 8192            # the tiled leg's REFLOW_TILE_BYTES
    save_every = 24          # leader ticks between chain elements
    delta_every = 4          # full checkpoint every 4th element
    eps_s = 0.05             # fixed epsilon on the within-1.2x walls
    out = {"producers": n_producers, "per_producer_batches": per_prod,
           "vocab": vocab, "tile_bytes": tile_b,
           "save_every": save_every, "delta_every": delta_every}

    def set_budget(b):
        if b > 0:
            os.environ["REFLOW_TILE_BYTES"] = str(b)
        else:
            os.environ.pop("REFLOW_TILE_BYTES", None)

    def words_for(pid, seq):
        rng = np.random.default_rng(pid * 100_000 + seq)
        return " ".join(f"w{int(x)}" for x in rng.integers(0, vocab, 24))

    def batch_for(pid, seq):
        if seq % 7 == 6:
            # an occasional retraction keeps the fold's cancellation
            # path hot without shrinking live state below 8x budget
            return wordcount.ingest_lines([words_for(pid, seq - 1)],
                                          weight=-1)
        return wordcount.ingest_lines([words_for(pid, seq)])

    def diff(a, b):
        return max((abs(a.get(kv, 0) - b.get(kv, 0))
                    for kv in set(a) | set(b)), default=0)

    def run_leg(tmp, label):
        wal_dir = os.path.join(tmp, f"wal-{label}")
        root = os.path.join(tmp, f"ckpt-{label}")
        g, src, sink = wordcount.build_graph()
        sched = DurableScheduler(g, wal_dir=wal_dir, fsync="tick",
                                 committer="thread",
                                 segment_bytes=1 << 12)
        fe = IngestFrontend(sched, window=CoalesceWindow(
            max_rows=65536, max_ticks=4, max_latency_s=0.002))
        chain = CheckpointChain(root, delta_every=delta_every)
        comp = WalCompactor(sched.wal, ckpt_dir=root,
                            min_segments=2, keep_segments=1)
        acked = [0] * n_producers
        last_save = 0

        def produce(pid, lo, hi):
            n = 0
            tickets = []

            def resolve():
                nonlocal n
                for t in tickets:
                    if t.result(timeout=120).applied:
                        n += 1
                tickets.clear()

            for seq in range(lo, hi):
                tickets.append(fe.submit(src, batch_for(pid, seq),
                                         batch_id=f"p{pid}-{seq}"))
                if len(tickets) >= 64:
                    resolve()
            resolve()
            acked[pid] += n

        def save_and_compact():
            nonlocal last_save
            fe.pause()
            try:
                chain.save(sched)
            finally:
                fe.resume()
            last_save = sched._tick
            comp.compact_once()

        def drive(lo, hi):
            threads = [threading.Thread(target=produce,
                                        args=(pid, lo, hi))
                       for pid in range(n_producers)]
            for t in threads:
                t.start()
            while any(t.is_alive() for t in threads):
                if sched._tick - last_save >= save_every:
                    save_and_compact()
                time.sleep(0.002)
            for t in threads:
                t.join()

        # a guaranteed mid-stream save so the replay tail crosses a
        # chain element (the compact bench's two-phase shape)
        split = (4 * per_prod) // 5
        drive(0, split)
        fe.flush()
        save_and_compact()
        drive(split, per_prod)
        fe.flush()
        sched.wal.sync()
        while comp.compact_once() is not None:
            pass  # drain: fold the sealed tail completely
        view = {kv: w for kv, w in sched.view(sink.name).items()
                if w != 0}
        tick = sched._tick
        fe.close()
        sched.close()
        return {"wal_dir": wal_dir, "root": root, "view": view,
                "tick": tick, "acked": sum(acked), "chain": chain,
                "comp": comp, "sink": sink}

    def timed_recover(wal_dir, root):
        g, _s, sink = wordcount.build_graph()
        sched = DirtyScheduler(g)
        t0 = time.perf_counter()
        recover(sched, wal_dir, root)
        dt = time.perf_counter() - t0
        view = {kv: w for kv, w in sched.view(sink.name).items()
                if w != 0}
        return dt, view, sched._tick

    def boot(tmp, wal_dir, root, target_tick, name, tile_param=None):
        """Bootstrap a fresh replica from {chain + tail}; the caller
        reads/asserts and must close both handles."""
        ship = SegmentShipper(wal_dir=wal_dir, ckpt_dir=root)
        g, _s, sink = wordcount.build_graph()
        kw = {} if tile_param is None else {"tile_bytes": tile_param}
        r = ReplicaScheduler(g, os.path.join(tmp, name), name=name, **kw)
        t0 = time.perf_counter()
        ship.attach(r)
        t_attach = time.perf_counter() - t0
        stalls = 0
        while r.published_horizon() < target_tick:
            if ship.pump_once() == 0:
                stalls += 1
                if stalls > 3:
                    break
            else:
                stalls = 0
        dt = time.perf_counter() - t0
        log(f"tiles[boot:{name}]: attach {t_attach:.3f}s, "
            f"tail pump {dt - t_attach:.3f}s, "
            f"{ship.tile_units_shipped} unit(s), "
            f"{ship.tile_unit_retries} retr(y/ies), "
            f"{ship.tile_bootstraps} tile boot(s)")
        assert r.published_horizon() == target_tick, \
            (name, r.published_horizon(), target_tick)
        _h, view = r.view_at(sink)
        return ship, r, sink, dt, view

    # -- per-tile crash-seam sweep ------------------------------------

    def seam_feed(tag, n_ticks=36):
        rng = np.random.default_rng(hash(tag) % (1 << 32))
        feed = []
        for t in range(n_ticks):
            words = " ".join(f"s{int(x)}"
                             for x in rng.integers(0, 220, 16))
            feed.append((f"{tag}-t{t}", wordcount.ingest_lines([words])))
        return feed

    def seam_log(wal_dir, feed, *, chain=None, crash_on=None):
        """Drive a small durable leader; optionally cut chain elements
        mid-feed, letting a CrashInjector kill a tiled save. Returns
        (live view, tick, acked, fired)."""
        g, src, sink = wordcount.build_graph()
        sched = DurableScheduler(g, wal_dir=wal_dir, fsync="tick",
                                 segment_bytes=1 << 12)
        acked = 0
        fired = False
        for i, (bid, b) in enumerate(feed):
            sched.push(src, b, batch_id=bid)
            sched.tick()
            acked += 1
            if chain is not None and not fired and (i + 1) % 12 == 0:
                try:
                    chain.save(sched)
                except CrashPoint:
                    fired = True
                    assert crash_on is not None and crash_on.fired
        view = {kv: w for kv, w in sched.view(sink.name).items()
                if w != 0}
        tick = sched._tick
        sched.close()
        return view, tick, acked, fired

    def sweep_compact_seam(base, seam):
        d = os.path.join(base, f"seam-{seam}")
        oracle, tick, acked, _ = seam_log(d, seam_feed(seam))
        inj = CrashInjector(at=2, only=seam)  # die PAST the first tile
        comp = WalCompactor(wal_dir=d, min_segments=2, keep_segments=1,
                            crash=inj)
        try:
            comp.compact_once()
            fired = False
        except CrashPoint:
            fired = True
        assert fired and inj.fired_seam == seam, (seam, inj.fired_seam)
        # next pass rolls forward (finished tiles are NOT refolded) and
        # the unchanged recovery path must land on exact parity
        comp2 = WalCompactor(wal_dir=d, min_segments=2, keep_segments=1)
        while comp2.compact_once() is not None:
            pass
        _dt, view, tick2 = timed_recover(d, None)
        assert tick2 == tick and diff(view, oracle) == 0, seam
        comp.close()
        comp2.close()
        return acked

    def sweep_ckpt_seam(base, seam):
        d = os.path.join(base, f"seam-{seam}")
        root = os.path.join(base, f"seam-{seam}-ckpt")
        inj = CrashInjector(at=2, only=seam)
        chain = CheckpointChain(root, delta_every=delta_every,
                                crash=inj)
        oracle, tick, acked, fired = seam_log(
            d, seam_feed(seam, n_ticks=48), chain=chain, crash_on=inj)
        assert fired and inj.fired_seam == seam, (seam, inj.fired_seam)
        # the torn save never flipped a manifest: recovery restores the
        # previous element (or replays from scratch) + the WAL tail
        _dt, view, tick2 = timed_recover(d, root)
        assert tick2 == tick and diff(view, oracle) == 0, seam
        return acked

    tmp = tempfile.mkdtemp(prefix="reflow-tiles-")
    prev_budget = env_int("REFLOW_TILE_BYTES")
    legs = {}
    try:
        for label, budget in (("untiled", 0), ("tiled", tile_b)):
            set_budget(budget)
            if budget:
                reset_tile_io_stats()
            leg = run_leg(tmp, label)
            assert leg["acked"] == n_producers * per_prod, \
                "acked-write loss at submit time"
            if budget:
                out["ckpt_writer_peak_bytes"] = \
                    TILE_IO_STATS["writer_peak_frame_bytes"]
                reset_tile_io_stats()
            t_rec, v_rec, tick_rec = timed_recover(leg["wal_dir"],
                                                   leg["root"])
            assert tick_rec == leg["tick"]
            assert diff(v_rec, leg["view"]) == 0
            if budget:
                out["ckpt_reader_peak_bytes"] = \
                    TILE_IO_STATS["reader_peak_frame_bytes"]
            ship, rep, sink, t_boot, v_boot = boot(
                tmp, leg["wal_dir"], leg["root"], leg["tick"],
                f"boot-{label}")
            assert diff(v_boot, leg["view"]) == 0
            leg.update(recover_s=t_rec, bootstrap_s=t_boot,
                       ship=ship, rep=rep, sink=sink)
            legs[label] = leg
            log(f"tiles[{label}]: recover {t_rec:.3f}s, "
                f"bootstrap {t_boot:.3f}s, {leg['tick']} tick(s)")

        full, tiled = legs["untiled"], legs["tiled"]
        out["acked_batches"] = tiled["acked"]
        out["leader_ticks"] = tiled["tick"]
        out["legs_parity_max_abs_diff"] = diff(full["view"],
                                               tiled["view"])
        assert out["legs_parity_max_abs_diff"] == 0

        # -- bound checks: nothing held more than ~2x the budget ------
        state_bytes = int(sum(
            _tiles.approx_row_bytes(kv, w)
            for kv, w in tiled["view"].items()))
        out["state_est_bytes"] = state_bytes
        out["state_over_budget_x"] = round(state_bytes / tile_b, 2)
        assert state_bytes >= 8 * tile_b, \
            f"state {state_bytes}B < 8x budget — the bench proves nothing"
        comp = tiled["comp"]
        chain = tiled["chain"]
        reg = MetricsRegistry()
        comp.publish_metrics(reg)
        out["compact_folds"] = comp.folds
        out["compact_peak_tile_bytes"] = reg.value(
            "compact.peak_tile_bytes", comp.peak_tile_bytes)
        out["ckpt_tile_count"] = chain.tile_count
        out["ckpt_peak_tile_bytes"] = chain.peak_tile_bytes
        assert 0 < out["compact_peak_tile_bytes"] <= 2 * tile_b, \
            f"compact peak {out['compact_peak_tile_bytes']}B " \
            f"vs budget {tile_b}B"
        assert 0 < out["ckpt_writer_peak_bytes"] <= 2 * tile_b, \
            f"ckpt writer peak {out['ckpt_writer_peak_bytes']}B " \
            f"vs budget {tile_b}B"
        assert 0 < out["ckpt_reader_peak_bytes"] <= 2 * tile_b, \
            f"ckpt reader peak {out['ckpt_reader_peak_bytes']}B " \
            f"vs budget {tile_b}B"
        assert chain.tile_count >= 4, \
            f"budget only planned {chain.tile_count} tile(s)"

        # -- tile-unit bootstrap actually ran -------------------------
        ship_t = tiled["ship"]
        out["tile_units_shipped"] = ship_t.tile_units_shipped
        out["tile_unit_retries"] = ship_t.tile_unit_retries
        out["tile_bootstraps"] = ship_t.tile_bootstraps
        assert ship_t.tile_bootstraps >= 1 \
            and ship_t.tile_units_shipped > 0, \
            "tiled bootstrap fell back to the monolithic path"
        rep_t = tiled["rep"]
        out["snapshot_tiles_reused"] = rep_t.snapshot_tiles_reused

        # -- read parity vs an untiled snapshot oracle ----------------
        # same leg, same WAL, same horizon — only snapshot publication
        # differs (tile_bytes=0 forces monolithic arrays)
        ship_o, rep_o, sink_o, _dt, v_o = boot(
            tmp, tiled["wal_dir"], tiled["root"], tiled["tick"],
            "boot-oracle", tile_param=0)
        assert diff(v_o, tiled["view"]) == 0
        k = 10
        h_t, top_t = rep_t.top_k(tiled["sink"], k, by="weight")
        h_o, top_o = rep_o.top_k(sink_o, k, by="weight")
        assert h_t == h_o == tiled["tick"]
        # tie order may differ between a per-tile merge and one global
        # argpartition: compare the rank sequence, then validate every
        # member's weight against the oracle's full view
        assert [w for _kv, w in top_t] == [w for _kv, w in top_o]
        assert all(v_o.get(kv) == w for kv, w in top_t)
        probe = list(tiled["view"])[:: max(1, len(tiled["view"]) // 64)]
        for kv in probe + [("w-never-seen", None)]:
            assert rep_t.lookup(tiled["sink"], kv) \
                == rep_o.lookup(sink_o, kv), kv
        out["topk_parity_ok"] = True
        out["lookup_probes"] = len(probe) + 1
        log(f"tiles[reads]: top_{k} + {len(probe) + 1} lookups match "
            f"the untiled oracle at horizon {h_t}")
        ship_o.close()
        rep_o.close()

        # -- per-tile crash-seam sweep --------------------------------
        set_budget(2048)  # small budget: even the seam feeds tile
        seam_acked = {}
        for seam in ("compact_tile_before_progress",
                     "compact_tile_after_progress"):
            seam_acked[seam] = sweep_compact_seam(tmp, seam)
        for seam in ("ckpt_tile_full_append", "ckpt_tile_append"):
            seam_acked[seam] = sweep_ckpt_seam(tmp, seam)
        set_budget(tile_b)
        out["crash_seams_survived"] = sorted(seam_acked)
        out["crash_seam_acked_batches"] = sum(seam_acked.values())
        log(f"tiles[seams]: {len(seam_acked)} per-tile seam(s) killed "
            f"and recovered to exact parity")

        # -- small-state walls: the bound must not cost a slowdown ----
        # the big legs coalesce nondeterministically (tick/anchor
        # layouts differ per leg), so their walls are reported but the
        # 1.2x criterion is measured on identical deterministic feeds
        def small_leg(label, budget):
            set_budget(budget)
            wal_dir = os.path.join(tmp, f"small-wal-{label}")
            root = os.path.join(tmp, f"small-ckpt-{label}")
            g, src, sink = wordcount.build_graph()
            sched = DurableScheduler(g, wal_dir=wal_dir, fsync="tick",
                                     segment_bytes=1 << 12)
            chain = CheckpointChain(root, delta_every=delta_every)
            comp = WalCompactor(sched.wal, ckpt_dir=root,
                                min_segments=2, keep_segments=1)
            for t in range(60):
                rng = np.random.default_rng(t)
                words = " ".join(f"w{int(x)}"
                                 for x in rng.integers(0, 600, 24))
                sched.push(src, wordcount.ingest_lines([words]),
                           batch_id=f"t{t}")
                sched.tick()
                if t == 44:
                    chain.save(sched)
                    comp.compact_once()
            sched.wal.sync()
            while comp.compact_once() is not None:
                pass
            view = {kv: w for kv, w in sched.view(sink.name).items()
                    if w != 0}
            tick = sched._tick
            sched.close()
            t_rec = 1e9
            for _ in range(3):
                dt, v_rec, tick_rec = timed_recover(wal_dir, root)
                assert tick_rec == tick and diff(v_rec, view) == 0
                t_rec = min(t_rec, dt)
            ship, rep, _sink_n, t_boot, v_boot = boot(
                tmp, wal_dir, root, tick, f"small-boot-{label}")
            assert diff(v_boot, view) == 0
            n_tiles = chain.tile_count
            ship.close()
            rep.close()
            comp.close()
            chain.close()
            return t_rec, t_boot, n_tiles

        small_rec_u, small_boot_u, _nt = small_leg("untiled", 0)
        small_rec_t, small_boot_t, n_tiles = small_leg("tiled", tile_b)
        assert n_tiles >= 2, \
            f"small-state leg planned {n_tiles} tile(s) — trivial pass"
        out["recover_untiled_s"] = round(full["recover_s"], 4)
        out["recover_tiled_s"] = round(tiled["recover_s"], 4)
        out["bootstrap_untiled_s"] = round(full["bootstrap_s"], 4)
        out["bootstrap_tiled_s"] = round(tiled["bootstrap_s"], 4)
        out["big_restore_wall_ratio_x"] = round(
            tiled["recover_s"] / max(full["recover_s"], 1e-9), 2)
        out["big_bootstrap_wall_ratio_x"] = round(
            tiled["bootstrap_s"] / max(full["bootstrap_s"], 1e-9), 2)
        out["small_recover_untiled_s"] = round(small_rec_u, 4)
        out["small_recover_tiled_s"] = round(small_rec_t, 4)
        out["small_bootstrap_untiled_s"] = round(small_boot_u, 4)
        out["small_bootstrap_tiled_s"] = round(small_boot_t, 4)
        out["small_state_tiles"] = n_tiles
        out["restore_wall_ratio_x"] = round(
            small_rec_t / max(small_rec_u, 1e-9), 2)
        out["bootstrap_wall_ratio_x"] = round(
            small_boot_t / max(small_boot_u, 1e-9), 2)
        out["restore_wall_ok"] = \
            small_rec_t <= 1.2 * small_rec_u + eps_s
        out["bootstrap_wall_ok"] = \
            small_boot_t <= 1.2 * small_boot_u + eps_s
        assert out["restore_wall_ok"], \
            f"tiled restore {small_rec_t:.3f}s vs untiled " \
            f"{small_rec_u:.3f}s at small state"
        assert out["bootstrap_wall_ok"], \
            f"tiled bootstrap {small_boot_t:.3f}s vs untiled " \
            f"{small_boot_u:.3f}s at small state"
        out["peak_bounds_ok"] = True
        out["zero_acked_loss"] = (
            out["legs_parity_max_abs_diff"] == 0
            and tiled["acked"] == full["acked"]
            == n_producers * per_prod)
        log(f"tiles[summary]: state {out['state_over_budget_x']}x "
            f"budget, compact peak {out['compact_peak_tile_bytes']}B, "
            f"ckpt peaks {out['ckpt_writer_peak_bytes']}/"
            f"{out['ckpt_reader_peak_bytes']}B (budget {tile_b}B), "
            f"{out['tile_units_shipped']} unit(s) shipped, walls "
            f"{out['restore_wall_ratio_x']}x/"
            f"{out['bootstrap_wall_ratio_x']}x untiled")
        comp.close()
        chain.close()
        full["comp"].close()
        full["chain"].close()
    finally:
        set_budget(prev_budget)
        for leg in legs.values():
            for h in ("ship", "rep"):
                try:
                    if h in leg:
                        leg[h].close()
                except Exception:  # noqa: BLE001 - teardown best-effort
                    pass
        shutil.rmtree(tmp, ignore_errors=True)
    return out


# -- leader-failover mode (REFLOW_BENCH_FAILOVER=1) ------------------------

def run_failover_bench() -> dict:
    """Promote-on-failure under load (docs/guide.md "Leader failover"):
    a wordcount leader (``DurableScheduler`` + ``IngestFrontend``) under
    sustained 16-producer writes with a ``SegmentShipper`` feeding N
    replicas — then the leader is killed mid-stream (a crash seam inside
    the WAL committer: the fsync raises, the committer dies, the pump
    crashes on its next window) and a ``FailoverCoordinator`` runs the
    whole failover: detect → final drain → fence → elect → promote →
    re-ship → re-point reads and ingestion.

    Producers use FIXED batch ids and a resubmit-until-acked loop: a
    ticket that dies with ``PumpCrashed`` is resubmitted with the same
    id after the rebind, so the WAL dedup — not the producer — decides
    exactly-once. The bench reports:

    - **detection_s / promotion_s / first_window_s**: kill → the
      coordinator confirms death; the promotion step's wall; promotion
      → the first commit window applied on the new leader;
    - **zero acked-write loss**: the new leader's final view exactly
      equals a fresh fold of every batch any producer got an ack for
      (applied or deduped) — acked ⊆ synced ⊆ shipped-after-drain;
    - **old-vs-new parity at the promotion horizon**: captured inside
      the promotion callback, before any new-epoch write lands.

    Host-side CPU work; runs on the CPU executor/platform."""
    import shutil
    import tempfile
    import threading

    from reflow_tpu.obs import REGISTRY
    from reflow_tpu.serve import (CoalesceWindow, FailoverCoordinator,
                                  IngestFrontend, LeaderReadAdapter,
                                  ReadTier, ReplicaScheduler)
    from reflow_tpu.utils.faults import CrashInjector
    from reflow_tpu.wal import DurableScheduler, FencedWrite, SegmentShipper
    from reflow_tpu.workloads import wordcount

    smoke = env_flag("REFLOW_BENCH_SMOKE")
    n_replicas = env_int("REFLOW_BENCH_FAILOVER_N", "2")
    n_producers = 16
    window_ticks = 4
    vocab = 2_000 if smoke else 20_000
    run_s = env_float("REFLOW_BENCH_FAILOVER_RUN_S", "0.3" if smoke else "1.0")

    tmp = tempfile.mkdtemp(prefix="reflow-failover-")
    out = {"replicas": n_replicas, "producers": n_producers,
           "window_ticks": window_ticks, "run_s": run_s, "vocab": vocab}
    fe = ship = coord = new_sched = None
    replicas = []
    try:
        g, src, sink = wordcount.build_graph()
        sched = DurableScheduler(g, wal_dir=os.path.join(tmp, "wal"),
                                 fsync="tick", committer="thread",
                                 segment_bytes=1 << 20)
        fe = IngestFrontend(sched, window=CoalesceWindow(
            max_rows=65536, max_ticks=window_ticks, max_latency_s=0.002))
        ship = SegmentShipper(sched.wal, leader_tick=lambda: sched._tick,
                              poll_s=0.001)
        for i in range(n_replicas):
            gr, _s, _k = wordcount.build_graph()
            r = ReplicaScheduler(gr, os.path.join(tmp, f"r{i}"),
                                 name=f"r{i}")
            ship.attach(r)
            replicas.append(r)
        tier = ReadTier(replicas, leader=LeaderReadAdapter(sched))
        ship.start()

        # old-vs-new parity at the promotion horizon, captured INSIDE
        # the promotion (before any new-epoch write can land)
        parity = {}

        def promote_fn(winner, epoch):
            # the winner's published view IS the old leader's durable
            # prefix at the promotion horizon (mirrored bytes, replayed
            # through the same machinery) — the new leader must equal
            # it exactly. The old leader's live in-memory view may be
            # ahead by its final un-synced (never-acked) window; that
            # overhang is reported, not an error.
            ph, pre = winner.view_at(sink.name)
            ns = winner.promote(epoch=epoch, fsync="tick",
                                committer="thread")
            new_view = {kv: w for kv, w in ns.view(sink.name).items()
                        if w != 0}
            diff = 0
            for kv in set(pre) | set(new_view):
                diff = max(diff, abs(pre.get(kv, 0)
                                     - new_view.get(kv, 0)))
            parity.update(horizon=ph, old_ticks=sched._tick,
                          overhang_ticks=sched._tick - ph,
                          max_abs_diff=diff)
            return ns

        coord = FailoverCoordinator(
            replicas, shipper=ship, handle=fe, read_tier=tier,
            confirm_intervals=2, promote_fn=promote_fn)
        coord.publish_metrics()

        # -- sustained writes with fixed ids + resubmit-until-acked
        stop = threading.Event()
        rebound = threading.Event()
        acked_lock = threading.Lock()
        acked: list = []   # (batch_id, words) with a terminal ack
        lost = [0]         # batches given up on (must stay 0)

        def produce(pid):
            rng = np.random.default_rng(1000 + pid)
            seq = 0
            while not stop.is_set():
                words = " ".join(
                    f"w{int(x)}" for x in rng.integers(0, vocab, 24))
                bid = f"p{pid}-{seq}"
                batch = wordcount.ingest_lines([words])
                deadline = time.monotonic() + 60
                ok = False
                while time.monotonic() < deadline:
                    try:
                        res = fe.submit(src, batch,
                                        batch_id=bid).result(timeout=60)
                    except Exception:  # noqa: BLE001 - PumpCrashed /
                        # FrontendClosed mid-failover: wait out the
                        # rebind, then resubmit the SAME id — the WAL
                        # dedup decides exactly-once, not this loop
                        rebound.wait(timeout=30)
                        time.sleep(0.002)
                        continue
                    if res.status in ("applied", "deduped"):
                        ok = True
                        break
                    time.sleep(0.001)
                if ok:
                    with acked_lock:
                        acked.append((bid, words))
                else:
                    lost[0] += 1
                seq += 1

        producers = [threading.Thread(target=produce, args=(pid,))
                     for pid in range(n_producers)]
        for t in producers:
            t.start()
        time.sleep(run_s)

        # -- kill the leader: the committer's next fsync dies
        sched.wal._crash = CrashInjector(at=1, only="wal_before_fsync")
        t_kill = time.perf_counter()
        log(f"failover: leader killed at tick {sched._tick}")

        t_detect = t_promoted = None
        deadline = time.monotonic() + 60
        while time.monotonic() < deadline:
            t0 = time.perf_counter()
            acts = coord.step()
            if any(a["kind"] == "failover_promote" for a in acts):
                t_detect, t_promoted = t0, time.perf_counter()
            if coord.promoted and not coord._pending_rebind:
                break
            time.sleep(0.002)
        assert coord.promoted, "failover never fired"
        rebound.set()
        new_sched = coord.leader_sched
        out["detection_s"] = round(t_detect - t_kill, 4)
        out["promotion_s"] = round(t_promoted - t_detect, 4)
        out["winner"] = coord.winner.name
        out["epoch"] = coord.epoch
        out["drained_bytes"] = coord.drained_bytes

        # first commit window on the new leader, through the SAME
        # frontend handle the producers are already using
        probe = fe.submit(src, wordcount.ingest_lines(["probe words"]),
                          batch_id="probe-1")
        probe.result(timeout=60)
        out["first_window_s"] = round(time.perf_counter() - t_promoted, 4)
        with acked_lock:
            acked.append(("probe-1", "probe words"))
        log(f"failover: {out['winner']} promoted to epoch "
            f"{out['epoch']} — detect {out['detection_s']}s, promote "
            f"{out['promotion_s']}s, first window "
            f"{out['first_window_s']}s")

        # reads survived the swing: the tier now falls back to the new
        # leader for fresh horizons
        res = tier.top_k(sink.name, 10, min_horizon=new_sched._tick,
                         by="value")
        out["post_failover_read_source"] = res.source

        time.sleep(run_s)  # keep writing on the new leader
        stop.set()
        for t in producers:
            t.join()
        fe.flush()
        new_sched.wal.sync()

        # the zombie is fenced: its log refuses appends, counted
        try:
            sched.wal.append({"kind": "tick", "tick": 10 ** 9})
            assert False, "zombie append was accepted"
        except FencedWrite:
            pass
        out["fence_rejected_appends"] = sched.wal.fence_rejected_appends

        # -- zero acked-write loss: every acked batch folded exactly once
        assert lost[0] == 0, f"{lost[0]} producer batch(es) gave up"
        from reflow_tpu.scheduler import DirtyScheduler
        go, so, ko = wordcount.build_graph()
        oracle = DirtyScheduler(go)
        with acked_lock:
            for bid, words in acked:
                oracle.push(so, wordcount.ingest_lines([words]),
                            batch_id=bid)
        oracle.tick()
        want = {kv: w for kv, w in oracle.view(ko.name).items() if w != 0}
        got = {kv: w for kv, w in new_sched.view(sink.name).items()
               if w != 0}
        diff = 0
        for kv in set(want) | set(got):
            diff = max(diff, abs(want.get(kv, 0) - got.get(kv, 0)))
        out["acked_batches"] = len(acked)
        out["acked_loss_max_abs_diff"] = diff
        assert diff == 0, f"acked-write loss: max_abs_diff={diff}"

        out["promotion_horizon"] = parity.get("horizon")
        out["promotion_overhang_ticks"] = parity.get("overhang_ticks")
        out["promotion_parity_max_abs_diff"] = parity.get("max_abs_diff")
        assert parity.get("max_abs_diff") == 0
        out["epoch_gauge"] = REGISTRY.value("failover.epoch", -1)
        out["new_leader_ticks"] = new_sched._tick
        log(f"failover: {len(acked)} acked batch(es), zero loss "
            f"(diff {diff}), promotion parity diff "
            f"{parity.get('max_abs_diff')} at horizon "
            f"{parity.get('horizon')}")
    finally:
        if fe is not None:
            fe.close()
        if coord is not None:
            coord.close()
        if ship is not None:
            ship.close()
        for r in replicas:
            r.close()
        if new_sched is not None:
            new_sched.close()
        shutil.rmtree(tmp, ignore_errors=True)
    return out


# -- chaos-soak mode (REFLOW_BENCH_CHAOS=1) --------------------------------

def run_chaos_bench() -> dict:
    """Replication-over-the-wire chaos soak (docs/guide.md
    "Replication over the wire"): a wordcount leader under sustained
    16-producer writes ships its WAL to N replicas over REAL TCP
    links, every link wrapped in a seeded :class:`WireFaults` /
    ``FaultyTransport`` pair, while a scripted schedule runs:

    A. **probabilistic storm** — drop (both directions), duplicate,
       reorder, frame corruption, payload corruption, delay on every
       link, under full write load;
    B. **scripted faults** — a one-way partition on the last link
       (driven to ``unreachable``, ejected from the read tier) and a
       connection reset on the first (forcing the reconnect path);
    C. **quiesce** — all faults stop; replicas must converge to lag
       <= one commit window within a bounded wall;
    D. **leader kill** — the last link is re-partitioned (so the
       ex-leader keeps undrained bytes for it), the committer is
       killed mid-fsync, and the coordinator runs the epoch-fenced
       promotion; after healing, the ex-leader's shipper is pumped at
       the re-anchored replicas and every shipment it offers must be
       NACKed ``fenced:`` — acked zero times, merged never.

    Producers use fixed batch ids with resubmit-until-acked, so the
    final zero-loss check is exact: the new leader's view equals a
    fresh fold of every acked batch, and every surviving replica's
    view at the shared horizon equals the new leader's with
    ``max_abs_diff == 0``.

    Host-side CPU work; runs on the CPU executor/platform."""
    import shutil
    import tempfile
    import threading

    from reflow_tpu.net import (FaultyTransport, ReconnectPolicy,
                                RemoteFollower, ReplicaServer,
                                TcpTransport)
    from reflow_tpu.obs import REGISTRY
    from reflow_tpu.serve import (CoalesceWindow, FailoverCoordinator,
                                  IngestFrontend, LeaderReadAdapter,
                                  ReadTier, ReplicaScheduler)
    from reflow_tpu.utils.faults import CrashInjector, WireFaults
    from reflow_tpu.wal import DurableScheduler, SegmentShipper
    from reflow_tpu.workloads import wordcount

    smoke = env_flag("REFLOW_BENCH_SMOKE")
    n_replicas = max(2, env_int("REFLOW_BENCH_CHAOS_N", "3"))
    n_producers = 16
    window_ticks = 4
    vocab = 2_000 if smoke else 20_000
    run_s = env_float("REFLOW_BENCH_CHAOS_RUN_S", "0.4" if smoke else "1.2")
    fault_seed = env_int("REFLOW_NET_FAULT_SEED", "0")

    tmp = tempfile.mkdtemp(prefix="reflow-chaos-")
    out = {"replicas": n_replicas, "producers": n_producers,
           "window_ticks": window_ticks, "run_s": run_s, "vocab": vocab,
           "fault_seed": fault_seed}
    fe = ship = coord = new_sched = None
    replicas, servers, links, faults = [], [], [], []
    producers: list = []
    stop = threading.Event()
    rebound = threading.Event()
    try:
        g, src, sink = wordcount.build_graph()
        sched = DurableScheduler(g, wal_dir=os.path.join(tmp, "wal"),
                                 fsync="tick", committer="thread",
                                 segment_bytes=1 << 20)
        fe = IngestFrontend(sched, window=CoalesceWindow(
            max_rows=65536, max_ticks=window_ticks, max_latency_s=0.002))
        ship = SegmentShipper(sched.wal, leader_tick=lambda: sched._tick,
                              poll_s=0.001)
        for i in range(n_replicas):
            gr, _s, _k = wordcount.build_graph()
            r = ReplicaScheduler(gr, os.path.join(tmp, f"r{i}"),
                                 name=f"r{i}")
            srv = ReplicaServer(r, TcpTransport()).start()
            # born quiet so attach()'s subscribe handshake lands; the
            # storm switches on (set_rates) once producers are running
            wf = WireFaults(seed=fault_seed + 17 * i + 1)
            # fast-recovery policy: bench wall-time, not prod defaults
            link = RemoteFollower(
                FaultyTransport(TcpTransport(), wf), srv.address,
                name=f"r{i}",
                policy=ReconnectPolicy(f"r{i}", base_s=0.005,
                                       cap_s=0.05, seed=fault_seed),
                io_timeout_s=0.05)
            ship.attach(link)
            replicas.append(r)
            servers.append(srv)
            links.append(link)
            faults.append(wf)
        tier = ReadTier(replicas, leader=LeaderReadAdapter(sched))
        for r, link in zip(replicas, links):
            tier.bind_link(r, link)
        ship.publish_metrics()
        tier.publish_metrics()
        ship.start()

        parity = {}

        def promote_fn(winner, epoch):
            ph, pre = winner.view_at(sink.name)
            ns = winner.promote(epoch=epoch, fsync="tick",
                                committer="thread")
            new_view = {kv: w for kv, w in ns.view(sink.name).items()
                        if w != 0}
            diff = 0
            for kv in set(pre) | set(new_view):
                diff = max(diff, abs(pre.get(kv, 0)
                                     - new_view.get(kv, 0)))
            parity.update(horizon=ph, max_abs_diff=diff)
            return ns

        coord = FailoverCoordinator(
            replicas, shipper=ship, handle=fe, read_tier=tier,
            confirm_intervals=2, promote_fn=promote_fn,
            drain_timeout_s=0.8)
        coord.publish_metrics()

        # -- sustained writes, fixed ids, resubmit-until-acked
        acked_lock = threading.Lock()
        acked: list = []
        lost = [0]

        def produce(pid):
            rng = np.random.default_rng(1000 + pid)
            seq = 0
            while not stop.is_set():
                words = " ".join(
                    f"w{int(x)}" for x in rng.integers(0, vocab, 24))
                bid = f"p{pid}-{seq}"
                batch = wordcount.ingest_lines([words])
                deadline = time.monotonic() + 60
                ok = False
                while time.monotonic() < deadline:
                    try:
                        res = fe.submit(src, batch,
                                        batch_id=bid).result(timeout=60)
                    except Exception:  # noqa: BLE001 - PumpCrashed /
                        # FrontendClosed mid-failover: wait out the
                        # rebind, resubmit the SAME id; the WAL dedup
                        # decides exactly-once
                        rebound.wait(timeout=30)
                        time.sleep(0.002)
                        continue
                    if res.status in ("applied", "deduped"):
                        ok = True
                        break
                    time.sleep(0.001)
                if ok:
                    with acked_lock:
                        acked.append((bid, words))
                else:
                    lost[0] += 1
                seq += 1

        producers.extend(threading.Thread(target=produce, args=(pid,))
                         for pid in range(n_producers))
        for t in producers:
            t.start()

        # -- phase A: probabilistic storm under load
        for wf in faults:
            wf.set_rates(drop_c2s=0.04, drop_s2c=0.04, dup=0.04,
                         reorder=0.04, corrupt_frame=0.01,
                         corrupt_payload=0.01, delay_p=0.08,
                         delay_s=0.002)
        time.sleep(run_s)

        # -- phase B: scripted one-way partition + connection reset
        target = n_replicas - 1
        faults[target].partition("c2s")
        faults[0].reset_once(1)
        deadline = time.monotonic() + 10
        while (links[target].conn_state != "unreachable"
               and time.monotonic() < deadline):
            time.sleep(0.005)
        out["partition_conn_state"] = links[target].conn_state
        # a few routed reads eject the dead-linked replica
        for _ in range(2 * n_replicas):
            tier.top_k(sink.name, 5, by="value")
        out["ejected_during_partition"] = any(
            r is replicas[target] for r in tier.ejected_replicas)
        time.sleep(0.1)

        # -- phase C: faults stop; converge to <= one commit window
        for wf in faults:
            wf.quiesce()
        t_quiesce = time.perf_counter()
        deadline = time.monotonic() + 30
        while time.monotonic() < deadline:
            if max(r.lag_ticks() for r in replicas) <= window_ticks:
                break
            time.sleep(0.005)
        out["converge_s"] = round(time.perf_counter() - t_quiesce, 4)
        lag_after = max(r.lag_ticks() for r in replicas)
        out["lag_after_quiesce_ticks"] = lag_after
        assert lag_after <= window_ticks, \
            f"lag {lag_after} > one commit window ({window_ticks})"
        # routed reads probe the healed link back into rotation
        for _ in range(2 * n_replicas):
            tier.top_k(sink.name, 5, by="value")
        out["tier_ejects"] = tier.ejects
        out["tier_restores"] = tier.restores
        log(f"chaos: converged {out['converge_s']}s after quiesce "
            f"(lag {lag_after}), ejects={tier.ejects} "
            f"restores={tier.restores}")

        # -- phase D: re-partition the last link, kill the leader
        faults[target].partition("c2s")
        time.sleep(0.05)  # writes land that the ex-leader can't drain
        # stop the pump thread: promote_now still drains via pump_once,
        # and a threadless old shipper means the coordinator's new
        # shipper starts threadless too — so the partitioned replica
        # stays BEHIND the old horizon until we pump it, making the
        # ex-leader's post-fence offer (and its fenced NACK) a
        # deterministic exchange instead of a race against catch-up
        ship.stop()
        sched.wal._crash = CrashInjector(at=1, only="wal_before_fsync")
        t_kill = time.perf_counter()
        t_detect = t_promoted = None
        deadline = time.monotonic() + 60
        while time.monotonic() < deadline:
            t0 = time.perf_counter()
            acts = coord.step()
            if any(a["kind"] == "failover_promote" for a in acts):
                t_detect, t_promoted = t0, time.perf_counter()
            if coord.promoted and not coord._pending_rebind:
                break
            time.sleep(0.002)
        assert coord.promoted, "failover never fired"
        rebound.set()
        new_sched = coord.leader_sched
        out["detection_s"] = round(t_detect - t_kill, 4)
        out["promotion_s"] = round(t_promoted - t_detect, 4)
        out["winner"] = coord.winner.name
        out["epoch"] = coord.epoch
        out["drained_bytes"] = coord.drained_bytes
        out["promotion_parity_max_abs_diff"] = parity.get("max_abs_diff")
        assert parity.get("max_abs_diff") == 0
        log(f"chaos: {out['winner']} promoted to epoch {out['epoch']} "
            f"— detect {out['detection_s']}s, promote "
            f"{out['promotion_s']}s")

        # the partitioned ex-leader heals and keeps shipping its OLD
        # epoch at the re-anchored replicas: every offer must be NACKed
        # fenced, ACKed never (the shipments counter is ACKs only)
        faults[target].heal()
        acks_before = ship.shipments
        deadline = time.monotonic() + 10
        while ship.fence_nacks == 0 and time.monotonic() < deadline:
            ship.pump_once()
            time.sleep(0.005)
        out["ex_leader_fence_nacks"] = ship.fence_nacks
        out["ex_leader_post_fence_acks"] = ship.shipments - acks_before
        assert ship.fence_nacks >= 1, "ex-leader was never fenced"
        assert ship.shipments == acks_before, \
            "a post-fence shipment from the ex-leader was ACKed"

        # now let the new epoch's shipper catch the survivors up
        coord.new_shipper.start()

        # -- keep writing on the new leader, then settle and check
        time.sleep(run_s / 2)
        stop.set()
        for t in producers:
            t.join()
        fe.flush()
        new_sched.wal.sync()
        survivors = [r for r in replicas if not r.promoted]
        deadline = time.monotonic() + 60
        while (any(r.published_horizon() != new_sched._tick
                   for r in survivors)
               and time.monotonic() < deadline):
            time.sleep(0.005)

        # zero acked-write loss: every acked batch folded exactly once
        assert lost[0] == 0, f"{lost[0]} producer batch(es) gave up"
        from reflow_tpu.scheduler import DirtyScheduler
        go, so, ko = wordcount.build_graph()
        oracle = DirtyScheduler(go)
        with acked_lock:
            for bid, words in acked:
                oracle.push(so, wordcount.ingest_lines([words]),
                            batch_id=bid)
        oracle.tick()
        want = {kv: w for kv, w in oracle.view(ko.name).items() if w != 0}
        got = {kv: w for kv, w in new_sched.view(sink.name).items()
               if w != 0}
        diff = 0
        for kv in set(want) | set(got):
            diff = max(diff, abs(want.get(kv, 0) - got.get(kv, 0)))
        out["acked_batches"] = len(acked)
        out["acked_loss_max_abs_diff"] = diff
        assert diff == 0, f"acked-write loss: max_abs_diff={diff}"

        # exact parity at equal horizons on every surviving replica
        parity_diff = 0
        for r in survivors:
            rh, rv = r.view_at(sink.name)
            assert rh == new_sched._tick, (r.name, rh, new_sched._tick)
            for kv in set(got) | set(rv):
                parity_diff = max(
                    parity_diff, abs(got.get(kv, 0) - rv.get(kv, 0)))
        out["parity_max_abs_diff"] = parity_diff
        assert parity_diff == 0

        # wire-level accounting: the storm really exercised the paths
        out["retransmit_bytes"] = ship.retransmit_bytes
        out["link_stalls"] = ship.link_stalls
        out["ship_nacks"] = ship.nacks
        out["reconnects_total"] = sum(l.reconnects_total for l in links)
        out["fault_stats"] = {
            f"r{i}": dict(wf.stats) for i, wf in enumerate(faults)}
        out["conn_state_gauge"] = REGISTRY.value(
            "replica.r0.conn_state", "?")
        assert ship.retransmit_bytes > 0, \
            "no retransmissions: the WAL-as-retransmit path never ran"
        assert out["reconnects_total"] >= 1, \
            "no reconnects: the backoff path never ran"
        log(f"chaos: {len(acked)} acked batch(es), zero loss, parity "
            f"diff {parity_diff}; {ship.retransmit_bytes} retransmit "
            f"byte(s), {out['reconnects_total']} reconnect(s), "
            f"{ship.nacks} nack(s), fenced ex-leader "
            f"({ship.fence_nacks} fence nack(s))")
    finally:
        # producers must see both events even on an assert mid-flight,
        # or their non-daemon threads outlive the bench
        stop.set()
        rebound.set()
        for t in producers:
            t.join(timeout=30)
        if fe is not None:
            fe.close()
        if coord is not None:
            coord.close()
        if ship is not None:
            ship.close()
        for srv in servers:
            srv.close()
        for r in replicas:
            r.close()
        if new_sched is not None:
            new_sched.close()
        shutil.rmtree(tmp, ignore_errors=True)
    return out


# -- fleet-telemetry mode (REFLOW_BENCH_FLEETOBS=1) ------------------------

def run_fleetobs_bench() -> dict:
    """Fleet-telemetry-plane numbers (docs/guide.md "Fleet telemetry"),
    two parts on the replicated topology (leader + N replicas over
    real TCP, 16 producers):

    A. **write-path overhead** — the same fixed work (16 producers x K
       batches through the frontend, WAL shipped to every replica) run
       with the telemetry plane fully off vs fully on (tracing +
       per-node registries + per-node :class:`TelemetryShipper` at the
       production ship interval streaming to a live
       :class:`FleetAggregator` over TCP), best-of-2 walls per mode;
       acceptance: overhead < 3% on an uncontended host. Like the obs
       bench's bound this is *recorded*, not asserted — on a shared
       1-core CI box the wall noise between identical legs dwarfs 3% —
       while the structural proofs in part B are hard asserts.
    B. **fleet proofs under chaos** — the telemetry-enabled topology
       with every data link behind seeded :class:`WireFaults` runs a
       storm, then a partition/heal cycle on the last data link; after
       the heal the trace rings are reset so every causal chain in the
       export is post-heal evidence (``trace_inspect
       --require-chain ship_segment,net_send,replica_replay`` >= 1).
       At quiesce the aggregator's per-node horizons / lag / spread
       must EQUAL ground truth read directly off the replicas. Then
       the telemetry link of one node is partitioned: the aggregator
       must keep answering ``fetch_fleet`` with that node stale-marked
       (never an error), and recover once the link heals.

    Host-side CPU work; runs on the CPU executor/platform."""
    import importlib.util
    import shutil
    import tempfile
    import threading

    from reflow_tpu import obs
    from reflow_tpu.net import (FaultyTransport, ReconnectPolicy,
                                RemoteFollower, ReplicaServer,
                                TcpTransport)
    from reflow_tpu.obs.fleet import FleetAggregator, TelemetryShipper
    from reflow_tpu.obs.wire import TelemetryLink, TelemetryServer
    from reflow_tpu.serve import (CoalesceWindow, IngestFrontend,
                                  LeaderReadAdapter, ReadTier,
                                  ReplicaScheduler)
    from reflow_tpu.utils.faults import WireFaults
    from reflow_tpu.wal import DurableScheduler, SegmentShipper
    from reflow_tpu.workloads import wordcount

    smoke = env_flag("REFLOW_BENCH_SMOKE")
    n_replicas = max(2, env_int("REFLOW_BENCH_CHAOS_N", "3"))
    n_prod = 16
    rows_per_batch = 8
    per_producer = env_int("REFLOW_BENCH_FLEETOBS_BATCHES",
                           "160" if smoke else "320")
    run_s = env_float("REFLOW_BENCH_CHAOS_RUN_S",
                      "0.3" if smoke else "0.8")
    fault_seed = env_int("REFLOW_NET_FAULT_SEED", "0")
    ship_interval = 0.05
    window_ticks = 4

    out = {"replicas": n_replicas, "producers": n_prod,
           "per_producer_batches": per_producer,
           "rows_per_batch": rows_per_batch, "run_s": run_s,
           "fault_seed": fault_seed}
    tmp = tempfile.mkdtemp(prefix="reflow-fleetobs-")

    def make_lines(producer: int, j: int) -> list:
        rng = np.random.default_rng(producer * 100_003 + j)
        return [" ".join(f"w{int(x)}"
                         for x in rng.integers(0, 1000, rows_per_batch))]

    # -- part A: fixed-work A/B on the clean replicated topology ----------

    def run_fixed(root: str, telemetry: bool) -> float:
        """One fixed-work pass; rows/s. Identical topology both ways —
        only the telemetry plane differs."""
        fe = ship = tsrv = agg = sched = None
        replicas, servers, shippers, regs = [], [], [], []
        try:
            g, src, _sink = wordcount.build_graph()
            sched = DurableScheduler(g, wal_dir=os.path.join(root, "wal"),
                                     fsync="tick", committer="thread",
                                     segment_bytes=1 << 20)
            fe = IngestFrontend(sched, window=CoalesceWindow(
                max_rows=65536, max_ticks=window_ticks,
                max_latency_s=0.002))
            ship = SegmentShipper(sched.wal,
                                  leader_tick=lambda: sched._tick,
                                  poll_s=0.001)
            for i in range(n_replicas):
                gr, _s, _k = wordcount.build_graph()
                r = ReplicaScheduler(gr, os.path.join(root, f"r{i}"),
                                     name=f"r{i}")
                srv = ReplicaServer(r, TcpTransport()).start()
                link = RemoteFollower(
                    TcpTransport(), srv.address, name=f"r{i}",
                    policy=ReconnectPolicy(f"r{i}", base_s=0.005,
                                           cap_s=0.05, seed=fault_seed),
                    io_timeout_s=0.2)
                ship.attach(link)
                replicas.append(r)
                servers.append(srv)
            if telemetry:
                obs.trace.reset()
                obs.enable()
                agg = FleetAggregator(retention=64, stale_after_s=2.0)
                tsrv = TelemetryServer(agg, TcpTransport()).start()
                reg_leader = obs.MetricsRegistry()
                fe.publish_metrics(reg_leader)
                ship.publish_metrics(reg_leader)
                regs.append(("leader", reg_leader))
                for i, r in enumerate(replicas):
                    reg_r = obs.MetricsRegistry()
                    r.publish_metrics(reg_r)
                    regs.append((f"r{i}", reg_r))
                for node, reg in regs:
                    # production-default ship interval: the A/B legs
                    # price the plane as deployed, not the fast beat
                    # part B uses to exercise staleness
                    sh = TelemetryShipper(
                        reg, TcpTransport(), tsrv.address, node=node,
                        policy=ReconnectPolicy(f"tele/{node}",
                                               base_s=0.005, cap_s=0.05,
                                               seed=fault_seed),
                        io_timeout_s=0.5)
                    sh.publish_metrics()
                    shippers.append(sh.start())
            else:
                obs.disable()
                obs.trace.reset()
            ship.start()

            tickets: list = []
            tk_lock = threading.Lock()

            def produce(pid, fe=fe, src=src):
                mine = [fe.submit(src, wordcount.ingest_lines(
                    make_lines(pid, j))) for j in range(per_producer)]
                with tk_lock:
                    tickets.extend(mine)

            threads = [threading.Thread(target=produce, args=(pid,))
                       for pid in range(n_prod)]
            t0 = time.perf_counter()
            for t in threads:
                t.start()
            for t in threads:
                t.join()
            fe.flush()
            wall = time.perf_counter() - t0
            assert all(t.result(timeout=30).applied for t in tickets)
            return n_prod * per_producer * rows_per_batch / wall
        finally:
            for sh in shippers:
                sh.close()
            if tsrv is not None:
                tsrv.close()
            if agg is not None:
                agg.close()
            if fe is not None:
                fe.close()
            if ship is not None:
                ship.close()
            for srv in servers:
                srv.close()
            for r in replicas:
                r.close()
            if sched is not None:
                sched.wal.close()
            obs.disable()

    try:
        rate_off = max(run_fixed(os.path.join(tmp, f"off{k}"), False)
                       for k in range(2))
        rate_on = max(run_fixed(os.path.join(tmp, f"on{k}"), True)
                      for k in range(2))
        out["disabled_rows_per_s"] = round(rate_off)
        out["enabled_rows_per_s"] = round(rate_on)
        overhead = 1.0 - rate_on / rate_off
        out["fleetobs_overhead_frac"] = round(overhead, 4)
        out["fleetobs_overhead_lt_3pct"] = overhead < 0.03
        log(f"fleetobs: off {rate_off:.0f} rows/s, on {rate_on:.0f} "
            f"rows/s (overhead {100 * overhead:.2f}%)")

        # -- part B: fleet proofs on the faulted topology ------------------
        fe = ship = tsrv = agg = probe = sched = None
        replicas, servers, links, faults = [], [], [], []
        shippers, tele_faults, producers = [], [], []
        stop = threading.Event()
        try:
            obs.trace.reset()
            obs.enable()
            g, src, sink = wordcount.build_graph()
            sched = DurableScheduler(g, wal_dir=os.path.join(tmp, "wal"),
                                     fsync="tick", committer="thread",
                                     segment_bytes=1 << 20)
            fe = IngestFrontend(sched, window=CoalesceWindow(
                max_rows=65536, max_ticks=window_ticks,
                max_latency_s=0.002))
            ship = SegmentShipper(sched.wal,
                                  leader_tick=lambda: sched._tick,
                                  poll_s=0.001)
            for i in range(n_replicas):
                gr, _s, _k = wordcount.build_graph()
                r = ReplicaScheduler(gr, os.path.join(tmp, f"br{i}"),
                                     name=f"r{i}")
                srv = ReplicaServer(r, TcpTransport()).start()
                wf = WireFaults(seed=fault_seed + 17 * i + 1)
                link = RemoteFollower(
                    FaultyTransport(TcpTransport(), wf), srv.address,
                    name=f"r{i}",
                    policy=ReconnectPolicy(f"r{i}", base_s=0.005,
                                           cap_s=0.05, seed=fault_seed),
                    io_timeout_s=0.05)
                ship.attach(link)
                replicas.append(r)
                servers.append(srv)
                links.append(link)
                faults.append(wf)
            tier = ReadTier(replicas, leader=LeaderReadAdapter(sched))
            for r, link in zip(replicas, links):
                tier.bind_link(r, link)

            # the telemetry plane: one registry + shipper per node,
            # every telemetry link behind its OWN WireFaults pair
            agg = FleetAggregator(retention=64, stale_after_s=0.35)
            tsrv = TelemetryServer(agg, TcpTransport()).start()
            reg_leader = obs.MetricsRegistry()
            fe.publish_metrics(reg_leader)
            ship.publish_metrics(reg_leader)
            tier.publish_metrics(reg_leader)
            node_regs = [("leader", reg_leader)]
            for i, r in enumerate(replicas):
                reg_r = obs.MetricsRegistry()
                r.publish_metrics(reg_r)
                node_regs.append((f"r{i}", reg_r))
            for node, reg in node_regs:
                tf = WireFaults(seed=fault_seed + 91 + len(tele_faults))
                sh = TelemetryShipper(
                    reg, FaultyTransport(TcpTransport(), tf),
                    tsrv.address, node=node, interval_s=ship_interval,
                    policy=ReconnectPolicy(f"tele/{node}", base_s=0.005,
                                           cap_s=0.05, seed=fault_seed),
                    io_timeout_s=0.25)
                sh.publish_metrics()
                tele_faults.append(tf)
                shippers.append(sh.start())
            ship.start()

            def produce(pid):
                rng = np.random.default_rng(1000 + pid)
                seq = 0
                while not stop.is_set():
                    words = " ".join(
                        f"w{int(x)}" for x in rng.integers(0, 1000, 24))
                    bid = f"p{pid}-{seq}"
                    batch = wordcount.ingest_lines([words])
                    deadline = time.monotonic() + 60
                    while time.monotonic() < deadline:
                        res = fe.submit(src, batch,
                                        batch_id=bid).result(timeout=60)
                        if res.status in ("applied", "deduped"):
                            break
                        time.sleep(0.001)
                    seq += 1

            producers.extend(
                threading.Thread(target=produce, args=(pid,))
                for pid in range(n_prod))
            for t in producers:
                t.start()

            # storm on every data link, then partition + heal the last
            for wf in faults:
                wf.set_rates(drop_c2s=0.03, drop_s2c=0.03, dup=0.03,
                             reorder=0.03, corrupt_frame=0.01,
                             delay_p=0.05, delay_s=0.002)
            time.sleep(run_s)
            target = n_replicas - 1
            faults[target].partition("c2s")
            time.sleep(0.15)
            faults[target].heal()
            for wf in faults:
                wf.quiesce()
            # post-heal evidence window: reset the rings so every
            # complete causal chain in the export was minted AFTER the
            # partition healed
            obs.trace.reset()
            time.sleep(run_s / 2)
            stop.set()
            for t in producers:
                t.join(timeout=60)
            fe.flush()
            sched.wal.sync()
            deadline = time.monotonic() + 30
            while (any(r.published_horizon() != sched._tick
                       for r in replicas)
                   and time.monotonic() < deadline):
                time.sleep(0.005)
            lag_after = max(r.lag_ticks() for r in replicas)
            out["lag_after_quiesce_ticks"] = lag_after
            assert lag_after == 0, f"replicas never converged: {lag_after}"

            # (b) aggregator vs ground truth at quiesce: force fresh
            # snapshots (twice, spaced, so the qps window exists)
            for _ in range(2 * n_replicas):
                tier.top_k(sink.name, 5, by="value")
            for sh in shippers:
                sh.ship_once()
            time.sleep(0.08)
            for _ in range(2 * n_replicas):
                tier.top_k(sink.name, 5, by="value")
            deadline = time.monotonic() + 10
            while time.monotonic() < deadline:
                if all(sh.ship_once() for sh in shippers):
                    break
                time.sleep(0.02)
            truth = {r.name: r.published_horizon() for r in replicas}
            snap = agg.fleet_snapshot()
            agg_h = {n: e["horizon"] for n, e in snap["nodes"].items()
                     if n != "leader"}
            assert agg_h == truth, (agg_h, truth)
            assert all(e["lag_ticks"] == 0
                       for n, e in snap["nodes"].items()
                       if n != "leader"), snap["nodes"]
            spread_truth = max(truth.values()) - min(truth.values())
            out["lag_spread_agg"] = snap["gauges"]["lag_spread"]
            out["lag_spread_truth"] = spread_truth
            assert snap["gauges"]["lag_spread"] == spread_truth
            assert snap["gauges"]["epoch_agree"] is True
            out["aggregate_read_qps"] = snap["gauges"][
                "aggregate_read_qps"]
            assert out["aggregate_read_qps"] is not None, \
                "fleet read-qps window never formed"
            out["fleet_nodes"] = snap["gauges"]["nodes_total"]
            assert out["fleet_nodes"] == n_replicas + 1
            log(f"fleetobs: aggregator horizons == ground truth "
                f"{truth}, spread {spread_truth}, "
                f"qps {out['aggregate_read_qps']}")

            # (c) causal chains survived the partition/heal cycle
            trace_path = os.path.join(tmp, "fleet_trace.json")
            obs.export_chrome_trace(trace_path)
            spec = importlib.util.spec_from_file_location(
                "trace_inspect", os.path.join(
                    os.path.dirname(os.path.abspath(__file__)),
                    "tools", "trace_inspect.py"))
            ti = importlib.util.module_from_spec(spec)
            spec.loader.exec_module(ti)
            causal = ti.inspect(trace_path, require_chain=[
                "ship_segment", "net_send", "replica_replay"])["causal"]
            out["post_heal_chains"] = causal["chains"]
            out["post_heal_complete_chains"] = causal["complete_chains"]
            out["post_heal_required_chains"] = causal["required_chains"]
            assert causal["required_chains"] >= 1, \
                "no post-heal causal chain spans ship->send->replay"
            keep_trace = env_str("REFLOW_TRACE_OUT",
                                 "/tmp/reflow_fleet_trace.json")
            shutil.copyfile(trace_path, keep_trace)
            out["trace_file"] = keep_trace
            log(f"fleetobs: {causal['required_chains']} post-heal "
                f"causal chain(s) ship_segment->net_send->"
                f"replica_replay -> {keep_trace}")

            # (d) telemetry-link partition: the aggregator keeps
            # serving with r0 stale-marked, then recovers on heal
            tele_faults[1].partition("c2s")  # node_regs[1] == r0
            deadline = time.monotonic() + 15
            stale = []
            while time.monotonic() < deadline:
                stale = agg.stale_nodes()
                if "r0" in stale:
                    break
                time.sleep(0.02)
            assert "r0" in stale, "telemetry partition never went stale"
            probe = TelemetryLink(TcpTransport(), tsrv.address,
                                  node="bench-probe", io_timeout_s=2.0)
            during = probe.fetch_fleet()
            assert during is not None, \
                "aggregator stopped serving during telemetry partition"
            assert during["nodes"]["r0"]["stale"] is True
            assert any(a.startswith("stale: r0")
                       for a in during["alerts"]), during["alerts"]
            out["stale_during_partition"] = sorted(
                n for n, e in during["nodes"].items() if e["stale"])
            r0_shipper = shippers[1]
            assert r0_shipper.dropped > 0, \
                "partitioned shipper never dropped a snapshot"
            out["telemetry_dropped_r0"] = r0_shipper.dropped
            tele_faults[1].heal()
            deadline = time.monotonic() + 15
            while time.monotonic() < deadline:
                if "r0" not in agg.stale_nodes():
                    break
                time.sleep(0.02)
            after = probe.fetch_fleet()
            assert after is not None \
                and after["nodes"]["r0"]["stale"] is False, \
                "telemetry link never recovered after heal"
            out["telemetry_partition_recovered"] = True
            out["snapshots_total"] = agg.snapshots_total
            fleet_path = "/tmp/reflow_fleet_snapshot.json"
            with open(fleet_path, "w") as f:
                json.dump(after, f, indent=2, sort_keys=True)
            out["fleet_snapshot_file"] = fleet_path
            log(f"fleetobs: aggregator served through the telemetry "
                f"partition (stale={out['stale_during_partition']}, "
                f"{r0_shipper.dropped} dropped) and recovered "
                f"-> {fleet_path}")
        finally:
            stop.set()
            for t in producers:
                t.join(timeout=30)
            if probe is not None:
                probe.close()
            for sh in shippers:
                sh.close()
            if tsrv is not None:
                tsrv.close()
            if agg is not None:
                agg.close()
            if fe is not None:
                fe.close()
            if ship is not None:
                ship.close()
            for srv in servers:
                srv.close()
            for r in replicas:
                r.close()
            if sched is not None:
                sched.wal.close()
            obs.disable()
            obs.trace.reset()
    finally:
        shutil.rmtree(tmp, ignore_errors=True)
    return out


# -- multi-process mode (REFLOW_BENCH_MULTIPROC=1) -------------------------

def run_multiproc_bench() -> dict:
    """The multi-controller leg as real OS processes (docs/guide.md
    "Multi-process deployment"): a leader + N replica + M producer
    *process* fleet under a kill -9 storm.

    Storm script: spawn the fleet (every child ships telemetry to the
    parent's FleetAggregator), let the producers pump over the
    ingestion RPC, then kill -9 every replica in turn (respawn each
    over its state directory; it recovers from its mirrored WAL and
    rejoins through the cross-process horizon barrier), then kill -9
    the *leader* and drive a FailoverCoordinator whose candidates are
    the replica processes — the winner promotes in-child and starts
    serving ingestion; producers reconnect, resubmit their in-doubt
    batches, and the dedup mirror keeps them exactly-once.

    Hard asserts: zero acked-write loss (a DirtyScheduler oracle
    refolds every acked batch — content regenerated from (producer,
    seq) alone — and must equal the new leader's wire-read view
    exactly); exact parity at equal horizons on every surviving
    replica; the promotion happened (epoch 1, winner is a replica);
    every producer exited with an empty in-doubt set; the reconnect /
    resubmit paths actually fired; the fleet aggregator saw every
    process. Host-side CPU work; children run with JAX_PLATFORMS=cpu.
    """
    import shutil
    import tempfile

    from reflow_tpu.proc import ProcHarness
    from reflow_tpu.proc.worker import producer_batch_words
    from reflow_tpu.proc.harness import ControlClient
    from reflow_tpu.scheduler import DirtyScheduler
    from reflow_tpu.workloads import wordcount

    smoke = env_flag("REFLOW_BENCH_SMOKE")
    n_replicas = max(2, env_int("REFLOW_BENCH_MULTIPROC_N", "3"))
    n_prod = max(1, env_int("REFLOW_BENCH_MULTIPROC_PRODUCERS", "4"))
    run_s = env_float("REFLOW_BENCH_MULTIPROC_RUN_S",
                      "0.6" if smoke else "1.5")

    # an oversubscribed host (fleet processes > cores) needs paced
    # producers, or the spin-looping fleet starves a recovering child
    n_procs = 1 + n_replicas + n_prod
    pace_s = 0.02 if (os.cpu_count() or 1) < n_procs else 0.0
    out = {"replicas": n_replicas, "producers": n_prod, "run_s": run_s,
           "producer_pace_s": pace_s}
    root = tempfile.mkdtemp(prefix="reflow-multiproc-")
    h = ProcHarness(root)       # roles pin themselves to the cpu
    try:
        h.spawn_leader(fsync="tick", epoch=0)
        rnames = [f"r{i}" for i in range(n_replicas)]
        for nm in rnames:
            h.spawn_replica(nm)
        h.attach_replicas()
        for i in range(n_prod):
            h.spawn_producer(f"p{i}", index=i, pace_s=pace_s)
        fleet_target = 1 + n_replicas + n_prod
        out["fleet_nodes_expected"] = fleet_target
        out["fleet_nodes_seen"] = (
            h.aggregator.await_nodes(fleet_target, timeout_s=15.0))
        assert out["fleet_nodes_seen"], \
            f"fleet aggregator saw {h.aggregator.node_count()} nodes, " \
            f"wanted {fleet_target}"
        time.sleep(run_s)

        # -- kill -9 storm over the replica tier, one at a time -------
        for nm in rnames:
            h.kill9(nm)
            time.sleep(0.1)
            h.respawn(nm)
            h.attach_replicas([nm])
            h.barrier(timeout_s=60.0)  # the respawn rejoins the cut
        time.sleep(run_s / 2)

        # -- then the leader: cross-process failover ------------------
        coord = h.coordinator(epoch=0, confirm_intervals=2,
                              drain_timeout_s=10.0)
        h.kill9("leader")
        t_kill = time.monotonic()
        promote_evt = None
        now = 0.0
        while promote_evt is None and time.monotonic() - t_kill < 60.0:
            for e in coord.step(now):
                if e.get("kind") == "failover_promote":
                    promote_evt = e
            now += 1.0
            time.sleep(0.02)
        assert promote_evt is not None, "leader death never promoted"
        out["promotion_s"] = time.monotonic() - t_kill
        out["winner"] = promote_evt["winner"]
        out["epoch"] = promote_evt["epoch"]
        out["drained_bytes"] = promote_evt["drained_bytes"]
        assert out["winner"] in rnames
        assert out["epoch"] == 1
        assert h.leader_name == out["winner"]

        # producers reconnect + resubmit against the recovered mirror
        time.sleep(run_s)

        # -- quiesce: stop producers (each drains its in-flight batch
        # to a terminal ack), then flush the new leader over the wire
        prod_exits = []
        for i in range(n_prod):
            st = h.child(f"p{i}").stop()
            assert st is not None and st.get("ok"), \
                f"producer p{i} died dirty: {st!r}"
            prod_exits.append(st)
        out["reconnects_total"] = sum(s["reconnects"]
                                      for s in prod_exits)
        out["resubmits_total"] = sum(s["resubmits"] for s in prod_exits)
        out["deduped_total"] = sum(s["deduped"] for s in prod_exits)
        for st in prod_exits:
            assert st["in_doubt"] == [], \
                f"{st['name']} exited in doubt: {st['in_doubt']}"
        assert out["reconnects_total"] >= n_prod, \
            "the leader kill never forced a producer reconnect"
        assert out["resubmits_total"] >= 1

        g, src, sink = wordcount.build_graph()
        ingest = ControlClient(h.ingest_address, io_timeout_s=30.0)
        ingest.call("flush", 20.0)
        _, leader_tick, leader_view = ingest.call("view", sink.name)

        # zero acked-write loss: refold every acked batch from
        # (producer index, seq) alone — the content is deterministic
        oracle = DirtyScheduler(g)
        acked_batches = 0
        for i, st in enumerate(prod_exits):
            for seq, _status in st["acked"]:
                words = " ".join(producer_batch_words(i, seq))
                oracle.push(src, wordcount.ingest_lines([words]),
                            batch_id=f"p{i}-{seq}")
                acked_batches += 1
        oracle.tick()
        want = {kv: w for kv, w in oracle.view(sink.name).items()
                if w != 0}
        got = {kv: w for kv, w in leader_view.items() if w != 0}
        diff = 0
        for kv in set(want) | set(got):
            diff = max(diff, abs(want.get(kv, 0) - got.get(kv, 0)))
        out["acked_batches"] = acked_batches
        out["acked_loss_max_abs_diff"] = diff
        assert diff == 0, f"acked-write loss: max_abs_diff={diff}"

        # exact parity at equal horizons on every surviving replica,
        # read over each child's own wire protocol
        survivors = [nm for nm in rnames if nm != h.leader_name]
        h.barrier(names=survivors, min_horizon=leader_tick,
                  timeout_s=30.0)
        parity_diff = 0
        for nm in survivors:
            _, rh, rv = h.control(nm).call("view", sink.name)
            assert rh == leader_tick, (nm, rh, leader_tick)
            for kv in set(got) | set(rv):
                parity_diff = max(
                    parity_diff, abs(got.get(kv, 0) - rv.get(kv, 0)))
        out["parity_max_abs_diff"] = parity_diff
        assert parity_diff == 0

        out["leader_tick"] = leader_tick
        out["kills"] = h.kills
        out["respawns"] = h.respawns
        assert h.kills == n_replicas + 1 and h.respawns == n_replicas
    finally:
        h.close()
        shutil.rmtree(root, ignore_errors=True)
    return out


# -- end-to-end tracing mode (REFLOW_BENCH_E2ETRACE=1) ---------------------

def run_e2etrace_bench() -> dict:
    """Follow-the-write under chaos (docs/guide.md "End-to-end tracing
    & flight recorder"): the multi-process topology — a leader + 2
    replica + N producer *processes* over the ingestion RPC, live wire
    subscribers pumped in the parent — with tracing AND flight
    recorders on in every child, then kill -9 of a replica and of the
    leader mid-run (cross-process promotion, producers and subscribers
    retargeted).

    Hard asserts, all structural:

    - **full chains** — merging every clean-exit child's exported
      trace plus the parent's own onto one timeline
      (``trace_inspect`` multi-file, ``baseTimeS``-anchored), at least
      one sampled write's causal group carries all nine links
      ``producer_submit -> rpc_admit -> admission -> wal_append ->
      ship_segment -> net_send -> replica_replay -> sub_fanout ->
      sub_deliver``, and at least one ``producer_submit`` was minted
      in the post-promotion epoch (the chain survived the failover);
    - **freshness tiles** — the ack->deliver decomposition of the
      full chains sums to their end-to-end latency within 10%;
    - **flight recordings survive kill -9** — the dead leader's disk
      corner (and the killed replica's archived ``.prev`` incarnation)
      merge via ``tools/reflow_flight`` into a timeline that carries
      the failover evidence, even though those processes never flushed
      a trace export;
    - **wire compat** — with tracing off, ``SubmitReq`` /
      ``SubmitAck`` / ``DeltaFrame`` wire forms pickle byte-identically
      to the pre-trace protocol (the trailing-``cause`` trim).

    Host-side CPU work; children run with ``JAX_PLATFORMS=cpu``.
    """
    import importlib.util
    import pickle
    import shutil
    import tempfile
    import threading

    from reflow_tpu import obs
    from reflow_tpu.net.transport import TcpTransport
    from reflow_tpu.proc import ProcHarness
    from reflow_tpu.proc.harness import ControlClient
    from reflow_tpu.serve.rpc import SubmitAck, SubmitReq, _trim
    from reflow_tpu.subs.client import Subscriber
    from reflow_tpu.subs.query import DeltaFrame, frames_to_wire
    from reflow_tpu.workloads import wordcount

    smoke = env_flag("REFLOW_BENCH_SMOKE")
    n_replicas = 2
    n_prod = max(1, env_int("REFLOW_BENCH_E2ETRACE_PRODUCERS",
                            "4" if smoke else "16"))
    run_s = env_float("REFLOW_BENCH_E2ETRACE_RUN_S",
                      "0.6" if smoke else "1.5")
    n_procs = 2 + n_replicas + n_prod  # + the parent pumping subs
    pace_s = 0.02 if (os.cpu_count() or 1) < n_procs else 0.0
    out = {"replicas": n_replicas, "producers": n_prod, "run_s": run_s,
           "producer_pace_s": pace_s}

    # -- wire compat: tracing-off frames byte-identical -----------------
    # (in-process, before the parent enables tracing: the trim must
    # reduce unstamped requests/acks/frames to the exact pre-trace
    # pickle bytes, and a stamped frame must still parse one-sided)
    req = SubmitReq("b-0", "words", ("payload",), 5.0)
    assert pickle.dumps(_trim(tuple(req))) == \
        pickle.dumps(("b-0", "words", ("payload",), 5.0))
    ack = SubmitAck("b-0", "applied", ("r",), None)
    assert pickle.dumps(_trim(tuple(ack))) == \
        pickle.dumps(("b-0", "applied", ("r",), None))
    frame = DeltaFrame(0, 4, "view", ((("k", "v"), 1),), False)
    assert pickle.dumps(frames_to_wire([frame])) == \
        pickle.dumps(((0, 4, "view", ((("k", "v"), 1),), False),))
    stamped = DeltaFrame(0, 4, "view", (), False, ("n#0#1",))
    assert frames_to_wire([stamped])[0][-1] == ("n#0#1",)
    out["wire_compat_identical"] = True

    root = tempfile.mkdtemp(prefix="reflow-e2etrace-")
    keep_dir = os.path.join(tempfile.gettempdir(),
                            "reflow_e2etrace_traces")
    child_env = {"REFLOW_TRACE": "1", "REFLOW_FLIGHT": "1"}
    h = ProcHarness(root, child_env=child_env)
    obs.trace.reset()
    obs.enable()  # the parent records sub_deliver — the chain's last link
    subs: dict = {}
    pumpers: list = []
    stop_pump = threading.Event()
    g, src, sink = wordcount.build_graph()
    try:
        h.spawn_leader(fsync="tick", epoch=0)
        rnames = [f"r{i}" for i in range(n_replicas)]
        for nm in rnames:
            h.spawn_replica(nm)
        h.attach_replicas()
        for i in range(n_prod):
            h.spawn_producer(f"p{i}", index=i, pace_s=pace_s)

        # live subscribers in the parent, one per replica, pumped from
        # background threads for the whole run (kills included)
        for nm in rnames:
            sub = Subscriber(TcpTransport(),
                             tuple(h.child(nm).ready["subs"]),
                             sink.name, kind="view", name=f"sub-{nm}")
            subs[nm] = sub

            def pump(sub=sub):
                while not stop_pump.is_set():
                    sub.pump(wait_s=0.1)

            t = threading.Thread(target=pump, name=f"pump/{nm}",
                                 daemon=True)
            t.start()
            pumpers.append(t)
        log("e2etrace: fleet up, load running")
        time.sleep(run_s)

        # -- kill -9 a replica mid-run: its flight ring survives on
        # disk; the respawn archives it as the .prev generation -------
        h.kill9(rnames[0])
        time.sleep(0.1)
        h.respawn(rnames[0])
        h.attach_replicas([rnames[0]])
        h.barrier(timeout_s=60.0)
        subs[rnames[0]].retarget(
            tuple(h.child(rnames[0]).ready["subs"]))
        log("e2etrace: replica kill/respawn healed")
        time.sleep(run_s / 2)

        # -- then the leader: cross-process failover ------------------
        coord = h.coordinator(epoch=0, confirm_intervals=2,
                              drain_timeout_s=10.0)
        h.kill9("leader")
        t_kill = time.monotonic()
        promote_evt = None
        now = 0.0
        while promote_evt is None and time.monotonic() - t_kill < 60.0:
            for e in coord.step(now):
                if e.get("kind") == "failover_promote":
                    promote_evt = e
            now += 1.0
            time.sleep(0.02)
        assert promote_evt is not None, "leader death never promoted"
        out["promotion_s"] = time.monotonic() - t_kill
        out["winner"] = promote_evt["winner"]
        out["epoch"] = promote_evt["epoch"]
        assert out["epoch"] == 1
        winner = out["winner"]
        log(f"e2etrace: promoted {winner} in {out['promotion_s']:.1f}s")
        survivors = [nm for nm in rnames if nm != winner]
        # the winner now serves ingestion; keep its subscriber on a
        # replica that still replays shipped windows
        subs[winner].retarget(
            tuple(h.child(survivors[0]).ready["subs"]))
        time.sleep(run_s)  # post-promotion writes: epoch-1 chains

        # -- quiesce + drain the last deltas to the subscribers -------
        prod_exits = []
        for i in range(n_prod):
            st = h.child(f"p{i}").stop()
            assert st is not None and st.get("ok"), \
                f"producer p{i} died dirty: {st!r}"
            assert st["in_doubt"] == [], \
                f"{st['name']} exited in doubt: {st['in_doubt']}"
            prod_exits.append(st)
        out["reconnects_total"] = sum(s["reconnects"]
                                      for s in prod_exits)
        log("e2etrace: producers stopped; draining")

        # -- deterministically mint a sampled write in the NEW epoch --
        # in-doubt resubmits keep their epoch-0 tokens, and on a 1-CPU
        # box the paced producers may never draw a 1-in-N sample inside
        # the short post-promotion window — so the parent probes the
        # promoted leader until one token carries epoch 1 (at most
        # ~2*SAMPLE_EVERY submits: the first mint happens before the
        # hello that learns the new epoch). Probing after the producer
        # quiesce keeps it off the saturated admission queue.
        from reflow_tpu.proc.worker import producer_batch_words
        from reflow_tpu.serve import APPLIED, DEDUPED, RemoteProducer
        probe = RemoteProducer(TcpTransport(), h.ingest_address,
                               name="probe")
        try:
            probe_cause = None
            t_probe0 = time.monotonic()
            for i in range(2 * obs.trace.SAMPLE_EVERY + 2):
                pbatch = wordcount.ingest_lines(
                    [" ".join(producer_batch_words(97, i))])
                ticket = probe.submit(src.name, pbatch, timeout=30.0)
                while True:
                    assert time.monotonic() - t_probe0 < 120.0, \
                        f"probe submit never acked ({i} sent)"
                    try:
                        res = ticket.result(timeout=0.3)
                    except TimeoutError:
                        continue
                    if res.status in (APPLIED, DEDUPED):
                        break
                    assert res.status != "rejected" or \
                        "backpressure" in str(res.reason), \
                        f"probe rejected: {res.reason}"
                    # backpressure/SHED: same id, retry
                    time.sleep(0.05)
                    ticket = probe.submit(src.name, pbatch,
                                          batch_id=ticket.batch_id,
                                          timeout=30.0)
                if ticket.cause is not None and "#1#" in ticket.cause:
                    probe_cause = ticket.cause
                    break
            assert probe_cause is not None, \
                "no probe token minted in the new epoch"
            out["probe_cause"] = probe_cause
            log(f"e2etrace: epoch-1 probe token {probe_cause}")
        finally:
            probe.close()

        ingest = ControlClient(h.ingest_address, io_timeout_s=30.0)
        ingest.call("flush", 20.0)
        _, leader_tick, _view = ingest.call("view", sink.name)
        out["leader_tick"] = leader_tick
        h.barrier(names=survivors, min_horizon=leader_tick,
                  timeout_s=30.0)
        stop_pump.set()
        for t in pumpers:
            t.join(timeout=30)
        for nm, sub in subs.items():
            assert sub.wait_horizon(leader_tick, timeout_s=30.0), \
                f"subscriber {nm} stalled at {sub.horizon}/{leader_tick}"
            assert sub.gaps_total == 0, f"subscriber {nm} saw a gap"
        out["sub_frames_applied"] = sum(
            s.frames_applied_total for s in subs.values())

        # -- fleet gauges: the new freshness/flight planes are visible
        # from the aggregator (children ship REGISTRY snapshots) ------
        deadline = time.monotonic() + 15.0
        fleet_f50 = fleet_flight = None
        while time.monotonic() < deadline:
            snap = h.aggregator.fleet_snapshot()
            fleet_f50 = snap["gauges"].get("subs.freshness_p50")
            fleet_flight = snap["gauges"].get("flight.events_total")
            if fleet_f50 is not None and fleet_flight is not None:
                break
            time.sleep(0.1)
        out["fleet_freshness_p50"] = fleet_f50
        out["fleet_flight_events"] = fleet_flight
        assert fleet_f50 is not None, \
            "subs.freshness_p50 never reached the fleet aggregator"
        assert fleet_flight is not None and fleet_flight >= 1, \
            "flight.events_total never reached the fleet aggregator"

        for sub in subs.values():
            sub.close()
        h.close()  # clean exits: every child exports <root>/<name>/trace.json

        # -- merge every process's trace onto one timeline ------------
        parent_trace = os.path.join(root, "parent-trace.json")
        obs.export_chrome_trace(parent_trace)
        trace_files = [parent_trace]
        for nm in h.children:
            p = os.path.join(root, nm, "trace.json")
            if os.path.exists(p):
                trace_files.append(p)
        # the killed leader never exported — by design; its story is
        # the flight recording below
        assert not os.path.exists(
            os.path.join(root, "leader", "trace.json"))
        out["trace_files_merged"] = len(trace_files)
        assert len(trace_files) >= 2 + n_replicas + n_prod - 1

        # keep the traces where the tier-1 smoke can re-check them —
        # copied BEFORE the structural asserts so a failing run leaves
        # its evidence behind
        shutil.rmtree(keep_dir, ignore_errors=True)
        os.makedirs(keep_dir, exist_ok=True)
        kept = []
        for p in trace_files:
            dst = os.path.join(
                keep_dir,
                f"{os.path.basename(os.path.dirname(p))}-trace.json")
            shutil.copyfile(p, dst)
            kept.append(dst)
        out["trace_files"] = kept

        spec = importlib.util.spec_from_file_location(
            "trace_inspect", os.path.join(
                os.path.dirname(os.path.abspath(__file__)),
                "tools", "trace_inspect.py"))
        ti = importlib.util.module_from_spec(spec)
        spec.loader.exec_module(ti)
        report = ti.inspect(trace_files,
                            require_chain=list(ti.FULL_CHAIN))
        causal = report["causal"]
        assert causal is not None, "no causal tokens in any trace"
        out["causal_groups"] = causal["groups"]
        out["full_chains"] = causal["full_chains"]
        out["required_chains"] = causal["required_chains"]
        if causal["full_chains"] < 1:
            # per-file cause-span inventory: WHICH process dropped its
            # link tells you where the chain broke
            per_file = {}
            for p in trace_files:
                evs, _ = ti.load_traces([p])
                names = sorted({
                    e["name"] for e in evs if e.get("ph") == "X"
                    and ((e.get("args") or {}).get("cause")
                         or (e.get("args") or {}).get("causes"))})
                per_file[os.path.basename(os.path.dirname(p))] = names
            raise AssertionError(
                f"no full submit->deliver chain: {causal['span_names']} "
                f"per-file: {per_file}")
        assert causal["required_chains"] >= 1
        fresh = report["freshness"]
        assert fresh is not None
        out["freshness_e2e_p50_us"] = fresh["e2e_p50_us"]
        out["freshness_max_dev_frac"] = fresh["max_dev_frac"]
        out["freshness_stages"] = {
            s: fresh["stages"][s]["p50_us"]
            for s in ti.FRESHNESS_STAGES}
        assert fresh["max_dev_frac"] <= 0.10, \
            f"freshness tiling off by {fresh['max_dev_frac']:.1%} " \
            f"(worst chain: {fresh['worst']}; traces kept in {keep_dir})"
        # at least one chain was minted AFTER the promotion: its token
        # carries the new epoch (origin#1#seq)
        events, _files = ti.load_traces(trace_files)
        post_promo = sum(
            1 for e in events
            if e.get("ph") == "X" and e.get("name") == "producer_submit"
            and "#1#" in str((e.get("args") or {}).get("cause", "")))
        out["post_promotion_submits"] = post_promo
        assert post_promo >= 1, "no sampled write in the new epoch"
        log(f"e2etrace: {causal['full_chains']} full chain(s) across "
            f"{len(trace_files)} trace file(s), freshness e2e p50 "
            f"{fresh['e2e_p50_us']:.0f}us (tiling dev "
            f"{100 * fresh['max_dev_frac']:.2f}%), {post_promo} "
            f"post-promotion sampled submit(s)")

        # -- post-mortem: the killed processes' flight recordings ------
        spec = importlib.util.spec_from_file_location(
            "reflow_flight", os.path.join(
                os.path.dirname(os.path.abspath(__file__)),
                "tools", "reflow_flight.py"))
        rf = importlib.util.module_from_spec(spec)
        spec.loader.exec_module(rf)
        flight = rf.merge([root])
        out["flight_nodes"] = sorted(flight["nodes"])
        assert "leader" in flight["nodes"] \
            and flight["nodes"]["leader"]["events"] >= 1, \
            "the kill -9'd leader left no flight recording"
        # the killed replica's dead incarnation survives as .prev
        # beside its respawn's live ring: two distinct pids recorded
        # under one corner (a short run may never flip a->b, so file
        # count alone proves less than recovered-pid count)
        assert len(flight["nodes"][rnames[0]]["pids"]) >= 2 and \
            flight["nodes"][rnames[0]]["files"] >= 2, \
            flight["nodes"][rnames[0]]
        assert any(ev["name"] in ("failover_elect", "failover_replay")
                   for ev in flight["events"]), \
            "no failover evidence in the merged flight timeline"
        out["flight_events_total"] = len(flight["events"])
        log(f"e2etrace: flight recordings from "
            f"{len(flight['nodes'])} node(s) "
            f"({out['flight_events_total']} event(s)) — killed "
            f"leader + {rnames[0]}'s .prev incarnation recovered")

        flight_path = os.path.join(keep_dir, "flight_merged.json")
        with open(flight_path, "w") as f:
            json.dump(flight, f, indent=2, sort_keys=True)
        out["flight_merged_file"] = flight_path
        out["kills"] = h.kills
        out["respawns"] = h.respawns
    finally:
        stop_pump.set()
        for sub in subs.values():
            try:
                sub.close()
            except Exception:  # noqa: BLE001 - teardown best-effort
                pass
        h.close()
        obs.disable()
        obs.trace.reset()
        shutil.rmtree(root, ignore_errors=True)
    return out


# -- tier / multi-graph serving mode (REFLOW_BENCH_TIER=1) -----------------

def run_tier_bench() -> dict:
    """Multi-graph serving-tier numbers (docs/guide.md "Serving tier"),
    three phases:

    A. **throughput** — 4 graphs x 4 producers each on a 2-thread
       ``ServeTier`` pump pool vs the same load on 4 independent
       ``IngestFrontend``\\ s (4 private pump threads), asserting zero
       forced syncs on every scheduler (the pool only ever calls
       ``tick_many``);
    B. **crash isolation** — a ``pool_window@<name>`` kill on one
       durable graph: its undecided tickets fail ``PumpCrashed``,
       siblings keep applying on the surviving pool, and WAL
       ``recover()`` + same-id re-send lands exactly-once;
    C. **QoS isolation** — a hot tenant saturating its budget ceiling
       next to a quiet tenant with a byte floor: the quiet tenant's
       admission p99 must stay bounded.

    Host-side CPU work.
    """
    import tempfile
    import threading

    from reflow_tpu.scheduler import DirtyScheduler
    from reflow_tpu.serve import (CoalesceWindow, GraphConfig,
                                  IngestFrontend, PumpCrashed, ServeTier)
    from reflow_tpu.utils.faults import CrashInjector
    from reflow_tpu.utils.metrics import summarize, summarize_tier
    from reflow_tpu.wal import DurableScheduler, recover
    from reflow_tpu.workloads import wordcount

    smoke = env_flag("REFLOW_BENCH_SMOKE")
    per_producer = env_int("REFLOW_BENCH_TIER_BATCHES", "30" if smoke else "200")
    rows_per_batch = 8
    n_graphs = n_prod = 4
    window = CoalesceWindow(max_rows=4096, max_ticks=8,
                            max_latency_s=0.005)

    def make_lines(graph: int, producer: int, j: int) -> list:
        rng = np.random.default_rng(
            (graph * 101 + producer) * 100_003 + j)
        return [" ".join(f"w{int(x)}"
                         for x in rng.integers(0, 1000, rows_per_batch))]

    out = {"graphs": n_graphs, "producers_per_graph": n_prod,
           "per_producer_batches": per_producer,
           "rows_per_batch": rows_per_batch}
    n_batches = n_graphs * n_prod * per_producer

    def drive(submit_targets):
        # submit_targets: list of (submitfn, src) per graph; returns wall
        tickets, tk_lock = [], threading.Lock()

        def produce(gi, pid, submitfn, src):
            mine = [submitfn(src, wordcount.ingest_lines(
                make_lines(gi, pid, j))) for j in range(per_producer)]
            with tk_lock:
                tickets.extend(mine)

        threads = [threading.Thread(target=produce,
                                    args=(gi, pid, fn, src))
                   for gi, (fn, src) in enumerate(submit_targets)
                   for pid in range(n_prod)]
        t0 = time.perf_counter()
        for t in threads:
            t.start()
        for t in threads:
            t.join()
        return tickets, t0

    # -- phase A: tier (2 pump threads) vs 4 independent frontends --------
    tier = ServeTier(max_bytes=64 << 20, pump_threads=2)
    scheds, targets, handles = [], [], []
    for gi in range(n_graphs):
        g, src, _sink = wordcount.build_graph()
        sched = DirtyScheduler(g)
        h = tier.register(f"g{gi}", sched, GraphConfig(window=window))
        scheds.append(sched)
        targets.append((h.submit, src))
        handles.append(h)
    tickets, t0 = drive(targets)
    for h in handles:
        h.flush()
    tier_wall = time.perf_counter() - t0
    assert all(t.result(timeout=30).applied for t in tickets)
    tm = summarize_tier(tier)
    forced = sum(summarize(s.history).forced_syncs for s in scheds)
    tier.close()
    tier_rate = n_batches * rows_per_batch / tier_wall
    out["tier_rows_per_s_4g_2threads"] = round(tier_rate)
    out["tier_pump_utilization"] = round(tm.pump_utilization, 3)
    out["tier_windows"] = tm.windows
    out["tier_sched_delay_p99_us"] = round(tm.sched_delay_p99_s * 1e6, 1)
    out["tier_budget_occupancy_peak"] = round(tm.budget_occupancy_peak, 4)
    out["tier_forced_syncs"] = forced
    log(f"tier[4g x 4p, 2 threads]: {n_batches} batches in "
        f"{tier_wall:.3f}s ({tier_rate:.0f} rows/s, util "
        f"{tm.pump_utilization:.2f}, forced_syncs={forced})")

    scheds, targets, fes = [], [], []
    for gi in range(n_graphs):
        g, src, _sink = wordcount.build_graph()
        sched = DirtyScheduler(g)
        fe = IngestFrontend(sched, window=window, max_bytes=16 << 20)
        scheds.append(sched)
        targets.append((fe.submit, src))
        fes.append(fe)
    tickets, t0 = drive(targets)
    for fe in fes:
        fe.flush()
    indep_wall = time.perf_counter() - t0
    assert all(t.result(timeout=30).applied for t in tickets)
    forced_i = sum(summarize(s.history).forced_syncs for s in scheds)
    for fe in fes:
        fe.close()
    indep_rate = n_batches * rows_per_batch / indep_wall
    out["indep_rows_per_s_4g_4threads"] = round(indep_rate)
    out["tier_vs_indep_x"] = round(tier_rate / indep_rate, 3)
    out["indep_forced_syncs"] = forced_i
    out["zero_forced_syncs"] = forced + forced_i == 0
    log(f"indep[4 frontends, 4 threads]: {indep_wall:.3f}s "
        f"({indep_rate:.0f} rows/s); tier/indep = "
        f"{out['tier_vs_indep_x']}x")

    # -- phase B: pump-crash on one durable graph; siblings + recovery ----
    with tempfile.TemporaryDirectory() as tmp:
        crash = CrashInjector(at=3, only="pump_before_tick@crashy")
        tier = ServeTier(max_bytes=64 << 20, pump_threads=2, crash=crash)
        g, src, sink = wordcount.build_graph()
        dsched = DurableScheduler(g, wal_dir=tmp, fsync="record")
        hc = tier.register("crashy", dsched, GraphConfig(window=window))
        g2, src2, sink2 = wordcount.build_graph()
        ok_sched = DirtyScheduler(g2)
        hok = tier.register("ok", ok_sched, GraphConfig(window=window))

        n_crash_batches = 40
        sent = [(f"c{j}", wordcount.ingest_lines(make_lines(9, 0, j)))
                for j in range(n_crash_batches)]
        crashy_tk = []
        for bid, batch in sent:
            try:
                crashy_tk.append(hc.submit(src, batch, batch_id=bid))
            except Exception:  # FrontendClosed once the crash lands
                break
            time.sleep(0.0005)  # several windows, not one giant one
        ok_before = hok.submit(src2, wordcount.ingest_lines(
            make_lines(8, 0, 0))).result(10)
        assert ok_before.applied
        statuses = {"applied": 0, "crashed": 0}
        for t in crashy_tk:
            try:
                t.result(timeout=10)
                statuses["applied"] += 1
            except PumpCrashed:
                statuses["crashed"] += 1
        assert crash.fired and statuses["crashed"] > 0, statuses
        assert tier.pool_crashes == 1
        # the pool survived: the sibling keeps applying AFTER the crash
        ok_after = hok.submit(src2, wordcount.ingest_lines(
            make_lines(8, 0, 1))).result(10)
        assert ok_after.applied
        tier.unregister("crashy", flush=False)
        tier.close()
        out["crash_applied_before"] = statuses["applied"]
        out["crash_failed_tickets"] = statuses["crashed"]

        # recover the WAL and re-send EVERY id: exactly-once means the
        # union lands once — replayed-or-reapplied, never doubled
        g3, src3, sink3 = wordcount.build_graph()
        rsched = DurableScheduler(g3, wal_dir=tmp, fsync="record")
        recover(rsched, tmp)
        fe = IngestFrontend(rsched, window=window)
        results = [fe.submit(src3, batch, batch_id=bid).result(10)
                   for bid, batch in sent]
        fe.flush()
        fe.close()
        deduped = sum(r.status == "deduped" for r in results)
        g4, src4, sink4 = wordcount.build_graph()
        want = DirtyScheduler(g4)
        for _bid, batch in sent:
            want.push(src4, batch)
            want.tick()
        assert dict(rsched.view(sink3.name)) == dict(want.view(sink4.name))
        out["crash_recover_deduped"] = deduped
        out["crash_exactly_once"] = True
        log(f"crash[@crashy]: {statuses['applied']} applied, "
            f"{statuses['crashed']} failed PumpCrashed; sibling ok "
            f"before+after; recover+resend exactly-once "
            f"({deduped} deduped)")

    # -- phase C: hot tenant vs quiet tenant isolation --------------------
    # budget sized so the hot tenant genuinely hits its byte ceiling
    # (wordcount micro-batches are tiny): saturation has to be real for
    # the quiet-tenant p99 bound to mean anything
    budget = 8 << 10
    tier = ServeTier(max_bytes=budget, pump_threads=2)
    g, src, sink = wordcount.build_graph()
    hot = tier.register("hot", DirtyScheduler(g), GraphConfig(
        weight=1.0, ceiling_bytes=budget // 2, window=window))
    g2, src2, sink2 = wordcount.build_graph()
    quiet = tier.register("quiet", DirtyScheduler(g2), GraphConfig(
        weight=4.0, floor_bytes=budget // 4, window=window))
    stop = threading.Event()

    def hammer(pid):
        # fire-and-forget: never waits on tickets, so the hot tenant
        # queues until ADMISSION (its byte ceiling) is what stops it —
        # real saturation, the scenario the quiet tenant must survive
        j = 0
        while not stop.is_set():
            hot.submit(src, wordcount.ingest_lines(
                make_lines(7, pid, j)), timeout=0.2)
            j += 1

    hammers = [threading.Thread(target=hammer, args=(pid,))
               for pid in range(3)]
    for t in hammers:
        t.start()
    quiet_n = 60 if smoke else 200
    t0 = time.perf_counter()
    applied0 = hot.frontend.applied
    for j in range(quiet_n):
        quiet.submit(src2, wordcount.ingest_lines(
            make_lines(6, 0, j))).result(timeout=30)
    hot_elapsed = time.perf_counter() - t0
    hot_applied = hot.frontend.applied - applied0
    stop.set()
    for t in hammers:
        t.join()
    quiet.flush()
    hot.flush()
    p99 = (float(np.percentile(quiet.frontend.admission_s, 99))
           if quiet.frontend.admission_s else 0.0)
    tm = summarize_tier(tier)
    tier.close()
    out["hot_rows_per_s"] = round(
        hot_applied * rows_per_batch / hot_elapsed)
    out["quiet_admission_p99_us"] = round(p99 * 1e6, 1)
    out["quiet_p99_bounded"] = p99 < 0.05
    out["hot_budget_peak_frac"] = round(
        tm.per_graph["hot"]["bytes_peak"] / budget, 3)
    log(f"isolation: hot {out['hot_rows_per_s']} rows/s (peak "
        f"{out['hot_budget_peak_frac']} of budget), quiet admission "
        f"p99 {p99 * 1e6:.0f}us (bounded={out['quiet_p99_bounded']})")
    return out


# -- pod-scale serving mode (REFLOW_BENCH_SHARDSERVE=1) --------------------

def run_shardserve_bench() -> dict:
    """Pod-scale serving numbers (docs/guide.md "Sharded serving").

    Three tiers over the same loop-free aggregation workload (source ->
    vectorized map -> reduce(sum), integer-valued f32 values so every
    view comparison is EXACT — elementwise math is sharding-invariant
    bit-for-bit, and integer-valued sums below 2^24 make the cross-row
    reduction order irrelevant), all committing through the fused
    mega-tick window path:

    A. **single-device baseline** — 8 tenants on one ``ServeTier``,
       every executor on the default device (windows serialize on one
       chip — the PR-7 state of the world);
    B. **spread placement** — the same 8 tenants with
       ``GraphConfig(placement="spread")``: one executor per mesh
       device, windows dispatch concurrently, and the structurally-
       identical tenants adopt ONE traced window program from the
       plan-signature cache (``megatick_cache_hits``);
    C. **sharded hot tenant** — the same total load on ONE graph whose
       ``ShardedTpuExecutor`` spans the mesh: queue buffers NamedSharded
       along the capacity axis, the window scan running under shard_map.

    Every tier's reduce tables are compared exactly (max_abs_diff must
    be 0.0) against a CPU per-tick oracle fed the identical batches, and
    the fallback counters must be 0 — the happy path has to BE the
    fused spread/sharded path, not a silent per-tick fallback.

    CPU-CI note: under ``--xla_force_host_platform_device_count=8`` all
    "devices" share the host cores (this container: one), so neither
    spread nor sharded can beat the baseline WALL here — the
    ``*_ge_baseline`` flags relax to ``ge_slack`` of baseline on cpu
    (1.0 on a real mesh) and the raw rows/s + ratios are the artifact;
    scaling headroom shows on real multi-chip hardware.
    """
    import threading

    import jax

    from reflow_tpu.delta import DeltaBatch, Spec
    from reflow_tpu.executors import get_executor
    from reflow_tpu.graph import FlowGraph
    from reflow_tpu.scheduler import DirtyScheduler
    from reflow_tpu.serve import CoalesceWindow, GraphConfig, ServeTier

    smoke = env_flag("REFLOW_BENCH_SMOKE")
    n_graphs = 8
    key_space = 256
    rows_per_batch = 64
    per_producer = env_int("REFLOW_BENCH_SHARDSERVE_BATCHES", "8" if smoke else "48")
    window = CoalesceWindow(max_rows=4096, max_ticks=4,
                            max_latency_s=0.003)
    n_devices = len(jax.devices())
    platform = jax.default_backend()
    ge_slack = 1.0 if platform == "tpu" else 0.25
    total_rows = n_graphs * per_producer * rows_per_batch

    def build():
        g = FlowGraph("shardserve")
        spec = Spec((), np.float32, key_space=key_space)
        src = g.source("events", spec)
        m = g.map(src, lambda v: v * np.float32(3) + np.float32(1),
                  vectorized=True)
        r = g.reduce(m, "sum", tol=0.0)
        return g, src, r

    def make_batch(gi: int, j: int) -> DeltaBatch:
        rng = np.random.default_rng(gi * 7919 + j + 1)
        keys = rng.integers(0, key_space, rows_per_batch).astype(np.int64)
        vals = rng.integers(0, 8, rows_per_batch).astype(np.float32)
        return DeltaBatch(keys, vals,
                          np.ones(rows_per_batch, np.int64))

    def table(sched, r):
        return {int(k): float(np.asarray(v).reshape(()))
                for k, v in sched.read_table(r).items()}

    def oracle(graph_ids):
        g, src, r = build()
        sched = DirtyScheduler(g, get_executor("cpu"))
        for gi in graph_ids:
            for j in range(per_producer):
                sched.push(src, make_batch(gi, j))
                sched.tick()
        return table(sched, r)

    def max_diff(got, want):
        ks = set(got) | set(want)
        return max((abs(got.get(k, 0.0) - want.get(k, 0.0)) for k in ks),
                   default=0.0)

    def drive(targets):
        # targets: (handle, src, gi) per producer thread; the wall covers
        # submission through the last committed window (flush)
        def produce(h, src, gi):
            for j in range(per_producer):
                h.submit(src, make_batch(gi, j))

        threads = [threading.Thread(target=produce, args=t)
                   for t in targets]
        t0 = time.perf_counter()
        for t in threads:
            t.start()
        for t in threads:
            t.join()
        for h, _src, _gi in targets:
            h.flush()
        return time.perf_counter() - t0

    def run_tier(placement):
        tier = ServeTier(max_bytes=64 << 20,
                         pump_threads=min(n_graphs, 8))
        scheds, targets, reduces = [], [], []
        for gi in range(n_graphs):
            g, src, r = build()
            sched = DirtyScheduler(g, get_executor("tpu"))
            cfg = (GraphConfig(window=window, placement=placement)
                   if placement else GraphConfig(window=window))
            h = tier.register(f"g{gi}", sched, cfg)
            scheds.append(sched)
            reduces.append(r)
            targets.append((h, src, gi))
        wall = drive(targets)
        tables = [table(s, r) for s, r in zip(scheds, reduces)]
        stats = {
            "windows": sum(s.megatick_windows for s in scheds),
            "fallbacks": sum(s.megatick_fallbacks for s in scheds),
            "cache_hits": sum(s.executor.megatick_cache_hits
                              for s in scheds),
            "devices": sorted({s.executor.device_label or "(default)"
                               for s in scheds}),
        }
        tier.close()
        return wall, tables, stats

    want = [oracle([gi]) for gi in range(n_graphs)]
    out = {"graphs": n_graphs, "per_producer_batches": per_producer,
           "rows_per_batch": rows_per_batch, "key_space": key_space,
           "devices": n_devices, "platform": platform,
           "ge_slack": ge_slack}

    # -- A: single-device baseline ----------------------------------------
    base_wall, base_tables, base_stats = run_tier(None)
    base_diff = max(max_diff(t, w) for t, w in zip(base_tables, want))
    base_rate = total_rows / base_wall
    out["single_rows_per_s"] = round(base_rate)
    out["single_windows"] = base_stats["windows"]
    out["single_fallbacks"] = base_stats["fallbacks"]
    log(f"shardserve[single]: {total_rows} rows in {base_wall:.3f}s "
        f"({base_rate:.0f} rows/s, windows={base_stats['windows']}, "
        f"fallbacks={base_stats['fallbacks']})")

    # -- B: 8 spread tenants ----------------------------------------------
    spread_wall, spread_tables, spread_stats = run_tier("spread")
    spread_diff = max(max_diff(t, w)
                      for t, w in zip(spread_tables, want))
    spread_rate = total_rows / spread_wall
    out["spread_rows_per_s"] = round(spread_rate)
    out["spread_vs_single_x"] = round(spread_rate / base_rate, 3)
    out["spread_ge_baseline"] = bool(
        spread_rate >= ge_slack * base_rate)
    out["spread_windows"] = spread_stats["windows"]
    out["spread_fallbacks"] = spread_stats["fallbacks"]
    out["spread_cache_hits"] = spread_stats["cache_hits"]
    out["spread_devices"] = spread_stats["devices"]
    out["spread_devices_distinct"] = bool(
        len(spread_stats["devices"]) == min(n_graphs, n_devices))
    out["spread_max_abs_diff"] = spread_diff
    log(f"shardserve[spread]: {spread_wall:.3f}s "
        f"({spread_rate:.0f} rows/s, {out['spread_vs_single_x']}x, "
        f"devices={len(spread_stats['devices'])}, "
        f"cache_hits={spread_stats['cache_hits']}, "
        f"fallbacks={spread_stats['fallbacks']})")

    # -- C: one sharded hot tenant ----------------------------------------
    from reflow_tpu.parallel.shard import ShardedTpuExecutor

    tier = ServeTier(max_bytes=64 << 20, pump_threads=2)
    g, src, r = build()
    hot = DirtyScheduler(g, ShardedTpuExecutor())
    h = tier.register("hot", hot, GraphConfig(window=window))
    sharded_wall = drive([(h, src, gi) for gi in range(n_graphs)])
    sharded_tab = table(hot, r)
    sharded_stats = {
        "windows": hot.megatick_windows,
        "fallbacks": hot.megatick_fallbacks,
        "device": hot.executor.device_label,
    }
    tier.close()
    want_all = oracle(range(n_graphs))
    sharded_diff = max_diff(sharded_tab, want_all)
    sharded_rate = total_rows / sharded_wall
    out["sharded_rows_per_s"] = round(sharded_rate)
    out["sharded_vs_single_x"] = round(sharded_rate / base_rate, 3)
    out["sharded_ge_baseline"] = bool(
        sharded_rate >= ge_slack * base_rate)
    out["sharded_windows"] = sharded_stats["windows"]
    out["sharded_fallbacks"] = sharded_stats["fallbacks"]
    out["sharded_device"] = sharded_stats["device"]
    out["sharded_max_abs_diff"] = sharded_diff
    log(f"shardserve[sharded {sharded_stats['device']}]: "
        f"{sharded_wall:.3f}s ({sharded_rate:.0f} rows/s, "
        f"{out['sharded_vs_single_x']}x, "
        f"windows={sharded_stats['windows']}, "
        f"fallbacks={sharded_stats['fallbacks']})")

    # hard correctness: exact per-tick view parity + no silent fallback
    assert base_diff == 0.0, f"baseline views diverged: {base_diff}"
    assert spread_diff == 0.0, f"spread views diverged: {spread_diff}"
    assert sharded_diff == 0.0, f"sharded views diverged: {sharded_diff}"
    fb = (base_stats["fallbacks"] + spread_stats["fallbacks"]
          + sharded_stats["fallbacks"])
    assert fb == 0, f"window path fell back {fb}x on the happy path"
    assert spread_stats["windows"] > 0 and sharded_stats["windows"] > 0
    out["views_match"] = True
    return out


def run_control_bench() -> dict:
    """Self-healing control-plane step-load scenario (docs/guide.md
    "Control plane"), two phases, both under a LIVE ``ControlPlane``
    thread (no manual intervention anywhere):

    A. **hot-tenant surge** — a hot graph saturates its budget ceiling
       while a quiet sibling keeps submitting. The controller must
       brown out ONLY the surging graph (the quiet tenant's brownout
       level stays 0 and its admission p99 stays bounded), and once the
       surge stops, walk the hot graph back to its configured policy
       within the analytic bound of control intervals (ladder rungs x
       ``recover_intervals`` + drain slack);
    B. **pump-crash storm** — every macro-tick of one graph crashes
       (``StormInjector``): the controller's breaker must open after K
       crashes (quarantining the graph while its sibling keeps
       applying), then — once the storm ends — heal it through a
       half-open probe back to closed, after which submissions apply
       again.

    Host-side CPU work.
    """
    import threading

    from bench_configs import control_scenario
    from reflow_tpu.obs import MetricsRegistry
    from reflow_tpu.scheduler import DirtyScheduler
    from reflow_tpu.serve import (CoalesceWindow, ControlConfig,
                                  ControlPlane, GraphConfig, SLOSpec,
                                  ServeTier)
    from reflow_tpu.utils.faults import StormInjector
    from reflow_tpu.workloads import wordcount

    smoke = env_flag("REFLOW_BENCH_SMOKE")
    kn = control_scenario(smoke)
    rows_per_batch = 8
    window = CoalesceWindow(max_rows=4096, max_ticks=8,
                            max_latency_s=0.005)
    reg = MetricsRegistry()   # private: don't pollute the global obs

    def make_lines(graph: int, producer: int, j: int) -> list:
        rng = np.random.default_rng(
            (graph * 101 + producer) * 100_003 + j)
        return [" ".join(f"w{int(x)}"
                         for x in rng.integers(0, 1000, rows_per_batch))]

    out = dict(kn)

    # -- phase A: hot-tenant surge, brownout confined to the offender -----
    budget = kn["budget_bytes"]
    tier = ServeTier(max_bytes=budget,
                     pump_threads=kn["pump_threads"])
    g, src, _sink = wordcount.build_graph()
    hot = tier.register("hot", DirtyScheduler(g), GraphConfig(
        weight=1.0, ceiling_bytes=budget // 2, window=window))
    g2, src2, _sink2 = wordcount.build_graph()
    quiet = tier.register("quiet", DirtyScheduler(g2), GraphConfig(
        weight=4.0, floor_bytes=budget // 4, window=window))
    slo = SLOSpec(budget_occupancy=kn["occupancy_slo"],
                  breach_intervals=kn["breach_intervals"],
                  recover_intervals=kn["recover_intervals"])
    # BOTH graphs carry the same SLO: the quiet tenant staying at level
    # 0 then proves per-graph confinement, not a missing spec
    cp = ControlPlane(
        tier, specs={"hot": slo, "quiet": slo},
        config=ControlConfig(interval_s=kn["interval_s"]),
        registry=reg).start()
    stop = threading.Event()

    def hammer(pid):
        # fire-and-forget saturation; under brownout the submits turn
        # into fast rejections/sheds, which IS the degraded mode
        j = 0
        while not stop.is_set():
            try:
                hot.submit(src, wordcount.ingest_lines(
                    make_lines(7, pid, j)), timeout=0.2)
            except Exception:  # noqa: BLE001 - saturation is the point
                pass
            j += 1

    hammers = [threading.Thread(target=hammer, args=(pid,))
               for pid in range(kn["hammers"])]
    for t in hammers:
        t.start()
    quiet_n = kn["quiet_batches"]
    hot_peak_level = 0
    quiet_peak_level = 0
    t0 = time.perf_counter()
    for j in range(quiet_n):
        quiet.submit(src2, wordcount.ingest_lines(
            make_lines(6, 0, j))).result(timeout=30)
        hot_peak_level = max(hot_peak_level, cp.level("hot"))
        quiet_peak_level = max(quiet_peak_level, cp.level("quiet"))
    surge_s = time.perf_counter() - t0
    # -- step-load falling edge: surge ends; measure recovery in ticks --
    stop.set()
    for t in hammers:
        t.join()
    ticks_at_surge_end = cp.ticks
    rungs = len(slo.ladder)
    recovery_bound = (rungs * kn["recover_intervals"]
                      + kn["recovery_slack_ticks"])
    deadline = time.perf_counter() + 30
    while cp.level("hot") > 0 and time.perf_counter() < deadline:
        time.sleep(kn["interval_s"])
    recovery_ticks = cp.ticks - ticks_at_surge_end
    p99 = (float(np.percentile(quiet.frontend.admission_s, 99))
           if quiet.frontend.admission_s else 0.0)
    out["quiet_admission_p99_us"] = round(p99 * 1e6, 1)
    out["quiet_p99_bounded"] = p99 < kn["quiet_p99_bound_s"]
    out["hot_peak_brownout_level"] = hot_peak_level
    out["quiet_peak_brownout_level"] = quiet_peak_level
    out["only_hot_degraded"] = (hot_peak_level > 0
                                and quiet_peak_level == 0
                                and quiet.frontend.policy == "block")
    out["hot_policy_after_recovery"] = hot.frontend.policy
    out["recovery_ticks"] = recovery_ticks
    out["recovery_bound_ticks"] = recovery_bound
    out["recovered_within_bound"] = (
        cp.level("hot") == 0 and hot.frontend.policy == "block"
        and recovery_ticks <= recovery_bound)
    out["brownouts_entered"] = reg.value("control.brownouts_entered", 0)
    out["brownouts_exited"] = reg.value("control.brownouts_exited", 0)
    out["quiet_rows_per_s_during_surge"] = round(
        quiet_n * rows_per_batch / surge_s)
    log(f"surge: hot browned to level {hot_peak_level}, quiet stayed "
        f"level {quiet_peak_level} (p99 {p99 * 1e6:.0f}us, bounded="
        f"{out['quiet_p99_bounded']}); recovered in {recovery_ticks} "
        f"ticks (bound {recovery_bound})")
    cp.stop()
    tier.close()

    # -- phase B: pump-crash storm -> breaker -> half-open heal -----------
    storm = StormInjector(only="pool_window@stormy")
    tier = ServeTier(max_bytes=budget, pump_threads=kn["pump_threads"],
                     crash=storm)
    g3, src3, _ = wordcount.build_graph()
    stormy = tier.register("stormy", DirtyScheduler(g3),
                           GraphConfig(window=window))
    g4, src4, _ = wordcount.build_graph()
    steady = tier.register("steady", DirtyScheduler(g4),
                           GraphConfig(window=window))
    reg2 = MetricsRegistry()
    cp = ControlPlane(
        tier,
        config=ControlConfig(
            interval_s=kn["interval_s"],
            max_crashes=kn["max_crashes"],
            crash_window_s=kn["crash_window_s"],
            respawn_backoff_s=kn["respawn_backoff_s"],
            respawn_backoff_max_s=kn["respawn_backoff_max_s"],
            breaker_cooldown_s=kn["breaker_cooldown_s"],
            breaker_cooldown_max_s=kn["breaker_cooldown_max_s"],
            probe_intervals=kn["probe_intervals"]),
        registry=reg2).start()
    t0 = time.perf_counter()
    deadline = t0 + 60
    j = 0
    while (cp.breaker_state("stormy") != "open"
           and time.perf_counter() < deadline):
        try:
            stormy.submit(src3, wordcount.ingest_lines(
                make_lines(5, 0, j)), timeout=0.1)
        except Exception:  # noqa: BLE001 - failed/quarantined mid-storm
            pass
        j += 1
        time.sleep(0.002)
    open_s = time.perf_counter() - t0
    out["breaker_opened"] = cp.breaker_state("stormy") == "open"
    out["breaker_open_after_s"] = round(open_s, 3)
    out["storm_crashes"] = storm.crashes
    # the sibling keeps applying while the storm rages / is quarantined
    sib = steady.submit(src4, wordcount.ingest_lines(
        make_lines(4, 0, 0))).result(timeout=30)
    out["sibling_applied_during_storm"] = sib.applied
    # storm ends: the breaker must heal the graph unattended
    storm.disarm()
    t0 = time.perf_counter()
    deadline = t0 + 60
    while (cp.breaker_state("stormy") != "closed"
           and time.perf_counter() < deadline):
        time.sleep(kn["interval_s"])
    heal_s = time.perf_counter() - t0
    out["breaker_recovered"] = cp.breaker_state("stormy") == "closed"
    out["breaker_heal_s"] = round(heal_s, 3)
    out["breaker_probes"] = reg2.value("control.breaker_probes", 0)
    out["respawns"] = reg2.value("control.respawns", 0)
    post = stormy.submit(src3, wordcount.ingest_lines(
        make_lines(5, 1, 0))).result(timeout=30)
    out["post_recovery_applied"] = post.applied
    out["pool_live_workers"] = tier.live_workers
    log(f"storm: opened={out['breaker_opened']} after {open_s:.2f}s "
        f"({storm.crashes} crashes), healed={out['breaker_recovered']} "
        f"in {heal_s:.2f}s ({out['respawns']} respawns, "
        f"{out['breaker_probes']} probes); post-recovery applied="
        f"{post.applied}")
    cp.stop()
    tier.close()
    return out


# -- config 3 measurements -------------------------------------------------

def run_pagerank_cpu(n_nodes: int, n_edges: int, churn: float, ticks: int,
                     tol: float) -> dict:
    """CPU oracle churn ticks (synchronous by construction)."""
    from reflow_tpu.executors import get_executor
    from reflow_tpu.scheduler import DirtyScheduler
    from reflow_tpu.workloads import pagerank

    pr, web = _build_pagerank(n_nodes, n_edges, churn, tol)
    sched = DirtyScheduler(pr.graph, get_executor("cpu"))
    sched.push(pr.teleport, pagerank.teleport_batch(n_nodes))
    sched.push(pr.edges, web.initial_batch())
    build_s, _ = _synced_tick(sched)

    walls, dops = [], []
    for _ in range(ticks):
        sched.push(pr.edges, web.churn(churn))
        wall, res = _synced_tick(sched)
        walls.append(wall)
        dops.append(res.delta_ops)
    return {
        "executor": "cpu", "nodes": n_nodes, "edges": n_edges,
        "cold_build_s": build_s,
        "tick_s_median": float(np.median(walls)),
        "delta_ops_per_s": float(sum(dops) / sum(walls)),
        "delta_ops_per_tick": float(np.mean(dops)),
    }


def run_pagerank_tpu_child(defer=None) -> dict:
    """Child process: the headline pipelined churn window on the device.

    The cold build, the churn-shape compile absorption and all pushes
    are streaming; a barrier drains them before the first window, and
    each window's wall runs to its own closing barrier — a true
    device-completion time for all N ticks.

    ``defer`` (pr_tpu_defer child): the same window under cross-tick
    residual deferral — quiescence is NOT asserted per tick; instead
    the child drains after the windows and verifies the drained ranks
    against the independent dense power-iteration oracle, recording the
    mid-stream and drained error bounds alongside the throughput."""
    from bench_configs import (_barrier, _median_window, _stream_window,
                               _timed_tick)
    from reflow_tpu.executors import get_executor
    from reflow_tpu.scheduler import DirtyScheduler
    from reflow_tpu.workloads import pagerank

    p = _params()
    pr, web = _build_pagerank(p["n_nodes"], p["n_edges"], p["churn"],
                              p["tol"], defer=defer)
    sched = DirtyScheduler(pr.graph, get_executor("tpu"))
    sched.push(pr.teleport, pagerank.teleport_batch(p["n_nodes"]))
    sched.push(pr.edges, web.initial_batch())
    t0 = time.perf_counter()
    sched.tick(sync=False)
    build_dispatch_s = time.perf_counter() - t0   # includes the compile
    warm = 2 if defer is None else max(2, 24 // defer)
    for _ in range(warm):  # absorb the churn-shape compile + (deferred:
        sched.push(pr.edges, web.churn(p["churn"]))   # converge the cold
        sched.tick(sync=False)                        # build's residue)
    _barrier(sched.executor)    # drain cold build + warmup ticks
    if defer is not None:
        # converge the cold build's residue before measuring: the window
        # then measures steady-state churn tracking, not amortized
        # initial convergence. Probe at the churn batch size so drain
        # ticks reuse the churn program signature (a 1-row probe's
        # 64-capacity bucket would compile a fresh program)
        n_churn = 2 * max(1, int(p["churn"] * p["n_edges"]))
        cold_drain_ticks = sched.drain(pr.edges, probe_rows=n_churn)
        log(f"cold-build residue drained in {cold_drain_ticks} ticks")

    # Per-tick streaming windows (the tick_many macro-tick has its own
    # mode, REFLOW_BENCH_MEGATICK). THREE windows, median throughput, so
    # one outlier window on a shared host does not set the record; every
    # window is a genuine completion-time wall.
    n = p["stream_ticks"]

    def run_churn_window():
        wall, dwall, results = _stream_window(
            sched, lambda i: sched.push(pr.edges, web.churn(p["churn"])), n)
        if defer is None:
            assert all(r.quiesced for r in results)
        return wall, dwall, sum(r.delta_ops for r in results)

    wall, dwall, dops, windows = _median_window(
        run_churn_window, log, f"pagerank churn x{n}"
        + (f" defer={defer}" if defer else ""))
    windows = [{"wall_s": round(w, 3), "dispatch_s": round(d, 3),
                "delta_ops": o} for w, d, o in windows]

    extra = {}
    if defer is None and not p["smoke"]:
        # the quiescent mode's own accuracy vs the independent oracle:
        # the fair baseline band for the deferred child's error fields
        # (both modes carry tol-lag; deferral must not add beyond it)
        import numpy as _np
        from reflow_tpu.workloads import pagerank as _pg
        ranks_q = _pg.ranks_to_array(sched.read_table(pr.new_rank),
                                     p["n_nodes"])
        ref_q = _pg.reference_ranks(web)
        extra["max_abs_err_vs_reference"] = round(
            float(_np.abs(ranks_q - ref_q).max()), 6)
        extra["max_rel_err_vs_reference"] = round(float(
            (_np.abs(ranks_q - ref_q) / _np.maximum(ref_q, 1.0)).max()), 6)
        log(f"quiescent accuracy vs reference: "
            f"abs={extra['max_abs_err_vs_reference']} "
            f"rel={extra['max_rel_err_vs_reference']}")
    if defer is not None:
        # the deferred mode's accuracy contract, measured in-record:
        # mid-stream lag right after the last window, then drained ranks
        # vs the INDEPENDENT dense power-iteration oracle (5e-4 is the
        # VERDICT-prescribed bound on the drained side)
        import numpy as _np
        from reflow_tpu.workloads import pagerank as _pg
        ref = _pg.reference_ranks(web)
        mid = _pg.ranks_to_array(sched.read_table(pr.new_rank),
                                 p["n_nodes"])
        t_dr = time.perf_counter()
        drain_ticks = sched.drain(
            pr.edges, probe_rows=2 * max(1, int(p["churn"] * p["n_edges"])))
        drain_s = time.perf_counter() - t_dr
        drained = _pg.ranks_to_array(sched.read_table(pr.new_rank),
                                     p["n_nodes"])
        rel = lambda a: float((_np.abs(a - ref)
                               / _np.maximum(ref, 1.0)).max())
        extra = {
            "defer_passes": defer,
            "mid_stream_max_abs_err": round(
                float(_np.abs(mid - ref).max()), 6),
            "mid_stream_max_rel_err": round(rel(mid), 6),
            "drain_ticks": drain_ticks,
            "drain_s": round(drain_s, 2),
            "drained_max_abs_err": round(
                float(_np.abs(drained - ref).max()), 6),
            "drained_max_rel_err": round(rel(drained), 6),
        }
        log(f"deferred accuracy: mid={extra['mid_stream_max_abs_err']} "
            f"(rel {extra['mid_stream_max_rel_err']}) "
            f"drained={extra['drained_max_abs_err']} "
            f"(rel {extra['drained_max_rel_err']}) "
            f"(drain {drain_ticks} ticks / {drain_s:.1f}s)")

    # post-window extra: one synchronous tick, timed to completion
    sched.push(pr.edges, web.churn(p["churn"]))
    synced_s, _ = _timed_tick(sched)

    trace_dir = env_str("REFLOW_BENCH_TRACE", None)
    if trace_dir:
        from reflow_tpu.utils.metrics import profile_trace
        sched.push(pr.edges, web.churn(p["churn"]))
        with profile_trace(trace_dir):
            _timed_tick(sched)

    return {
        "executor": "tpu", "nodes": p["n_nodes"], "edges": p["n_edges"],
        "build_dispatch_s": round(build_dispatch_s, 2),
        "window_ticks": n,
        "window_wall_s": round(wall, 3),
        "window_dispatch_s": round(dwall, 3),
        "windows": windows,
        "tick_s_amortized": round(wall / n, 4),
        "delta_ops_per_s": round(dops / wall),
        "delta_ops_per_tick": round(dops / n),
        "tick_s_synced": round(synced_s, 3),
        **extra,
    }


def run_pagerank_full_child() -> dict:
    """Child process: warm full-recompute baseline (its own process so
    its device memory and program cache start clean); see the min-of-3
    rationale below."""
    from bench_configs import _barrier
    from reflow_tpu.executors import get_executor
    from reflow_tpu.scheduler import DirtyScheduler
    from reflow_tpu.workloads import pagerank

    p = _params()
    pr, web = _build_pagerank(p["n_nodes"], p["n_edges"], p["churn"],
                              p["tol"])
    ex = get_executor("tpu")
    sched = DirtyScheduler(pr.graph, ex)
    sched.push(pr.teleport, pagerank.teleport_batch(p["n_nodes"]))
    sched.push(pr.edges, web.initial_batch())
    sched.tick(sync=False)   # absorb the compile; leaves cache warm

    # fresh states over the same graph each round: bind() resets state,
    # keeps the compiled-program cache. Three measurements, MINIMUM wall:
    # full_recompute_s is the NUMERATOR of incr_vs_full, so the outlier
    # guard must never inflate it: min() picks the wall closest to real
    # device cost.
    _barrier(ex)             # drain the absorption tick before timing
    walls = []
    for ix in range(3):
        sched2 = DirtyScheduler(pr.graph, ex)
        sched2.push(pr.teleport, pagerank.teleport_batch(p["n_nodes"]))
        sched2.push(pr.edges, web.initial_batch())
        t0 = time.perf_counter()
        sched2.tick(sync=False)
        _barrier(ex)
        walls.append(time.perf_counter() - t0)
        log(f"full recompute {ix}: {walls[-1]:.2f}s")
    return {"executor": "tpu",
            "full_recompute_s": round(min(walls), 3),
            "full_recompute_walls_s": [round(w, 2) for w in walls]}


# -- subprocess orchestration ----------------------------------------------

_CHILDREN = {}


def _child(name):
    def deco(fn):
        _CHILDREN[name] = fn
        return fn
    return deco


@_child("pr_tpu")
def _c_pr_tpu():
    return run_pagerank_tpu_child()


@_child("pr_tpu_defer")
def _c_pr_tpu_defer():
    return run_pagerank_tpu_child(defer=_params()["defer"])


@_child("pr_full")
def _c_pr_full():
    return run_pagerank_full_child()


def _cfg_child(name, fn_name):
    @_child(name)
    def _run():
        import bench_configs
        getattr(bench_configs, fn_name)(_params()["smoke"], log)
        return {"ok": True}
    return _run


_cfg_child("cfg1", "cfg1_wordcount")
_cfg_child("cfg2", "cfg2_tfidf")
_cfg_child("cfg4", "cfg4_knn")
_cfg_child("cfg5", "cfg5_image_embed")


def _device():
    """The device a child ran on, as JAX reports it — None for a child
    that never imported JAX (the CPU-oracle wordcount config)."""
    if "jax" not in sys.modules:
        return None
    from reflow_tpu.utils.runtime import device_record

    return device_record()


def _spawn(name: str) -> dict:
    """Run one measurement in a fresh process — the one process that
    holds the chip while it runs (see the module docstring), which is
    why this parent must not have imported JAX. Child stderr streams
    through (records/logs); child stdout's last line is its JSON
    result."""
    if "jax" in sys.modules:
        raise RuntimeError(
            "bench.py's parent imported jax before spawning a child: a "
            "parent that has touched JAX holds the chip and every "
            "device child then fails or hangs")
    env = dict(os.environ)
    env["REFLOW_BENCH_CHILD"] = name
    t0 = time.perf_counter()
    p = subprocess.run([sys.executable, os.path.abspath(__file__)],
                       stdout=subprocess.PIPE, env=env, text=True)
    log(f"[{name}] child finished in {time.perf_counter()-t0:.0f}s "
        f"rc={p.returncode}")
    lines = [ln for ln in (p.stdout or "").strip().splitlines() if ln]
    if p.returncode == 0 and lines:
        try:
            return json.loads(lines[-1])
        except json.JSONDecodeError:
            pass
    return {"error": f"child {name} rc={p.returncode}",
            "stdout_tail": lines[-3:]}


def _emit(result: dict, json_out=None, mode: str = None) -> None:
    """Print the final result as the one parseable stdout line; when
    ``--json-out`` was given, also write it there pretty-printed (the
    machine-comparison artifact — stdout stays the contract). Every
    result carries the ``reflow.bench/1`` schema stamp plus its bench
    ``mode`` so directory-level readers (``fleet_inspect
    --bench-dir``) can classify artifacts without guessing from
    filenames; pre-stamp files remain readable there by design."""
    result = {"schema": "reflow.bench/1", "mode": mode, **result}
    print(json.dumps(result))
    if json_out:
        with open(json_out, "w") as f:
            json.dump(result, f, indent=2, sort_keys=True)
            f.write("\n")
        log(f"result written to {json_out}")


def main() -> None:
    ap = argparse.ArgumentParser(add_help=False)
    ap.add_argument("--json-out", default=None, metavar="PATH")
    cli, _ = ap.parse_known_args()
    json_out = cli.json_out

    if env_flag("REFLOW_BENCH_TIER"):
        # tier mode is host-side CPU work — no subprocesses
        os.environ.setdefault("JAX_PLATFORMS", "cpu")
        out = run_tier_bench()
        _emit({
            "metric": "tier_rows_per_s_4g_2threads",
            "value": out["tier_rows_per_s_4g_2threads"],
            "unit": "rows/s",
            **out,
        }, json_out, mode="tier")
        return

    if env_flag("REFLOW_BENCH_SHARDSERVE"):
        # pod-scale serving mode: on cpu, force 8 host devices BEFORE jax
        # imports so the spread/sharded tiers have a mesh to span (a real
        # TPU platform uses its native device set)
        os.environ.setdefault("JAX_PLATFORMS", "cpu")
        if "cpu" in os.environ.get("JAX_PLATFORMS", ""):
            flags = os.environ.get("XLA_FLAGS", "")
            if "xla_force_host_platform_device_count" not in flags:
                os.environ["XLA_FLAGS"] = (
                    flags + " --xla_force_host_platform_device_count=8"
                ).strip()
        out = run_shardserve_bench()
        _emit({
            "metric": "shardserve_spread_rows_per_s",
            "value": out["spread_rows_per_s"],
            "unit": "rows/s",
            **out,
        }, json_out, mode="shardserve")
        return

    if env_flag("REFLOW_BENCH_CONTROL"):
        # control mode is host-side CPU work — no subprocesses
        os.environ.setdefault("JAX_PLATFORMS", "cpu")
        out = run_control_bench()
        _emit({
            "metric": "control_quiet_admission_p99_us_during_surge",
            "value": out["quiet_admission_p99_us"],
            "unit": "us",
            **out,
        }, json_out, mode="control")
        return

    if env_flag("REFLOW_BENCH_SERVE"):
        # serve mode is host-side CPU work — no subprocesses
        os.environ.setdefault("JAX_PLATFORMS", "cpu")
        out = run_serve_bench()
        _emit({
            "metric": "serve_ingest_rows_per_s_16_producers",
            "value": out["serve_16p_rows_per_s"],
            "unit": "rows/s",
            **out,
        }, json_out, mode="serve")
        return

    if env_flag("REFLOW_BENCH_WALPIPE"):
        # walpipe mode is host-side CPU work — no subprocesses
        os.environ.setdefault("JAX_PLATFORMS", "cpu")
        out = run_walpipe_bench()
        _emit({
            "metric": "walpipe_speedup_16p",
            "value": out["walpipe_speedup_16p"],
            "unit": "x",
            **out,
        }, json_out, mode="walpipe")
        return

    if env_flag("REFLOW_BENCH_REPLICA"):
        # replica mode is host-side CPU work — no subprocesses
        os.environ.setdefault("JAX_PLATFORMS", "cpu")
        out = run_replica_bench()
        _emit({
            "metric": "replica_read_scaling_x",
            "value": out["read_scaling_x"],
            "unit": "x",
            **out,
        }, json_out, mode="replica")
        return

    if env_flag("REFLOW_BENCH_SUBS"):
        # subs mode is host-side CPU work over loopback
        os.environ.setdefault("JAX_PLATFORMS", "cpu")
        out = run_subs_bench()
        _emit({
            "metric": "subs_write_p99_overhead_x",
            "value": out["write_p99_overhead_x"],
            "unit": "x",
            **out,
        }, json_out, mode="subs")
        return

    if env_flag("REFLOW_BENCH_COMPACT"):
        # bounded-history mode is host-side CPU work
        os.environ.setdefault("JAX_PLATFORMS", "cpu")
        out = run_compact_bench()
        _emit({
            "metric": "compact_recover_speedup_x",
            "value": out["recover_speedup_x"],
            "unit": "x",
            **out,
        }, json_out, mode="compact")
        return

    if env_flag("REFLOW_BENCH_TILES"):
        # tiles mode is host-side CPU work — no subprocesses
        os.environ.setdefault("JAX_PLATFORMS", "cpu")
        out = run_tiles_bench()
        _emit({
            "metric": "tiles_restore_wall_ratio_x",
            "value": out["restore_wall_ratio_x"],
            "unit": "x",
            **out,
        }, json_out, mode="tiles")
        return

    if env_flag("REFLOW_BENCH_CHAOS"):
        # chaos mode is host-side CPU work over local TCP
        os.environ.setdefault("JAX_PLATFORMS", "cpu")
        out = run_chaos_bench()
        _emit({
            "metric": "chaos_converge_s",
            "value": out["converge_s"],
            "unit": "s",
            **out,
        }, json_out, mode="chaos")
        return

    if env_flag("REFLOW_BENCH_FAILOVER"):
        # failover mode is host-side CPU work — no subprocesses
        os.environ.setdefault("JAX_PLATFORMS", "cpu")
        out = run_failover_bench()
        _emit({
            "metric": "failover_promotion_s",
            "value": out["promotion_s"],
            "unit": "s",
            **out,
        }, json_out, mode="failover")
        return

    if env_flag("REFLOW_BENCH_FLEETOBS"):
        # fleetobs mode is host-side CPU work over local TCP
        os.environ.setdefault("JAX_PLATFORMS", "cpu")
        out = run_fleetobs_bench()
        _emit({
            "metric": "fleetobs_overhead_frac",
            "value": out["fleetobs_overhead_frac"],
            "unit": "frac",
            **out,
        }, json_out, mode="fleetobs")
        return

    if env_flag("REFLOW_BENCH_MULTIPROC"):
        # multiproc mode spawns its own CPU-pinned children; the
        # parent does host-side control work only
        os.environ.setdefault("JAX_PLATFORMS", "cpu")
        out = run_multiproc_bench()
        _emit({
            "metric": "multiproc_promotion_s",
            "value": out["promotion_s"],
            "unit": "s",
            **out,
        }, json_out, mode="multiproc")
        return

    if env_flag("REFLOW_BENCH_E2ETRACE"):
        # e2etrace mode spawns its own CPU-pinned children; the parent
        # pumps subscribers and merges traces
        os.environ.setdefault("JAX_PLATFORMS", "cpu")
        out = run_e2etrace_bench()
        _emit({
            "metric": "e2etrace_full_chains",
            "value": out["full_chains"],
            "unit": "chains",
            **out,
        }, json_out, mode="e2etrace")
        return

    if env_flag("REFLOW_BENCH_OBS"):
        # obs mode is host-side CPU work — no subprocesses
        os.environ.setdefault("JAX_PLATFORMS", "cpu")
        out = run_obs_bench()
        _emit({
            "metric": "serve_obs_overhead_frac",
            "value": out["obs_overhead_frac"],
            "unit": "frac",
            **out,
        }, json_out, mode="obs")
        return

    if env_flag("REFLOW_BENCH_RECOVERY"):
        # WAL mode is mostly host-side work; the device-path section runs
        # on whatever backend JAX_PLATFORMS selects (default cpu)
        os.environ.setdefault("JAX_PLATFORMS", "cpu")
        out = run_recovery_bench()
        _emit({
            "metric": "wal_recovery_time_to_first_tick_s",
            "value": out["time_to_first_tick_s"],
            "unit": "s",
            **out,
        }, json_out, mode="recovery")
        return

    if env_flag("REFLOW_BENCH_PIPELINE"):
        # pipelined-window mode measures the device window path — do NOT
        # force cpu; the tier-1 smoke sets JAX_PLATFORMS=cpu explicitly
        out = run_pipeline_bench()
        _emit({
            "metric": "pipeline_depth2_vs_depth1_x",
            "value": out["depth2_vs_depth1_x"],
            "unit": "x",
            **out,
        }, json_out, mode="pipeline")
        return

    if env_flag("REFLOW_BENCH_MEGATICK"):
        # mega-tick mode measures the device window path — do NOT force
        # cpu here; the tier-1 smoke sets JAX_PLATFORMS=cpu explicitly
        out = run_megatick_bench()
        _emit({
            "metric": "megatick_amortized_tick_over_window_dispatch_x",
            "value": out["amortized_over_dispatch_x"],
            "unit": "x",
            **out,
        }, json_out, mode="megatick")
        return

    child = env_str("REFLOW_BENCH_CHILD", None)
    if child:
        if child != "cfg1":     # cfg1 is the CPU oracle: no jax, no cache
            from reflow_tpu.utils.runtime import place_compile_cache
            place_compile_cache()
        try:
            out = _CHILDREN[child]()
        except Exception as e:  # noqa: BLE001 - boundary: report, exit 1
            import traceback
            traceback.print_exc(file=sys.stderr)
            print(json.dumps({"error": f"{type(e).__name__}: {e}"}),
                  flush=True)
            sys.exit(1)
        print(json.dumps({**out, "device": _device()}), flush=True)
        return

    p = _params()
    failed = []     # children whose failure this command's exit reports

    # configs 1/2/4/5 first (records on stderr), headline (config 3) last
    # so the final stdout line stays the parseable result
    if env_flag("REFLOW_BENCH_ALL"):
        for name in ("cfg1", "cfg2", "cfg4", "cfg5"):
            r = _spawn(name)
            if "error" in r:
                log(json.dumps({"config": name, **r}))
                failed.append(name)

    tpu = _spawn("pr_tpu")
    log("tpu:", json.dumps(tpu))
    if "error" in tpu:
        _emit({
            "metric": ("pagerank_incremental_delta_ops_per_s_speedup"
                       "_vs_cpu_executor"),
            "value": None, "unit": "x", "error": tpu["error"],
            "failed_children": failed + ["pr_tpu"],
        }, json_out, mode="pagerank")
        sys.exit(1)
    # the backend line comes from the first device child: this parent
    # never initialises a JAX backend (one process per chip)
    dev = tpu["device"]
    log(f"jax backend={dev['platform']} kind={dev['kind']} "
        f"devices={dev['count']}")
    # the deferred window (cross-tick residual deferral, defer_passes):
    # the incr_vs_full lever, with its accuracy contract measured in the
    # child (mid-stream + drained error vs the independent oracle)
    tpud = None
    if p["defer"]:
        tpud = _spawn("pr_tpu_defer")
        log("tpu_defer:", json.dumps(tpud))
        if "error" in tpud:
            failed.append("pr_tpu_defer")
            tpud = None

    # full-recompute baseline: MEDIAN OF 3 SUBPROCESSES (VERDICT r4 #2 —
    # one subprocess snapshot was the bottom of the variance band). Each
    # child still takes min-of-3 in-process rounds (the outlier guard on
    # the numerator); the cross-process median guards run-to-run spread.
    full_runs = []
    for i in range(1 if p["smoke"] else 3):
        r = _spawn("pr_full")
        log(f"full[{i}]:", json.dumps(r))
        if "error" in r:
            failed.append(f"pr_full[{i}]")
        else:
            full_runs.append(r["full_recompute_s"])
    incr_vs_full = incr_vs_full_q = None
    incr_vs_full_runs = []
    full_med = float(np.median(full_runs)) if full_runs else None
    if full_med is not None:
        incr_vs_full_q = full_med / tpu["tick_s_amortized"]
        log(f"incremental-vs-full (quiescent window): "
            f"{incr_vs_full_q:.1f}x")
        if tpud is not None:
            incr_vs_full = full_med / tpud["tick_s_amortized"]
            incr_vs_full_runs = [
                round(f / tpud["tick_s_amortized"], 2) for f in full_runs]
            log(f"incremental-vs-full (deferred window, "
                f"defer={tpud.get('defer_passes')}): {incr_vs_full:.1f}x "
                f"runs={incr_vs_full_runs}")
        else:
            incr_vs_full = incr_vs_full_q
            incr_vs_full_runs = [
                round(f / tpu["tick_s_amortized"], 2) for f in full_runs]

    # CPU baseline: measured at the cap, with a scaling sweep making the
    # per-row-rate extrapolation explicit (the rate is flat-to-declining
    # in size, so quoting the cap-size rate at full scale is conservative)
    if p["cpu_full"]:
        cpu = run_pagerank_cpu(p["n_nodes"], p["n_edges"], p["churn"], 1,
                               p["tol"])
    else:
        sweep = []
        cap = min(p["cpu_cap"], p["n_edges"])
        e = max(256, cap // 4)
        while e <= cap:
            scale = e / p["n_edges"]
            r = run_pagerank_cpu(max(64, int(p["n_nodes"] * scale)), e,
                                 p["churn"], 1, p["tol"])
            sweep.append(r)
            log(f"cpu sweep @ {e} edges: "
                f"{r['delta_ops_per_s']:.0f} delta-ops/s")
            e *= 2
        cpu = sweep[-1]
    log("cpu:", json.dumps(cpu))

    speedup = tpu["delta_ops_per_s"] / cpu["delta_ops_per_s"]
    _emit({
        "metric": "pagerank_incremental_delta_ops_per_s_speedup_vs_cpu_executor",
        "value": round(speedup, 2),
        "unit": "x",
        "vs_baseline": round(speedup / 20.0, 3),
        "tpu_delta_ops_per_s": round(tpu["delta_ops_per_s"]),
        "tpu_window_ticks": tpu.get("window_ticks"),
        "tpu_window_dispatch_s": tpu.get("window_dispatch_s"),
        "cpu_delta_ops_per_s": round(cpu["delta_ops_per_s"]),
        "cpu_edges": cpu["edges"],
        "incr_vs_full": (round(incr_vs_full, 2)
                         if incr_vs_full is not None else None),
        "incr_vs_full_runs": incr_vs_full_runs,
        "incr_vs_full_quiescent": (round(incr_vs_full_q, 2)
                                   if incr_vs_full_q is not None else None),
        "full_recompute_runs_s": full_runs,
        "device": dev,
        "failed_children": failed,
        **({"defer_passes": tpud.get("defer_passes"),
            "deferred_tick_s_amortized": tpud.get("tick_s_amortized"),
            "deferred_mid_stream_max_abs_err":
                tpud.get("mid_stream_max_abs_err"),
            "deferred_mid_stream_max_rel_err":
                tpud.get("mid_stream_max_rel_err"),
            "deferred_drained_max_abs_err":
                tpud.get("drained_max_abs_err"),
            "deferred_drained_max_rel_err":
                tpud.get("drained_max_rel_err"),
            "quiescent_max_rel_err":
                tpu.get("max_rel_err_vs_reference")} if tpud else {}),
    }, json_out, mode="pagerank")
    if failed:
        log(f"FAILED children: {failed}")
        sys.exit(1)


if __name__ == "__main__":
    main()
