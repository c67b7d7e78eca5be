"""Metrics/observability (SURVEY.md §5): aggregate the scheduler's
per-tick records into the BASELINE metrics, and profile a tick on device.

``TickResult`` (scheduler.py) is the raw per-tick record: deltas in/out,
dirty-set size, pass count, wall time. This module turns a run's history
into the headline numbers (delta-ops/sec, percentile tick walls) and
offers a ``jax.profiler`` context for capturing a device trace of a tick.
"""

from __future__ import annotations

import contextlib
import dataclasses
import warnings
from typing import List, Sequence

import numpy as np

__all__ = ["MetricsSummary", "ServeMetrics", "TierMetrics", "WalMetrics",
           "percentile", "summarize", "summarize_serve", "summarize_tier",
           "summarize_wal", "profile_trace"]


def percentile(xs, q: float) -> float:
    """Shared percentile over any sample sequence (list, tuple, deque,
    ndarray): the one helper every ``summarize_*`` and the obs tooling
    use. Empty input answers 0.0 (a run that never exercised the path
    reports a zero latency, not a crash); a single sample answers
    itself at every q."""
    xs = np.asarray(xs, dtype=float)
    if xs.size == 0:
        return 0.0
    return float(np.percentile(xs, q))


def _jsonify(obj):
    """Recursively coerce numpy scalars/arrays to plain Python so the
    result survives ``json.dumps`` — metric records are written to
    JSON so runs can be diffed across PRs."""
    if isinstance(obj, np.generic):
        return obj.item()
    if isinstance(obj, np.ndarray):
        return obj.tolist()
    if isinstance(obj, dict):
        return {k: _jsonify(v) for k, v in obj.items()}
    if isinstance(obj, (list, tuple)):
        return [_jsonify(v) for v in obj]
    return obj


@dataclasses.dataclass
class MetricsSummary:
    ticks: int
    delta_ops: int
    wall_s: float
    delta_ops_per_s: float
    tick_p50_s: float
    tick_p95_s: float
    passes_mean: float
    quiesced_all: bool
    #: ticks that forced a mid-stream device readback (the host
    #: stalled on the device); a streaming-shaped run should show 0
    #: here until its sync point
    forced_syncs: int

    def as_dict(self) -> dict:
        return dataclasses.asdict(self)


def summarize(history: Sequence) -> MetricsSummary:
    """Aggregate a scheduler's ``history`` (list of TickResult).

    Streaming ticks' scalar fields may still be device-resident (and
    ``quiesced`` a deferred callable); force each record to host values
    first — ``block()`` is idempotent and this is a sync point anyway.
    """
    if not history:
        # keyword-only on purpose: positional construction is exactly
        # how a field addition silently shifts every later field
        return MetricsSummary(
            ticks=0, delta_ops=0, wall_s=0.0, delta_ops_per_s=0.0,
            tick_p50_s=0.0, tick_p95_s=0.0, passes_mean=0.0,
            quiesced_all=True, forced_syncs=0)
    # ONE batched device_get of every device-resident scalar first: the
    # per-record block() then hits each jax.Array's cached host value
    # instead of issuing O(ticks x fields) sequential round trips
    # (callable-wrapped parts stay lazy and are forced by block itself)
    leaves = []
    for r in history:
        for f in (getattr(r, "passes", None), getattr(r, "deltas_in", None),
                  getattr(r, "deltas_out", None),
                  getattr(r, "quiesced", None)):
            parts = f.parts if hasattr(f, "parts") else (f,)
            leaves += [p for p in parts
                       if hasattr(p, "dtype") and hasattr(p, "addressable_shards")]
    if leaves:
        import jax

        jax.device_get(leaves)
    for r in history:
        if hasattr(r, "block"):
            r.block()
    walls = np.array([r.wall_s for r in history])
    dops = sum(r.delta_ops for r in history)
    return MetricsSummary(
        ticks=len(history),
        delta_ops=int(dops),
        wall_s=float(walls.sum()),
        delta_ops_per_s=float(dops / max(walls.sum(), 1e-12)),
        tick_p50_s=float(np.percentile(walls, 50)),
        tick_p95_s=float(np.percentile(walls, 95)),
        passes_mean=float(np.mean([r.passes for r in history])),
        quiesced_all=all(r.quiesced for r in history),
        forced_syncs=sum(bool(getattr(r, "forced_sync", False))
                         for r in history),
    )


@dataclasses.dataclass
class WalMetrics:
    """Durable-ingestion observability (``reflow_tpu.wal``): append and
    fsync latency percentiles from the log's recorded walls, plus the
    replay counters of a ``recovery.recover()`` run when one happened.
    """

    fsync_policy: str
    appends: int
    bytes_written: int
    fsyncs: int
    append_p50_s: float
    append_p95_s: float
    fsync_p50_s: float
    fsync_p95_s: float
    replayed_pushes: int
    deduped_pushes: int
    replayed_ticks: int
    #: group-commit shape under ``fsync="record"``: appends covered per
    #: fsync (1.0 everywhere = no batching happened; the serve frontend's
    #: coalesced appends should push these well above 1)
    group_commits: int = 0
    group_p50: float = 0.0
    group_max: float = 0.0

    def as_dict(self) -> dict:
        return dataclasses.asdict(self)

    def to_dict(self) -> dict:
        """``as_dict`` with every value JSON-serializable (numpy
        scalars coerced) — the cross-PR diffable export."""
        return _jsonify(dataclasses.asdict(self))


def summarize_wal(wal, recovery=None) -> WalMetrics:
    """Aggregate a ``wal.WriteAheadLog``'s counters (and optionally a
    ``wal.RecoveryReport``'s replay counters) into one record."""
    pct = percentile
    return WalMetrics(
        fsync_policy=wal.fsync_policy,
        appends=wal.appends,
        bytes_written=wal.bytes_written,
        fsyncs=wal.fsyncs,
        append_p50_s=pct(wal.append_s, 50),
        append_p95_s=pct(wal.append_s, 95),
        fsync_p50_s=pct(wal.fsync_s, 50),
        fsync_p95_s=pct(wal.fsync_s, 95),
        replayed_pushes=getattr(recovery, "replayed_pushes", 0),
        deduped_pushes=getattr(recovery, "deduped_pushes", 0),
        replayed_ticks=getattr(recovery, "replayed_ticks", 0),
        group_commits=len(getattr(wal, "group_sizes", [])),
        group_p50=pct(getattr(wal, "group_sizes", []), 50),
        group_max=float(max(getattr(wal, "group_sizes", []) or [0.0])),
    )


@dataclasses.dataclass
class ServeMetrics:
    """Ingestion-frontend observability (``reflow_tpu.serve``): admission
    outcomes, coalescing effectiveness, and producer-visible latency.

    ``coalesce_factor`` is the headline: micro-batches applied per
    scheduler tick. 1.0 means the window never merged anything (light
    traffic); ``tests/test_serve.py`` asserts > 1 under 8 producers.
    """

    policy: str
    submitted: int
    admitted: int
    applied: int
    deduped: int
    rejected: int
    shed: int
    ticks: int
    pump_iterations: int
    coalesce_factor: float
    ticks_per_pump_mean: float
    admission_p50_s: float
    admission_p95_s: float
    queue_depth_p95: float
    inflight_bytes_peak: int
    #: pipelined-pump view: configured in-flight window depth, windows
    #: that took the stage/dispatch/retire path, how many of those
    #: staged while a previous window was still in flight, and the
    #: fraction of host staging wall that overlapped device compute
    #: (0.0 at depth 1 — staging and execution strictly alternate);
    #: ``blocks_resolved_before_retire``: staged windows whose tickets
    #: resolved (at durability) while the window was still unretired
    window_depth: int = 1
    windows_staged: int = 0
    windows_pipelined: int = 0
    stage_overlap_frac: float = 0.0
    blocks_resolved_before_retire: int = 0

    def as_dict(self) -> dict:
        return dataclasses.asdict(self)

    def to_dict(self) -> dict:
        """``as_dict`` with every value JSON-serializable (numpy
        scalars coerced) — the cross-PR diffable export."""
        return _jsonify(dataclasses.asdict(self))


def summarize_serve(frontend) -> ServeMetrics:
    """Aggregate an ``IngestFrontend``'s counters into one record."""
    pct = percentile
    tp = frontend.ticks_per_pump
    return ServeMetrics(
        policy=frontend.policy,
        submitted=frontend.submitted,
        admitted=frontend.admitted,
        applied=frontend.applied,
        deduped=frontend.deduped,
        rejected=frontend.rejected,
        shed=frontend.shed,
        ticks=frontend.ticks,
        pump_iterations=frontend.pump_iterations,
        coalesce_factor=frontend.applied / max(frontend.ticks, 1),
        ticks_per_pump_mean=float(np.mean(tp)) if tp else 0.0,
        admission_p50_s=pct(frontend.admission_s, 50),
        admission_p95_s=pct(frontend.admission_s, 95),
        queue_depth_p95=pct(frontend.queue_depth_samples, 95),
        inflight_bytes_peak=frontend.inflight_bytes_peak,
        window_depth=getattr(frontend, "depth", 1),
        windows_staged=getattr(frontend, "windows_staged", 0),
        windows_pipelined=getattr(frontend, "windows_pipelined", 0),
        stage_overlap_frac=getattr(frontend, "stage_overlap_frac", 0.0),
        blocks_resolved_before_retire=getattr(
            frontend, "blocks_resolved_before_retire", 0),
    )


@dataclasses.dataclass
class TierMetrics:
    """Multi-graph serving-tier observability (``serve.tier``): pool
    health (utilization, windows, crash count), shared-budget occupancy,
    and cross-graph scheduling delay — the time a ready graph waited for
    a pool thread, the number QoS weighting is supposed to keep bounded
    for quiet tenants under a hot sibling.

    ``per_graph`` nests each live graph's ``ServeMetrics.to_dict()``
    plus its QoS/budget/pool view (weight, floor/ceiling, bytes used and
    peak, windows served, rows applied, scheduling-delay and admission
    p99, frontend state).
    """

    graphs: int
    pump_threads: int
    windows: int
    pool_crashes: int
    pump_utilization: float
    budget_total_bytes: int
    budget_used_bytes: int
    budget_peak_bytes: int
    #: high-water shared-budget occupancy fraction (peak/total)
    budget_occupancy_peak: float
    sched_delay_p50_s: float
    sched_delay_p99_s: float
    per_graph: dict
    #: pool supervision view: workers alive now vs the scale target,
    #: deaths recorded and respawns performed (control-plane healing)
    live_workers: int = 0
    worker_deaths: int = 0
    worker_respawns: int = 0
    #: picks where every positive-deficit candidate's bound device
    #: already had a window in flight (placement-aware DWRR could not
    #: avoid stacking; persistent growth = graphs-per-device skew)
    device_collisions: int = 0

    def as_dict(self) -> dict:
        return dataclasses.asdict(self)

    def to_dict(self) -> dict:
        """``as_dict`` with every value JSON-serializable (numpy
        scalars coerced) — the cross-PR diffable export."""
        return _jsonify(dataclasses.asdict(self))


def summarize_tier(tier) -> TierMetrics:
    """Aggregate a ``serve.ServeTier``'s pool/budget counters and every
    live graph's frontend counters into one record."""
    pct = percentile
    handles = tier.graphs()
    shares = tier.budget.shares()
    per_graph = {}
    all_delays: List[float] = []
    for name, h in handles.items():
        fe = h.frontend
        g = summarize_serve(fe).to_dict()
        share = shares.get(name)
        g.update(
            weight=h.config.weight,
            floor_bytes=h.config.floor_bytes,
            ceiling_bytes=(share.ceiling if share is not None
                           else h.config.ceiling_bytes),
            bytes_used=share.used if share is not None else 0,
            bytes_peak=share.peak if share is not None else 0,
            windows=h.windows,
            rows_applied=h.rows_applied,
            sched_delay_p50_s=pct(h.sched_delay_s, 50),
            sched_delay_p99_s=pct(h.sched_delay_s, 99),
            admission_p99_s=pct(fe.admission_s, 99),
            state=fe._state,
            policy=fe.policy,
            crashes=h.crashes,
            revives=fe.revives,
            device=h.device_label,
        )
        per_graph[name] = g
        all_delays.extend(h.sched_delay_s)
    return TierMetrics(
        graphs=len(handles),
        pump_threads=tier.pump_threads,
        windows=tier.windows,
        pool_crashes=tier.pool_crashes,
        pump_utilization=tier.pump_utilization,
        budget_total_bytes=tier.budget.total_bytes,
        budget_used_bytes=tier.budget.used,
        budget_peak_bytes=tier.budget.peak,
        budget_occupancy_peak=tier.budget.peak / tier.budget.total_bytes,
        sched_delay_p50_s=pct(all_delays, 50),
        sched_delay_p99_s=pct(all_delays, 99),
        per_graph=per_graph,
        live_workers=tier.live_workers,
        worker_deaths=tier.worker_deaths,
        worker_respawns=tier.worker_respawns,
        device_collisions=tier.device_collisions,
    )


@contextlib.contextmanager
def profile_trace(log_dir: str):
    """Capture a ``jax.profiler`` device trace around a block of ticks::

        with profile_trace("/tmp/trace"):
            sched.tick()

    View with TensorBoard / xprof against the produced log dir.

    Degrades gracefully: when ``jax.profiler`` is unavailable (CPU-only
    builds, stripped wheels) or refuses to start, the context runs the
    block untraced and warns instead of raising — profiling is
    observability, never correctness.
    """
    try:
        import jax

        start, stop = jax.profiler.start_trace, jax.profiler.stop_trace
        start(log_dir)
    except Exception as e:  # noqa: BLE001 - degrade to a no-op trace
        warnings.warn(
            f"jax.profiler unavailable ({e!r}); profile_trace is a "
            f"no-op for this block", RuntimeWarning, stacklevel=3)
        yield
        return
    try:
        yield
    finally:
        stop()
