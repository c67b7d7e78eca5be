"""DirtyScheduler: the change-driven recompute loop (SURVEY.md §2 #8, §3 #2).

Tick protocol (tick-synchronous, batched — SURVEY.md §0):

1. ``push`` buffers deltas at sources (host boundary in).
2. ``tick()`` drains the buffers, computes the structural dirty frontier
   (nodes reachable from dirty sources, in topo order — no device values are
   consulted), and hands the plan to the executor.
3. Deltas arriving on back-edges re-enter at loop nodes; the scheduler
   re-runs the (restricted) plan until quiescence or ``max_loop_iters`` —
   this is the host-driven fixpoint for iterative graphs like PageRank.
4. Sink deltas are folded into materialized host views (host boundary out).

The scheduler is deliberately cheap, host-side Python: all heavy lifting is
in the executor.
"""

from __future__ import annotations

import dataclasses
import time
from collections import Counter, defaultdict
from typing import Callable, Dict, List, Optional, Sequence

import numpy as np

from reflow_tpu.delta import DeltaBatch, lossy_value_cast
from reflow_tpu.executors import CpuExecutor, Executor
from reflow_tpu.graph import FlowGraph, GraphError, Node
from reflow_tpu.obs import trace as _trace
from reflow_tpu.utils.faults import DeliveryError

__all__ = ["DirtyScheduler", "TickResult"]


class LazyScalar:
    """Deferred sum of host ints and device scalars.

    Composing tick metadata (``1 + iters``, ``deltas_in + loop_rows``)
    with eager jnp arithmetic would dispatch a device op per tick, and
    every dispatch carries a fixed overhead. This keeps the parts
    un-combined until ``int()`` forces them at the sync point."""

    __slots__ = ("parts",)

    def __init__(self, *parts):
        self.parts = parts

    def __int__(self) -> int:
        def force(p):
            if isinstance(p, int):
                return p
            if callable(p):
                return int(p())
            return int(np.asarray(p).sum())

        return sum(force(p) for p in self.parts)

    def __bool__(self) -> bool:
        return int(self) != 0

    def __add__(self, other):
        return LazyScalar(*self.parts, other)

    __radd__ = __add__


def lazy_add(a, b):
    """a + b without an eager device op when either side is device-resident."""
    if isinstance(a, int) and isinstance(b, int):
        return a + b
    return LazyScalar(a, b)


def _count_nonzero_global(w) -> int:
    """Live-row count of a weights column that may be a MULTI-HOST global
    array (process-local ingestion): np.asarray on a partially-
    addressable array is illegal, so count the addressable shards. The
    count is deliberately the PROCESS-LOCAL share on multi-controller
    runs — never a hidden cross-process collective, which would deadlock
    any non-SPMD metrics access (e.g. `if process_index() == 0:
    summarize(...)`). Sum deltas_in across processes for global totals.
    Single-controller arrays take the plain path."""
    if getattr(w, "is_fully_addressable", True):
        return int(np.count_nonzero(np.asarray(w)))
    return sum(int(np.count_nonzero(np.asarray(s.data)))
               for s in w.addressable_shards)


@dataclasses.dataclass
class TickResult:
    """Per-tick observability record (SURVEY.md §5 metrics).

    After ``tick(sync=False)`` the scalar fields may still be
    device-resident (pipelined streaming: nothing blocked on the device);
    call :meth:`block` to force them to host Python values.
    """

    tick: int
    sink_deltas: Dict[str, DeltaBatch]
    passes: int
    dirty_nodes: int
    deltas_in: int
    deltas_out: int
    wall_s: float
    quiesced: bool
    #: captured executor error check for streaming ticks whose per-tick
    #: check was deferred; ``block()`` (the documented streaming sync
    #: point) runs it so sticky flags can't finish a run unsurfaced
    #: (ADVICE r2: a pure-streaming run never otherwise checked)
    _check_errors: Optional[Callable[[], None]] = dataclasses.field(
        default=None, repr=False, compare=False)
    #: this tick forced a mid-stream device readback (synchronous tick or
    #: sink materialization on a device executor): the host waited for
    #: the device — counted by MetricsSummary.forced_syncs
    forced_sync: bool = False

    @property
    def delta_ops(self) -> int:
        """Delta rows processed — numerator of delta-ops/sec (BASELINE.md)."""
        return self.deltas_in + self.deltas_out

    def block(self) -> "TickResult":
        """Force any device-resident scalar fields to host values and
        surface deferred executor errors (the streaming sync point; a
        no-op for synchronous ticks). Macro-tick results (tick_many)
        carry per-tick [K] stacks; they aggregate here."""
        def to_int(x):
            if isinstance(x, (int, LazyScalar)):
                return int(x)
            return int(np.asarray(x).sum())

        self.passes = to_int(self.passes)
        self.deltas_in = to_int(self.deltas_in)
        self.deltas_out = to_int(self.deltas_out)
        q = self.quiesced() if callable(self.quiesced) else self.quiesced
        self.quiesced = bool(np.asarray(q).all())
        if self._check_errors is not None:
            check, self._check_errors = self._check_errors, None
            check()
        return self


class SourceCursor:
    """Deterministic batch-id mint for exactly-once ingestion.

    Under at-least-once upstream delivery, ``push(batch_id=...)`` dedups
    replays. On MULTI-CONTROLLER runs the dedup sets must stay
    SPMD-identical across processes (checkpoint meta assumes it —
    verified collectively at save); deriving ids from a shared monotone
    cursor makes that identity true BY CONSTRUCTION: every process mints
    ``"<source>@<seq>"`` for the same global batch, regardless of which
    local rows it contributes (``shard_batch_process_local``).

    ``resume`` re-derives the cursor position after a checkpoint restore
    from the restored dedup window, so a restarted driver neither reuses
    an accepted id (its push would dedup away) nor skips one.
    """

    __slots__ = ("name", "seq")

    def __init__(self, source: Node, start: int = 0):
        self.name = source.name
        self.seq = start

    def next_id(self) -> str:
        bid = f"{self.name}@{self.seq}"
        self.seq += 1
        return bid

    @classmethod
    def resume(cls, sched: "DirtyScheduler", source: Node) -> "SourceCursor":
        prefix = source.name + "@"
        top = -1
        for bid in sched._seen_batch_ids:
            if bid.startswith(prefix):
                try:
                    top = max(top, int(bid[len(prefix):]))
                except ValueError:
                    pass
        return cls(source, top + 1)


class _StagedTicks:
    """Handle for one staged-but-undispatched fused window
    (``stage_window`` → ``dispatch_staged`` → ``retire_staged``): the
    executor's :class:`StagedWindow` plus the scheduler-side facts the
    dispatch needs to build the aggregated TickResult."""

    __slots__ = ("sw", "k", "host_rows", "plan")

    def __init__(self, sw, k: int, host_rows: int, plan):
        self.sw = sw
        self.k = k
        self.host_rows = host_rows
        self.plan = plan


class DirtyScheduler:
    def __init__(self, graph: FlowGraph, executor: Optional[Executor] = None,
                 *, max_loop_iters: int = 10_000,
                 dedup_window: int = 1 << 20):
        graph.validate()
        self.graph = graph
        self.executor = executor if executor is not None else CpuExecutor()
        self.executor.bind(graph)
        self.max_loop_iters = max_loop_iters
        self._pending: Dict[int, List[DeltaBatch]] = defaultdict(list)
        #: insertion-ordered dedup set for idempotent pushes, bounded to
        #: the newest ``dedup_window`` ids (upstream redelivery must stay
        #: within that horizon)
        self._seen_batch_ids: Dict[str, None] = {}
        self._metric_keys: list = []  # (registry, key) published
        self.dedup_window = dedup_window
        self._tick = 0
        self.sink_views: Dict[str, Counter] = {s.name: Counter() for s in graph.sinks}
        self.history: List[TickResult] = []
        #: mid-stream device readbacks this scheduler forced (sync ticks,
        #: sink materialization, read_table on a device executor): each
        #: one stalls the host until the device drains, so a streaming
        #: caller wants this at one per batch, not one per tick
        self.forced_syncs = 0
        #: mega-tick window path (docs/guide.md "Compiled mega-ticks"):
        #: windows dispatched through the device ingress queue vs windows
        #: that fell back (ragged feeds too wasteful, over-capacity
        #: batches, device-resident feeds, unsupported graph)
        self.megatick_windows = 0
        self.megatick_fallbacks = 0
        #: max tolerated padding waste: the fraction of the window's
        #: (tick, source) slots that would be zero-row padding. Divergent
        #: per-tick dirty sets above this run the per-tick path instead
        self.megatick_waste = 0.5

    # -- host boundary in --------------------------------------------------

    def push(self, source: Node, batch: DeltaBatch, *,
             batch_id: Optional[str] = None) -> bool:
        """Buffer deltas at a source — or at a loop variable, which is how a
        fixpoint computation receives its initial condition.

        ``batch_id`` makes ingestion idempotent (exactly-once under
        at-least-once upstream delivery, SURVEY.md §5): a batch whose id
        was already accepted — including before a checkpoint/restore — is
        dropped. Returns whether the batch was accepted.
        """
        if source.kind not in ("source", "loop"):
            raise GraphError(f"can only push to sources/loops, not {source}")
        why = lossy_value_cast(source.spec, batch)
        if why is not None:
            # refused before the id is registered: a corrected batch may
            # come again under the same id
            raise DeliveryError(f"{source}: {why}")
        if batch_id is not None and not self._register_batch_id(batch_id):
            return False
        # device-resident batches are enqueued unconditionally: their
        # len() is a device->host readback (DeviceDelta.__len__) that
        # would stall the pipelined stream — a padded all-zero-weight
        # batch is a cheap no-op
        if not hasattr(batch, "nonzero") and not len(batch):
            return True
        self._pending[source.id].append(batch)
        return True

    def _register_batch_id(self, batch_id: str) -> bool:
        """Record ``batch_id`` in the bounded dedup window. Returns False
        (without touching the window) when the id is already held — a
        replay inside the horizon. Eviction is pure insertion order: a
        rejected replay does NOT refresh its id's position, so the
        horizon is "newest ``dedup_window`` *accepted* ids"."""
        if batch_id in self._seen_batch_ids:
            return False
        self._seen_batch_ids[batch_id] = None
        while len(self._seen_batch_ids) > self.dedup_window:
            self._seen_batch_ids.pop(next(iter(self._seen_batch_ids)))
        return True

    # -- dirty planning (structural) --------------------------------------

    def _dirty_plan(self, dirty_roots: Sequence[int]) -> List[Node]:
        dirty = set(dirty_roots)
        plan = []
        for node in self.graph.nodes:  # construction order == topo order
            if node.id in dirty:
                plan.append(node)
                continue
            if node.kind in ("source", "loop"):
                continue
            if any(i.id in dirty for i in node.inputs):
                dirty.add(node.id)
                plan.append(node)
        return plan

    # -- the tick ----------------------------------------------------------

    def tick(self, *, sync: bool = True) -> TickResult:
        """Run one tick. ``sync=False`` (streaming mode) skips the
        per-tick device readback for iterative graphs fully fused on
        device: ticks enqueue back-to-back and the returned TickResult's
        scalars stay device-resident until ``block()``. Graphs with sinks
        or host-driven loops still materialize synchronously."""
        t0 = time.perf_counter()

        def _merge_pending(batches):
            # a device-resident batch passes through untouched (host
            # concat would force readbacks); it cannot be merged with
            # other same-tick batches for the same source
            if any(hasattr(b, "nonzero") for b in batches):
                if len(batches) > 1:
                    raise GraphError(
                        "a device-resident batch cannot be merged with "
                        "other pending batches for the same source in "
                        "one tick; push it alone")
                return batches[0]
            return DeltaBatch.concat(batches)

        ingress: Dict[int, DeltaBatch] = {
            nid: _merge_pending(batches)
            for nid, batches in self._pending.items()
        }
        self._pending.clear()
        # device batches defer their live-row count entirely (len() or an
        # eager nonzero() would read back / dispatch mid-tick);
        # TickResult.block() counts them at the sync point
        deltas_in = sum(len(b) for b in ingress.values()
                        if not hasattr(b, "nonzero"))
        dev_counts = [
            (lambda w=b.weights: _count_nonzero_global(w))
            for b in ingress.values() if hasattr(b, "nonzero")]
        if dev_counts:
            deltas_in = LazyScalar(deltas_in, *dev_counts)
        deltas_out = 0
        passes = 0
        dirty_union: set = set()
        sink_deltas: Dict[str, List[DeltaBatch]] = defaultdict(list)
        quiesced = True
        sink_ids = {s.id: s for s in self.graph.sinks}

        while ingress:
            if passes >= self.max_loop_iters:
                # PAUSE, don't drop: the leftover loop deltas re-enter as
                # pending for the next tick, so join/reduce state stays
                # mutually consistent and a later tick (or a repair
                # protocol like workloads/sssp.repair) resumes exactly
                # where the halted iteration stopped
                quiesced = False
                for nid, batch in ingress.items():
                    self._pending[nid].append(batch)
                break
            plan = self._dirty_plan(list(ingress))
            dirty_union.update(n.id for n in plan)
            if passes == 0 and self.graph.loops:
                # iterative graph: let the executor fuse the entire tick
                # (all fixpoint passes) into one on-device program
                fx = self.executor.run_tick_fixpoint(
                    plan, ingress, self.max_loop_iters, sync=sync)
                if fx is not None:
                    (sink_batches, fx_passes, loop_rows, quiesced,
                     extra_dirty, leftover) = fx
                    passes = fx_passes
                    deltas_in = lazy_add(deltas_in, loop_rows)
                    dirty_union.update(extra_dirty)
                    for sid, batches in sink_batches.items():
                        sink_deltas[sink_ids[sid].name].extend(batches)
                    # a max_iters halt pauses: live carry re-enters as
                    # pending so the next tick resumes the iteration
                    for nid, b in leftover.items():
                        self._pending[nid].append(b)
                    break
            egress = self.executor.run_pass(plan, ingress)
            passes += 1
            ingress = {}
            for nid, batch in egress.items():
                if nid in sink_ids:
                    if len(batch):
                        sink_deltas[sink_ids[nid].name].append(batch)
                elif len(batch):  # loop back-edge -> next pass
                    ingress[nid] = batch
                    deltas_in += len(batch)

        # fail loudly if any op state carries a sticky error flag (e.g. a
        # retraction exhausted a min/max candidate buffer) BEFORE corrupt
        # deltas are folded into the materialized sink views. Streaming
        # ticks (sync=False) defer the check to the next sync point —
        # unless sink views are about to be materialized, which forces a
        # sync anyway and must not fold corrupt deltas
        checked = sync or bool(sink_deltas)
        if checked:
            if getattr(self.executor, "name", "") != "cpu":
                self.forced_syncs += 1
            self.executor.check_errors()

        out: Dict[str, DeltaBatch] = {}
        for name, batches in sink_deltas.items():
            # sink batches may still be device-resident (deferred readback:
            # the host crossing happens once per tick, not once per pass)
            merged = DeltaBatch.concat(
                [self.executor.materialize(b) for b in batches]).consolidate()
            out[name] = merged
            deltas_out += len(merged)
            view = self.sink_views[name]
            for k, v, w in merged.rows():
                view[(k, v)] += w
                if view[(k, v)] == 0:
                    del view[(k, v)]

        self._tick += 1
        result = TickResult(
            tick=self._tick,
            sink_deltas=out,
            passes=passes,
            dirty_nodes=len(dirty_union),
            deltas_in=deltas_in,
            deltas_out=deltas_out,
            wall_s=time.perf_counter() - t0,
            quiesced=quiesced,
            _check_errors=None if checked else self.executor.check_errors,
            forced_sync=checked and getattr(self.executor, "name",
                                            "") != "cpu",
        )
        if _trace.ENABLED:
            _trace.evt("tick", t0, result.wall_s,
                       args={"tick": self._tick,
                             "dirty": result.dirty_nodes})
        self.history.append(result)
        return result

    def tick_many(self, feeds: Sequence[Dict[Node, DeltaBatch]], *,
                  feed_ids: Optional[Sequence[Dict[Node, Sequence[str]]]]
                  = None) -> TickResult:
        """K consecutive streaming ticks, fused into ONE device execution
        when the executor supports it (the macro-tick; see
        ``TpuExecutor.run_tick_fixpoint_many``). ``feeds[t]`` is tick
        ``t``'s source-push set; semantics are identical to pushing and
        ticking each feed in order with ``sync=False``.

        ``feed_ids`` (parallel to ``feeds``) carries the producer batch
        ids a coalesced feed entry commits — the serving frontend merges
        several ``submit()`` micro-batches into one feed batch, and their
        ids must land in the dedup window atomically with the macro-tick
        so replays dedup exactly as ``push(batch_id=...)`` replays do.
        Ids are *recorded*, not filtered: the caller (the frontend's
        admission path) is responsible for rejecting duplicates before
        coalescing.

        Returns ONE aggregated TickResult covering all K ticks (scalar
        fields sum/all-combine at ``block()``). Falls back to the
        per-tick loop for executors/graphs without the fused path.
        Requires no pending pushes (push() + tick_many don't mix) and a
        sink-free graph on the fused path.
        """
        if any(self._pending.values()):
            raise GraphError("tick_many cannot run with pending push()ed "
                             "batches; tick() them first")
        if feed_ids is not None:
            if len(feed_ids) != len(feeds):
                raise GraphError(
                    f"feed_ids must parallel feeds "
                    f"({len(feed_ids)} != {len(feeds)})")
            for ids_map in feed_ids:
                for ids in ids_map.values():
                    for bid in ids:
                        self._register_batch_id(bid)
        feeds = [{src.id: b for src, b in f.items()} for f in feeds]
        for f in feeds:
            for nid in f:
                node = self.graph.nodes[nid]
                if node.kind not in ("source", "loop"):
                    raise GraphError(
                        f"can only feed sources/loops, not {node}")

        t0 = time.perf_counter()
        fx = None
        plan = self._dirty_plan(sorted({n for f in feeds for n in f}))
        if feeds:
            fx = self._run_window_path(plan, feeds)
        runner = getattr(self.executor, "run_tick_fixpoint_many", None)
        if fx is None and runner is not None and feeds:
            fx = runner(plan, feeds, self.max_loop_iters)
        if fx is None:
            # fallback: ordinary streaming ticks, aggregated lazily (no
            # readbacks here — everything combines at block(), keeping
            # the deferred-sync contract even on the unfused path)
            results = []
            for f in feeds:
                for nid, b in f.items():
                    self._pending[nid].append(b)
                results.append(self.tick(sync=False))
            merged_sinks: Dict[str, List[DeltaBatch]] = defaultdict(list)
            for r in results:
                for name, b in r.sink_deltas.items():
                    merged_sinks[name].append(b)
            agg = TickResult(
                tick=self._tick,
                sink_deltas={name: DeltaBatch.concat(bs)
                             for name, bs in merged_sinks.items()},
                passes=LazyScalar(*[r.passes for r in results]),
                dirty_nodes=max((r.dirty_nodes for r in results),
                                default=0),
                deltas_in=LazyScalar(*[r.deltas_in for r in results]),
                deltas_out=LazyScalar(*[r.deltas_out for r in results]),
                wall_s=time.perf_counter() - t0,
                quiesced=(lambda rs=results: all(
                    bool(np.asarray(r.quiesced).all()) for r in rs)),
                _check_errors=self.executor.check_errors,
            )
            if _trace.ENABLED:
                _trace.evt("tick_many", t0, agg.wall_s,
                           args=_trace.with_win(
                               {"ticks": len(feeds), "fused": False}))
            self.history.append(agg)
            return agg

        passes_base, iters, rows, conv, extra_dirty = fx
        K = len(feeds)
        host_rows = sum(len(b) for f in feeds for b in f.values())
        plan_ids = {n.id for n in plan}
        self._tick += K
        result = TickResult(
            tick=self._tick,
            sink_deltas={},
            # per-tick [K] stacks stay device-resident; block() aggregates
            passes=LazyScalar(passes_base, iters),
            dirty_nodes=len(plan_ids | extra_dirty),
            deltas_in=LazyScalar(host_rows, rows),
            deltas_out=0,
            wall_s=time.perf_counter() - t0,
            quiesced=conv,
            _check_errors=self.executor.check_errors,
        )
        if _trace.ENABLED:
            _trace.evt("tick_many", t0, result.wall_s,
                       args=_trace.with_win({"ticks": K, "fused": True}))
        self.history.append(result)
        return result

    # -- mega-tick window path (docs/guide.md "Compiled mega-ticks") -------

    @property
    def window_support(self) -> bool:
        """Whether the executor advertises the fused window path for the
        bound graph (the serve frontend reads this to pick admission
        accounting and the pump's default window behavior)."""
        sup = getattr(self.executor, "supports_window", None)
        return bool(sup()) if callable(sup) else False

    def _zero_batch(self, nid: int) -> DeltaBatch:
        spec = self.graph.nodes[nid].spec
        vshape = tuple(spec.value_shape)
        return DeltaBatch(np.zeros(0, np.int64),
                          np.zeros((0,) + vshape, spec.value_dtype),
                          np.zeros(0, np.int64))

    def _run_window_path(self, plan, feeds):
        """Try the device-resident window executor on this tick_many
        call: pad ragged per-tick feeds to the window's union source set
        with zero-row deltas (weight-0 rows are semantic no-ops, so the
        compiled body keeps ONE fixed plan for the whole window) and
        hand the window to ``executor.run_window``. Returns the fused
        result tuple or None — padding waste above ``megatick_waste``,
        over-capacity batches, and executor refusals fall back to the
        stacked/per-tick paths, counted in ``megatick_fallbacks``.
        Device-resident batches skip silently (they ride their own feed
        slot by design — that's the walpipe protocol, not a fallback).
        """
        run = getattr(self.executor, "run_window", None)
        if run is None or not self.window_support:
            return None
        for f in feeds:
            for b in f.values():
                if hasattr(b, "nonzero"):
                    return None
        K = len(feeds)
        union = sorted({n for f in feeds for n in f})
        if not union:
            return None
        pad_slots = sum(1 for f in feeds for nid in union
                        if nid not in f or len(f[nid]) == 0)
        if pad_slots / (K * len(union)) > self.megatick_waste:
            # dirty sets diverge too much: padding every tick to the
            # union would mostly move zeros — per-tick plans win
            self.megatick_fallbacks += 1
            return None
        padded = [dict(f) for f in feeds]
        for f in padded:
            for nid in union:
                if nid not in f:
                    f[nid] = self._zero_batch(nid)
        fx = run(plan, padded, self.max_loop_iters)
        if fx is None:
            self.megatick_fallbacks += 1
        else:
            self.megatick_windows += 1
        return fx

    # -- staged (pipelined) window path ------------------------------------
    #
    # The serve pump's software-pipelined drive of the same mega-tick:
    # stage_window (host slot writes + WAL append) can overlap a previous
    # window's device execution; dispatch_staged commits the tick horizon
    # and returns the TickResult; retire_staged re-adopts the donated
    # buffers off the critical path. stage → dispatch → retire on one
    # window is semantically identical to tick_many's fused branch.

    def stage_window(self, feeds: Sequence[Dict[Node, DeltaBatch]], *,
                     feed_ids: Optional[Sequence[Dict[Node, Sequence[str]]]]
                     = None):
        """Stage (but do not dispatch) one K-tick fused window: validate
        and pad the feeds exactly as ``tick_many``'s window path does,
        slot-write them into the executor's ingress queue, and seal the
        staged generation. Returns an opaque handle for
        :meth:`dispatch_staged` / :meth:`retire_staged`, or None when the
        window doesn't fit the fused path — the caller then falls back to
        :meth:`tick_many`, which re-checks and counts the fallback itself
        (nothing is counted or logged here on refusal, so the fallback
        isn't double-counted).

        A successful stage has already WAL-logged the window's pushes
        (append-before-dispatch, same order as ``tick_many``) and
        registered its batch ids, so the caller MUST follow with
        ``dispatch_staged`` — abandoning a staged window is a crash, not
        a fallback."""
        if any(self._pending.values()):
            raise GraphError("stage_window cannot run with pending "
                             "push()ed batches; tick() them first")
        stage = getattr(self.executor, "stage_window", None)
        if stage is None or not self.window_support or not feeds:
            return None
        nfeeds = []
        for f in feeds:
            entry = {}
            for src, b in f.items():
                if src.kind not in ("source", "loop"):
                    raise GraphError(
                        f"can only feed sources/loops, not {src}")
                if hasattr(b, "nonzero"):
                    return None  # device-resident: walpipe's own slot
                entry[src.id] = b
            nfeeds.append(entry)
        K = len(nfeeds)
        union = sorted({n for f in nfeeds for n in f})
        if not union:
            return None
        pad_slots = sum(1 for f in nfeeds for nid in union
                        if nid not in f or len(f[nid]) == 0)
        if pad_slots / (K * len(union)) > self.megatick_waste:
            return None
        plan = self._dirty_plan(union)
        padded = [dict(f) for f in nfeeds]
        for f in padded:
            for nid in union:
                if nid not in f:
                    f[nid] = self._zero_batch(nid)
        sw = stage(plan, padded, self.max_loop_iters)
        if sw is None:
            return None
        # the stage is committed: register ids and WAL-log the pushes NOW
        # (append-before-dispatch). On the earlier refusals above nothing
        # was registered, so the tick_many fallback re-registers cleanly
        # (_register_batch_id tolerates replays).
        if feed_ids is not None:
            if len(feed_ids) != len(feeds):
                raise GraphError(
                    f"feed_ids must parallel feeds "
                    f"({len(feed_ids)} != {len(feeds)})")
            for ids_map in feed_ids:
                for ids in ids_map.values():
                    for bid in ids:
                        self._register_batch_id(bid)
        self._log_window_feeds(feeds, feed_ids)
        host_rows = sum(len(b) for f in nfeeds for b in f.values())
        return _StagedTicks(sw, K, host_rows, plan)

    def _log_window_feeds(self, feeds, feed_ids) -> None:
        """Durability hook for a successfully staged window: the base
        scheduler has no log; ``DurableScheduler`` appends the window's
        push records here (append-before-dispatch)."""

    def dispatch_staged(self, handle: "_StagedTicks") -> TickResult:
        """Dispatch a staged window: ONE device execution, the tick
        horizon advances by K, and the aggregated TickResult (identical
        to ``tick_many``'s fused branch) is returned. The dispatch is
        async — the caller can stage the next window immediately and
        ``retire_staged`` this one later."""
        t0 = time.perf_counter()
        fx = self.executor.dispatch_window(handle.sw)
        if fx is None:
            # stage_window guaranteed the fused program exists — a None
            # here is a lifecycle bug, and the window's WAL records are
            # already appended, so falling back would double-log
            raise GraphError("staged window refused dispatch")
        self.megatick_windows += 1
        passes_base, iters, rows, conv, extra_dirty = fx
        K = handle.k
        plan_ids = {n.id for n in handle.plan}
        self._tick += K
        result = TickResult(
            tick=self._tick,
            sink_deltas={},
            passes=LazyScalar(passes_base, iters),
            dirty_nodes=len(plan_ids | extra_dirty),
            deltas_in=LazyScalar(handle.host_rows, rows),
            deltas_out=0,
            wall_s=time.perf_counter() - t0,
            quiesced=conv,
            _check_errors=self.executor.check_errors,
        )
        if _trace.ENABLED:
            _trace.evt("tick_many", t0, result.wall_s,
                       args=_trace.with_win(
                           {"ticks": K, "fused": True, "staged": True}))
        self.history.append(result)
        return result

    def retire_staged(self, handle: "_StagedTicks") -> None:
        """Settle a dispatched window off the critical path: hand the
        window program's returned zeroed stack back to the ingress queue
        (placement re-assertion included) and free its generation."""
        self.executor.retire_window(handle.sw)

    def publish_metrics(self, registry=None, *, name: Optional[str]
                        = None) -> str:
        """Register live scheduler gauges (tick horizon, forced syncs,
        pending pushes) into an obs registry. Gauges only read host
        counters — never ``summarize(history)``, whose ``block()`` would
        force device syncs from the telemetry thread. Returns the
        gauge-name prefix (``sched.<graph>``)."""
        from reflow_tpu.obs import REGISTRY
        reg = registry if registry is not None else REGISTRY
        key = f"sched.{name or self.graph.name}"
        reg.gauge(f"{key}.tick", lambda: self._tick)
        reg.gauge(f"{key}.forced_syncs", lambda: self.forced_syncs)
        reg.gauge(f"{key}.pending_batches",
                  lambda: sum(len(v) for v in self._pending.values()))
        reg.gauge(f"{key}.history_len", lambda: len(self.history))
        reg.gauge(f"{key}.megatick_windows", lambda: self.megatick_windows)
        reg.gauge(f"{key}.megatick_fallbacks",
                  lambda: self.megatick_fallbacks)
        reg.gauge(f"{key}.megatick_cache_hits",
                  lambda: getattr(self.executor, "megatick_cache_hits", 0))
        # "is the chip busy": sums of the executor's ``window_device``
        # spans (device completion of each window); zero while tracing
        # is off — nothing watches the device then
        reg.gauge(f"{key}.device_busy_s",
                  lambda: getattr(self.executor, "device_busy_s", 0.0))
        reg.gauge(f"{key}.windows_done",
                  lambda: getattr(self.executor, "windows_done", 0))
        # what the operators counted on the device (a KnnIndex: ticks
        # that rescanned, ticks that merged incrementally, rows folded),
        # read from the device when a snapshot is taken
        read = getattr(self.executor, "op_counters", None)
        for node, names in (getattr(self.executor, "counter_names", dict)()
                            .items()):
            for c in names:
                reg.gauge(f"{key}.{node}.{c}",
                          lambda node=node, c=c: read().get(node, {})
                          .get(c, 0))
        self._metric_keys.append((reg, key))
        return key

    def rederive(self, source: Node, batch: DeltaBatch):
        """Invalidate-and-re-derive (the ``refresh_minmax`` pattern
        generalized to arbitrary derived state): retract ``batch``'s rows
        at ``source`` and tick, then re-insert them and tick.

        Because the retraction removes exactly the inputs that derived
        the stale state, the affected keys' derived values vanish through
        the normal exact algebra — retraction waves shrink monotonically
        (no counting-to-infinity), so the retract tick quiesces even when
        a normal incremental tick would not (e.g. an orphaned sustaining
        cycle after an SSSP edge deletion — ``workloads/sssp.repair``).
        The re-insertion then re-derives the keys from *current* upstream
        values. A tick halted at ``max_loop_iters`` beforehand is fine:
        its paused loop deltas resume inside the retract tick.

        Returns the two synchronous TickResults (retract, re-insert).
        """
        if not len(batch):
            raise GraphError("rederive needs a non-empty batch")
        self.push(source, DeltaBatch(batch.keys, batch.values,
                                     -np.asarray(batch.weights)))
        r1 = self.tick()
        self.push(source, batch)
        r2 = self.tick()
        return r1, r2

    def drain(self, source: Node, *, max_ticks: int = 256,
              probe_rows: int = 1) -> int:
        """Tick with empty (zero-weight probe) input at ``source`` until
        the graph quiesces. Flushes the residue a deferred fixpoint
        (``close_loop(defer_passes=...)``) carries across ticks: each
        drain tick runs up to ``defer_passes`` more loop passes over the
        in-flight observables, so the state converges to the same
        fixpoint a quiescent tick would have reached (docs/guide.md
        "Deferred fixpoint"). Synchronous by necessity (each round reads
        the quiescence flag back); call at stream boundaries, not inside
        a pipelined window. Returns the number of ticks used; raises if
        quiescence is not reached within ``max_ticks``."""
        if source.kind not in ("source", "loop"):
            raise GraphError(f"drain probes a source/loop, not {source}")
        # the probe must structurally reach every deferred loop's region,
        # or its ticks would report quiescence without ever running the
        # region's program (belt-and-braces: the fused program runs the
        # loop on ANY tick, but a fallback executor honors only the plan)
        deferred = [l for l in self.graph.loops if l.defer_passes]
        if deferred:
            plan_ids = {n.id for n in self._dirty_plan([source.id])}
            for l in deferred:
                if l.back_input.id not in plan_ids:
                    raise GraphError(
                        f"drain({source.name}) does not reach deferred "
                        f"loop {l.name}'s region; probe a source feeding "
                        f"that region instead")
        # probe_rows: all-zero-weight rows are semantic no-ops, so the
        # count only picks the padded capacity BUCKET — pass the steady
        # batch size to reuse an already-compiled program signature
        # instead of compiling a fresh tiny-capacity one just for the
        # drain
        vshape = tuple(source.spec.value_shape)
        probe = DeltaBatch(
            np.zeros(probe_rows, np.int64),
            np.zeros((probe_rows,) + vshape, source.spec.value_dtype),
            np.zeros(probe_rows, np.int64))
        for i in range(max_ticks):
            self.push(source, probe)
            r = self.tick(sync=False).block()
            if r.quiesced:
                return i + 1
        raise GraphError(
            f"drain: {self.graph.name} not quiescent after {max_ticks} "
            f"ticks (deferred residue not converging, or the loop region "
            f"is genuinely divergent)")

    def close(self) -> None:
        """Release durable resources: just the published obs gauges
        here (the in-memory scheduler holds nothing else) — part of the
        scheduler surface so lifecycle code (``IngestFrontend.close``,
        ``ServeTier``) can shut any scheduler down uniformly;
        ``DurableScheduler`` overrides it to also seal its WAL."""
        for reg, key in self._metric_keys:
            reg.unregister_prefix(f"{key}.")
        self._metric_keys = []
        self._close_executor()

    def _close_executor(self) -> None:
        """Stop what the executor runs beside the ticks (the traced
        device watcher); executors without a ``close`` have nothing."""
        closefn = getattr(self.executor, "close", None)
        if closefn is not None:
            closefn()

    # -- host boundary out -------------------------------------------------

    def read_table(self, node: Node) -> Dict:
        """Materialized {key: value} of a stateful node's collection at the
        tick boundary (Reduce: last emitted aggregates; Join: the left
        table). This is the sink-style host crossing for collections that
        live inside loop regions, where a per-pass delta sink would force
        mid-tick readbacks."""
        if getattr(self.executor, "name", "") != "cpu":
            self.forced_syncs += 1
        return self.executor.read_table(node)

    def view(self, sink: str | Node) -> Counter:
        """Materialized multiset {(key, value): weight} at a sink."""
        name = sink if isinstance(sink, str) else sink.name
        return self.sink_views[name]

    def refresh_minmax(self, node: Node, batch: DeltaBatch) -> None:
        """Maintenance: rebuild a buffered min/max Reduce's candidate
        buffers for every key in ``batch`` from a replay of its full live
        multiset, resetting the monotone overflow latches (device
        executors; the exact CPU oracle ignores it). Keeps long-running
        heavy-churn keys exact instead of eventually tripping the loud
        buffer-exhaustion error. Call between ticks."""
        from reflow_tpu.executors.lowerings import LINEAR_DEVICE_REDUCERS
        from reflow_tpu.graph import GraphError

        if (node.kind != "op" or node.op.kind != "reduce"
                or node.op.how in LINEAR_DEVICE_REDUCERS):
            raise GraphError(f"{node}: refresh_minmax needs a min/max "
                             f"Reduce node")
        self.executor.refresh_minmax(node, batch)

    def view_dict(self, sink: str | Node) -> Dict:
        """Materialized {key: value} for unique-keyed sink collections."""
        d: Dict = {}
        for (k, v), w in self.view(sink).items():
            if w > 0:
                if k in d:
                    raise GraphError(f"sink {sink} is not unique-keyed at {k!r}")
                d[k] = v
        return d
