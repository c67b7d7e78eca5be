"""IngestFrontend: backpressured multi-producer admission onto one
scheduler.

The paper's tick-synchronous model assumes *someone* feeds the
scheduler; this is that someone. N concurrent producers call
``submit(source, batch)`` from their own threads; a single **pump**
owns the scheduler (``DirtyScheduler`` or ``DurableScheduler`` — never
touch it directly while the frontend is running), coalesces the queued
micro-batches into ``tick_many`` macro-ticks, and resolves each
submission's :class:`~reflow_tpu.serve.tickets.Ticket`.

Admission control (per submit, in order):

1. **id mint / dedup** — a missing ``batch_id`` is minted through
   ``SourceCursor`` (restart-safe: cursors resume past the scheduler's
   recovered dedup window); a duplicate id resolves the ticket
   ``DEDUPED`` immediately, never silently dropped.
2. **backpressure** — per-source queue depth + the in-flight byte
   budget (a :class:`~reflow_tpu.serve.budget.BudgetShare`), with the
   configured policy: ``block`` (wait for room; a ``close()`` releases
   blocked producers with :class:`FrontendClosed`), ``reject`` (resolve
   ``REJECTED`` now), ``shed-oldest`` (evict the oldest admitted
   entries — their tickets resolve ``SHED`` — to admit the newer one).

Two pump deployments share all of the above (the refactor the serving
tier forced — admission and pumping are **injectable**):

- ``start=True`` (default): the frontend owns a private pump thread —
  the PR-2 standalone shape.
- ``start=False`` + ``lock=``/``work=``/``budget=``: an external pump
  pool (``serve.tier.ServeTier``) drives the frontend through
  ``_poll`` / ``_take_window`` / ``_run_window`` / ``_finish_window``,
  under a lock shared with sibling graphs. The ``_executing`` flag is
  the per-graph in-flight latch: a graph's macro-tick never interleaves
  with itself, whoever pumps it.

Steady-state traffic rides the fused streaming path: the pump calls
``tick_many`` (never a synchronous ``tick``), so on a device executor
no mid-stream forced syncs happen — the zero-``forced_syncs`` property
``tests/test_serve.py::test_multi_producer_differential_matches_bare_loop``
asserts.

Durability pipeline (durable schedulers): the pump never blocks on an
fsync. ``tick_many(wait_durable=False)`` returns once the window's WAL
records are written+flushed; ticket resolution is deferred into a
:class:`_ResBlock` registered with ``wal.when_durable(lsn, ...)`` and
fires when the committer thread's fsync passes the window's LSN — so
window N's disk latency overlaps window N+1's merge and dispatch, while
commit-before-resolve holds (a crash between execute and fsync leaves
the tickets unresolved; upstream re-sends, replay dedups). The block is
registered as soon as the window's dispatch has returned, at any
pipeline depth: a pipelined window's retire (the hand-back of its
ingress buffers) comes later and no ticket waits for it. Device
batches submitted with ``preimage=`` log the host pre-image instead of
paying a device readback (``DurableScheduler.push_preimage``).

Crash seams (``utils.faults.CrashInjector``): ``producer_submit`` /
``producer_admitted`` on the submitting thread, ``pump_coalesce`` /
``pump_before_tick`` / ``pump_after_tick`` on the pump. A named
frontend (tier-managed) scopes its seams as ``<seam>@<name>`` so one
graph of a pool can be killed in isolation. A pump kill fails every
undecided ticket of the drained set it was working on and of the
backlog with :class:`PumpCrashed` and releases blocked producers
(windows dispatched earlier are on the durable watermark, which decides
them); a durable scheduler's WAL then carries exactly-once across
``recover()`` + upstream re-send.
"""

from __future__ import annotations

import dataclasses
import os
import threading
import time
from collections import deque
from typing import Deque, Dict, List, Optional, Tuple

from reflow_tpu.delta import lossy_value_cast
from reflow_tpu.graph import GraphError, Node
from reflow_tpu.obs import trace as _trace
from reflow_tpu.scheduler import SourceCursor
from reflow_tpu.utils.config import env_int
from reflow_tpu.utils.runtime import named_lock

from .budget import AdmissionBudget
from .coalesce import CoalesceWindow, build_feeds
from .queues import Entry, SourceQueues, batch_nbytes
from .tickets import (APPLIED, DEDUPED, REJECTED, SHED, FrontendClosed,
                      PumpCrashed, Ticket, TicketResult)

__all__ = ["IngestFrontend"]

POLICIES = ("block", "reject", "shed-oldest")


@dataclasses.dataclass
class _ResBlock:
    """One executed chunk awaiting its durability point: the tickets of
    a ``tick_many`` call whose WAL records are written but possibly not
    yet fsynced. Resolution fires from ``wal.when_durable`` — on the
    committer thread when the fsync overlapped later work, inline on
    the pump when the LSN was already durable."""

    #: (entry, committed tick, coalesced_with) per micro-batch
    items: List[Tuple[Entry, int, int]]
    lsn: int
    nticks: int
    t_ready: float
    t_exec0: float
    t_exec1: float
    #: the pump's window id (joins this block's spans to the window's)
    win: int = 0
    #: the chunk went through the staged lifecycle, so its window has a
    #: retire of its own (which this block does not wait for)
    staged: bool = False
    #: traced runs: when ``_wire_block`` handed the block to the durable
    #: watermark — ``[t_exec1, t_wired]`` is the ticket span
    #: ``wire_wait``, the part of ``fsync`` no disk was waited for
    t_wired: Optional[float] = None


class _PumpClock:
    """Traced private pumps only: the instant up to which the pump
    thread's wall is under a span. Every top-level span the pump
    records goes through ``IngestFrontend._pump_span``, which first
    books the stretch since the previous one as ``pump_turn`` (loop
    bookkeeping: lock waits, the fire check, the window take, budget
    release, block wiring) — so the pump's spans tile its wall by
    construction and nothing it does is unlabelled. The ``w_*`` fields
    hold an idle episode still open (``wakes`` > 0): every admission
    wakes the pump, and the wake-ups that only re-check the triggers and
    wait again are one ``pump_wait`` span, not one each."""

    __slots__ = ("t", "c", "w_t0", "w_c0", "w_t1", "w_c1", "wakes",
                 "notified")

    def __init__(self) -> None:
        self.t = time.perf_counter()
        self.c = time.thread_time()
        self.wakes = 0


@dataclasses.dataclass
class _InflightWindow:
    """One dispatched-but-unretired pipelined window: the scheduler's
    staged handle (whose retire re-adopts the donated queue generation)
    and what the ``window_retire`` span says of it. Its tickets are not
    here: their :class:`_ResBlock` went onto the durable watermark when
    the dispatch returned, and a pump crash reads nothing of an entry —
    it drops them unretired."""

    handle: object               # scheduler _StagedTicks
    win: int
    nticks: int


#: per-sample metric retention: percentile summaries only need a recent
#: window, and a long-running serving process must not grow them forever
METRIC_WINDOW = 4096


class IngestFrontend:
    """Thread-safe streaming ingestion frontend over one scheduler.

    ``policy``: backpressure policy (``block`` / ``reject`` /
    ``shed-oldest``). ``queue_batches``: per-source queue bound.
    ``max_bytes``: in-flight payload budget (ignored when ``budget`` is
    injected). ``window``: the coalescing window (rows / ticks /
    latency triggers). ``crash``: a ``CrashInjector`` wired to the
    documented seams (tests only).

    Tier injection (``serve.tier`` wires these; standalone callers
    leave them defaulted): ``budget`` — a ``BudgetShare`` of a shared
    ``AdmissionBudget``; ``lock`` — the lock every sibling frontend and
    the pump pool share; ``work`` — the pool's shared work condition
    (must be built on ``lock``); ``name`` — the graph name, used to
    scope crash seams; ``start=False`` — no private pump thread, the
    pool pumps.
    """

    def __init__(self, sched, *, policy: str = "block",
                 queue_batches: int = 256, max_bytes: int = 64 << 20,
                 window: Optional[CoalesceWindow] = None, crash=None,
                 start: bool = True, budget=None, lock=None, work=None,
                 name: Optional[str] = None, admission: str = "auto",
                 depth: Optional[int] = None):
        if policy not in POLICIES:
            raise ValueError(f"policy {policy!r} not in {POLICIES}")
        if admission not in ("auto", "host", "device"):
            raise ValueError(
                f"admission {admission!r} not in ('auto', 'host', "
                f"'device')")
        self.sched = sched
        self.policy = policy
        self.window = window if window is not None else CoalesceWindow()
        self.name = name
        #: the executor advertises the fused mega-tick window path for
        #: this graph: the pump's tick_many windows dispatch through the
        #: device ingress queue (docs/guide.md "Compiled mega-ticks")
        self.megatick = bool(getattr(sched, "window_support", False))
        #: what a host batch's admission charge measures: "host" = its
        #: payload bytes, "device" = the queue-slot bytes it will reserve
        #: on device (backpressure then tracks device memory pressure);
        #: "auto" picks "device" exactly when the window path engages
        self.admission = ("device" if admission == "auto" and self.megatick
                          else "host" if admission == "auto" else admission)
        #: pipelined window depth: how many dispatched-but-unretired
        #: windows may be in flight while the NEXT one stages (software
        #: pipelining over the async device dispatch). 1 = the fully
        #: serial stage→dispatch→retire loop, bit-for-bit today's
        #: behavior; >1 requires the staged scheduler surface, so it is
        #: forced to 1 off the fused mega-tick path.
        if depth is None:
            depth = env_int("REFLOW_WINDOW_DEPTH")
        staged = (self.megatick
                  and getattr(sched, "stage_window", None) is not None)
        self.depth = max(1, int(depth)) if staged else 1
        #: dispatched windows awaiting their retire step, oldest first.
        #: Owned by whoever holds the pump latch (or the pool's settle
        #: latch) — never mutated concurrently.
        self._inflight: Deque[_InflightWindow] = deque()
        self._crash = crash
        self._lock = (lock if lock is not None
                      else named_lock(f"serve.frontend.{name}" if name
                                      else "serve.frontend"))
        self._not_full = threading.Condition(self._lock)   # producers
        self._work = (work if work is not None
                      else threading.Condition(self._lock))  # pump
        self._idle = threading.Condition(self._lock)       # flush/pause
        if budget is None:
            budget = AdmissionBudget(max_bytes).register(name or "frontend")
        budget.attach(self._not_full)
        self._budget = budget
        self._queues = SourceQueues(queue_batches, budget)
        self._cursors: Dict[int, SourceCursor] = {}
        #: admission-side mirror of the scheduler's dedup window (the
        #: pump owns the scheduler, so producers can't read it): seeded
        #: from the (possibly recovered) window, bounded the same way
        self._admitted: Dict[str, None] = dict.fromkeys(
            sched._seen_batch_ids)
        self._state = "running"
        self._closing_flush = True
        self._paused = False
        self._executing = False
        self._flush_pending = False
        #: executed chunks whose tickets await the durable watermark
        self._pending_res = 0
        self.pump_error: Optional[BaseException] = None
        # -- counters/samples (utils.metrics.summarize_serve) --
        self.submitted = 0
        self.admitted = 0
        self.applied = 0
        self.deduped = 0
        self.rejected = 0
        self.shed = 0
        self.ticks = 0
        self.pump_iterations = 0
        #: pipelining counters: fused windows staged through the split
        #: lifecycle, how many staged while a previous window was still
        #: in flight, and the host-stage seconds in each bucket
        #: (``stage_overlap_frac`` is the overlapped fraction)
        self.windows_staged = 0
        self.windows_pipelined = 0
        #: staged windows whose tickets resolved (at their durability
        #: point) while the window was still dispatched-but-unretired:
        #: how often resolution did not wait for the retire
        self.blocks_resolved_before_retire = 0
        self.stage_s_total = 0.0
        self.stage_overlap_s = 0.0
        #: times a failed frontend was re-armed (:meth:`revive`)
        self.revives = 0
        # bounded reservoirs (most recent METRIC_WINDOW samples) — the
        # totals above are exact; only percentile inputs are windowed
        self.queue_depth_samples: Deque[int] = deque(maxlen=METRIC_WINDOW)
        self.admission_s: Deque[float] = deque(maxlen=METRIC_WINDOW)
        self.ticks_per_pump: Deque[int] = deque(maxlen=METRIC_WINDOW)
        self.inflight_bytes_peak = 0
        # obs wiring: registered metric sources (publish_metrics) and
        # the current window's ready/take stamps (trace spans)
        self._metric_keys: List = []
        self._win_t_ready: Optional[float] = None
        #: windows numbered as they are staged (or, unfused, ticked):
        #: the ``win`` every span of one window carries
        self._win_seq = 0
        #: id of the newest staged window handed back by its retire
        #: (windows retire oldest first, so every staged window up to
        #: this one is retired); written by the pump only
        self._win_retired = 0
        #: the private pump's tiling clock while tracing is on
        self._clk: Optional[_PumpClock] = None
        self._thread: Optional[threading.Thread] = None
        if start:
            self._thread = threading.Thread(
                target=self._pump_loop, name="reflow-ingest-pump",
                daemon=True)
            self._thread.start()

    # -- crash seams -------------------------------------------------------

    def _crash_point(self, name: str) -> None:
        if self._crash is not None:
            self._crash.point(
                name if self.name is None else f"{name}@{self.name}")

    # -- producer side -----------------------------------------------------

    def submit(self, source: Node, batch, *, batch_id: Optional[str] = None,
               timeout: Optional[float] = None, preimage=None,
               cause: Optional[str] = None,
               sampled: Optional[bool] = None) -> Ticket:
        """Admit one micro-batch for ``source``; returns a Ticket that
        resolves once the batch's fate is decided. Thread-safe; callable
        from any number of producers. ``timeout`` bounds a ``block``
        admission wait (expiry resolves the ticket REJECTED).

        ``preimage``: for a device-resident ``batch``, the host-side
        ``DeltaBatch`` it was uploaded from — a durable scheduler then
        logs these bytes instead of reading the device copy back (the
        zero-readback logging path). Ignored for host batches.

        ``cause`` / ``sampled``: cross-process trace adoption (the
        ingestion RPC). ``sampled=None`` keeps today's local 1-in-N
        decision; a bool ADOPTS the wire decision that rode in with the
        producer's causality token, so every process records the same
        writes. A locally-sampled submit with no token mints one, so
        in-process callers get full chains too."""
        if source.kind not in ("source", "loop"):
            raise GraphError(
                f"can only submit to sources/loops, not {source}")
        if preimage is not None and hasattr(preimage, "nonzero"):
            raise GraphError(
                "preimage must be the HOST DeltaBatch the device batch "
                "was uploaded from, not another device batch")
        t0 = time.perf_counter()
        deadline = None if timeout is None else t0 + timeout
        # a ticket this frontend may choose to follow and that brings no
        # token mints one from the epoch, which is the WAL's to say,
        # under the WAL's lock; the committer resolves blocks (frontend
        # lock) under that lock, so the epoch is read before the
        # frontend lock is taken, never under it
        epoch = (getattr(self.sched, "epoch", 0)
                 if _trace.ENABLED and cause is None and sampled is not False
                 else 0)
        with self._lock:
            t_lock = time.perf_counter() if _trace.ENABLED else t0
            self._crash_point("producer_submit")
            self.submitted += 1
            if self._state != "running":
                raise FrontendClosed(
                    f"frontend is {self._state}; submissions not accepted")
            if batch_id is None:
                batch_id = self._cursor(source).next_id()
            ticket = Ticket(batch_id)
            if _trace.ENABLED:
                if sampled is None:
                    ticket.trace = _trace.mint(batch_id, t0)
                    if cause is not None:
                        ticket.trace.cause = cause
                else:
                    ticket.trace = _trace.TraceCtx(batch_id, t0,
                                                   sampled, cause)
                if ticket.trace.sampled and ticket.trace.cause is None:
                    from reflow_tpu.obs.wire import node_id
                    ticket.trace.cause = _trace.mint_cause(node_id(), epoch)
                if ticket.trace.sampled:
                    # inside ``admission``: how long this producer stood
                    # at the frontend lock before admission could begin
                    _trace.evt("admit_lock_wait", t0, t_lock - t0,
                               track=f"ticket/{batch_id}",
                               args={"batch_id": batch_id})
            if batch_id in self._admitted:
                self.deduped += 1
                ticket._resolve(TicketResult(
                    DEDUPED, batch_id,
                    reason="batch_id already admitted"))
                self._trace_submit(ticket, "deduped")
                return ticket
            device = hasattr(batch, "nonzero")
            rows = 0 if device else len(batch)
            if not device and rows == 0:
                # an empty host batch is a semantic no-op; report it
                # applied rather than occupying a queue slot
                self._note_admitted(batch_id)
                ticket._resolve(TicketResult(APPLIED, batch_id,
                                             reason="empty batch"))
                self._trace_submit(ticket, "empty")
                return ticket
            why = lossy_value_cast(source.spec, batch)
            if why is not None:
                # the pump would cast the rows to the spec's dtype and
                # fold garbage: the producer is told instead
                self.rejected += 1
                ticket._resolve(TicketResult(REJECTED, batch_id,
                                             reason=why))
                self._trace_submit(ticket, "rejected")
                return ticket
            nbytes = self._charge_bytes(source, batch, device)
            if not self._admit(source, nbytes, ticket, batch_id, deadline):
                return ticket  # ticket already resolved REJECTED/…
            if batch_id in self._admitted:
                # a blocked admission drops the lock in wait(): another
                # producer may have admitted this very id meanwhile —
                # pushing now would fold the batch twice
                self.deduped += 1
                ticket._resolve(TicketResult(
                    DEDUPED, batch_id,
                    reason="batch_id admitted concurrently while this "
                           "submit was blocked on backpressure"))
                self._trace_submit(ticket, "deduped")
                return ticket
            entry = Entry(ticket, source, batch, batch_id, nbytes,
                          time.perf_counter(), device, rows,
                          preimage=preimage if device else None)
            self._note_admitted(batch_id)
            self._queues.push(entry)
            self.admitted += 1
            self.admission_s.append(time.perf_counter() - t0)
            self.queue_depth_samples.append(self._queues.queued_batches)
            self.inflight_bytes_peak = max(
                self.inflight_bytes_peak,
                self._queues.queued_bytes + self._queues.executing_bytes)
            self._trace_submit(ticket, "admitted")
            self._work.notify()
            self._crash_point("producer_admitted")
        return ticket

    @staticmethod
    def _trace_submit(ticket: Ticket, outcome: str) -> None:
        # producer-track span covering this submit() call: admission
        # wait plus its terminal outcome (the sampled ticket's own
        # six-stage timeline is emitted at resolve time by the pump)
        ctx = ticket.trace
        if ctx is not None and ctx.sampled:
            _trace.evt("submit", ctx.t0,
                       time.perf_counter() - ctx.t0,
                       args={"batch_id": ticket.batch_id,
                             "outcome": outcome})

    def _charge_bytes(self, source: Node, batch, device: bool) -> int:
        """What this batch's admission charges against the byte budget.
        Under device-keyed admission (``admission="device"``, the
        mega-tick default) a host batch is charged the device bytes its
        ingress-queue slot will reserve — the capacity-bucketed padded
        footprint — so backpressure reflects actual device memory
        pressure, not host payload size. Device-resident batches always
        charge their (device) payload bytes; both reads are metadata,
        never a device sync."""
        if not device and self.admission == "device":
            from reflow_tpu.executors.ingress_queue import slot_nbytes

            return slot_nbytes(source.spec, len(batch))
        return batch_nbytes(batch)

    def _admit(self, source: Node, nbytes: int, ticket: Ticket,
               batch_id: str, deadline: Optional[float]) -> bool:
        # caller holds the lock; resolves the ticket and returns False
        # when admission is refused
        while not self._queues.room_for(source.id, nbytes):
            if self.policy == "reject":
                self.rejected += 1
                ticket._resolve(TicketResult(
                    REJECTED, batch_id, reason="backpressure: queue full"))
                self._trace_submit(ticket, "rejected")
                return False
            if self.policy == "shed-oldest":
                if not self._queues.fits_alone(nbytes):
                    self.rejected += 1
                    ticket._resolve(TicketResult(
                        REJECTED, batch_id,
                        reason=f"batch of {nbytes}B exceeds the "
                               f"{self._queues.max_bytes}B budget"))
                    self._trace_submit(ticket, "rejected")
                    return False
                shed_any = False
                for e in self._queues.shed_for(source.id, nbytes):
                    self.shed += 1
                    shed_any = True
                    # the evicted batch never reached the scheduler: drop
                    # it from the dedup mirror so the re-send the SHED
                    # ticket demands is admitted, not DEDUPED away
                    self._admitted.pop(e.batch_id, None)
                    e.ticket._resolve(TicketResult(
                        SHED, e.batch_id,
                        reason="shed-oldest backpressure; re-send"))
                    self._trace_submit(e.ticket, "shed")
                if shed_any:
                    # freed bytes are budget-wide: a sibling graph's
                    # blocked producer may fit now
                    self._budget.notify_room()
                if self._queues.room_for(source.id, nbytes):
                    return True
                # executing bytes hold the budget: fall through to wait
            # block (and shed-oldest squeezed by in-flight execution)
            remaining = (None if deadline is None
                         else deadline - time.perf_counter())
            if remaining is not None and remaining <= 0:
                self.rejected += 1
                ticket._resolve(TicketResult(
                    REJECTED, batch_id,
                    reason="backpressure: admission timed out"))
                self._trace_submit(ticket, "rejected")
                return False
            if not self._not_full.wait(timeout=remaining):
                self.rejected += 1
                ticket._resolve(TicketResult(
                    REJECTED, batch_id,
                    reason="backpressure: admission timed out"))
                self._trace_submit(ticket, "rejected")
                return False
            if self._state != "running":
                raise FrontendClosed(
                    "frontend closed while blocked on admission")
        return True

    def _cursor(self, source: Node) -> SourceCursor:
        cur = self._cursors.get(source.id)
        if cur is None:
            cur = self._cursors[source.id] = SourceCursor.resume(
                self.sched, source)
        return cur

    def _note_admitted(self, batch_id: str) -> None:
        self._admitted[batch_id] = None
        while len(self._admitted) > self.sched.dedup_window:
            self._admitted.pop(next(iter(self._admitted)))

    def admitted_ids(self, batch_ids) -> list:
        """Which of ``batch_ids`` the dedup mirror currently remembers.
        The ingestion RPC's reconnect handshake: a producer that died
        in an ack window sends its in-doubt ids here, then resubmits —
        a remembered id resolves DEDUPED, keeping resubmission
        exactly-once without the producer ever guessing."""
        with self._lock:
            return [b for b in batch_ids if b in self._admitted]

    # -- lifecycle ---------------------------------------------------------

    def flush(self, timeout: Optional[float] = None) -> None:
        """Block until every batch admitted so far has been ticked."""
        deadline = (None if timeout is None
                    else time.perf_counter() + timeout)
        with self._lock:
            if self._state == "failed":
                raise PumpCrashed(f"pump died: {self.pump_error!r}")
            if self._paused:
                raise GraphError("flush() while paused would never "
                                 "complete; resume() first")
            self._flush_pending = True
            self._work.notify_all()
            try:
                while (self._queues.queued_batches or self._executing
                       or self._pending_res):
                    if self._state == "failed":
                        raise PumpCrashed(
                            f"pump died: {self.pump_error!r}")
                    if self._state == "closed":
                        return
                    remaining = (None if deadline is None
                                 else deadline - time.perf_counter())
                    if remaining is not None and remaining <= 0:
                        raise TimeoutError("flush timed out")
                    self._idle.wait(timeout=remaining)
            finally:
                self._flush_pending = False

    def drain(self, source: Optional[Node] = None, *, max_ticks: int = 256,
              probe_rows: int = 1) -> int:
        """Flush, then run the scheduler's ``drain`` (deferred-fixpoint
        residue) with the pump paused. ``source`` defaults to the
        graph's sole source; pass one explicitly on multi-source graphs.
        Returns the scheduler drain's tick count."""
        if source is None:
            srcs = [n for n in self.sched.graph.nodes
                    if n.kind == "source"]
            if len(srcs) != 1:
                raise GraphError(
                    f"drain needs an explicit source on a graph with "
                    f"{len(srcs)} sources")
            source = srcs[0]
        self.flush()
        self.pause()
        try:
            return self.sched.drain(source, max_ticks=max_ticks,
                                    probe_rows=probe_rows)
        finally:
            self.resume()

    def pause(self) -> None:
        """Stop pumping (admission continues to queue); returns once the
        in-flight macro-tick (if any) completes. The scheduler may then
        be inspected/driven directly until :meth:`resume`."""
        with self._lock:
            self._paused = True
            # also wait out dispatched-but-unretired pipelined windows:
            # their retire mutates the ingress queue the caller is about
            # to drive directly
            while self._executing or self._inflight:
                self._idle.wait()

    def resume(self) -> None:
        with self._lock:
            self._paused = False
            self._work.notify_all()

    def close(self, *, flush: bool = True,
              timeout: Optional[float] = None) -> None:
        """Quiesce and shut down: stop admission, release blocked
        producers with :class:`FrontendClosed`, tick out the remaining
        backlog (``flush=True``) or fail its tickets (``flush=False``),
        stop the pump, and seal a durable scheduler's WAL. Idempotent.

        On an externally-pumped frontend the draining is done by the
        pool (which must still be serving — ``ServeTier`` closes graphs
        before stopping its threads); this call waits for it."""
        deadline = (None if timeout is None
                    else time.perf_counter() + timeout)
        with self._lock:
            seal_only = self._state in ("closed", "failed")
        if seal_only:
            # outside the lock: sealing a durable scheduler closes its
            # WAL, whose final fsync may fire when_durable callbacks
            # that re-take this (non-reentrant) lock
            self._seal()
            return
        with self._lock:
            if self._state not in ("closed", "failed"):
                if self._state == "running":
                    self._closing_flush = flush
                # else: a retry after a close() timeout — keep the
                # original call's flush intent rather than silently
                # downgrading it
                self._state = "closing"
                self._paused = False
                self._not_full.notify_all()
                self._work.notify_all()
        if self._thread is not None:
            if self._thread.is_alive():
                self._thread.join(timeout=timeout)
                if self._thread.is_alive():
                    # the pump is still mid-macro-tick: sealing the WAL
                    # now would close a file it is appending to. Stay
                    # "closing" (admission already refused) and let the
                    # caller retry.
                    raise TimeoutError(
                        f"close() timed out after {timeout}s with the "
                        f"pump still draining; frontend left in state "
                        f"'closing' — call close() again to finish")
        else:
            self._close_external(deadline, timeout)
        with self._lock:
            if self._state != "failed":
                self._state = "closed"
            self._idle.notify_all()
        self._seal()

    def _close_external(self, deadline: Optional[float],
                        timeout: Optional[float]) -> None:
        # externally-pumped shutdown: with flush intent the pool drains
        # the backlog (closing graphs fire unconditionally in _poll);
        # without it we only wait out an in-flight window, then strand-
        # fail whatever is still queued
        with self._lock:
            while self._state == "closing" and (
                    self._executing or self._inflight
                    or (self._closing_flush
                        and self._queues.queued_batches)):
                remaining = (None if deadline is None
                             else deadline - time.perf_counter())
                if remaining is not None and remaining <= 0:
                    raise TimeoutError(
                        f"close() timed out after {timeout}s with the "
                        f"pump pool still draining; frontend left in "
                        f"state 'closing' — call close() again to "
                        f"finish")
                self._idle.wait(timeout=remaining)
            if self._state == "closing" and not self._closing_flush:
                self._exit_pump_locked()

    @property
    def stage_overlap_frac(self) -> float:
        """Fraction of host staging time that overlapped an in-flight
        device dispatch (0.0 at depth 1 or before any fused window)."""
        return (self.stage_overlap_s / self.stage_s_total
                if self.stage_s_total > 0 else 0.0)

    def publish_metrics(self, registry=None) -> str:
        """Register this frontend's live counters (the
        ``summarize_serve().to_dict()`` schema) as an obs metric source
        — live snapshots and offline summaries stay one schema.
        Unregistered automatically at :meth:`close`. Returns the source
        key (``serve.<name>``). Beside it goes the process's thread
        ledger, source ``proc.threads`` (``obs/threads.py``): one key
        however many frontends publish, and not this frontend's to take
        away at its close."""
        from reflow_tpu.obs import REGISTRY, threads
        from reflow_tpu.utils.metrics import summarize_serve
        reg = registry if registry is not None else REGISTRY
        key = f"serve.{self.name or 'frontend'}"
        reg.register_source(key,
                            lambda: summarize_serve(self).to_dict())
        self._metric_keys.append((reg, key))
        threads.publish(reg)
        return key

    def _seal(self) -> None:
        for reg, key in self._metric_keys:
            reg.unregister_source(key)
        self._metric_keys = []
        closefn = getattr(self.sched, "close", None)
        if closefn is not None:
            closefn()

    def __enter__(self) -> "IngestFrontend":
        return self

    def __exit__(self, *exc) -> None:
        self.close(flush=exc == (None, None, None))

    # -- the pump ----------------------------------------------------------

    def _fire_or_timeout(self, now: float):
        # under lock: (fire, wait_timeout)
        if self._state == "closing":
            return True, None
        if self._paused or self._queues.queued_batches == 0:
            return False, None
        if self._flush_pending:
            return True, None
        w = self.window
        if self._queues.queued_rows >= w.max_rows:
            return True, None
        if self._queues.pending_feed_rounds(w.max_rows) >= w.max_ticks:
            return True, None
        oldest = self._queues.oldest_t()
        age = now - oldest if oldest is not None else 0.0
        if age >= w.max_latency_s:
            return True, None
        return False, w.max_latency_s - age

    # external-pump surface (the tier's pool; every method below up to
    # _run_window is called with the shared lock held) ---------------------

    def _poll(self, now: float):
        """Pool eligibility: (fire, wait_s). Never fires while the
        in-flight latch is held (single-owner invariant), after a
        failure, or once closed; a closing graph fires only while a
        flush-close still has backlog to tick out."""
        if self._executing or self._state in ("closed", "failed"):
            return False, None
        if self._state == "closing":
            return (self._closing_flush
                    and self._queues.queued_batches > 0), None
        return self._fire_or_timeout(now)

    def _take_window(self, ready_since: Optional[float] = None
                     ) -> Dict[int, List[Entry]]:
        """Claim the backlog as one macro-tick work item and set the
        in-flight latch; the caller must follow with ``_run_window``
        (lock released) and ``_finish_window`` (lock re-held).
        ``ready_since`` (tier pool): when the window first became
        eligible — the gap to now is cross-graph scheduling delay on
        the trace timeline."""
        self._win_t_ready = (ready_since if ready_since is not None
                             else time.perf_counter())
        drained = self._queues.drain_all()
        self._flush_pending = False
        self._executing = True
        return drained

    def _finish_window(self) -> None:
        """Release the latch and the window's remaining budget bytes
        (staged chunks already released theirs at stage-complete); wake
        blocked producers (budget-wide) and flush/pause waiters."""
        self._executing = False
        self._queues.commit_executing()
        self._budget.notify_room()
        self._idle.notify_all()

    def _needs_settle(self) -> bool:
        """Pool eligibility for a settle-only iteration (caller holds
        the lock): dispatched windows are waiting for their retire and
        nobody owns the latch. Ignores ``_paused`` deliberately — pause
        WAITS on the in-flight windows, so settling must proceed."""
        return (bool(self._inflight) and not self._executing
                and self._state != "failed")

    def _begin_settle(self) -> None:
        """Latch the graph for a settle-only iteration (caller holds
        the lock; follow with ``_settle_all`` unlocked, then
        ``_finish_window``)."""
        self._executing = True

    def _pump_loop(self) -> None:
        try:
            while True:
                drained = None
                # the tiling clock lives exactly while tracing is on
                if not _trace.ENABLED:
                    self._clk = None
                elif self._clk is None:
                    self._clk = _PumpClock()
                with self._lock:
                    while True:
                        if self._state == "closing" and (
                                not self._closing_flush
                                or self._queues.queued_batches == 0):
                            if not self._inflight:
                                self._exit_pump_locked()
                                return
                            self._begin_settle()  # retire first
                            break
                        fire, wait_t = self._fire_or_timeout(
                            time.perf_counter())
                        if fire:
                            drained = self._take_window()
                            break
                        if self._inflight:
                            # idle with windows in flight: the device has
                            # nothing to overlap with, so retire now
                            # (latched, so pause/close wait it out)
                            self._begin_settle()
                            break
                        if self._clk is not None:
                            self._traced_wait(wait_t)
                        else:
                            self._work.wait(timeout=wait_t)
                if self._clk is not None and self._clk.wakes:
                    self._end_wait()
                if drained is None:
                    self._settle_all()
                else:
                    self._run_window(drained)
                with self._lock:
                    self._finish_window()
        except BaseException as e:  # noqa: BLE001 - incl. CrashPoint kills
            self._on_pump_crash(e)
        finally:
            self._clk = None

    def _traced_wait(self, wait_t: Optional[float]) -> None:
        """The pump's idle wait under tracing (caller holds the lock):
        extends the open idle episode, which ``_end_wait`` records once
        the loop has something to do."""
        clk = self._clk
        if not clk.wakes:
            clk.w_t0, clk.w_c0 = time.perf_counter(), time.thread_time()
        clk.notified = self._work.wait(timeout=wait_t)
        clk.w_t1, clk.w_c1 = time.perf_counter(), time.thread_time()
        clk.wakes += 1

    def _end_wait(self) -> None:
        """Record the idle episode that just ended as one ``pump_wait``
        span. ``woke`` says what ended it: ``timeout`` (the latency
        trigger came due) or ``notify`` (an admission, flush, resume or
        close); ``wakes`` counts the wake-ups inside it, all but the
        last of which found nothing to fire."""
        clk = self._clk
        wakes, clk.wakes = clk.wakes, 0
        self._pump_span("pump_wait", clk.w_t0, clk.w_c0, clk.w_t1, {
            "woke": "notify" if clk.notified else "timeout",
            "wakes": wakes}, c1=clk.w_c1)

    def _pump_span(self, name: str, t0: float, c0: float, t1: float,
                   args: dict, c1: Optional[float] = None) -> None:
        """Record one top-level span of the pumping thread, ``[t0, t1]``
        with ``cpu_s`` (``c0`` / ``c1``: ``time.thread_time()`` at its
        ends; ``c1`` defaults to now). On the private pump the stretch
        since the previous span is booked first, as ``pump_turn``
        (:class:`_PumpClock`). Call under ``_trace.ENABLED``."""
        if c1 is None:
            c1 = time.thread_time()
        clk = self._clk
        if clk is not None:
            if t0 > clk.t:
                _trace.evt("pump_turn", clk.t, t0 - clk.t, args={
                    "graph": self.name or "frontend",
                    "cpu_s": _trace.cpu_s(clk.c, t0 - clk.t, c0)})
            clk.t, clk.c = t1, c1
        args["graph"] = self.name or "frontend"
        args["cpu_s"] = _trace.cpu_s(c0, t1 - t0, c1)
        _trace.evt(name, t0, t1 - t0, args=args)

    def _exit_pump_locked(self) -> None:
        # caller holds the lock; fail whatever close(flush=False) strands
        stranded = self._queues.drain_all()
        self._queues.commit_executing()
        for entries in stranded.values():
            for e in entries:
                e.ticket._fail(FrontendClosed(
                    f"frontend closed before batch {e.batch_id!r} "
                    f"was ticked"))
        self._budget.notify_room()
        self._idle.notify_all()
        self._not_full.notify_all()

    def _device_label(self) -> Optional[str]:
        """Executing-device obs tag for this graph's spans (placement
        skew shows up in trace_inspect's per-device breakdown)."""
        return getattr(getattr(self.sched, "executor", None),
                       "device_label", None)

    def _run_window(self, drained: Dict[int, List[Entry]]) -> None:
        self._window_entries = drained  # crash path fails their tickets
        tr = _trace.ENABLED
        t_w0 = time.perf_counter()
        c_w0 = time.thread_time() if tr else 0.0
        t_ready = self._win_t_ready or t_w0
        feeds = build_feeds(drained, self.window.max_rows)
        k = self.window.max_ticks
        # one drained set becomes ceil(feeds / k) windows: the spans
        # that cover all of them carry the first and the last id
        win_first = self._win_seq + 1
        win_last = self._win_seq + max(1, -(-len(feeds) // k))
        if tr:
            self._pump_span("host_merge", t_w0, c_w0, time.perf_counter(),
                            {"feeds": len(feeds), "win": win_first,
                             "win_last": win_last})
        self._crash_point("pump_coalesce")
        wal = getattr(self.sched, "wal", None)
        push_pre = getattr(self.sched, "push_preimage", None)
        if wal is not None and push_pre is not None:
            # ingest-time pre-images: hand the durable scheduler the
            # host payloads captured at submit() so device batches log
            # without a forced readback
            for f in feeds:
                for entries in f.entries.values():
                    for e in entries:
                        if e.device and e.preimage is not None:
                            push_pre(e.batch_id, e.preimage)
        push_cause = getattr(self.sched, "push_cause", None)
        if tr and wal is not None and push_cause is not None:
            # register sampled tickets' causality tokens so the WAL
            # stamps them onto this window's push records — the shipper
            # and replicas then re-emit the same tokens, stitching the
            # chain across processes
            for f in feeds:
                for entries in f.entries.values():
                    for e in entries:
                        ctx = e.ticket.trace
                        if ctx is not None and ctx.cause:
                            push_cause(e.batch_id, ctx.cause)
        for i in range(0, len(feeds), k):
            chunk = feeds[i:i + k]
            # bound the pipeline: at most depth dispatched windows may
            # exist once this chunk dispatches, so retire the oldest
            # until a slot is free (depth 1 ⇒ settle everything here ⇒
            # the serial stage→dispatch→retire loop, today's behavior)
            while len(self._inflight) > self.depth - 1:
                self._settle_one()
            self._crash_point("pump_before_tick")
            self._win_seq += 1
            win = self._win_seq
            if tr:
                # the emit sites below the pump (scheduler, executor)
                # read the window id from here
                _trace.set_window(win)
            handle = None
            if self.depth > 1:
                t_s0 = time.perf_counter()
                c_s0 = time.thread_time() if tr else 0.0
                inflight0 = len(self._inflight)
                handle = self.sched.stage_window(
                    [f.batches for f in chunk],
                    feed_ids=[f.ids for f in chunk])
                if handle is not None:
                    t_s1 = time.perf_counter()
                    self.windows_staged += 1
                    self.stage_s_total += t_s1 - t_s0
                    if inflight0 > 0:
                        self.windows_pipelined += 1
                        self.stage_overlap_s += t_s1 - t_s0
                    if tr:
                        self._pump_span("window_stage", t_s0, c_s0, t_s1,
                                        {"win": win, "ticks": len(chunk),
                                         "inflight": inflight0,
                                         "device": self._device_label()})
                    # stage-complete budget release: the chunk's rows now
                    # live in the device ingress queue, so their admission
                    # bytes stop occupying the frontend — producers
                    # unblock a window earlier than the retire
                    chunk_bytes = sum(
                        e.nbytes for f in chunk
                        for entries in f.entries.values() for e in entries)
                    with self._lock:
                        self._queues.release_executing(chunk_bytes)
                        self._budget.notify_room()
            if handle is not None:
                tick0 = self.sched._tick
                t_exec0 = time.perf_counter()
                c_e0 = time.thread_time() if tr else 0.0
                self.sched.dispatch_staged(handle)
                lsn = wal.last_lsn() if wal is not None else 0
                t_exec1 = time.perf_counter()
                if tr:
                    self._pump_span("pump_execute", t_exec0, c_e0, t_exec1,
                                    {"win": win, "ticks": len(chunk),
                                     "lsn": lsn, "megatick": True,
                                     "depth": len(self._inflight) + 1,
                                     "device": self._device_label()})
                self._crash_point("pump_after_tick")
                block = _ResBlock(self._chunk_items(chunk, tick0), lsn,
                                  len(chunk), t_ready, t_exec0, t_exec1,
                                  win, staged=True)
                with self._lock:
                    self._pending_res += 1
                self._inflight.append(
                    _InflightWindow(handle, win, len(chunk)))
                # the window's records and its durability request are in
                # the WAL and ``lsn`` covers them: its tickets resolve
                # when the watermark passes it, whenever the retire comes
                self._wire_block(block)
                continue
            # unfused (or depth-1) chunk: retire the pipeline first (the
            # serial path below re-uses the ingress queue; every earlier
            # block is already on the watermark, in dispatch = LSN
            # order), then run today's serial tick_many path verbatim
            # (it re-checks the window fit and counts any fallback
            # exactly once)
            self._settle_all()
            tick0 = self.sched._tick
            t_exec0 = time.perf_counter()
            c_e0 = time.thread_time() if tr else 0.0
            if wal is not None:
                self.sched.tick_many([f.batches for f in chunk],
                                     feed_ids=[f.ids for f in chunk],
                                     wait_durable=False)
                lsn = wal.last_lsn()
            else:
                self.sched.tick_many([f.batches for f in chunk],
                                     feed_ids=[f.ids for f in chunk])
                lsn = 0
            t_exec1 = time.perf_counter()
            if tr:
                self._pump_span("pump_execute", t_exec0, c_e0, t_exec1,
                                {"win": win, "ticks": len(chunk),
                                 "lsn": lsn, "megatick": self.megatick,
                                 "depth": 1,
                                 "device": self._device_label()})
            self._crash_point("pump_after_tick")
            block = _ResBlock(self._chunk_items(chunk, tick0), lsn,
                              len(chunk), t_ready, t_exec0, t_exec1, win)
            with self._lock:
                self._pending_res += 1
            self._wire_block(block)
        if tr:
            _trace.set_window(None)
            # the umbrella over everything above: it overlaps the tiling
            # spans, so it goes past the pump clock
            _trace.evt("window", t_w0, time.perf_counter() - t_w0,
                       args={"graph": self.name or "frontend",
                             "feeds": len(feeds), "win": win_first,
                             "win_last": win_last,
                             "device": self._device_label()})
        self._win_t_ready = None
        with self._lock:
            self.pump_iterations += 1
            self.ticks_per_pump.append(len(feeds))
            more = (self._state == "running" and not self._paused
                    and not self._flush_pending
                    and self._queues.queued_batches > 0)
        self._window_entries = None
        # keep the pipeline primed only when another window is imminent:
        # its stage will overlap these dispatches. Otherwise retire now,
        # inside the latch, so flush/pause/close observe a settled graph.
        if self.depth <= 1 or not more:
            self._settle_all()

    @staticmethod
    def _chunk_items(chunk, tick0: int) -> List[Tuple[Entry, int, int]]:
        items = []
        for j, f in enumerate(chunk):
            for entries in f.entries.values():
                for e in entries:
                    items.append((e, tick0 + j + 1, len(entries) - 1))
        return items

    def _settle_one(self) -> None:
        """Retire the OLDEST dispatched window (lock NOT held): re-adopt
        its donated queue generation, and nothing else — the window's
        tickets went onto the durable watermark at its dispatch and
        resolve without waiting for this. Runs off the stage→dispatch
        critical path — under pipelining this executes while the next
        window is already on the device."""
        iw = self._inflight.popleft()
        tr = _trace.ENABLED
        t_r0 = time.perf_counter() if tr else 0.0
        c_r0 = time.thread_time() if tr else 0.0
        self.sched.retire_staged(iw.handle)
        self._win_retired = iw.win
        if tr:
            self._pump_span("window_retire", t_r0, c_r0,
                            time.perf_counter(),
                            {"win": iw.win, "ticks": iw.nticks})

    def _settle_all(self) -> None:
        while self._inflight:
            self._settle_one()

    def _wire_block(self, block: _ResBlock) -> None:
        """Park one executed chunk's tickets on the durable watermark
        (``_pending_res`` was already taken). Called by the pump as soon
        as the chunk's dispatch (staged) or ``tick_many`` (serial) has
        returned and ``block.lsn`` has been read, so blocks are wired in
        LSN order and a window's retire has no part in it. Pipelined
        resolution: commit-before-resolve holds, but the commit (the
        fsync) may still be in flight — ``when_durable`` fires on the
        committer once the window's LSN is covered, so the pump overlaps
        the disk latency instead of serializing behind it. A
        non-durable scheduler has nothing to wait for: its tickets
        resolve here."""
        if _trace.ENABLED:
            block.t_wired = time.perf_counter()
        wal = getattr(self.sched, "wal", None)
        if wal is None:
            self._complete_block(block, None)
            return
        try:
            deferred = wal.when_durable(
                block.lsn,
                lambda err, b=block: self._complete_block(
                    b, err, "committer"))
        except BaseException:
            with self._lock:
                self._pending_res -= 1
            raise
        if not deferred:
            self._complete_block(block, None)

    def _complete_block(self, block: _ResBlock,
                        err: Optional[BaseException],
                        where: str = "pump") -> None:
        """Resolve one executed chunk's tickets at its durability point.
        Runs inline on the pump (LSN already durable / non-durable
        scheduler) or, ``where="committer"``, as the ``when_durable``
        continuation on the thread that advanced the watermark — the
        WAL committer's (pipelined fsync), under the WAL's lock. A
        staged window may still be dispatched-but-unretired either way
        (``blocks_resolved_before_retire``), or already handed back:
        nothing here depends on it. ``err`` is the committer's death
        cause — the chunk's records may never become durable, so its
        undecided tickets fail with :class:`PumpCrashed` instead (the
        upstream re-sends; replay after ``recover()`` dedups)."""
        if err is not None:
            crash = PumpCrashed(
                f"wal committer died before the window's records were "
                f"durable: {err!r}")
            crash.__cause__ = err
            with self._lock:
                self._state = "failed"
                self.pump_error = err
                self._pending_res -= 1
                self._not_full.notify_all()
                self._work.notify_all()
                self._idle.notify_all()
            for e, _tick, _co in block.items:
                if not e.ticket.done():
                    e.ticket._fail(crash)
            return
        tr = _trace.ENABLED
        t_dur = time.perf_counter()
        c_dur = time.thread_time() if tr else 0.0
        applied = 0
        for e, tick, co in block.items:
            if e.ticket.done():
                continue  # a pump-crash path decided it first
            ctx = e.ticket.trace
            e.ticket._resolve(TicketResult(
                APPLIED, e.batch_id, tick=tick, coalesced_with=co,
                lsn=block.lsn or None))
            applied += 1
            if tr and ctx is not None and ctx.sampled:
                _trace.ticket_stages(
                    ctx, t_adm=e.t_admitted, t_ready=block.t_ready,
                    t_exec0=block.t_exec0, t_exec1=block.t_exec1,
                    t_dur=t_dur, t_res=time.perf_counter(),
                    win=block.win, t_wired=block.t_wired)
                if ctx.cause:
                    # the write's durability boundary on the shared
                    # chain: execute end -> durable watermark passed
                    _trace.evt("wal_append", block.t_exec1,
                               t_dur - block.t_exec1, track="wal",
                               args={"batch_id": e.batch_id,
                                     "cause": ctx.cause,
                                     "lsn": block.lsn or None})
        with self._lock:
            self._pending_res -= 1
            self.ticks += block.nticks
            self.applied += applied
            if block.staged and block.win > self._win_retired:
                self.blocks_resolved_before_retire += 1
            self._idle.notify_all()
        if tr:
            # ``where``: on the WAL committer's thread (under the WAL's
            # lock) when the fsync overlapped later work, inline on the
            # pump when the LSN was already durable; ``inflight``:
            # windows dispatched and unretired right now (the pump owns
            # the deque; another thread may only take its length)
            inflight = len(self._inflight)
            dur = time.perf_counter() - t_dur
            _trace.evt("resolve_block", t_dur, dur,
                       args={"graph": self.name or "frontend",
                             "win": block.win, "tickets": applied,
                             "where": where, "inflight": inflight,
                             "cpu_s": _trace.cpu_s(c_dur, dur)})

    def _on_pump_crash(self, error: BaseException,
                       window: Optional[Dict[int, List[Entry]]] = None,
                       ) -> None:
        """Fail the frontend after its pump died: every undecided ticket
        of the drained set the pump was working on and of the stranded
        backlog resolves with :class:`PumpCrashed`, blocked producers
        are released, and the graph's budget bytes return to the pool.
        On a tier, only THIS graph fails — the pool thread survives and
        keeps serving siblings (``window`` carries the drained entries
        when the crash fired before ``_run_window`` stamped them).

        A chunk whose dispatch (or ``tick_many``) had returned is on the
        durable watermark already and keeps its own fate: a ticket the
        watermark decided stays APPLIED (its records are durable and
        ``recover()`` replays them), and one it has not decided yet is
        decided by it — or by the committer's death, as
        :class:`PumpCrashed` — unless it belongs to the drained set
        above, whose undecided tickets fail here and now. Either way the
        block's ``_pending_res`` unit comes back through
        ``_complete_block``, never from here."""
        with self._lock:
            self._state = "failed"
            self.pump_error = error
            self._executing = False
            # dispatched-but-unretired pipelined windows die with the
            # pump unretired: their device work may or may not have
            # completed, and their ids STAY in the dedup mirror (a
            # re-send dedups). Their queue generations are never handed
            # back; the executor's use-after-donate guard already
            # dropped the queue on a dispatch crash, and a fresh one is
            # allocated next window.
            self._inflight.clear()
            stranded = self._queues.drain_all()
            self._queues.commit_executing()
            # the stranded backlog never reached the scheduler: drop its
            # ids from the dedup mirror (same reasoning as the shed
            # path) so a re-send after revive() is admitted, not
            # DEDUPED. The in-flight window's ids stay mirrored — they
            # may have executed before the crash, and a re-send that
            # turns out unapplied still dedups safely at replay.
            for entries in stranded.values():
                for e in entries:
                    self._admitted.pop(e.batch_id, None)
            self._budget.notify_room()
            self._not_full.notify_all()
            self._work.notify_all()
            self._idle.notify_all()
        crash = PumpCrashed(f"ingest pump died: {error!r}")
        crash.__cause__ = error
        if window is None:
            window = getattr(self, "_window_entries", None) or {}
        for entries in list(window.values()) + list(stranded.values()):
            for e in entries:
                if not e.ticket.done():
                    e.ticket._fail(crash)

    def _bind_sched(self, sched) -> None:
        """Re-point a settled frontend at a new scheduler (the failover
        path; caller holds the lock, state is ``"failed"``). The dedup
        mirror is REBUILT from the new scheduler's recovered window:
        a batch the old leader committed *and shipped* dedups here,
        while a batch only the dead leader ever saw is dropped from the
        mirror — its ticket failed with ``PumpCrashed``, the producer's
        resubmit is admitted, and it folds exactly once on the new
        leader."""
        self.sched = sched
        self._cursors.clear()  # auto-id cursors re-derive from new sched
        self._admitted = dict.fromkeys(sched._seen_batch_ids)
        self.megatick = bool(getattr(sched, "window_support", False))
        if not self.megatick and self.admission == "device":
            self.admission = "host"
        staged = (self.megatick
                  and getattr(sched, "stage_window", None) is not None)
        if not staged:
            self.depth = 1

    def revive(self, sched=None) -> None:
        """Re-arm a failed frontend: ``"failed"`` → ``"running"`` — the
        control plane's respawn actuator (callers can also use it by
        hand). Only valid after :meth:`_on_pump_crash` settled the
        graph: queues drained, budget released, every undecided ticket
        failed — so the frontend is structurally identical to a freshly
        registered one and new submissions flow immediately. Upstreams
        re-send the batches whose tickets failed with
        :class:`PumpCrashed`; a durable graph's replay dedups any that
        actually executed.

        ``sched=`` re-points the frontend at a NEW scheduler before
        re-arming — the failover path: after a leader dies and a
        replica promotes, the tier revives the same frontend over the
        promoted ``DurableScheduler`` so producers keep their handle
        and resubmit through the (rebuilt) dedup mirror.

        Durability caveat: reviving is at-most-once for the CRASHED
        window on a volatile graph (its deltas are gone); a durable
        graph loses nothing acknowledged — unacknowledged batches are
        the upstream's to re-send, same as process-crash recovery. If
        the scheduler's WAL committer is dead this raises — call
        ``wal.restart_committer()`` first (or pass the promoted
        ``sched=``), or the next window would fail the graph right
        back."""
        with self._lock:
            if self._state != "failed":
                raise GraphError(
                    f"revive() re-arms a failed frontend; state is "
                    f"{self._state!r}")
            if sched is not None and sched is not self.sched:
                self._bind_sched(sched)
            wal = getattr(self.sched, "wal", None)
            if wal is not None and wal.committer_error is not None:
                raise GraphError(
                    "scheduler's WAL committer is dead; "
                    "restart_committer() before revive()")
            self._state = "running"
            self.pump_error = None
            self._executing = False
            self.revives += 1
            if self._thread is not None and not self._thread.is_alive():
                # the pump thread died WITH the crash (its own window
                # hit the dead committer) rather than surviving it (the
                # committer thread failing tickets via when_durable):
                # re-arm the loop itself, not just the state flag, or
                # nothing drains the queues and flush() never returns
                self._thread = threading.Thread(
                    target=self._pump_loop, name="reflow-ingest-pump",
                    daemon=True)
                self._thread.start()
            self._not_full.notify_all()
            self._work.notify_all()
            self._idle.notify_all()
