"""TpuExecutor: one tick pass = one jit-compiled XLA step.

North star (BASELINE.json): the DirtyScheduler's per-tick batch of
invalidated nodes is lowered to a single ``jax.jit`` step — vmapped
Map/Filter, dense segment reductions for GroupBy/Reduce, table×arena
products for Join — with delta buffers device-resident and host callbacks
only at graph sources (``to_device``) and sinks (``to_host``). Back-edge
(loop) deltas stay on device between passes; the only mid-tick readback is
one scalar liveness count per pass for the scheduler's quiescence check
(removed entirely by the on-device ``lax.while_loop`` fixpoint path — see
``fixpoint.py``).

Compiled pass programs are cached per (plan, ingress-capacity-bucket)
signature, so steady-state ticks hit the cache and pay zero tracing cost.
Mega-tick window programs additionally share a process-wide cache keyed
on the plan *signature* (graph structure + fn code, not node identity),
so structurally-identical tenants — e.g. K spread-placed twins on a
serving tier — trace their window program once (``megatick_cache_hits``).
"""

from __future__ import annotations

import contextlib
import queue as _queue
import threading
import time
from typing import Dict, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from reflow_tpu.delta import DeltaBatch
from reflow_tpu.executors.arena import propagate_plan_caps
from reflow_tpu.executors.base import Executor
from reflow_tpu.executors.device_delta import (DeviceDelta, bucket_capacity,
                                               check_weight_mass, to_device,
                                               to_host)
from reflow_tpu.executors import join as _join
from reflow_tpu.executors.lowerings import (DEVICE_REDUCERS, OP_COUNTERS,
                                            knn_state, lower_node,
                                            minmax_refresh_core, reduce_state)
from reflow_tpu.graph import FlowGraph, GraphError, Node
from reflow_tpu.obs import threads as _threads
from reflow_tpu.obs import trace as _trace
from reflow_tpu.utils.runtime import named_lock

__all__ = ["TpuExecutor", "StagedWindow"]


# -- process-wide window-program sharing (plan-signature cache) ------------
#
# Two graphs built by the same code are distinct Node objects with
# distinct (per-graph) ids, so the per-executor program cache cannot see
# that their dirty plans are the same computation. The signature below
# captures everything the traced window program can observe — node
# structure, op configuration, fn CODE identity (plus captured scalar
# cells), specs, plan positions, capacities — so identical tenants share
# one traced program object (jax then caches compiled executables per
# argument sharding/device underneath, so the share also spans devices).
# Anything the tokenizer can't prove shareable (arrays or rich objects in
# a closure, fn-less callables) falls back to the per-executor cache.

_SHARED_WINDOW_PROGRAMS: Dict[tuple, object] = {}
_SHARED_WINDOW_LOCK = named_lock("executors.window_cache")


class _Unshareable(Exception):
    pass


#: stands in for a profiler annotation a dispatch does not enter
_NO_NOTE = contextlib.nullcontext()


@contextlib.contextmanager
def _dispatch_notes(K: int, window: bool, traced: bool):
    """The profiler annotations around one macro-tick dispatch:
    ``reflow.window[K]`` on the mega-tick path, and under tracing the
    clock anchor ``reflow.clock[<perf_counter_ns>]`` inside it."""
    with (jax.profiler.TraceAnnotation(f"reflow.window[{K}]")
          if window else _NO_NOTE), \
         (jax.profiler.TraceAnnotation(_trace.clock_anchor_name())
          if traced else _NO_NOTE):
        yield


def _completion_token(states, counter_ids=()):
    """One small OUTPUT of a loop-free window program that nobody
    donates, for the device watcher to wait on: every other output of
    that program is state (donated to the next window) or the fresh
    ingress stack (re-adopted by the queue and donated by a later
    window), so none of them may be touched once the pump has moved on.
    It reads one element of every state leaf's final value, so it
    cannot be ready before the window's last update is. Only the
    program a *traced* dispatch builds has it: with the token in every
    program the untraced paced TF-IDF cell read p50 97-102 ms for the
    parent's 93-96 on the v5e (PERF.md, PR 24), so an untraced leader
    runs the program it always ran.

    A graph whose operators count on the device (``counter_ids``: the
    nodes with a ``counters`` state leaf) gets the same one output as an
    int32 vector, the scalar's bits followed by those counters: the
    watcher, which waits on the token anyway, then reads what each
    window left them at, for no further output and no dispatch."""
    tok = jnp.zeros((), jnp.float32)
    for x in jax.tree.leaves(states):
        if getattr(x, "size", 0):
            tok = tok + x[(0,) * x.ndim].astype(jnp.float32)
    if not counter_ids:
        return tok
    return jnp.concatenate(
        [jax.lax.bitcast_convert_type(tok, jnp.int32)[None]]
        + [states[nid]["counters"] for nid in counter_ids])


class _DeviceWatch:
    """Traced runs only: ONE thread that waits, in dispatch order, on a
    completion token of each dispatched window and records its
    ``window_device`` span on the ``device/<label>`` track —
    ``[max(launch returned, previous window done), done]`` — so the
    program itself says when the device finished a window. The token is
    an output of the window program, never a second dispatch, and never
    an array a later dispatch may donate. Off the pump's path as it is,
    it is also where the process's thread ledger is read
    (``obs/threads.py``): one instant event ``thread_ledger`` on track
    ``proc`` after a window's span, at most every ``LEDGER_EVERY_S``.
    With tracing off the executor builds none of this."""

    #: seconds between two ``thread_ledger`` events, at least
    LEDGER_EVERY_S = 0.5

    def __init__(self, executor: "TpuExecutor"):
        self._ex = executor
        label = executor.device_label or "default"
        self.track = f"device/{label}"
        #: a SimpleQueue: the hand-over costs the dispatching thread no
        #: Python-level lock, and it runs on every traced window
        self._q: "_queue.SimpleQueue[Optional[tuple]]" = _queue.SimpleQueue()
        self.handed = 0          # windows handed over (dispatching thread)
        self.seen = 0            # windows waited out (watcher thread)
        self._thread = threading.Thread(
            target=self._run, name=f"reflow-device-watch/{label}",
            daemon=True)
        self._thread.start()

    def put(self, token, t_launch0: float, t_launch: float, ticks: int,
            kind: str) -> None:
        self.handed += 1
        self._q.put((token, t_launch0, t_launch, _trace.current_window(),
                     ticks, kind))

    def _run(self) -> None:
        ex = self._ex
        prev_done = ledger_at = 0.0
        while True:
            item = self._q.get()
            if item is None:
                return
            token, t_launch0, t_launch, win, ticks, kind = item
            try:
                jax.block_until_ready(token)
            except Exception as e:  # noqa: BLE001 - the program's
                # failure reaches its caller through check_errors /
                # block(); the watcher only loses this window's span
                ex.device_watch_error = e
                self.seen += 1
                continue
            done = time.perf_counter()
            start = max(t_launch, prev_done)
            prev_done = done
            ex.device_busy_s += done - start
            ex.windows_done += 1
            # an int32 vector carries the counters behind its first
            # word; the fused linear fixpoint's token is its ``conv``
            counters = (ex._note_counters(np.asarray(token)[1:])
                        if token.ndim and token.dtype == np.int32
                        else None)
            # the device may start while the launch call is still
            # returning (on a busy host that takes milliseconds), so
            # the span is a lower bound of the window's device time
            # and ``dur + launch_s`` (launch entered -> done, when
            # nothing was queued ahead) an upper one
            args = {"ticks": ticks, "kind": kind,
                    "queued_s": start - t_launch,
                    "launch_s": t_launch - t_launch0}
            if win is not None:
                args["win"] = win
            if counters:
                # cumulative since bind, as this window left them
                args["counters"] = {
                    name: list(c.values()) for name, c in counters.items()}
            _trace.evt("window_device", start, done - start,
                       track=self.track, args=args)
            if done - ledger_at >= self.LEDGER_EVERY_S:
                ledger_at = done
                read = _threads.ledger()
                # what the read itself took rides along: the
                # instrument's cost is in the trace it writes
                read["read_s"] = time.perf_counter() - done
                _trace.evt("thread_ledger", done, 0.0, track="proc",
                           args=read)
            self.seen += 1

    def drain(self, timeout_s: float = 60.0) -> None:
        """Block until every window handed over so far is recorded
        (tests and exporters; raises if the device does not get there
        in ``timeout_s``)."""
        deadline = time.monotonic() + timeout_s
        while self.seen < self.handed:
            if time.monotonic() > deadline:
                raise TimeoutError(
                    f"device watcher saw {self.seen} of {self.handed} "
                    f"windows in {timeout_s}s")
            time.sleep(0.001)

    def close(self) -> None:
        self._q.put(None)
        self._thread.join(timeout=30.0)


class StagedWindow:
    """A staged-but-not-yet-dispatched K-tick window: the ingress queue
    generation its slot writes landed in, the [K, cap] stack to hand the
    window program, and everything :meth:`TpuExecutor.dispatch_window` /
    :meth:`TpuExecutor.retire_window` need to finish the lifecycle.
    ``fresh`` is filled by dispatch (the program's returned zeroed
    pass-through stack) and consumed by retire, as is ``done``: a
    fixpoint window's per-tick ``converged`` flags, an output of its
    program that nothing donates, which the retire waits for (None for
    a loop-free window)."""

    __slots__ = ("plan", "caps", "K", "max_iters", "queue", "gen", "stack",
                 "qsig", "fresh", "done")

    def __init__(self, plan, caps, K, max_iters, queue, gen, stack, qsig):
        self.plan = plan
        self.caps = caps
        self.K = K
        self.max_iters = max_iters
        self.queue = queue
        self.gen = gen
        self.stack = stack
        self.qsig = qsig
        self.fresh = None
        self.done = None


def _value_token(v):
    if v is None or isinstance(v, (bool, int, float, str, bytes)):
        return v
    if isinstance(v, tuple):
        return tuple(_value_token(x) for x in v)

    if isinstance(v, np.generic):
        return (str(v.dtype), v.item())
    if isinstance(v, np.dtype) or (isinstance(v, type)
                                   and issubclass(v, np.generic)):
        return str(np.dtype(v))
    if callable(v):
        return _fn_token(v)
    raise _Unshareable


def _fn_token(fn):
    """Identity of a user fn AS TRACED: its code object plus the values
    it closes over / defaults to. Two lambdas from the same source line
    share the code object; differing captured scalars split the token."""
    code = getattr(fn, "__code__", None)
    if code is None:
        raise _Unshareable
    toks = [_value_token(c.cell_contents) for c in (fn.__closure__ or ())]
    toks += [_value_token(d) for d in (fn.__defaults__ or ())]
    return ("fn", code, tuple(toks))


def _spec_token(spec):
    return (tuple(spec.value_shape), str(np.dtype(spec.value_dtype)),
            int(spec.key_space), bool(spec.unique))


def _op_token(op):
    toks = [type(op).__name__]
    for k in sorted(vars(op)):
        v = vars(op)[k]
        if k in ("params", "param_specs"):
            # params are program ARGUMENTS (op state), not traced
            # constants: only their presence shapes the program
            toks.append((k, v is not None))
        elif hasattr(v, "value_shape"):
            toks.append((k, _spec_token(v)))
        else:
            toks.append((k, _value_token(v)))
    return tuple(toks)


def _node_token(node: Node):
    # node.name is observability-only (error strings), deliberately out
    return (node.id, node.kind,
            _op_token(node.op) if node.op is not None else None,
            tuple(i.id for i in node.inputs), _spec_token(node.spec),
            node.back_input.id if node.back_input is not None else None,
            node.sharding, node.stage, node.defer_passes)


class TpuExecutor(Executor):
    name = "tpu"

    @property
    def states(self):
        return self._states

    @states.setter
    def states(self, value):
        # new states may bring arenas of any fill
        self._states = value
        self._room.forget()

    def __init__(self, *, fixpoint: bool = True, linear_fixpoint: bool = True):
        #: room for the indexed joins' appends, made between ticks
        self._room = _join.ArenaRoom()
        super().__init__()
        self._cache: Dict[tuple, object] = {}
        #: lower whole ticks of iterative graphs to one lax.while_loop
        #: program (False forces the host-driven per-pass loop)
        self.fixpoint = fixpoint
        #: allow the fused delta-vector loop for declared-linear regions
        #: (False forces the row-based while_loop program); it runs on
        #: both the single-device and the sharded executor (the sharded
        #: variant runs the loop inside one shard_map region — see
        #: linear_fixpoint.py)
        self.linear_fixpoint = linear_fixpoint
        self._fx_structure = None
        self._fx_unsupported = not fixpoint
        #: mesh size for sharded subclasses: arena overflow is bounded
        #: against the per-shard slice (worst-case key skew)
        self._arena_divisor = 1
        #: the bound graph's declared-linear structure
        #: (``analyze_linear``); None while the fused loop is not in use
        self._linear_structure = None
        #: ``_build_fixpoint`` asked and the fused loop does not fit this
        #: graph (no such structure, or shapes outside its fused-f32
        #: representation): it does not ask again until the next graph
        self._linear_unfit = False
        #: class name of the fixpoint program ``_build_fixpoint`` last
        #: built ("LinearFixpointProgram" / "FixpointProgram"; None
        #: before the first loop tick, or when neither fit and the
        #: scheduler's host loop runs). The selection there falls from
        #: engine to engine without raising, so callers that need a
        #: particular one (chip_smoke.py) read the outcome here.
        self.fixpoint_engine: Optional[str] = None
        #: ONE persistent sorted-arena CSR cache per join node, shared by
        #: every LinearFixpointProgram signature over that join (a
        #: per-program copy would duplicate tens of MB of HBM per ingress
        #: bucket and re-sort appends the other signature already covered)
        self._csr_cache: Dict[int, dict] = {}
        #: mega-tick window path (run_window): per-source host batches
        #: above this row bound don't fit a reasonable queue slot — the
        #: scheduler falls back to the per-tick path instead
        self.megatick_max_rows = 1 << 16
        #: windows dispatched through the device-resident ingress queue
        self.window_dispatches = 0
        #: tenant placement: the jax.Device this executor's state, ingress
        #: uploads, queue buffers — and therefore every compiled program's
        #: execution — are committed to. None = jax's default device. Set
        #: via :meth:`place` (the serve tier's GraphConfig placement path).
        self.device = None
        #: window programs adopted from the process-wide plan-signature
        #: cache instead of traced locally (surfaced as a scheduler gauge)
        self.megatick_cache_hits = 0
        #: device completion, as the program's own ``window_device``
        #: spans see it: their summed seconds and their count (scheduler
        #: gauges; zero while tracing is off, when nothing watches), the
        #: watcher itself (built at the first traced dispatch), and the
        #: last error a watched token raised
        self.device_busy_s = 0.0
        self.windows_done = 0
        self._watch: Optional[_DeviceWatch] = None
        self.device_watch_error: Optional[BaseException] = None
        #: node name -> {counter: value}: the last reading of each
        #: operator's device-resident counters (``op_counters``)
        self._op_counters: Dict[str, Dict[str, int]] = {}

    #: subclasses whose traced programs close over executor-specific
    #: context (e.g. the sharded executor's mesh/axis in ``_lower``) must
    #: opt out of the process-wide window-program share
    _share_window_programs = True

    # -- tenant placement --------------------------------------------------

    def place(self, device) -> None:
        """Commit this executor to one device: states move, and every
        subsequent upload, queue buffer, and compiled-program execution
        follows them (jit dispatch targets the committed argument device).
        ``device`` is a ``jax.Device`` or an index into ``jax.devices()``.
        Compiled programs and cached queues reference buffers on the old
        device, so the program cache is dropped; call between windows."""
        if isinstance(device, int):
            device = jax.devices()[device]
        self.device = device
        self._cache.clear()
        self._csr_cache.clear()
        if self.states:
            self.states = jax.device_put(self.states, device)

    @property
    def device_label(self) -> Optional[str]:
        """Short obs tag for spans/gauges: ``"cpu:3"``-style for a pinned
        executor, None when running on the default device."""
        d = self.device
        if d is None:
            return None
        return f"{getattr(d, 'platform', 'dev')}:{getattr(d, 'id', '?')}"

    def drain_device_watch(self) -> None:
        """Wait until the ``window_device`` span of every window
        dispatched so far under tracing is recorded (a no-op when
        nothing was)."""
        if self._watch is not None:
            self._watch.drain()

    def _counting(self) -> List[Tuple[Node, Tuple[str, ...]]]:
        """The bound graph's nodes whose lowering keeps counters in its
        device state (a ``counters`` leaf), each with their names; a
        loop node's are the row fixpoint program's."""
        out = []
        for n in (self.graph.nodes if self.graph else ()):
            kind = n.op.kind if n.kind == "op" else n.kind
            st = self.states.get(n.id) or ()
            if kind in OP_COUNTERS and "counters" in st:
                out.append((n, OP_COUNTERS[kind]))
        return out

    def counter_names(self) -> Dict[str, Tuple[str, ...]]:
        """Node name -> the counters that node keeps on the device."""
        return {n.name: names for n, names in self._counting()}

    def _note_counters(self, flat) -> Dict[str, Dict[str, int]]:
        """Keep one reading of every counting node's counters, given
        flat in node order, and return it by node name."""
        at, out = 0, {}
        for n, names in self._counting():
            out[n.name] = {c: int(v) for c, v in
                           zip(names, flat[at:at + len(names)])}
            at += len(names)
        self._op_counters = out
        return out

    def op_counters(self) -> Dict[str, Dict[str, int]]:
        """What the operators counted on the device, by node name
        (``lowerings.OP_COUNTERS``; cumulative since bind). This is a
        device read: it waits for the last dispatched window, so call it
        where a snapshot is taken, not per tick. From another thread
        than the dispatching one the state it reads may already be
        donated to the next window; the last reading is returned then."""
        try:
            flat = [v for n, _ in self._counting()
                    for v in np.asarray(self.states[n.id]["counters"])]
        except (RuntimeError, KeyError):  # donated under us, or rebinding
            return self._op_counters
        return self._note_counters(flat)

    def close(self) -> None:
        """Stop the device watcher, if tracing ever started one. The
        executor stays usable: a later traced dispatch starts another."""
        watch, self._watch = self._watch, None
        if watch is not None:
            watch.close()

    def _ingress_placement(self):
        """Placement handed to ingress buffers (queue slots, stacked
        feeds): the pinned device here; the sharded subclass returns its
        ``(mesh, axis)`` so the capacity axis lands shard-local."""
        return self.device

    # -- bind: validate lowerability, build device state -------------------

    def _indexes_joins(self) -> bool:
        """Do unique-left joins keep an index of their arena
        (``join.join_layout``), and do they and the row fixpoint's loop
        count on the device? The sharded executor, whose per-shard
        scalars ride as mesh-length vectors, overrides this: neither."""
        return True

    def _row_fixpoint_loop(self, graph: FlowGraph) -> Optional[Node]:
        """The loop node in whose state the row fixpoint program counts
        (``OP_COUNTERS["loop"]``), or None: where the graph's ticks will
        not run on ``FixpointProgram`` as far as the graph alone says
        (no loop, fusion off, no on-device structure, or a region the
        fused linear program takes), and where the executor keeps no
        counters (``_indexes_joins``)."""
        from reflow_tpu.executors.fixpoint import analyze
        from reflow_tpu.executors.linear_fixpoint import analyze_linear

        if not (self._indexes_joins() and self.fixpoint and graph.loops):
            return None
        structure = analyze(graph)
        if structure is None or (
                self.linear_fixpoint
                and analyze_linear(graph, structure) is not None):
            return None
        return structure.loops[0]

    def bind(self, graph: FlowGraph) -> None:
        # compiled passes close over graph nodes: rebinding the *same* graph
        # (fresh state, e.g. a full-recompute baseline) keeps the jit cache;
        # a different graph invalidates it
        if graph is not self.graph:
            self._cache.clear()
            self._fx_structure = None
            self._fx_unsupported = not self.fixpoint
            self._linear_structure = None
            self._linear_unfit = False
        # state is reset below: any sorted-arena cache is now stale (the
        # (gen, rcount) predicate would also catch this via count > rcount,
        # but an explicit drop is cheaper than relying on it)
        self._csr_cache.clear()
        self.graph = graph
        self.states = {}
        counted_loop = self._row_fixpoint_loop(graph)
        if counted_loop is not None:
            self.states[counted_loop.id] = {"counters": jnp.zeros(
                (len(OP_COUNTERS["loop"]),), jnp.int32)}
        for loop in graph.loops:
            if loop.defer_passes:
                # cross-tick residual deferral: the loop carries its
                # un-propagated emission deltas as dense linear
                # observables [K, P+1] (flattened dval columns + dw).
                # SEMANTIC state — checkpointed with the state tree,
                # unlike the derived CSR cache (docs/guide.md).
                K = loop.spec.key_space
                if K <= 0:
                    raise GraphError(
                        f"{loop}: defer_passes needs key_space > 0")
                P = int(np.prod(loop.spec.value_shape)) if \
                    loop.spec.value_shape else 1
                self.states[loop.id] = dict(
                    self.states.get(loop.id, {}),
                    resid=jnp.zeros((K, P + 1), jnp.float32))
        for node in graph.nodes:
            if node.kind != "op":
                continue
            op = node.op
            if op.kind == "map" and op.params is not None:
                for leaf in jax.tree.leaves(op.params):
                    if not hasattr(leaf, "shape"):
                        raise GraphError(
                            f"{node}: Map params leaves must be arrays, got "
                            f"{type(leaf).__name__}; close fn over static "
                            f"(shape-driving) config instead of passing it "
                            f"in params")
                # deep-copy: tick programs DONATE state, and aliasing the
                # caller's arrays would delete them out from under the
                # user on the first tick
                self.states[node.id] = {
                    "params": jax.tree.map(lambda x: jnp.array(x, copy=True),
                                           op.params)}
                continue
            if op.kind in ("map", "filter", "groupby", "union"):
                continue
            in_specs = [i.spec for i in node.inputs]
            for s in in_specs:
                if s.key_space <= 0:
                    raise GraphError(
                        f"{node}: TPU lowering needs key_space > 0 on every "
                        f"keyed-op input Spec")
            if op.kind == "reduce":
                if op.how not in DEVICE_REDUCERS:
                    raise GraphError(
                        f"{node}: reducer {op.how!r} has no device lowering "
                        f"yet (have {DEVICE_REDUCERS}); run it on the cpu "
                        f"executor")
                self.states[node.id] = reduce_state(op, in_specs[0], node.spec)
            elif op.kind == "knn":
                for port, s in enumerate(in_specs):
                    if tuple(s.value_shape) != (op.dim,):
                        raise GraphError(
                            f"{node}: knn input {port} value_shape "
                            f"{s.value_shape} != (dim={op.dim},)")
                D = in_specs[1].key_space
                if D > op.scan_chunk and D % op.scan_chunk:
                    raise GraphError(
                        f"{node}: corpus key_space {D} must be a multiple "
                        f"of scan_chunk {op.scan_chunk}")
                self.states[node.id] = knn_state(op, *in_specs)
            elif op.kind == "join":
                if op.merge is None:
                    # the default merge lowers to the flattened
                    # concatenation of (va, vb) — the device encoding of
                    # the host oracle's tuple; the out Spec must size it
                    flat = int(np.prod(in_specs[0].value_shape or (1,))
                               ) + int(np.prod(in_specs[1].value_shape
                                                or (1,)))
                    got = int(np.prod(node.spec.value_shape or (1,)))
                    if got != flat:
                        raise GraphError(
                            f"{node}: default-merge device Join needs a "
                            f"spec with {flat} flat value elements "
                            f"(va ++ vb), got {node.spec.value_shape}")
                self.states[node.id] = _join.join_state(
                    op, in_specs[0], in_specs[1], _join.join_layout(
                        op, in_specs[0], looped=bool(graph.loops),
                        index=self._indexes_joins()))
            else:
                raise GraphError(f"{node}: no TPU lowering for {op.kind}")
        self._room.bind(graph, self.states)
        if self.device is not None:
            # placed BEFORE bind: move the freshly-built state tree onto
            # the pinned device (the jnp.zeros above land on the default)
            self.states = jax.device_put(self.states, self.device)

    # -- one pass ----------------------------------------------------------

    def _to_device_ingress(self, ingress) -> Dict[int, DeviceDelta]:
        """Host boundary in: upload host batches; pass device ones through."""
        dev_ingress: Dict[int, DeviceDelta] = {}
        for nid, b in ingress.items():
            if isinstance(b, DeviceDelta):
                # jit dispatch follows committed args: a pinned executor
                # pulls a stray default-device batch over (no-op when it
                # already lives on self.device)
                if self.device is not None:
                    b = jax.tree.map(
                        lambda x: jax.device_put(x, self.device), b)
                dev_ingress[nid] = b
            else:
                dev_ingress[nid] = to_device(b, self.graph.nodes[nid].spec,
                                             device=self.device)
        return dev_ingress

    def run_pass(self, plan: Sequence[Node],
                 ingress: Dict[int, DeltaBatch]) -> Dict[int, object]:
        dev_ingress = self._to_device_ingress(ingress)

        sig = (
            tuple(n.id for n in plan),
            tuple(sorted((nid, d.capacity) for nid, d in dev_ingress.items())),
        )
        fn = self._cache.get(sig)
        if fn is None:
            fn = self._build(list(plan))
            self._cache[sig] = fn

        # fail loudly BEFORE truncation
        self._room.make(self._states, self._track_arena(
            plan, {nid: d.capacity for nid, d in dev_ingress.items()}), 1)
        op_states = {nid: st for nid, st in self.states.items()}
        new_states, egress_dev = fn(op_states, dev_ingress)
        self._states = new_states

        # everything stays device-resident: sink batches are materialized
        # lazily by the scheduler once per tick, loop back-edges feed the
        # next pass directly on device
        return dict(egress_dev)

    # -- whole-tick on-device fixpoint (SURVEY.md §7.9, hard part e) -------

    def run_tick_fixpoint(self, plan: Sequence[Node],
                          ingress: Dict[int, DeltaBatch], max_iters: int,
                          *, sync: bool = True):
        """Run an entire tick (initial pass + fixpoint + exit pass) as one
        compiled program. Returns ``(sink_batches, passes, loop_rows,
        quiesced)`` or None when the graph doesn't fit the on-device
        structure (the scheduler then uses its host-driven loop).

        With ``sync=False`` the scalar tick metadata stays device-resident
        (no readback, so pipelined ticks enqueue back-to-back); the dirty
        set is then reported conservatively (as if the loop iterated)."""
        from reflow_tpu.executors.fixpoint import analyze

        if self._fx_unsupported:
            return None
        if self._fx_structure is None:
            self._fx_structure = analyze(self.graph)
            if self._fx_structure is None:
                self._fx_unsupported = True
                return None

        dev_ingress = self._to_device_ingress(ingress)
        caps = {nid: d.capacity for nid, d in dev_ingress.items()}

        sig = ("fx", tuple(n.id for n in plan),
               tuple(sorted(caps.items())), max_iters)
        prog = self._cache.get(sig)
        if prog is None:
            prog = self._build_fixpoint(plan, caps, max_iters)
            if prog is None:
                return None
            self._cache[sig] = prog

        st = self._fx_structure
        self._track_arena(plan, caps)
        if st.exit_plan:
            self._track_arena(
                list(st.exit_plan),
                {n.id: 2 * n.inputs[0].spec.key_space for n in st.boundary})

        t_d0 = time.perf_counter() if _trace.ENABLED else 0.0
        new_states, sink_egress, carry, iters, rows, converged = prog(
            dict(self.states), dev_ingress)
        if _trace.ENABLED:
            _trace.evt("device_dispatch", t_d0,
                       time.perf_counter() - t_d0,
                       args=_trace.with_win(
                           {"kind": "fixpoint",
                            "device": self.device_label}))
        self.states = new_states
        exit_passes = 1 if st.exit_plan else 0
        leftover = {}
        if sync:
            iters = int(iters)
            passes = 1 + iters + exit_passes
            rows = int(rows)
            converged = bool(converged)
            looped = iters > 0
            if not converged and carry:
                # max_iters halt: hand the live carry back so the
                # scheduler stashes it as pending — the halted iteration
                # RESUMES on the next tick instead of silently dropping
                # in-flight loop deltas (which would desync the join's
                # left table from the reduce's emissions)
                leftover = dict(carry)
        else:
            # LazyScalar, not eager jnp arithmetic: a per-tick scalar op
            # would dispatch an extra device execution; int() combines at
            # the sync point instead
            from reflow_tpu.scheduler import LazyScalar

            passes = LazyScalar(1 + exit_passes, iters)
            looped = True  # conservative dirty-set report
            if carry:
                # streaming mode cannot branch on the device-resident
                # converged flag, so the ROW program's carry stashes
                # UNCONDITIONALLY: a quiescent tick's carry is all
                # weight-0 rows (a semantic no-op that keeps the next
                # tick's ingress signature stable), and a max_iters halt
                # resumes losslessly instead of silently desyncing the
                # join's left table. The fused linear program returns
                # carry=None (its in-flight state is the defer resid),
                # so the streaming headline path is untouched.
                leftover = dict(carry)
        # nodes the fused passes executed beyond the phase-A plan (for the
        # scheduler's dirty-set observability): region + exit nodes, which
        # only ran if the loop actually iterated
        extra_dirty = (set(st.region_ids) | {n.id for n in st.exit_plan}
                       if looped else set())
        return ({sid: list(batches) for sid, batches in sink_egress.items()},
                passes, rows, converged, extra_dirty, leftover)

    def run_tick_fixpoint_many(self, plan, feeds, max_iters):
        """K consecutive ticks as ONE device execution (the macro-tick).

        ``feeds`` is a list of K ``{node_id: DeltaBatch}`` ingress dicts
        with identical node sets and identical padded capacities. Only
        sink-free graphs qualify (sink egress would need per-tick host
        materialization): iterative graphs scan the fused fixpoint
        program, loop-free graphs scan the plain pass program. NOTE:
        the scan discards per-tick fixpoint carries between iterations,
        so a ROW-program tick that halts at max_iters inside a
        macro-tick does NOT pause/resume (its conv flag comes back
        False at block() — size max_loop_iters to quiesce, or stream
        per-tick; the fused linear program's defer resid is in-state
        and carries fine). Returns
        ``(passes_base, iters, rows, converged, extra_dirty)`` with any
        per-tick scalars device-resident (zero readbacks — the streaming
        fast path), or None when the graph/feeds don't fit (caller falls
        back to the per-tick loop).

        Why: every device execution carries a fixed per-dispatch
        overhead independent of program size; ``lax.scan``-ing K ticks
        into one execution amortizes it K-fold.
        """
        if not self.supports_window():
            return None
        K = len(feeds)
        node_ids = sorted(feeds[0])
        if any(sorted(f) != node_ids for f in feeds):
            return None

        t_h0 = time.perf_counter() if _trace.ENABLED else 0.0
        stack, caps = self._stack_feeds(feeds)
        if _trace.ENABLED:
            _trace.evt("stack_feeds", t_h0, time.perf_counter() - t_h0,
                       args=_trace.with_win({"ticks": K}))
        return self._dispatch_many(plan, stack, caps, K, max_iters)

    def supports_window(self) -> bool:
        """Does this executor's bound graph fit the fused macro-tick /
        mega-tick window path? The scheduler's ``window_support``
        property and the serve frontend read this to decide whether the
        window path can engage at all. ``fixpoint=False`` is the
        whole-tick-fusion opt-out (and what the staged executor, whose
        states are pinned per stage device, relies on to keep tick_many
        on the per-tick fallback); sinks need per-tick host egress."""
        from reflow_tpu.executors.fixpoint import analyze

        if self.graph is None or not self.fixpoint or self.graph.sinks:
            return False
        if not self.graph.loops:
            return True
        if self._fx_unsupported:
            return False
        if self._fx_structure is None:
            self._fx_structure = analyze(self.graph)
            if self._fx_structure is None:
                self._fx_unsupported = True
                return False
        return True

    def run_window(self, plan, feeds, max_iters):
        """One K-tick commit window as ONE dispatch fed from the
        device-resident ingress queue (the compiled mega-tick).

        Same contract as :meth:`run_tick_fixpoint_many` — ``feeds`` is a
        list of K ``{node_id: DeltaBatch}`` dicts over an identical
        (scheduler-padded) source set — but instead of restacking host
        [K, C] arrays every window, each batch is index-written into a
        persistent per-(plan, caps, K) queue slot and the window program
        scans the queue buffers in place. Returns the
        ``(passes_base, iters, rows, converged, extra_dirty)`` tuple
        with per-tick counters device-resident, or None when the window
        doesn't fit (device-resident batches, rows above
        ``megatick_max_rows``, unsupported graph) — the scheduler then
        falls back to the stacked/per-tick paths.

        This is the depth-1 composition of the staged lifecycle the
        pipelined pump drives directly: :meth:`stage_window` →
        :meth:`dispatch_window` → :meth:`retire_window`.
        """
        sw = self.stage_window(plan, feeds, max_iters)
        if sw is None:
            return None
        out = self.dispatch_window(sw)
        if out is None:
            return None
        # a serial caller holds the window's results and paces itself
        sw.done = None
        self.retire_window(sw)
        return out

    def stage_window(self, plan, feeds, max_iters):
        """Front half of the window lifecycle: validate the window fits
        the fused path, slot-write every host batch into the ingress
        queue's staging generation, and SEAL that generation (its buffers
        now belong to the upcoming dispatch — the queue's next write
        rotates onto a fresh set, so a pipelined caller can stage window
        N+1 while N is in flight). Returns a :class:`StagedWindow` to
        pass to :meth:`dispatch_window`, or None when the window doesn't
        fit (same conditions as :meth:`run_window`; nothing is staged or
        sealed in that case).

        A successful stage GUARANTEES the dispatch can engage: for loop
        graphs the fused fixpoint program (``call_many``) is built and
        cache-checked here, so the caller may commit irreversible work
        (WAL appends) between stage and dispatch without risking a
        silent fallback in between."""
        if not self.supports_window():
            return None
        K = len(feeds)
        node_ids = sorted(feeds[0])
        if any(sorted(f) != node_ids for f in feeds):
            return None
        caps: Dict[int, int] = {}
        for nid in node_ids:
            rows = 0
            for f in feeds:
                b = f[nid]
                if hasattr(b, "nonzero"):
                    # already device-resident: no host rows to slot-write
                    # (and len() would force a readback) — stack path
                    return None
                rows = max(rows, len(b))
            if rows > self.megatick_max_rows:
                return None
            caps[nid] = bucket_capacity(rows)

        if self.graph.loops:
            # pre-build the fused fixpoint program NOW: dispatch must not
            # be able to return None after the caller has WAL-logged the
            # staged window (a post-stage fallback would double-append)
            sig = ("fx", tuple(n.id for n in plan),
                   tuple(sorted(caps.items())), max_iters)
            prog = self._cache.get(sig)
            if prog is None:
                prog = self._build_fixpoint(plan, caps, max_iters)
                if prog is None:
                    return None
                self._cache[sig] = prog
            if not hasattr(prog, "call_many"):
                return None

        qsig = ("ingress_q", tuple(n.id for n in plan),
                tuple(sorted(caps.items())), K)
        queue = self._cache.get(qsig)
        if queue is None:
            from reflow_tpu.executors.ingress_queue import DeviceIngressQueue

            # negotiate capacity with the arena BEFORE reserving device
            # memory: impossible ingress sizes raise here, not mid-window
            self._track_arena(plan, caps)
            queue = DeviceIngressQueue(
                {nid: self.graph.nodes[nid].spec for nid in node_ids},
                caps, K, placement=self._ingress_placement())
            self._cache[qsig] = queue

        tr = _trace.ENABLED
        t_h0 = time.perf_counter() if tr else 0.0
        c_h0 = time.thread_time() if tr else 0.0
        for t, f in enumerate(feeds):
            for nid in node_ids:
                queue.write(t, nid, f[nid])
        if tr:
            # dur - cpu_s: the slot writes waiting for the device (they
            # queue behind the running window's program)
            dur = time.perf_counter() - t_h0
            _trace.evt("queue_write", t_h0, dur,
                       args=_trace.with_win(
                           {"ticks": K, "slots": K * len(node_ids),
                            "inflight": queue.in_flight,
                            "cpu_s": _trace.cpu_s(c_h0, dur)}))
        stack = queue.stacked()
        gen = queue.seal()
        return StagedWindow(plan, caps, K, max_iters, queue, gen, stack,
                            qsig)

    def dispatch_window(self, sw: "StagedWindow"):
        """Middle of the window lifecycle: one device dispatch over the
        staged stack (DONATED to the program). Stores the program's
        returned zeroed pass-through stack on ``sw.fresh`` for
        :meth:`retire_window` — the dispatch itself is async, so a
        pipelined caller returns here while the device is still
        executing and can immediately stage the next window."""
        try:
            out = self._dispatch_many(sw.plan, sw.stack, sw.caps, sw.K,
                                      sw.max_iters, window=True, staged=sw)
        except Exception:
            # the stack was DONATED: if the dispatch died mid-flight the
            # queue's buffers are gone — drop it so the next window
            # allocates fresh instead of writing into deleted arrays
            self._cache.pop(sw.qsig, None)
            raise
        if out is None:
            # unreachable by construction (stage pre-builds the program);
            # nothing was donated, so un-seal the generation
            sw.queue.cancel(sw.gen)
            return None
        self.window_dispatches += 1
        return out

    def retire_window(self, sw: "StagedWindow") -> None:
        """Tail of the window lifecycle: hand the dispatched program's
        fresh zeroed stack back to the ingress queue, re-asserting
        placement and freeing the generation for restaging. Off the
        critical path — a pipelined pump runs this after the NEXT window
        is already in flight.

        A fixpoint window is retired once the device has finished it
        (``sw.done``). Every call of a window's lifecycle is
        asynchronous, and a fixpoint window costs its passes, seconds
        where a frontier is deep: a caller that retired such windows
        without waiting would run ahead of the device by as many as the
        runtime queues, its producers would block on admission past the
        RPC's submit cap, and all that was admitted would be owed after
        the traffic stops (PERF.md, PR 41). With the wait, a pump of
        depth ``d`` bounds the fixpoint windows the device has not
        finished to ``d``: at 2 one running and one queued behind it,
        all the overlap there is to have. A loop-free window is retired
        as it was dispatched, asynchronously (one rule for both: an
        issue of its own, ROADMAP B10)."""
        if sw.done is not None:
            sw.done.block_until_ready()
            sw.done = None
        sw.queue.retire(sw.gen, sw.fresh)
        sw.fresh = None

    def cancel_window(self, sw: "StagedWindow") -> None:
        """Abandon a staged window whose dispatch never ran (nothing was
        donated): the generation goes straight back to the free list."""
        sw.queue.cancel(sw.gen)

    def _window_signature(self, plan, caps) -> Optional[tuple]:
        """Process-wide share key for a loop-free window program: the
        whole graph's structural tokens plus the plan positions and
        capacity buckets. None when sharing is off for this executor or
        any node resists tokenization (``_Unshareable``) — those fall
        back to the per-executor cache, never to a wrong share."""
        if not self._share_window_programs or self.graph is None:
            return None
        try:
            nodes = tuple(_node_token(n) for n in self.graph.nodes)
        except _Unshareable:
            return None
        return ("pass_many", nodes, tuple(n.id for n in plan),
                tuple(sorted(caps.items())))

    def _dispatch_many(self, plan, stack, caps, K, max_iters, *,
                       window: bool = False, staged=None):
        """Shared macro-tick dispatch tail: compile (or reuse) the K-tick
        scan program for ``plan``/``caps``, run it over the [K, C]
        ingress ``stack``, and return the scheduler-facing
        ``(passes_base, iters, rows, converged, extra_dirty)`` tuple
        (None when the fixpoint program lacks a fused ``call_many``).
        The stack is DONATED to the program; when ``staged`` (a
        :class:`StagedWindow`) is given, the program's returned fresh
        (zeroed) stack is parked on it for the retire step instead of
        being re-adopted inline — the queue and the window never hold
        two live copies either way. ``window=True`` tags the dispatch
        span as the mega-tick path and wraps it in a ``jax.profiler``
        annotation (``reflow.window[K]``) so Perfetto lines host stages
        up against device occupancy. Under tracing the dispatch also
        enters a ``reflow.clock[<perf_counter_ns>]`` annotation — one
        reading of the offset between the span clock and the profiler's
        — and hands a completion token (an output of the program) to
        the device watcher. Both annotations are entered directly: a
        ``TraceMe`` is a no-op while no profiler runs, and a profiler
        that cannot annotate must fail loudly, not thin out a trace."""
        if not self.graph.loops:
            # loop-free sink-free graph (e.g. streaming TF-IDF): scan the
            # PLAIN pass program over the K stacked feeds — one device
            # execution for K ticks, zero per-tick egress by construction
            tr = _trace.ENABLED
            # a traced dispatch runs a twin of the window program that
            # also outputs the watcher's completion token
            sig = ("pass_many", tuple(n.id for n in plan),
                   tuple(sorted(caps.items()))) + (("token",) if tr else ())
            prog = self._cache.get(sig)
            if prog is None:
                # with the window program, what makes room for it: a
                # compaction inside a served window is then a dispatch
                # and never a compile
                self._room.compile(self._states)
                shared_sig = self._window_signature(plan, caps)
                if shared_sig is not None:
                    shared_sig += sig[3:]
                    with _SHARED_WINDOW_LOCK:
                        prog = _SHARED_WINDOW_PROGRAMS.get(shared_sig)
                if prog is not None:
                    # a structurally-identical tenant already traced this
                    # window — adopt its program (jax compiles per
                    # device/sharding underneath, so cross-device is fine)
                    self.megatick_cache_hits += 1
                else:
                    pass_fn = self.build_pass_fn(list(plan))
                    with_token = tr
                    counter_ids = tuple(n.id for n, _ in self._counting())

                    def scan_fn(op_states, ing_stack):
                        def body(states, ing):
                            states2, egress = pass_fn(states, ing)
                            if egress:  # trace-time structural check
                                raise RuntimeError("loop-free sink-free "
                                                   "pass produced egress")
                            return states2, ()

                        states, _ = jax.lax.scan(body, op_states, ing_stack)
                        # hand back a FRESH zeroed stack: the input was
                        # donated, and returning new zeros (not the dead
                        # input) lets XLA alias the donated memory while
                        # giving the ingress queue valid buffers to adopt
                        out = (states,
                               jax.tree.map(jnp.zeros_like, ing_stack))
                        if with_token:
                            out += (_completion_token(states,
                                                      counter_ids),)
                        return out

                    prog = jax.jit(scan_fn, donate_argnums=(0, 1))
                    if shared_sig is not None:
                        with _SHARED_WINDOW_LOCK:
                            prog = _SHARED_WINDOW_PROGRAMS.setdefault(
                                shared_sig, prog)
                self._cache[sig] = prog
            self._room.make(self._states, self._track_arena(plan, caps), K)
            kind = "window" if window else "pass_many"
            t_d0 = time.perf_counter() if tr else 0.0
            c_d0 = time.thread_time() if tr else 0.0
            with _dispatch_notes(K, window, tr):
                out = prog(dict(self.states), stack)
            self._states, fresh = out[0], out[1]
            if staged is not None:
                staged.fresh = fresh
            if tr:
                self._dispatched(out[2], t_d0, c_d0, K, kind)
            return K, 0, 0, True, set()

        sig = ("fx", tuple(n.id for n in plan),
               tuple(sorted(caps.items())), max_iters)
        prog = self._cache.get(sig)
        if prog is None:
            prog = self._build_fixpoint(plan, caps, max_iters)
            if prog is None:
                return None
            self._cache[sig] = prog
        if not hasattr(prog, "call_many"):
            return None

        st = self._fx_structure
        self._track_arena(plan, caps)
        if st.exit_plan:
            self._track_arena(
                list(st.exit_plan),
                {n.id: 2 * n.inputs[0].spec.key_space for n in st.boundary})

        kind = "window" if window else "fixpoint_many"
        tr = _trace.ENABLED
        t_d0 = time.perf_counter() if tr else 0.0
        c_d0 = time.thread_time() if tr else 0.0
        # a traced dispatch of the row program over a graph whose
        # operators count runs a twin that also outputs the loop-free
        # windows' token, counters and all; otherwise ``conv`` is the
        # token: a program output the scheduler only ever reads
        # (TickResult.quiesced), never donates
        from reflow_tpu.executors.fixpoint import FixpointProgram

        counter_ids = (tuple(n.id for n, _ in self._counting())
                       if tr and isinstance(prog, FixpointProgram) else ())
        extra = ((lambda st: _completion_token(st, counter_ids),)
                 if counter_ids else ())
        with _dispatch_notes(K, window, tr):
            new_states, (iters, rows, conv), fresh, *token = prog.call_many(
                dict(self.states), stack, K, *extra)
        if staged is not None:
            staged.fresh, staged.done = fresh, conv
        if tr:
            self._dispatched(token[0] if token else conv, t_d0, c_d0, K,
                             kind)
        self.states = new_states
        extra_dirty = set(st.region_ids) | {n.id for n in st.exit_plan}
        passes_base = K * (1 + (1 if st.exit_plan else 0))
        return passes_base, iters, rows, conv, extra_dirty

    def _dispatched(self, token, t_d0: float, c_d0: float, K: int,
                    kind: str) -> None:
        """Traced tail of a macro-tick dispatch: the ``device_dispatch``
        span (launch wall and CPU on the dispatching thread) and the
        completion token's hand-over to the device watcher."""
        t_d1 = time.perf_counter()
        _trace.evt("device_dispatch", t_d0, t_d1 - t_d0,
                   args=_trace.with_win(
                       {"kind": kind, "ticks": K,
                        "device": self.device_label,
                        "cpu_s": _trace.cpu_s(c_d0, t_d1 - t_d0)}))
        if self._watch is None:
            self._watch = _DeviceWatch(self)
        self._watch.put(token, t_d0, t_d1, K, kind)

    def _stack_feeds(self, feeds):
        """Host-side [K, C] stacking of K per-tick ingress dicts: ONE
        transfer per ingress column instead of K separate uploads. The
        upload follows the executor's ingress placement (pinned device,
        or sharded capacity axis on the mesh subclass)."""
        place = self._ingress_placement()

        def _up(x):
            if place is None:
                return jnp.asarray(x)
            if isinstance(place, tuple):
                from jax.sharding import NamedSharding, PartitionSpec

                mesh, axis = place
                dims = (None, axis) + (None,) * (x.ndim - 2)
                return jax.device_put(
                    x, NamedSharding(mesh, PartitionSpec(*dims)))
            return jax.device_put(x, place)

        K = len(feeds)
        stack = {}
        caps = {}
        for nid in sorted(feeds[0]):
            spec = self.graph.nodes[nid].spec
            cap = max(bucket_capacity(len(f[nid])) for f in feeds)
            caps[nid] = cap
            keys = np.zeros((K, cap), np.int32)
            weights = np.zeros((K, cap), np.int32)
            values = np.zeros((K, cap) + tuple(spec.value_shape),
                               spec.value_dtype)
            for t, f in enumerate(feeds):
                b = f[nid]
                check_weight_mass(b)   # same host-boundary guard as to_device
                n = len(b)
                if n:
                    keys[t, :n] = b.keys.astype(np.int64)
                    weights[t, :n] = b.weights
                    values[t, :n] = np.asarray(b.values).reshape(
                        (n,) + tuple(spec.value_shape))
            stack[nid] = DeviceDelta(_up(keys), _up(values), _up(weights))
        return stack, caps

    def _build_fixpoint(self, plan, caps, max_iters):
        """Pick the fused delta-vector program when the region's operator
        chain is declared linear; otherwise the row-based while_loop.
        Returns None (and disables fixpoint fusion) when neither fits."""
        from reflow_tpu.executors.fixpoint import FixpointProgram
        from reflow_tpu.executors.linear_fixpoint import (
            LinearFixpointProgram, analyze_linear)

        prog = None
        if self.linear_fixpoint and not self._linear_unfit:
            if self._linear_structure is None:
                self._linear_structure = analyze_linear(
                    self.graph, self._fx_structure)
                self._linear_unfit = self._linear_structure is None
            if self._linear_structure is not None:
                try:
                    prog = LinearFixpointProgram(
                        self, plan, caps, max_iters,
                        structure=self._fx_structure,
                        linear=self._linear_structure)
                except ValueError:
                    # shapes don't fit the fused-f32 representation; use
                    # the row-based program below
                    self._linear_structure = None
                    self._linear_unfit = True
        if prog is None:
            try:
                prog = FixpointProgram(self, plan, caps, max_iters,
                                       structure=self._fx_structure)
            except ValueError:
                self._fx_unsupported = True
        self.fixpoint_engine = None if prog is None else type(prog).__name__
        return prog

    def materialize(self, batch) -> DeltaBatch:
        if isinstance(batch, DeviceDelta):
            self.materialize_count += 1
            return to_host(batch)
        return batch

    def update_params(self, node: Node, params) -> None:
        """Swap a params-bearing Map's parameter pytree in place.

        Because params are program *arguments* (op state), this triggers
        no recompilation — the next tick simply runs with the new values.
        """
        if node.id not in self.states or "params" not in self.states[node.id]:
            raise GraphError(f"{node} holds no params state")
        fresh = {
            "params": jax.tree.map(lambda x: jnp.array(x, copy=True), params)}
        if self.device is not None:
            fresh = jax.device_put(fresh, self.device)
        self.states[node.id] = fresh

    def on_states_replaced(self) -> None:
        """Checkpoint restore swapped the state tree: drop the sorted-arena
        CSR caches. The (gen, rcount) validity predicate cannot detect a
        lineage swap whose counters line up (two histories can share a
        (gen, rcount) pair over different arena contents), so restore must
        invalidate explicitly — the next loop tick rebuilds in-program."""
        self._csr_cache.clear()

    def refresh_minmax(self, node: Node, batch: DeltaBatch) -> None:
        """Host-triggered latch refresh for a buffered min/max Reduce
        (ROADMAP r3 #3): ``batch`` replays the FULL live multiset of
        every key it mentions; those keys' candidate buffers rebuild
        from it and the monotone overflow latches reset. Pure
        maintenance — the aggregate cannot change (a contradicting
        replay sets the sticky error instead). Call between ticks, from
        the same host thread that ticks (node validation lives in the
        scheduler wrapper — the one call site)."""
        d = to_device(batch, node.inputs[0].spec, device=self.device)
        K = node.inputs[0].spec.key_space
        sig = ("mmrefresh", node.id, d.capacity)
        fn = self._cache.get(sig)
        if fn is None:
            op, oshape, odt = node.op, tuple(node.spec.value_shape), \
                node.spec.value_dtype

            def refresh_fn(st, dd):
                return minmax_refresh_core(op, K, oshape, odt, st, dd)

            fn = self._cache[sig] = jax.jit(refresh_fn, donate_argnums=0)
        self.states[node.id] = fn(self.states[node.id], d)

    def check_errors(self) -> None:
        # one batched device_get for all sticky flags: every join and
        # min/max reducer carries an 'error' leaf, and per-leaf bool()
        # round trips serialize
        flagged = [(nid, st["error"]) for nid, st in self.states.items()
                   if isinstance(st, dict) and "error" in st]
        if not flagged:
            return
        vals = jax.device_get([e for _, e in flagged])
        for (nid, _), v in zip(flagged, vals):
            if v:
                node = self.graph.nodes[nid]
                raise RuntimeError(f"{node}: {self._error_reason(node)}")

    @staticmethod
    def _error_reason(node: Node) -> str:
        if (node.kind == "op" and node.op.kind == "reduce"
                and node.op.how in ("min", "max")):
            return ("device min/max error: retraction churn exhausted a "
                    "key's candidate buffer (the bounded exactness window "
                    "— raise Reduce(candidates=...)); this tick's state "
                    "is invalid — re-run on the CPU executor or widen "
                    "the buffer")
        if node.kind == "op" and node.op.kind == "join":
            return _join.ERROR_REASON
        return ("sticky device error flag set (sparse-route overflow: key "
                "skew exceeded the ROUTE_SLACK per-destination budget); "
                "this tick's state is invalid — raise the delta capacity "
                "or rebalance the key space")

    def read_table(self, node: Node):
        st = self.states.get(node.id)
        if st is None:
            raise KeyError(f"{node} holds no materialized state")
        if node.op.kind == "reduce":
            if "error" in st and bool(st["error"]):
                raise RuntimeError(f"{node}: {self._error_reason(node)}")
            has = np.asarray(st["emitted_has"])
            vals = np.asarray(st["emitted"])
            keys = np.nonzero(has)[0]
            return {int(k): vals[k] if vals.ndim > 1 else vals[k].item()
                    for k in keys}
        if node.op.kind == "join":
            if "error" in st and bool(st["error"]):
                raise RuntimeError(f"{node}: {self._error_reason(node)}")
            if _join.layout_of(st) == "multiset":
                raise KeyError(
                    f"{node}: a multiset-left join has no unique left "
                    f"table to read; attach a sink to observe its output")
            lw = np.asarray(st["lw"])
            lval = np.asarray(st["lval"])
            keys = np.nonzero(lw > 0)[0]
            return {int(k): lval[k] if lval.ndim > 1 else lval[k].item()
                    for k in keys}
        if node.op.kind == "knn":
            has = np.asarray(st["em_has"])
            rows = np.asarray(st["emitted"])
            return {int(q): rows[q] for q in np.nonzero(has)[0]}
        raise KeyError(f"{node} ({node.op.kind}) has no table to read")

    def _track_arena(self, plan, ingress_caps: Dict[int, int]):
        """Static per-tick capacity sanity for Join arenas
        (:func:`arena.propagate_plan_caps`; the dynamic high-water check
        is the compiled program's): rejects one tick's right-delta
        capacity exceeding the whole (per-shard) arena. ``ingress_caps``
        maps seeded node ids (sources, loops, fixpoint boundary
        producers) to their delta capacities. Returns the per-node
        capacities it found, for ``ArenaRoom.make``."""
        return propagate_plan_caps(plan, ingress_caps, self._arena_divisor,
                                   self._room.joins)

    # -- trace & compile one pass program ----------------------------------

    def _lower(self, node: Node, state, ins):
        """Per-node lowering hook (sharded subclass swaps in shard-aware
        keyed-op kernels; the pass traversal itself is shared)."""
        return lower_node(node, state, ins)

    def _build(self, plan: List[Node]):
        # the state pytree is donated: every tick would otherwise copy the
        # full arena + dense tables (VERDICT r2: multi-GB copies per tick
        # were a prime suspect for the streaming-mode collapse). The caller
        # contract is run_pass's: old state refs are dropped immediately.
        return jax.jit(self.build_pass_fn(plan), donate_argnums=0)

    def build_pass_fn(self, plan: List[Node], extra_egress: Sequence[int] = ()):
        """The pure, jittable pass program: ``(states, ingress) -> (states',
        egress)`` over DeviceDelta pytrees. Exposed un-jitted so callers
        (``__graft_entry__``, the sharded executor) can wrap it with their
        own ``jax.jit`` / sharding annotations.

        ``extra_egress`` adds node ids whose outputs the program must also
        return — the stage-boundary handoff for topo-partitioned execution
        (parallel/topo.py)."""
        graph = self.graph
        sink_inputs = [(s.inputs[0].id, s.id) for s in graph.sinks]
        back_edges = [(l.back_input.id, l.id) for l in graph.loops
                      if l.back_input is not None]
        extra = tuple(extra_egress)

        def pass_fn(states, ingress):
            # ingress seeds *any* node's output (sources/loops in the normal
            # tick; boundary producers in the fixpoint exit pass; stage
            # boundaries under topo-partitioning) — seeded nodes are not
            # recomputed
            outs: Dict[int, DeviceDelta] = dict(ingress)
            new_states = dict(states)
            for node in plan:
                if node.id in outs or node.kind in ("source", "loop"):
                    continue
                if node.kind == "sink":
                    continue
                ins = [outs.get(i.id) for i in node.inputs]
                if all(x is None for x in ins):
                    continue
                # absent inputs stay None: lowerings skip the corresponding
                # work entirely (trace-static), e.g. a Join with no left
                # delta never sweeps its arena
                out, st = self._lower(node, new_states.get(node.id), ins)
                if st is not None:
                    new_states[node.id] = st
                outs[node.id] = out
            egress: Dict[int, DeviceDelta] = {}
            for src_id, sink_id in sink_inputs:
                if src_id in outs:
                    egress[sink_id] = outs[src_id]
            for back_id, loop_id in back_edges:
                if back_id in outs:
                    egress[loop_id] = outs[back_id]
            for nid in extra:
                if nid in outs:
                    egress[nid] = outs[nid]
            return new_states, egress

        return pass_fn
