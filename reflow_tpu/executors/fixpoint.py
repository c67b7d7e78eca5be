"""On-device fixpoint: a whole tick as ONE compiled XLA program.

SURVEY.md §2 item 13 / §7.9 / hard part (e): the host-driven loop in
``DirtyScheduler.tick`` pays one device dispatch plus one scalar readback
*per fixpoint pass* — tens of round-trips per tick for iterative graphs
like PageRank, each a host stall on the device stream. This module
lowers the entire tick to one jit-compiled program:

    phase A   one pass over the dirty plan (source ingest; sinks outside
              loop regions emit here),
    phase B   ``lax.while_loop`` over the cyclic region with the loop
              deltas as carry and an on-device quiescence predicate
              (any live delta row left?),
    phase C   one "exit pass" over nodes strictly downstream of the
              region, fed the *telescoped* boundary deltas (see below).

Host↔device crossings per tick: ingress upload, one (iters, rows) scalar
readback, sink materialization. Nothing else.

Boundary telescoping: a consumer outside the region would, under the host
loop, receive one delta batch per pass. Those per-pass emissions of a
Reduce telescope (retract prev / insert next), so their multiset sum equals
the diff of the Reduce's emitted table before phase B vs after. We
therefore require every region-exit edge to originate at a Reduce (true of
keyed iterative graphs — the back-edge value is an aggregate), snapshot
its ``emitted`` table after phase A, and emit the table diff to the exit
pass once, after quiescence. Graphs violating the restriction fall back to
the host-driven loop (``supports_fixpoint`` returns False).

Loop-carry shapes: XLA needs the while-carry shape-stable, but a pass's
output capacity is a static function of its input capacities, so we solve
caps = f(caps) by abstract evaluation (``jax.eval_shape`` — no FLOPs, no
transfers) and pad phase A's loop deltas up to the fixed point. Divergence
(pathological graphs whose emission capacity grows without bound) falls
back to the host loop.
"""

from __future__ import annotations

import dataclasses
from typing import Dict, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp

from reflow_tpu.executors.device_delta import DeviceDelta
from reflow_tpu.executors.lowerings import _differs
from reflow_tpu.graph import FlowGraph, Node

__all__ = ["FixpointProgram", "FixpointStructure", "analyze"]

_CAP_SOLVER_ITERS = 32


@dataclasses.dataclass(frozen=True)
class FixpointStructure:
    """Static decomposition of a graph for on-device fixpoint execution."""

    loops: Tuple[Node, ...]          # loop nodes (all have back_input)
    region_ids: frozenset            # the cyclic region (includes loops)
    loop_plan: Tuple[Node, ...]      # region nodes, topo order
    boundary: Tuple[Node, ...]       # region producers with outside consumers
    exit_plan: Tuple[Node, ...]      # non-region nodes downstream of boundary


def analyze(graph: FlowGraph) -> Optional[FixpointStructure]:
    """Static feasibility analysis; None = use the host-driven loop."""
    loops = tuple(l for l in graph.loops if l.back_input is not None)
    if not loops:
        return None
    region = graph.loop_region()
    region_ids = frozenset(n.id for n in region)
    for node in region:
        if (node.kind == "op" and node.op.kind == "join"
                and node.inputs[1].id in region_ids):
            # a loop-carried right (arena) input appends rows every
            # while_loop iteration, invisibly to the host-side overflow
            # tracker — only the host-driven loop tracks those (ADVICE r1)
            return None
    boundary = []
    for node in region:
        if any(c.id not in region_ids for c, _ in graph.consumers(node)):
            boundary.append(node)
    for node in boundary:
        if node.kind != "op" or node.op.kind != "reduce":
            # only Reduce emissions telescope into a table diff
            return None
    # nodes strictly downstream of the boundary, outside the region
    downstream = set(n.id for n in boundary)
    exit_plan = []
    for node in graph.nodes:  # construction order == topo order
        if node.id in region_ids or node.id in downstream:
            continue
        if any(i.id in downstream for i in node.inputs):
            downstream.add(node.id)
            exit_plan.append(node)
    return FixpointStructure(
        loops=loops,
        region_ids=region_ids,
        loop_plan=tuple(n for n in region),
        boundary=tuple(boundary),
        exit_plan=tuple(exit_plan),
    )


def _pad_delta(d: DeviceDelta, cap: int) -> DeviceDelta:
    """Grow a delta to ``cap`` rows with weight-0 padding (trace-static)."""
    extra = cap - d.capacity
    if extra == 0:
        return d
    if extra < 0:
        raise ValueError(f"cannot shrink delta {d.capacity} -> {cap}")
    return DeviceDelta(
        keys=jnp.concatenate([d.keys, jnp.zeros((extra,), d.keys.dtype)]),
        values=jnp.concatenate(
            [d.values, jnp.zeros((extra,) + d.values.shape[1:],
                                 d.values.dtype)]),
        weights=jnp.concatenate(
            [d.weights, jnp.zeros((extra,), d.weights.dtype)]),
    )


def _abstract_delta(spec, cap: int) -> DeviceDelta:
    import numpy as np

    return DeviceDelta(
        keys=jax.ShapeDtypeStruct((cap,), jnp.int32),
        values=jax.ShapeDtypeStruct((cap,) + tuple(spec.value_shape),
                                    np.dtype(spec.value_dtype)),
        weights=jax.ShapeDtypeStruct((cap,), jnp.int32),
    )


def _solve_carry_caps(body_fn, states, structure: FixpointStructure,
                      caps: Dict[int, int]) -> Optional[Dict[int, int]]:
    """Fixed point of the loop body's capacity map (abstract eval only)."""
    specs = {l.id: l.spec for l in structure.loops}
    for _ in range(_CAP_SOLVER_ITERS):
        carry = {lid: _abstract_delta(specs[lid], c) for lid, c in caps.items()}
        _, egress = jax.eval_shape(body_fn, states, carry)
        if any(lid not in egress for lid in caps):
            return None  # a loop's back-edge produced nothing: structural bug
        new = {lid: egress[lid].keys.shape[0] for lid in caps}
        if new == caps:
            return caps
        caps = {lid: max(caps[lid], new[lid]) for lid in caps}
    return None


def _emitted_diff(snap: Tuple[jax.Array, jax.Array], state: dict,
                  node: Node) -> DeviceDelta:
    """Telescoped boundary delta: diff of a Reduce's emitted table.

    Unchanged keys keep bit-identical stored values (the lowering writes
    through where-masks), so exact inequality is the right changed-test.
    """
    em_a, has_a = snap
    em_f, has_f = state["emitted"], state["emitted_has"]
    differ = _differs(em_a, em_f, 0.0)
    ret = has_a & (~has_f | differ)
    ins = has_f & (~has_a | differ)
    K = em_a.shape[0]
    keys = jnp.arange(K, dtype=jnp.int32)
    return DeviceDelta(
        keys=jnp.concatenate([keys, keys]),
        values=jnp.concatenate([em_a, em_f]),
        weights=jnp.concatenate(
            [-ret.astype(jnp.int32), ins.astype(jnp.int32)]),
    )


def make_scan_program(tick_fn, token_fn=None):
    """K consecutive ticks fused into ONE device execution.

    ``lax.scan`` over the tick program with the K per-tick ingress
    pytrees stacked on a leading axis. Every execution carries a
    fixed per-dispatch overhead regardless of program size, so batching
    K ticks into one program amortizes it K-fold — the "macro-tick"
    streaming fast path.
    Sink-free graphs only (the caller guards): per-tick sink egress
    would otherwise need stacking and per-tick host materialization.

    The ingress stack is DONATED alongside the state pytree (the
    mega-tick queue's buffers would otherwise stay live across the whole
    window execution — one extra copy per source) and a fresh zeroed
    stack rides back out in (potentially) the same memory, so the
    persistent ingress queue can re-bind it (``run_window``) and keep
    slot-writing in place.

    ``token_fn(states)``: the twin a traced dispatch builds also returns
    its value, a small output nobody donates, for the device watcher
    (``tpu._completion_token``: it carries the operators' counters).
    """
    import jax

    def scan_fn(op_states, ing_stack):
        def body(states, ing):
            states2, sink_eg, _carry, iters, rows, conv = tick_fn(states,
                                                                  ing)
            if sink_eg:  # trace-time structural check
                raise RuntimeError("macro-tick requires a sink-free graph")
            return states2, (iters, rows, conv)

        states, ys = jax.lax.scan(body, op_states, ing_stack)
        out = (states, ys, jax.tree.map(jnp.zeros_like, ing_stack))
        return out if token_fn is None else out + (token_fn(states),)

    return jax.jit(scan_fn, donate_argnums=(0, 1))


class _MacroTickMixin:
    """Shared macro-tick entry for the two fixpoint program kinds: both
    set ``self.tick_fn`` (the unjitted tick) in ``__init__``."""

    def call_many(self, op_states, ing_stack, n_ticks: int, token_fn=None):
        """-> (states', (iters[K], rows[K], converged[K]), fresh_stack),
        and last ``token_fn(states')`` where one is given (the traced
        twin, ``make_scan_program``). ``ing_stack`` is donated;
        ``fresh_stack`` is the zeroed replacement the ingress queue
        re-binds."""
        cache = getattr(self, "_many_cache", None)
        if cache is None:
            cache = self._many_cache = {}
        key = (n_ticks, token_fn is not None)
        prog = cache.get(key)
        if prog is None:
            prog = cache[key] = make_scan_program(self.tick_fn, token_fn)
        return prog(op_states, ing_stack)


class FixpointProgram(_MacroTickMixin):
    """One compiled tick: phase A pass + while_loop + exit pass.

    Built per (dirty-plan, ingress-capacity) signature and cached by the
    executor exactly like single-pass programs.
    """

    def __init__(self, executor, plan: Sequence[Node],
                 ingress_caps: Dict[int, int], max_iters: int,
                 structure: Optional[FixpointStructure] = None):
        graph = executor.graph
        if structure is None:
            structure = analyze(graph)
        if structure is None:
            raise ValueError("graph has no on-device-fixpoint structure")
        self.structure = structure
        self.max_iters = max_iters
        self.sink_ids = [s.id for s in graph.sinks]

        full_pass = executor.build_pass_fn(list(plan))
        body_pass = executor.build_pass_fn(list(structure.loop_plan))
        exit_pass = (executor.build_pass_fn(list(structure.exit_plan))
                     if structure.exit_plan else None)

        # solve the while-carry capacity fixed point (abstract)
        specs = {l.id: l.spec for l in structure.loops}
        ingress_abstract = {
            nid: _abstract_delta(graph.nodes[nid].spec, cap)
            for nid, cap in ingress_caps.items()}
        states_abstract = jax.tree.map(
            lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype), executor.states)
        _, eg_a = jax.eval_shape(full_pass, states_abstract, ingress_abstract)
        caps0 = {
            l.id: (eg_a[l.id].keys.shape[0] if l.id in eg_a else 64)
            for l in structure.loops}
        caps = _solve_carry_caps(body_pass, states_abstract, structure, caps0)
        if caps is None:
            raise ValueError("loop-carry capacities do not stabilize")
        self.carry_caps = caps

        loops = structure.loops
        boundary = structure.boundary
        mi = max_iters
        #: where this program counts (``lowerings.OP_COUNTERS["loop"]``):
        #: the first loop's state, if the executor gave it the leaf
        count_id = loops[0].id
        n_fixed = 1 + (1 if exit_pass is not None else 0)

        def tick_fn(op_states, ingress):
            with jax.named_scope("fixpoint.phase_a"):
                states, eg_a = full_pass(op_states, ingress)
            carry = {}
            for l in loops:
                d = eg_a.get(l.id)
                if d is None:
                    d = DeviceDelta.empty(specs[l.id], caps[l.id])
                carry[l.id] = _pad_delta(d, caps[l.id])
            snaps = {n.id: (states[n.id]["emitted"],
                            states[n.id]["emitted_has"]) for n in boundary}

            def live_rows(cr):
                n = jnp.zeros((), jnp.int32)
                for d in cr.values():
                    n = n + jnp.sum((d.weights != 0).astype(jnp.int32))
                return n

            def cond(c):
                st, cr, it, rows = c
                return jnp.logical_and(it < mi, live_rows(cr) > 0)

            def body(c):
                st, cr, it, rows = c
                rows = rows + live_rows(cr)
                st2, eg = body_pass(st, cr)
                cr2 = {lid: eg[lid] for lid in cr}
                return st2, cr2, it + 1, rows

            with jax.named_scope("fixpoint.loop"):
                states, carry, iters, rows = jax.lax.while_loop(
                    cond, body, (states, carry, jnp.zeros((), jnp.int32),
                                 jnp.zeros((), jnp.int32)))
            # converged iff the carry actually went dead (distinguishes
            # "quiesced on the last allowed iteration" from "exhausted")
            converged = live_rows(carry) == 0

            eg_b = {}
            if exit_pass is not None:
                with jax.named_scope("fixpoint.exit"):
                    diffs = {n.id: _emitted_diff(snaps[n.id],
                                                 states[n.id], n)
                             for n in boundary}
                    states, eg_b = exit_pass(states, diffs)

            if "counters" in (states.get(count_id) or ()):
                # cumulative, in the state: no output, no host sync
                states = dict(states)
                states[count_id] = dict(
                    states[count_id],
                    counters=states[count_id]["counters"] + jnp.stack(
                        [n_fixed + iters, jnp.ones((), jnp.int32),
                         1 - converged.astype(jnp.int32)]))

            sink_egress = {}
            for sid in self.sink_ids:
                batches = []
                if sid in eg_a:
                    batches.append(eg_a[sid])
                if sid in eg_b:
                    batches.append(eg_b[sid])
                if batches:
                    sink_egress[sid] = tuple(batches)
            # the final carry rides out so a max_iters halt can PAUSE
            # instead of dropping in-flight loop deltas (the scheduler
            # stashes live carries as pending; all-dead when converged)
            return states, sink_egress, dict(carry), iters, rows, converged

        # donate the state pytree: ticks update arenas/tables in place
        # instead of copying them (the executor drops old refs on return)
        self.tick_fn = tick_fn
        self._fn = jax.jit(tick_fn, donate_argnums=0)

    def __call__(self, op_states, dev_ingress):
        """-> (states', {sink_id: (DeviceDelta, ...)}, {loop_id: carry},
        iters, loop_rows, converged)."""
        return self._fn(op_states, dev_ingress)
