"""Incremental single-source shortest paths: iterative Join + min-Reduce.

The min-plus analog of PageRank's sum-loop, and the graph shape that
exercises the retraction-capable device min/max (executors/lowerings.py
``minmax_core``) inside the general on-device fixpoint
(executors/fixpoint.py ``FixpointProgram``): every distance improvement
emits retract(old)/insert(new) through the min-Reduce, and edge churn
retracts relaxation candidates outright.

**Served deployment.** The benchmark's ``sssp-graph500`` configuration
(``benchmarks/configs/sssp-graph500.py``) serves this graph as Graph500's
kernel 3 over a Kronecker graph that grows: half of the dataset's edge
list loaded, the other half streamed as insert batches through
``DurableScheduler`` -> ``IngestFrontend`` -> ``RpcIngestServer``, one
tick a window, each tick its own fixpoint. That traffic is
**insert-only**, the contract under which every tick quiesces (below);
deletions need ``sched.rederive`` (:func:`repair`), which is not on the
served path. What a run counts on the device
(``TpuExecutor.op_counters()``, gauges
``sched.sssp.<node>.<counter>``; ``lowerings.OP_COUNTERS``): the
fixpoint program's ``dist.passes`` / ``ticks`` / ``unquiesced``, the
loop join's ``relax.probes`` (passes whose frontier's pairs it took
through its key-sorted view of the arena) / ``sweeps`` (passes past the
slot budget, a hub's, which read the whole arena) / ``swept_rows`` /
``pairs`` / ``left_rows``, the minimum's ``best.touched`` / ``evicted``
/ ``blocks`` / ``merged_slots``.

Graph::

    edges   source {src: [dst, weight]}
    seeds   source {node: dist}          (0.0 at the SSSP source)
    dist    loop   {node: best dist}     (unique)
    relax   Join(dist, edges, merge=[dst, d + w], )
    cands   GroupBy(dst, value d + w)
    best    Reduce('min')( Union(cands, seeds) )
    close_loop(dist, best)

Per tick the loop relaxes until no node's best distance changes — the
host-driven loop on the CPU oracle, one compiled ``lax.while_loop``
program on the TPU executor. Edge deletions retract the corresponding
relaxation candidates; the device path stays exact while each node's
candidate-distance churn fits the min-Reduce's ``candidates`` buffer and
fails loudly beyond it.

**Quiescence contract.** Distances must stay positive (min-plus
semiring). Insertion ticks always quiesce (relaxation only improves
distances, and a shortest path has at most ``n_nodes - 1`` hops). A
DELETION tick quiesces too — *unless* it disconnects a cycle from the
source: the orphaned cycle's nodes then sustain each other with
ever-growing candidate distances (the classic incremental-SSSP
invalidation problem; cf. Ramalingam–Reps-style algorithms that track
shortest-path trees to break such cycles). Because every legitimate tick
converges within ``n_nodes`` relaxation passes, running the scheduler
with ``max_loop_iters = n_nodes + 2`` (see :func:`max_loop_iters`) turns
that divergence into a cheap, sound detection: ``TickResult.quiesced``
comes back False, the loop state is NOT trustworthy, and the driver
falls back to a from-scratch rebuild (fresh scheduler over the surviving
edges) — incremental-with-fallback, demonstrated in
``tests/test_sssp.py``.
"""

from __future__ import annotations

import dataclasses
from typing import Optional

import numpy as np

from reflow_tpu.delta import DeltaBatch, Spec
from reflow_tpu.graph import FlowGraph, Node


@dataclasses.dataclass
class SsspGraph:
    graph: FlowGraph
    edges: Node
    seeds: Node
    dist: Node    # loop var
    best: Node    # the min-Reduce; read_table -> {node: distance}


def _relax_merge(k, d, vb):
    """(dist, [dst, w]) -> [dst, dist + w] (array contract, ndim branch)."""
    if getattr(vb, "ndim", 1) <= 1:
        return np.asarray([vb[0], d + vb[1]])
    import jax.numpy as jnp

    return jnp.stack([vb[:, 0], d + vb[:, 1]], axis=-1)


def build_graph(n_nodes: int, *, arena_capacity: Optional[int] = None,
                candidates: int = 16) -> SsspGraph:
    dist_spec = Spec((), np.float32, key_space=n_nodes, unique=True)
    scalar = Spec((), np.float32, key_space=n_nodes)
    edge2 = Spec((2,), np.float32, key_space=n_nodes)
    arena = arena_capacity if arena_capacity is not None else 1 << 15

    g = FlowGraph("sssp")
    edges = g.source("edges", edge2)
    seeds = g.source("seeds", scalar)
    dist = g.loop("dist", dist_spec)
    relax = g.join(dist, edges, merge=_relax_merge, spec=edge2,
                   arena_capacity=arena, name="relax")
    cands = g.group_by(relax, key_fn=lambda k, v: v[:, 0].astype("int32"),
                       value_fn=lambda k, v: v[:, 1], vectorized=True,
                       spec=scalar, name="cands")
    best = g.reduce(g.union(cands, seeds), "min", name="best",
                    spec=dist_spec, candidates=candidates)
    g.close_loop(dist, best)
    return SsspGraph(g, edges, seeds, dist, best)


def max_loop_iters(n_nodes: int) -> int:
    """The quiescence bound: a legitimate tick converges in <= n_nodes
    relaxation passes, so exceeding this proves an orphaned sustaining
    cycle (rebuild from scratch — see the module docstring)."""
    return n_nodes + 2


def edge_batch(src, dst, w, weight: int = 1) -> DeltaBatch:
    """Edge rows keyed by src with [dst, w] values; ``weight=-1``
    retracts (values must replay the inserted rows exactly)."""
    src = np.asarray(src, np.int64)
    vals = np.stack([np.asarray(dst, np.float32),
                     np.asarray(w, np.float32)], axis=1)
    return DeltaBatch(src, vals, np.full(len(src), weight, np.int64))


def seed_batch(node: int) -> DeltaBatch:
    return DeltaBatch(np.array([node], np.int64),
                      np.zeros(1, np.float32), np.ones(1, np.int64))


def affected_set(n_nodes: int, src, dst, w, dist_prev: dict,
                 del_src, del_dst, del_w) -> set:
    """Conservative affected set for a batch of edge deletions
    (Ramalingam–Reps phase 1, host-side, O(E)).

    ``dist_prev`` is the TRUSTWORTHY pre-deletion distance table;
    ``src/dst/w`` are the SURVIVING edges. A node is affected when its
    (pre-deletion) shortest path may have used a deleted edge: seed with
    each deleted edge's head whose distance was tight through it
    (``dist[v] == dist[u] + w``), then close over the shortest-path DAG
    of the surviving edges (descendants of a stale node are themselves
    suspect). Conservative — a superset only costs re-derivation work,
    never correctness.
    """
    inf = np.inf
    d = np.full(n_nodes, inf)
    for k, v in dist_prev.items():
        d[int(k)] = v
    def _tight(du, dv, ww):
        # device distances are f32: tightness must tolerate one rounding
        # (a false positive only widens the conservative superset)
        return (np.isfinite(du) & np.isfinite(dv)
                & np.isclose(dv, du + ww, rtol=1e-6, atol=1e-5))

    seeds = set()
    for u, v, ww in zip(np.asarray(del_src, np.int64),
                        np.asarray(del_dst, np.int64),
                        np.asarray(del_w, np.float64)):
        if _tight(d[u], d[v], ww):
            seeds.add(int(v))
    if not seeds:
        return set()
    src = np.asarray(src, np.int64)
    dst = np.asarray(dst, np.int64)
    w = np.asarray(w, np.float64)
    tight = _tight(d[src], d[dst], w)
    affected = set(seeds)
    frontier = list(seeds)
    # adjacency over tight (shortest-path DAG) surviving edges only
    from collections import defaultdict
    adj = defaultdict(list)
    for u, v in zip(src[tight], dst[tight]):
        adj[int(u)].append(int(v))
    while frontier:
        u = frontier.pop()
        for v in adj[u]:
            if v not in affected:
                affected.add(v)
                frontier.append(v)
    return affected


def repair(sched, sg: SsspGraph, src, dst, w, affected: set):
    """Ramalingam–Reps-style in-place repair after edge deletions
    (module docstring: the orphaned-cycle case), WITHOUT a fresh
    scheduler: ``sched.rederive`` the surviving in-edges of the affected
    set. The retraction makes every affected candidate vanish through
    the exact algebra (a shrinking wave — it quiesces even from a
    paused, divergent iteration), and the re-insertion re-derives the
    affected region from the valid boundary distances. Device work is
    proportional to the affected region's in-edges + the relaxation
    cascade — incremental, not a rebuild.

    ``src/dst/w`` are the SURVIVING edges; returns the two TickResults.
    """
    src = np.asarray(src, np.int64)
    dst = np.asarray(dst, np.int64)
    mask = np.isin(dst, np.fromiter(affected, np.int64,
                                    len(affected)))
    if not mask.any():
        raise ValueError("repair: affected set has no surviving in-edges "
                         "(nothing to re-derive — the keys are simply "
                         "unreachable; a normal tick settles that)")
    batch = edge_batch(src[mask], dst[mask], np.asarray(w)[mask])
    return sched.rederive(sg.edges, batch)


def reference_distances(n_nodes, src_arr, dst_arr, w_arr, source: int):
    """Bellman-Ford oracle -> {node: distance} for reachable nodes."""
    dist = np.full(n_nodes, np.inf)
    dist[source] = 0.0
    for _ in range(n_nodes):
        nd = dist[src_arr] + w_arr
        new = dist.copy()
        np.minimum.at(new, dst_arr, nd)
        if np.array_equal(new, dist):
            break
        dist = new
    return {int(i): float(dist[i]) for i in range(n_nodes)
            if np.isfinite(dist[i])}
