"""sssp-graph500: Graph500's kernel 3, single-source shortest paths, as
a standing query over a Kronecker graph that grows. Data, reference and
comparison; the graph is the program's
(``reflow_tpu.workloads.sssp.build_graph``: ``Join(dist, edges)`` ->
``GroupBy(dst)`` -> ``Reduce('min')`` -> ``close_loop``).

The dataset, written down from memory (no network here; ``assumed`` in
the ``.json`` says what is mine). Graph500 3.0's generator, as its
Octave reference ``kronecker_generator`` has it: ``M = edgefactor x
2^scale`` edge tuples; every tuple draws one quadrant of the initiator
``[[A, B], [C, D]] = [[.57, .19], [.19, .05]]`` per bit of the vertex
number (``ii_bit = rand > A + B``; ``jj_bit = rand > C / (C + D)`` where
``ii_bit`` else ``> A / (A + B)``), a weight uniform on the unit
interval, then the vertex labels and the edge list are each permuted at
random. The list keeps its self-loops and duplicate edges ("may be
ignored in the subsequent kernels but must be included in the edge list
provided to the kernel"): a self-loop never improves a distance and a
duplicate is one more candidate, so they are sent as they come. The
graph is undirected: a tuple is two rows, ``u -> v`` and ``v -> u``.

What is the dataset's and what is the seed's. A published dataset is
a fixed file, so structure, weights, edge order and root come from the
configuration's ``dataset_seed`` and are the same in every run;
``--seed`` deals the vertex labels (the generator's own last step but
one) and the order of the rows inside a batch. A seed then changes every
key on the wire and in every table, and not the passes a tick needs.

``kronecker`` lives here and nowhere in the program: the generator is
the yardstick's (``chip_smoke.py`` and the tests load it from this
file).

History and stream: the dataset's first half is loaded in set-up, all
but its last batch in one tick through ``push`` / ``tick`` with the
root's seed row beside it (one fixpoint from scratch), its last batch
through the served path as the warm-up; the second half is the stream,
``stream_batches`` batches in the dataset's order.
"""

from __future__ import annotations

from types import SimpleNamespace

import numpy as np

from common import Check, Minted
from reflow_tpu.delta import DeltaBatch


def kronecker(scale: int, edgefactor: int, initiator, dataset_seed: int,
              quantum_log2: int):
    """``(u, v, w)`` of the dataset's ``edgefactor x 2^scale`` tuples in
    the dataset's own labels and order. Weights are ``(1 + floor(r x
    2^q)) x 2^-q``, uniform over the ``2^q`` multiples of ``2^-q`` in
    (0, 1]: 0 is outside the min-plus contract (a zero-weight cycle
    never quiesces), and sums of such weights are exact in float32 up to
    2^(24 - q), so the comparison can be exact."""
    a, b, c, d = initiator
    rng = np.random.default_rng([dataset_seed, scale])
    m = edgefactor << scale
    u = np.zeros(m, np.int64)
    v = np.zeros(m, np.int64)
    for bit in range(scale):
        ii = rng.random(m) > a + b
        jj = rng.random(m) > np.where(ii, c / (c + d), a / (a + b))
        u += ii.astype(np.int64) << bit
        v += jj.astype(np.int64) << bit
    q = 1 << quantum_log2
    w = (1.0 + np.floor(rng.random(m) * q)) / q
    labels = rng.permutation(1 << scale)
    order = rng.permutation(m)
    return labels[u][order], labels[v][order], w[order]


class Stream:
    """The dataset, its history and its stream. NumPy only."""

    source = "edges"

    def __init__(self, cfg: dict, seed: int, lanes: int):
        if lanes != 1:
            raise SystemExit("sssp-graph500: the stream is one lane (the "
                             "dataset's edge order is the order of "
                             "application)")
        self.cfg = cfg
        self.n = 1 << cfg["scale"]
        u, v, w = kronecker(cfg["scale"], cfg["edgefactor"],
                            cfg["initiator"], cfg["dataset_seed"],
                            cfg["weight_quantum_log2"])
        self.m = len(u)
        self.half = self.m // 2
        self.batch = self.half // cfg["stream_batches"]   # tuples a batch
        if self.batch * cfg["stream_batches"] != self.half:
            raise SystemExit("stream_batches must divide half the dataset")
        deg = (np.bincount(u[:self.half], minlength=self.n)
               + np.bincount(v[:self.half], minlength=self.n))
        rng = np.random.default_rng([seed, 0])
        deal = rng.permutation(self.n)        # --seed deals the labels
        self.u, self.v, self.w = deal[u], deal[v], w
        self.root = int(deal[int(np.argmax(deg))])
        self.rng = np.random.default_rng([seed, 1])
        #: tuples [0, at) are in: the load takes all of the first half
        #: but its last batch, which is the first ``next``
        self.at = self.half - self.batch

    def _rows(self, lo: int, hi: int, order=None) -> DeltaBatch:
        u, v, w = self.u[lo:hi], self.v[lo:hi], self.w[lo:hi]
        keys = np.concatenate([u, v])
        vals = np.stack([np.concatenate([v, u]).astype(np.float32),
                         np.concatenate([w, w]).astype(np.float32)],
                        axis=1)
        if order is not None:
            keys, vals = keys[order], vals[order]
        return DeltaBatch(keys, vals, np.ones(len(keys), np.int64))

    def load(self):
        seed_row = DeltaBatch(np.array([self.root], np.int64),
                              np.zeros(1, np.float32), np.ones(1, np.int64))
        # edges first, the seed row last, in ONE tick: phase A appends
        # the edges and seeds the root, the loop then settles the
        # history from scratch once
        return [[("edges", self._rows(0, self.at), "load/edges"),
                 ("seeds", seed_row, "load/seed")]]

    def next(self, lane: int) -> Minted:
        lo, hi = self.at, self.at + self.batch
        if hi > self.m:
            raise SystemExit("sssp-graph500: the dataset is spent; a mix "
                             "may mint stream_batches batches and one "
                             "warm-up")
        self.at = hi
        delta = self._rows(lo, hi, self.rng.permutation(2 * self.batch))
        return Minted(delta, self.batch, (lo, hi))


class Reference:
    """Bellman-Ford in NumPy over every edge tuple that was sent:
    independent of the program. float64; the weights are multiples of
    ``2^-q`` and the distances far under ``2^(24 - q)``, so float64 and
    a sound float32 agree to the bit."""

    def __init__(self, stream: Stream):
        self.s = stream
        self.sent = np.zeros(stream.m, np.bool_)
        self.sent[:stream.half - stream.batch] = True       # the load

    def apply(self, ref) -> None:
        lo, hi = ref
        self.sent[lo:hi] = True

    def expected(self, precision: str = "float64") -> dict:
        """``precision="bfloat16"`` is the control: every relaxation's
        sum ``dist[u] + w`` is rounded to bfloat16, the nearest
        precision below the float32 the configuration states."""
        if precision == "float64":
            hold = lambda x: x                              # noqa: E731
        elif precision == "bfloat16":
            import ml_dtypes
            hold = lambda x: x.astype(np.float32).astype(   # noqa: E731
                ml_dtypes.bfloat16).astype(np.float64)
        else:
            raise ValueError(precision)
        s = self.s
        idx = np.flatnonzero(self.sent)
        src = np.concatenate([s.u[idx], s.v[idx]])
        dst = np.concatenate([s.v[idx], s.u[idx]])
        w = np.concatenate([s.w[idx], s.w[idx]])
        order = np.argsort(dst, kind="stable")
        src, dst, w = src[order], dst[order], w[order]
        starts = np.flatnonzero(np.r_[True, dst[1:] != dst[:-1]])
        heads = dst[starts]
        dist = np.full(s.n, np.inf)
        dist[s.root] = 0.0
        for _ in range(s.n):
            best = np.minimum.reduceat(hold(dist[src] + w), starts)
            new = dist.copy()
            new[heads] = np.minimum(new[heads], best)
            if np.array_equal(new, dist):
                break
            dist = new
        return {"dist": dist, "errors": 0, "unquiesced": 0}


def build(cfg: dict):
    """The deployment's graph, from the program. Arena, key space and
    candidate buffers are sized for the whole dataset: two rows a tuple,
    nothing regrows inside a window. A program whose row fixpoint keeps
    no counters cannot be held to the configuration's guarantee (every
    tick quiesces: its ``unquiesced`` counter) and is refused here, at
    once."""
    from reflow_tpu.executors.lowerings import OP_COUNTERS
    from reflow_tpu.workloads import sssp

    if "loop" not in OP_COUNTERS:
        raise SystemExit("sssp-graph500: this program's row fixpoint "
                         "keeps no `unquiesced` counter; the "
                         "configuration cannot be served by it")
    sg = sssp.build_graph(1 << cfg["scale"],
                          arena_capacity=2 * (cfg["edgefactor"]
                                              << cfg["scale"]),
                          candidates=cfg["candidates"])
    relax = next(n for n in sg.graph.nodes if n.name == "relax")
    return SimpleNamespace(graph=sg.graph,
                           sources={"edges": sg.edges, "seeds": sg.seeds},
                           best=sg.best, loop=sg.dist, relax=relax)


def read_state(cfg: dict, dep, sched) -> dict:
    """The served distances (the min-Reduce's table; unreachable =
    infinity), what the operators say of
    themselves (the join's and the minimum's sticky ``error``), and the
    fixpoint program's cumulative device counter ``unquiesced``: ticks
    whose loop stopped at ``max_iters`` with deltas still in flight."""
    st = sched.executor.states
    best = st[dep.best.id]
    dist = np.where(np.asarray(best["emitted_has"]),
                    np.asarray(best["emitted"], np.float64), np.inf)
    errors = sum(int(bool(np.asarray(st[x.id]["error"])))
                 for x in (dep.relax, dep.best))
    unquiesced = int(np.asarray(st[dep.loop.id]["counters"])[2])
    return {"dist": dist, "errors": errors, "unquiesced": unquiesced}


def compare(cfg: dict, got: dict, expected: dict):
    """Exact: a vertex is reached in both tables or in neither, and
    every reached vertex's distance is the reference's to the bit (the
    weights' quantum makes float32 sums exact; ``kronecker``). The
    guarantees beside them: every tick quiesced, no operator latched
    its error."""
    g, w = got["dist"], expected["dist"]
    fin_g, fin_w = np.isfinite(g), np.isfinite(w)
    reach = int(np.count_nonzero(fin_g != fin_w))
    both = fin_g & fin_w
    wrong = int(np.count_nonzero(g[both] != w[both]))
    unq, ops = got["unquiesced"], got["errors"]
    return [Check("reach_mismatch", float(reach), 0.0, reach == 0),
            Check("dist_mismatches", float(wrong), 0.0, wrong == 0),
            Check("ticks_not_quiesced", float(unq), 0.0, unq == 0),
            Check("operator_errors", float(ops), 0.0, ops == 0)]
