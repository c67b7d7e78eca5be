"""pagerank-1m: incremental PageRank, 100 000 nodes / 1 000 000 edges,
1 % edge churn per batch. Data, reference and comparison; the graph is
the program's (``reflow_tpu.workloads.pagerank.build_graph``).

``WebGraph``, the churn, ``reference_ranks`` and ``ranks_to_array`` are
copied from ``reflow_tpu/workloads/pagerank.py``, the arena sizing from
its ``churn_arena_capacity``, so that later changes to that file do not
move the yardstick. Differences from the originals: the out-degree
is computed once (rewiring preserves it; the original recomputes it over
all edges for every batch), and churn is drawn per lane from that lane's
own edges, so lanes never touch the same edge and their batches commute.
"""

from __future__ import annotations

from types import SimpleNamespace

import numpy as np

from common import Check, Minted, bucket_capacity, pad_batch
from reflow_tpu.delta import DeltaBatch


class Stream:
    """The graph and its churn, both from ``--seed``. NumPy only.

    ``WebGraph.random`` draws sources uniformly and destinations by a
    popularity law (``numpy`` ``power(0.3)``). Here both are that law's
    expectation, dealt out in a seeded order: every node has the mean
    out-degree, the destinations are the law's ``edges`` evenly spaced
    quantiles, and ``--seed`` deals sources, destinations and node
    labels. Every seed wires a different graph over the same degree
    sequences, so the seed changes the topology and not the amount of
    work. Independent draws move the largest hub's size and its
    out-degree (6 to 13 over six seeds), with them the size of the
    contributions it sends and the passes a tick needs to settle them
    under an absolute ``tol``: 6 % of ``rows_per_s`` between seeds."""

    source = "edges"

    def __init__(self, cfg: dict, seed: int, lanes: int):
        self.cfg = cfg
        self.lanes = lanes
        n, e = cfg["nodes"], cfg["edges"]
        rng = np.random.default_rng([seed, 0])
        self.n = n
        self.src = rng.permutation(np.arange(e, dtype=np.int64) % n)
        # power(a) has the distribution function x**a: quantile u**(1/a)
        u = (np.arange(e) + 0.5) / e
        popular = (n * u ** (1.0 / 0.3)).astype(np.int64) % n
        self.dst = rng.permutation(n)[popular][rng.permutation(e)]
        deg = np.zeros(n, np.int64)
        np.add.at(deg, self.src, 1)
        self.deg = deg
        self.inv = (1.0 / deg[self.src]).astype(np.float32)
        self.dst0 = self.dst.copy()
        self.m = max(1, int(e * cfg["churn_fraction"]))
        self.rngs = [np.random.default_rng([seed, 1, lane])
                     for lane in range(lanes)]
        self.own = [np.arange(lane, e, lanes) for lane in range(lanes)]

    def _rows(self, idx: np.ndarray, weight: int) -> DeltaBatch:
        vals = np.stack([self.dst[idx].astype(np.float32), self.inv[idx]],
                        axis=-1)
        return DeltaBatch(self.src[idx].copy(), vals,
                          np.full(len(idx), weight, dtype=np.int64))

    def load(self):
        n, d = self.n, self.cfg["damping"]
        teleport = DeltaBatch(np.arange(n, dtype=np.int64),
                              np.full(n, 1.0 - d, dtype=np.float32),
                              np.ones(n, dtype=np.int64))
        edges = self._rows(np.arange(len(self.src)), 1)
        return [[("teleport", teleport, "load/teleport"),
                 ("edges", edges, "load/edges")]]     # one tick

    def next(self, lane: int) -> Minted:
        """Rewire ``m`` of this lane's edges (out-degree preserving):
        one retraction and one insertion per edge."""
        rng, own = self.rngs[lane], self.own[lane]
        idx = own[rng.choice(len(own), size=self.m, replace=False)]
        retract = self._rows(idx, -1)
        new = rng.integers(0, self.n, self.m)
        self.dst[idx] = new
        insert = self._rows(idx, 1)
        delta = DeltaBatch.concat([retract, insert])
        rows = len(delta)
        return Minted(pad_batch(delta, self.cfg["pad_rows"]), rows,
                      (idx, new))


class Reference:
    """The graph as it stands after the applied batches, and its ranks by
    plain power iteration in NumPy: independent of the program."""

    def __init__(self, stream: Stream):
        self.s = stream
        self.dst = stream.dst0.copy()

    def apply(self, ref) -> None:
        idx, new = ref
        self.dst[idx] = new

    def expected(self, precision: str = "float64", iters: int = 200,
                 tol: float = 1e-8) -> np.ndarray:
        """``precision="bfloat16"`` is the control: ranks and the
        accumulated contributions are rounded to bfloat16 every
        iteration, the nearest precision below the float32 the
        configuration states. Rounding after a float32 accumulation is
        the mildest form of it."""
        s, d = self.s, self.s.cfg["damping"]
        n = s.n
        if precision == "float64":
            hold = lambda x: x                              # noqa: E731
        elif precision == "bfloat16":
            import ml_dtypes
            hold = lambda x: x.astype(np.float32).astype(   # noqa: E731
                ml_dtypes.bfloat16).astype(np.float64)
        else:
            raise ValueError(precision)
        inv = np.where(s.deg > 0, 1.0 / np.maximum(s.deg, 1), 0.0)
        r = np.ones(n, np.float64)
        for _ in range(iters):
            contrib = hold(np.bincount(self.dst, weights=r[s.src] * inv[s.src],
                                       minlength=n))
            r_new = hold((1.0 - d) + d * contrib)
            done = np.abs(r_new - r).max() < tol
            r = r_new
            if done:
                break
        return r


def build(cfg: dict):
    """The deployment's graph, from the program; arena sized for live
    rows plus churn headroom as ``workloads.pagerank.churn_arena_capacity``
    does."""
    from reflow_tpu.workloads import pagerank

    churn_cap = bucket_capacity(
        2 * int(cfg["churn_fraction"] * cfg["edges"]) + 2)
    arena = bucket_capacity(cfg["edges"]) + 8 * churn_cap
    pr = pagerank.build_graph(cfg["nodes"], damping=cfg["damping"],
                              tol=cfg["tol"], arena_capacity=arena)
    return SimpleNamespace(graph=pr.graph,
                           sources={"teleport": pr.teleport,
                                    "edges": pr.edges},
                           rank=pr.new_rank)


def read_state(cfg: dict, dep, sched) -> np.ndarray:
    """Dense rank vector from the served table; a missing key holds the
    teleport floor ``1 - damping`` (a node with no in-edges)."""
    out = np.full(cfg["nodes"], 1.0 - cfg["damping"])
    for k, v in sched.read_table(dep.rank).items():
        out[int(k)] = float(v)
    return out


def rel_err(got: np.ndarray, ref: np.ndarray) -> float:
    return float((np.abs(got - ref) / np.maximum(np.abs(ref), 1.0)).max())


def compare(cfg: dict, got: np.ndarray, expected: np.ndarray):
    """One number: ``max |got - ref| / max(|ref|, 1)``.

    The limit is the configuration's ``rank_rel_err_limit``, set from
    readings (PERF.md section 2 has them): three times the largest a
    sound run of the program gave over the seeds tried at the cell's 256
    batches, 9.63e-4, and a third of the smallest the bfloat16 control
    gave, 8.79e-3. ``tol / (1 - damping)`` = 6.67e-4, the distance two
    tol-converged fixpoints can lie apart, is not a usable limit:
    tol-suppressed changes add up over a run's 272 ticks. Ranks average
    1.0 and reach the thousands on this graph, so ranks or accumulators
    held in bfloat16 (a relative rounding step of 2**-9 = 2.0e-3) land
    near 1e-2."""
    limit = cfg["rank_rel_err_limit"]
    err = rel_err(got, expected)
    ok = bool(np.isfinite(got).all()) and err < limit
    return [Check("rank_max_rel_err", err, limit, ok)]
