"""knn-1m768: the k-NN re-index. Standing top-k queries over a corpus of
int8 embeddings that embedder workers keep re-embedding. Data, reference
and comparison; the graph is ``reflow_tpu.workloads.knn.build_graph``'s.

The corpus is a mixture: ``clusters`` topic centres drawn uniformly on
the sphere, and every document (and every standing query) is its topic's
centre plus noise of the same expected length (``cluster_noise`` =
E|noise|^2 / |centre|^2 = 1; independent components, each uniform over
255 evenly spaced levels about 0, which over 768 dimensions is as good
as Gaussian and costs a byte a draw where a normal costs ~16 ns: the
corpus is minted in two processes in every run), normalised. Two
documents of one topic then have cosine 0.5 +- 0.03 and two of different
topics 0 +- 0.036, which is what sentence and product embeddings look
like (neighbours at 0.5 - 0.9, strangers near 0). With independent
Gaussians instead, every cosine would be 0 +- 0.036 and the top-16 of a
million would be the tail of that noise, separated by less than the
arithmetic's error: a ranking no reference could be compared with.

What is sent is what ``workloads.knn.quantize_int8`` makes of a unit
vector: ``round(u * 127)``, one byte a dimension (copied here; nothing of
the program is imported by the data or the reference). An update is a
retraction row carrying the old int8 vector and an insertion row of the
same id carrying the new one; each lane owns the ids ``id % lanes ==
lane``, so lanes' batches commute.
"""

from __future__ import annotations

from types import SimpleNamespace

import numpy as np

from common import Check, Minted
from reflow_tpu.delta import DeltaBatch

_SCALE = 127.0
_REF_BLOCK = 65536          # corpus rows scored per matmul
_REF_MARGIN = 8             # candidates kept beyond k before the exact pass
_NEG = float(np.finfo(np.float32).min)


def _quantize(u: np.ndarray) -> np.ndarray:
    """Rows (not all zero) -> int8 wire form: normalise, scale by 127,
    round to nearest. Works in place: ``u`` is spent."""
    u *= np.float32(_SCALE) / np.linalg.norm(u, axis=1, keepdims=True)
    return np.rint(u, out=u).astype(np.int8)


def _bf16(x: np.ndarray) -> np.ndarray:
    """float32 rounded to the nearest bfloat16 (ties to even), kept as
    float32: the upper 16 bits."""
    b = np.ascontiguousarray(x, np.float32).view(np.uint32)
    b = (b + np.uint32(0x7FFF) + ((b >> np.uint32(16)) & np.uint32(1))
         ) & np.uint32(0xFFFF0000)
    return b.view(np.float32)


def _zipf_ranks(rng, n: int, size: int) -> np.ndarray:
    """``n`` ranks in ``[0, size)`` by Zipf's law with exponent 1, in its
    continuous form (``tfidf-wiki``'s ``_zipf_words``)."""
    r = np.exp(rng.random(n) * np.log(size + 1.0)).astype(np.int64) - 1
    return np.minimum(r, size - 1)


def _first_unique(xs: np.ndarray, n: int, taken=None) -> np.ndarray:
    """The first ``n`` distinct values of ``xs`` in order of appearance,
    leaving out those in ``taken``."""
    _, first = np.unique(xs, return_index=True)
    out = xs[np.sort(first)]
    if taken is not None:
        out = out[~np.isin(out, taken)]
    return out[:n]


class Stream:
    """The corpus, the standing queries and the re-embedding batches,
    from the seed. NumPy only."""

    source = "docs"

    def __init__(self, cfg: dict, seed: int, lanes: int):
        self.cfg, self.lanes = cfg, lanes
        self.dim, self.slots = cfg["dim"], cfg["doc_slots"]
        b = cfg["batch"]
        self.n_upd, self.n_ins, self.n_del = (b["updates"], b["inserts"],
                                              b["deletes"])
        if self.n_ins != self.n_del:
            raise ValueError("inserts != deletes: the live count would drift")
        if (cfg["corpus"] % lanes or self.slots % lanes
                or cfg["corpus"] + lanes * self.n_ins > self.slots):
            raise ValueError("corpus and doc_slots must divide by lanes, "
                             "with free ids for a batch's inserts")
        if cfg["queries"] > cfg["clusters"]:
            raise ValueError("each standing query has a topic of its own")
        self._rng = np.random.default_rng([seed, 0])
        self.rngs = [np.random.default_rng([seed, 1, lane])
                     for lane in range(lanes)]
        # a noise component is an integer uniform in -127 .. 127 (variance
        # (255^2 - 1) / 12) times this: variance cluster_noise / dim
        self.noise_unit = np.float32(np.sqrt(
            cfg["cluster_noise"] / self.dim * 12.0 / (255.0 ** 2 - 1.0)))
        self.centres = None       # [clusters, dim] float32 unit rows
        self.vecs = None          # [slots, dim] int8: the corpus as sent
        self.cluster_of = None    # [slots] int32: a document's topic
        self.queries = None       # [queries, dim] float32 as sent
        self.order = []           # lane -> its live ids, by Zipf rank
        self.free = []            # lane -> its ids that are not live

    def _embed(self, rng, clusters: np.ndarray) -> np.ndarray:
        """One float32 vector a row: the topic's centre plus noise."""
        x = rng.integers(-127, 128, (len(clusters), self.dim),
                         dtype=np.int8).astype(np.float32)
        x *= self.noise_unit
        x += self.centres[clusters]
        return x

    def load(self):
        """The queries, then the corpus in equal ticks of at most
        ``load_rows_per_tick`` rows (one program shape), as rounds of
        ``(source, batch, batch id)``."""
        cfg, rng = self.cfg, self._rng
        n, lanes = cfg["corpus"], self.lanes
        c = rng.standard_normal((cfg["clusters"], self.dim),
                                dtype=np.float32)
        self.centres = c / np.linalg.norm(c, axis=1, keepdims=True)
        q_topics = rng.permutation(cfg["clusters"])[:cfg["queries"]]
        q = self._embed(rng, q_topics)
        self.queries = (q / np.linalg.norm(q, axis=1, keepdims=True)
                        ).astype(np.float32)
        self.cluster_of = np.full(self.slots, -1, np.int32)
        self.cluster_of[:n] = rng.integers(0, cfg["clusters"], n)
        self.vecs = np.zeros((self.slots, self.dim), np.int8)
        parts = max(1, -(-n // cfg["load_rows_per_tick"]))
        edges = np.linspace(0, n, parts + 1).astype(np.int64)
        rounds = [[("queries", DeltaBatch(
            np.arange(cfg["queries"], dtype=np.int64), self.queries,
            np.ones(cfg["queries"], np.int64)), "load/queries")]]
        for i, (a, b) in enumerate(zip(edges[:-1], edges[1:])):
            self.vecs[a:b] = _quantize(self._embed(rng,
                                                   self.cluster_of[a:b]))
            rounds.append([("docs", DeltaBatch(
                np.arange(a, b, dtype=np.int64), self.vecs[a:b].copy(),
                np.ones(b - a, np.int64)), f"load/corpus/{i}")])
        # ids 0 .. corpus-1 start live; a lane's live ids in a seeded
        # order are its Zipf ranking, the rest of its ids are free
        for lane in range(lanes):
            mine = np.arange(lane, self.slots, lanes, dtype=np.int64)
            live = mine[mine < n]
            self.order.append(live[rng.permutation(len(live))])
            self.free.append(list(mine[mine >= n][::-1]))
        return rounds

    def next(self, lane: int) -> Minted:
        """One batch of a lane: ``updates`` re-embeddings (a retraction
        row with the old vector, then an insertion row with the new), of
        ids drawn by Zipf's law over the lane's live documents;
        ``inserts`` new documents under ids free in the lane;
        ``deletes`` deletions, uniform over the lane's live documents.
        No id takes part twice, and a deleted id is free from the next
        batch on."""
        rng, order, free = self.rngs[lane], self.order[lane], self.free[lane]
        size = len(order)
        upd_at = np.empty(0, np.int64)
        while len(upd_at) < self.n_upd:
            upd_at = _first_unique(np.concatenate(
                [upd_at, _zipf_ranks(rng, 4 * self.n_upd, size)]),
                self.n_upd)
        del_at = np.empty(0, np.int64)
        while len(del_at) < self.n_del:
            del_at = _first_unique(np.concatenate(
                [del_at, rng.integers(0, size, 2 * self.n_del)]),
                self.n_del, taken=upd_at)
        upd, gone = order[upd_at], order[del_at]
        new = np.array([free.pop() for _ in range(self.n_ins)], np.int64)
        self.cluster_of[new] = rng.integers(0, self.cfg["clusters"],
                                            self.n_ins)
        fresh = _quantize(self._embed(
            rng, self.cluster_of[np.concatenate([upd, new])]))

        rows = 2 * self.n_upd + self.n_ins + self.n_del
        keys = np.empty(rows, np.int64)
        vals = np.empty((rows, self.dim), np.int8)
        wgt = np.ones(rows, np.int64)
        u2 = 2 * self.n_upd
        keys[0:u2:2], keys[1:u2:2] = upd, upd
        vals[0:u2:2], vals[1:u2:2] = self.vecs[upd], fresh[:self.n_upd]
        wgt[0:u2:2] = -1
        keys[u2:u2 + self.n_ins] = new
        vals[u2:u2 + self.n_ins] = fresh[self.n_upd:]
        keys[u2 + self.n_ins:] = gone
        vals[u2 + self.n_ins:] = self.vecs[gone]
        wgt[u2 + self.n_ins:] = -1

        # the mirror: new vectors in place, a new document takes the
        # rank of a deleted one, the deleted ids go back to the lane
        self.vecs[upd] = fresh[:self.n_upd]
        self.vecs[new] = fresh[self.n_upd:]
        order[del_at] = new
        free[:0] = gone[::-1].tolist()
        delta = DeltaBatch(keys, vals, wgt)
        return Minted(delta, rows, delta)


class Reference:
    """The corpus table as it stands after the applied batches, kept by
    applying every sent row in order, and each standing query's top-k by
    brute force over it. Imports nothing of the program."""

    def __init__(self, stream: Stream):
        self.cfg = stream.cfg
        self.table = stream.vecs.copy()
        self.live = np.zeros(stream.slots, bool)
        self.live[:stream.cfg["corpus"]] = True
        self.queries = stream.queries

    def apply(self, ref) -> None:
        """Row by row, in order: an insertion writes the vector and sets
        the id live (whatever it was), a retraction sets it dead."""
        table, live = self.table, self.live
        for key, vec, w in zip(ref.keys.tolist(), ref.values,
                               ref.weights.tolist()):
            if w > 0:
                table[key] = vec
                live[key] = True
            elif w < 0:
                live[key] = False

    def _scored(self, precision: str):
        """Queries and a function from corpus rows to their scores, in
        the arithmetic the configuration states: a query is cast to
        bfloat16 on the wire, normalised in float32 and held in
        bfloat16; a corpus row is dequantised in bfloat16,
        ``bf16(int8 * bf16(1/127))``; products are exact and the 768 of
        them are accumulated in float32 (here in float32 blocks, and in
        float64 for the candidates that are reported). The control
        accumulates in bfloat16 instead: partial sums of 128 terms, each
        rounded to bfloat16 and added in bfloat16."""
        q = _bf16(self.queries)
        q = _bf16(q / np.sqrt(np.sum(q * q, axis=1, keepdims=True)))
        # every int8 value's dequantised form, looked up by value + 128
        lut = _bf16(np.arange(-128, 128, dtype=np.float32)
                    * _bf16(np.float32(1.0 / _SCALE)))

        def rows(ix):
            return lut[self.table[ix].astype(np.int16) + 128]

        if precision == "float32":
            return q, rows, lambda d: q @ d.T
        if precision != "bfloat16":
            raise ValueError(precision)

        def lower(d):
            acc = np.zeros((len(q), len(d)), np.float32)
            for j in range(0, q.shape[1], 128):
                acc = _bf16(acc + _bf16(q[:, j:j + 128] @ d[:, j:j + 128].T))
            return acc
        return q, rows, lower

    def expected(self, precision: str = "float32") -> dict:
        """``table`` / ``live``: the corpus; ``ids`` / ``scores``
        ``[queries, k]``: each query's top-k, best first, ties to the
        lowest id, ``-1`` / NEG where fewer than k documents are live;
        ``score_of(q, ids)``: the reference's own score of any document
        for a query (float64). ``precision="bfloat16"`` is the control:
        the nearest precision below the float32 accumulation the
        configuration states."""
        k = self.cfg["k"]
        q, rows, score = self._scored(precision)
        nq, keep = len(q), k + _REF_MARGIN
        cand_i = [np.empty((nq, 0), np.int64)]
        cand_s = [np.empty((nq, 0), np.float32)]
        for a in range(0, len(self.table), _REF_BLOCK):
            live = self.live[a:a + _REF_BLOCK]
            if not live.any():
                continue
            s = score(rows(slice(a, a + _REF_BLOCK)))
            s[:, ~live] = -np.inf
            take = min(keep, s.shape[1])
            top = np.argpartition(-s, take - 1, axis=1)[:, :take]
            cand_i.append(top + a)
            cand_s.append(np.take_along_axis(s, top, axis=1))
        cand_i = np.concatenate(cand_i, axis=1)
        cand_s = np.concatenate(cand_s, axis=1).astype(np.float64)
        q64 = q.astype(np.float64)

        def score_of(qi: int, ids: np.ndarray) -> np.ndarray:
            out = rows(ids).astype(np.float64) @ q64[qi]
            return np.where(self.live[ids], out, -np.inf)

        ids = np.full((nq, k), -1, np.int64)
        scores = np.full((nq, k), _NEG, np.float64)
        for qi in range(nq):
            ci = cand_i[qi][np.isfinite(cand_s[qi])]
            # the float32 pass only picks candidates; what is reported
            # is scored again in float64 (the control reports its own)
            cs = (score_of(qi, ci) if precision == "float32"
                  else cand_s[qi][np.isfinite(cand_s[qi])])
            best = np.lexsort((ci, -cs))[:k]
            ids[qi, :len(best)] = ci[best]
            scores[qi, :len(best)] = cs[best]
        return {"table": self.table, "live": self.live, "ids": ids,
                "scores": scores, "score_of": score_of}


def build(cfg: dict):
    """The deployment's dataflow: ``workloads.knn.build_graph`` at the
    configuration's widths and dtypes."""
    import jax.numpy as jnp

    from reflow_tpu.workloads import knn

    kg = knn.build_graph(cfg["queries"], cfg["doc_slots"], cfg["dim"],
                         cfg["k"], scan_chunk=cfg["scan_chunk"],
                         dtype=jnp.dtype(cfg["query_dtype"]),
                         doc_dtype=jnp.dtype(cfg["doc_dtype"]),
                         precision=cfg["mxu_precision"])
    return SimpleNamespace(graph=kg.graph, index=kg.index,
                           sources={"queries": kg.queries, "docs": kg.docs})


def read_state(cfg: dict, dep, sched) -> dict:
    """The device's corpus table and live mask, and the served top-k
    table, in the reference's form."""
    st = sched.executor.states[dep.index.id]
    served = sched.read_table(dep.index)
    nq, k = cfg["queries"], cfg["k"]
    ids = np.full((nq, k), -1, np.int64)
    scores = np.full((nq, k), _NEG, np.float64)
    for qi, row in served.items():
        row = np.asarray(row)
        ids[int(qi)] = row[:, 0].astype(np.int64)
        scores[int(qi)] = row[:, 1]
    return {"table": np.asarray(st["dvec"]), "live": np.asarray(st["dlive"]),
            "ids": ids, "scores": scores}


def score_limit(cfg: dict) -> float:
    """The limit on a served score's distance from the reference's.

    Both sides multiply the same bfloat16 numbers, and a product of two
    bfloat16 numbers is exact in float32, so all that can differ is the
    accumulation of ``dim`` products in float32: at most ``dim * 2^-24``
    times the sum of their magnitudes, which is at most |q| |d| <= 1.02
    for a bfloat16-rounded unit query and a dequantised int8 row. At 768
    that is 4.7e-5. A few components of a query land on the other side
    of a bfloat16 rounding where the chip's float32 ``x / sqrt(sum x^2)``
    differs from NumPy's in the last place: each moves a score by at
    most 2^-8 |q_i| |d_i| ~ 5e-6. The limit is four times the
    accumulation bound, 1.9e-4 at 768, and lies between its two
    readings at the cell's own size (PERF.md, PR 26): the program on the
    v5e reads 1e-7, the bfloat16-accumulation control 4.5e-3 - 5.5e-3."""
    return 4.0 * cfg["dim"] * 2.0 ** -24 * 1.02


def compare(cfg: dict, got: dict, expected: dict):
    limit = score_limit(cfg)
    live = expected["live"]
    rows_bad = int(np.count_nonzero(
        (got["table"] != expected["table"]).any(axis=1) & live))
    live_bad = int(np.count_nonzero(got["live"] != live))
    both = (got["ids"] >= 0) & (expected["ids"] >= 0)
    err = float(np.max(np.abs(got["scores"] - expected["scores"])[both],
                       initial=0.0))
    # a served id may differ from the reference's at a rank only where
    # the reference itself scores the two within the limit of each other
    beyond = int(np.count_nonzero((got["ids"] >= 0) != (expected["ids"] >= 0)))
    for qi, r in zip(*np.nonzero(both & (got["ids"] != expected["ids"]))):
        s = expected["score_of"](qi, got["ids"][qi, r:r + 1])[0]
        beyond += not abs(s - expected["scores"][qi, r]) <= limit
    return [
        Check("corpus_rows_mismatch", float(rows_bad), 0.0, rows_bad == 0),
        Check("live_mismatch", float(live_bad), 0.0, live_bad == 0),
        Check("topk_score_max_abs_err", err, limit, err <= limit),
        Check("topk_id_mismatch_beyond_gap", float(beyond), 0.0,
              beyond == 0),
    ]
