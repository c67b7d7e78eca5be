"""tfidf-wiki: streaming TF-IDF over Wikipedia-edit deltas. Data,
reference and comparison; the graph is
``reflow_tpu.workloads.tfidf.build_graph``'s, copied with one repair
(see ``build``).

The corpus has WikiText-103's shapes (Merity et al., arXiv:1609.07843,
table 1, training split: 28 475 articles, 103 227 021 tokens, 267 735
words): articles of 3 625 tokens on average over that vocabulary, words
by Zipf's law. An edit is a new revision of one article: a span of its
tokens is taken out and new words are put in its place, and what the
system is sent is the change in the article's term counts, one row per
``(article, term)`` whose count moved, keyed by a dense pair id.

The interning of pairs and the delta an edit produces follow
``reflow_tpu/workloads/tfidf.py`` (``Corpus.edit``), over NumPy arrays
and not dicts of Python ints, because an article here has thousands of
tokens and a corpus tens of millions (only the few pairs an article
gets after the load are kept in a dict). Minting an edit is what every
run pays for twice, in the generator before the window and in the
leader after it, a quarter of a million times in the backlog cell: it
is written for few NumPy calls (``tests/test_streams.py`` holds it to
the plain formulation). Each lane edits only its own
articles (``doc % lanes``), so lanes' batches commute and an edit's
retractions always find their rows.
"""

from __future__ import annotations

from types import SimpleNamespace
from typing import List

import numpy as np

from common import Check, Minted
from reflow_tpu.delta import DeltaBatch

_BLOCK = 512        # edits drawn per numpy call
_DOC_SHIFT = 20     # (doc << 20 | term) is a pair's sort key; terms < 2**20


def _zipf_words(rng, n: int, vocab: int) -> np.ndarray:
    """``n`` word ranks in ``[0, vocab)`` by Zipf's law with exponent 1,
    in its continuous form: rank ``r`` (from 1) has the probability
    ``ln(1 + 1/r) / ln(vocab + 1)``. One ``exp`` a draw, where a table
    lookup over 267 735 words would cost a binary search a token."""
    r = np.exp(rng.random(n) * np.log(vocab + 1.0)).astype(np.int64) - 1
    return np.minimum(r, vocab - 1).astype(np.int32)


def _spans(rng, n: int, cfg: dict) -> np.ndarray:
    """``n`` span lengths: Pareto with shape 1 from ``edit_span_min``
    tokens, capped at ``edit_span_max``."""
    u = 1.0 - rng.random(n)                      # (0, 1]
    return np.minimum(cfg["edit_span_min"] / u,
                      cfg["edit_span_max"]).astype(np.int64)


def _pair_counts(docs: List[np.ndarray]):
    """Every ``(doc, term)`` with its count, sorted by doc then term:
    three arrays. The brute-force recount."""
    lens = np.fromiter((len(d) for d in docs), np.int64, len(docs))
    doc_of = np.repeat(np.arange(len(docs), dtype=np.int64), lens)
    key = (doc_of << _DOC_SHIFT) | np.concatenate(docs).astype(np.int64)
    key, counts = np.unique(key, return_counts=True)
    return key >> _DOC_SHIFT, key & ((1 << _DOC_SHIFT) - 1), counts


class Stream:
    """The corpus and its edits, from the seed. NumPy only."""

    source = "tokens"

    def __init__(self, cfg: dict, seed: int, lanes: int):
        if cfg["vocab"] > cfg["terms"] or cfg["terms"] > 1 << _DOC_SHIFT:
            raise ValueError("vocab <= terms <= 2**20 does not hold")
        self.cfg = cfg
        self.lanes = lanes
        self.n_pairs = cfg["pair_capacity"]
        self.used = 0                                # pair ids handed out
        self.docs: List[np.ndarray] = []             # doc -> its tokens
        self.doc_terms: List[np.ndarray] = []        # doc -> sorted terms
        self.doc_pids: List[np.ndarray] = []         # ... and their pair ids
        self.doc_later: List[dict] = []              # pairs since the load
        rng = np.random.default_rng([seed, 0])
        self._load_rng = rng
        rank = rng.permutation(cfg["docs"])          # doc -> Zipf rank
        self.lane_docs, self.lane_cdf = [], []
        for lane in range(lanes):
            docs = np.arange(lane, cfg["docs"], lanes)
            w = 1.0 / (rank[docs] + 1.0)
            self.lane_docs.append(docs)
            self.lane_cdf.append(np.cumsum(w / w.sum()))
        self.rngs = [np.random.default_rng([seed, 1, lane])
                     for lane in range(lanes)]
        self._drawn = [None] * lanes
        self._at = [0] * lanes

    def load(self):
        """The corpus as rounds of ``(source, batch, batch id)``, a tick
        each: equal parts of at most ``load_rows_per_tick`` rows, so
        every load tick has one shape."""
        cfg, rng = self.cfg, self._load_rng
        n = cfg["docs"]
        # article lengths: log-normal about the source's mean
        sigma = cfg["tokens_sigma"]
        lens = cfg["tokens_mean"] * np.exp(
            sigma * rng.standard_normal(n) - 0.5 * sigma * sigma)
        lens = np.clip(lens, cfg["tokens_min"],
                       cfg["tokens_max"]).astype(np.int64)
        words = _zipf_words(rng, int(lens.sum()), cfg["vocab"])
        ends = np.cumsum(lens)
        self.docs = [words[e - k:e] for e, k in zip(ends.tolist(),
                                                    lens.tolist())]
        doc, term, count = _pair_counts(self.docs)
        self.used = len(doc)
        if self.used > self.n_pairs:
            raise ValueError(f"pair capacity overflow (> {self.n_pairs})")
        pids = np.arange(self.used, dtype=np.int64)
        cuts = np.searchsorted(doc, np.arange(1, n)).tolist()
        self.doc_terms = np.split(term, cuts)
        self.doc_pids = np.split(pids, cuts)
        self.doc_later = [None] * n
        vals = np.stack([term, doc], axis=-1).astype(np.float32)
        parts = max(1, -(-self.used // cfg["load_rows_per_tick"]))
        edges = np.linspace(0, self.used, parts + 1).astype(np.int64)
        return [[("tokens", DeltaBatch(pids[a:b], vals[a:b], count[a:b]),
                  f"load/corpus/{i}")]
                for i, (a, b) in enumerate(zip(edges[:-1], edges[1:]))]

    def _pids(self, doc: int, terms: np.ndarray) -> np.ndarray:
        """Pair ids of one article's ``terms`` (sorted, unique); a pair
        not seen before gets the next id (``Corpus._pair``). The pairs
        of the load are looked up in its sorted arrays; a pair the
        article got since is kept in a dict, ``term -> pair id``, which
        ``filed`` sorts in when the reference asks."""
        have, pids = self.doc_terms[doc], self.doc_pids[doc]
        # an article has terms from the load on: ``have`` is not empty
        near = np.minimum(np.searchsorted(have, terms), len(have) - 1)
        out = pids[near]
        miss = np.flatnonzero(have[near] != terms)
        if len(miss):
            later = self.doc_later[doc]
            if later is None:
                later = self.doc_later[doc] = {}
            ids = []
            for term in terms[miss].tolist():
                pid = later.get(term)
                if pid is None:
                    pid = later[term] = self.used
                    self.used += 1
                ids.append(pid)
            if self.used > self.n_pairs:
                raise ValueError(
                    f"pair capacity overflow (> {self.n_pairs})")
            out[miss] = ids
        return out

    def filed(self):
        """Every article's terms, sorted, and their pair ids beside
        them: the load's arrays with the later pairs sorted in."""
        for doc, later in enumerate(self.doc_later):
            if later:
                n = len(later)
                terms = np.concatenate([self.doc_terms[doc], np.fromiter(
                    later.keys(), np.int64, n)])
                pids = np.concatenate([self.doc_pids[doc], np.fromiter(
                    later.values(), np.int64, n)])
                order = terms.argsort()
                self.doc_terms[doc] = terms[order]
                self.doc_pids[doc] = pids[order]
                self.doc_later[doc] = None
        return self.doc_terms, self.doc_pids

    def next(self, lane: int) -> Minted:
        at = self._at[lane] % _BLOCK
        if at == 0:
            rng, cfg = self.rngs[lane], self.cfg
            ix = np.minimum(np.searchsorted(self.lane_cdf[lane],
                                            rng.random(_BLOCK)),
                            len(self.lane_docs[lane]) - 1)
            out_n = _spans(rng, _BLOCK, cfg)
            in_n = _spans(rng, _BLOCK, cfg)
            self._drawn[lane] = (
                self.lane_docs[lane][ix], out_n, rng.random(_BLOCK), in_n,
                np.cumsum(in_n), _zipf_words(rng, int(in_n.sum()),
                                             cfg["vocab"]))
        docs, out_n, where, in_n, ends, words = self._drawn[lane]
        self._at[lane] += 1
        doc = int(docs[at])
        old = self.docs[doc]
        take = min(int(out_n[at]), len(old))
        start = int(where[at] * (len(old) - take + 1))
        put = words[ends[at] - in_n[at]:ends[at]]
        gone = old[start:start + take]
        # the change in the article's term counts (``Corpus.edit``):
        # +1 for a word put in, -1 for one taken out, summed by term
        # (``np.unique`` and ``np.bincount`` written out: one sort)
        while True:
            both = np.concatenate([put, gone])
            perm = both.argsort()
            both = both[perm]
            first = np.empty(len(both), bool)
            first[0] = True
            np.not_equal(both[1:], both[:-1], out=first[1:])
            starts = np.flatnonzero(first)
            wgt = np.add.reduceat(np.where(perm < len(put), 1, -1), starts)
            moved = wgt != 0
            if moved.any():
                break
            # the same words came back: an edit that changes nothing is
            # no edit (an empty batch reaches no queue and no log)
            put = put.copy()
            put[0] = (put[0] + 1) % self.cfg["vocab"]
        self.docs[doc] = np.concatenate(
            [old[:start], put, old[start + take:]])
        terms, wgt = both[starts][moved].astype(np.int64), wgt[moved]
        vals = np.empty((len(terms), 2), np.float32)
        vals[:, 0] = terms
        vals[:, 1] = doc
        delta = DeltaBatch(self._pids(doc, terms), vals, wgt)
        return Minted(delta, len(delta), (doc, start, take, put))


class Reference:
    """The corpus as it stands after the applied edits, spliced here
    from the edits themselves, and ``tf``, ``df`` and ``ndocs`` counted
    from its tokens by brute force (``Corpus.reference_tfidf``'s counts,
    before the final combine)."""

    def __init__(self, stream: Stream):
        self.s = stream
        self.docs = list(stream.docs)      # edits replace, never mutate

    def apply(self, ref) -> None:
        doc, start, take, put = ref
        old = self.docs[doc]
        self.docs[doc] = np.concatenate(
            [old[:start], put, old[start + take:]])

    def expected(self, precision: str = "float32") -> dict:
        """Each table as ``(keys, values)``. ``precision="bfloat16"`` is
        the control: the counts held in bfloat16, the nearest precision
        below the float32 the graph's specs state. bfloat16 holds
        integers exactly only up to 256."""
        s = self.s
        doc, term, count = _pair_counts(self.docs)
        # a pair's id: where the generator's mirror filed it
        doc_terms, doc_pids = s.filed()
        starts = np.fromiter((len(t) for t in doc_terms), np.int64,
                             len(doc_terms))
        starts = np.concatenate([[0], np.cumsum(starts)])
        key = (doc << _DOC_SHIFT) | term
        filed = (np.repeat(np.arange(len(doc_terms), dtype=np.int64),
                           np.diff(starts)) << _DOC_SHIFT
                 ) | np.concatenate(doc_terms)
        pids = np.concatenate(doc_pids)[np.searchsorted(filed, key)]
        df_terms, df = np.unique(term, return_counts=True)
        out = {"tf": (pids, count.astype(np.float64)),
               "df": (df_terms, df.astype(np.float64)),
               "ndocs": (np.zeros(1, np.int64),
                         np.array([float(len(np.unique(doc)))]))}
        if precision == "bfloat16":
            import ml_dtypes
            out = {name: (k, v.astype(np.float32).astype(
                ml_dtypes.bfloat16).astype(np.float64))
                for name, (k, v) in out.items()}
        elif precision != "float32":
            raise ValueError(precision)
        return out


_RADIX = 4096       # a term id is held as two float32-exact components


def _split_term(v):
    """[C, 2] (term, doc) -> [C, 2] (term // R, term % R); NumPy on the
    CPU oracle, jnp under the device lowering."""
    if isinstance(v, np.ndarray):
        xp = np
    else:
        import jax.numpy as xp
    t = v[:, 0]
    hi = t // _RADIX
    return xp.stack([hi, t - hi * _RADIX], axis=-1)


def build(cfg: dict):
    """The deployment's dataflow, written against the program's public
    graph API: ``reflow_tpu.workloads.tfidf.build_graph`` copied, with
    one change. The pair-presence reduce hands on a term id as the mean
    of a constant, ``(c * tf) / tf``, and float32 division on the TPU is
    not exact: it returns a value below ``c`` for 15 % of the pairs
    ``(c, tf)`` up to 4095 x 8192, and for none while ``tf <= 32`` (my
    chip run, PR 23). The original rebuilds the id by truncation, so an
    article that uses a word 33 times or more is counted under the
    neighbouring term; here the id is rounded."""
    from reflow_tpu.delta import Spec
    from reflow_tpu.graph import FlowGraph

    n_pairs, n_terms, n_docs = (cfg["pair_capacity"], cfg["terms"],
                                cfg["docs"])
    f32 = np.float32
    g = FlowGraph("tfidf")
    src = g.source("tokens", Spec((2,), f32, key_space=n_pairs))
    ones = g.map(src, lambda v: 1.0, spec=Spec((), f32, key_space=n_pairs),
                 name="ones")
    tf = g.reduce(ones, "sum", name="tf")
    term_of = g.map(src, _split_term, vectorized=True,
                    spec=Spec((2,), f32, key_space=n_pairs), name="term_of")
    pres = g.reduce(term_of, "mean", name="pair_presence")
    bterm = g.group_by(
        pres, key_fn=lambda k, v: v[0] * _RADIX + v[1] + 0.5,
        value_fn=lambda k, v: 1.0,
        spec=Spec((), f32, key_space=n_terms), name="by_term")
    df = g.reduce(bterm, "sum", name="df")
    bdoc = g.group_by(src, key_fn=lambda k, v: v[1],
                      value_fn=lambda k, v: 1.0,
                      spec=Spec((), f32, key_space=n_docs), name="by_doc")
    doctok = g.reduce(bdoc, "sum", name="doc_tokens")
    bone = g.group_by(doctok, key_fn=lambda k, v: 0,
                      value_fn=lambda k, v: 1.0,
                      spec=Spec((), f32, key_space=8), name="all_docs")
    ndocs = g.reduce(bone, "sum", name="ndocs")
    return SimpleNamespace(graph=g, sources={"tokens": src},
                           tables={"tf": tf, "df": df, "ndocs": ndocs})


def read_state(cfg: dict, dep, sched) -> dict:
    """Each served table as ``(keys, values)``."""
    out = {}
    for name, node in dep.tables.items():
        table = sched.read_table(node)
        out[name] = (np.fromiter(table.keys(), np.int64, len(table)),
                     np.fromiter(table.values(), np.float64, len(table)))
    return out


def _mismatches(got, want, space: int) -> int:
    """Keys on which two tables differ; a key one side lacks counts,
    unless the other side holds 0 for it."""
    a, b = np.zeros(space), np.zeros(space)
    a[got[0]] = got[1]
    b[want[0]] = want[1]
    return int(np.count_nonzero(a != b))


def compare(cfg: dict, got: dict, expected: dict):
    """``tf``, ``df`` and ``ndocs`` are counts: the served tables must
    equal the reference exactly, so each limit is 0 mismatching keys."""
    spaces = {"tf": cfg["pair_capacity"], "df": cfg["terms"], "ndocs": 8}
    out = []
    for name in ("tf", "df", "ndocs"):
        bad = _mismatches(got[name], expected[name], spaces[name])
        out.append(Check(f"{name}_mismatches", float(bad), 0.0, bad == 0))
    return out
