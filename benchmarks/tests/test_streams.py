"""The TF-IDF stream against its own reference, with no device between
them: the deltas it mints, summed by pair id, are the reference's
recount of the final articles. That holds the pair ids together: the
load's sorted arrays, the pairs an article got since (a dict until
``filed`` sorts them in), and the reference's join through ``filed``."""

import numpy as np

from conftest import tiny_cell


def test_tfidf_deltas_sum_to_the_recount_and_pair_ids_hold():
    cfg, traffic, mod = tiny_cell("tfidf-wiki", "edits-backlog")
    lanes = traffic["producers"]
    stream = mod.Stream(cfg, 2**31 + 9, lanes)
    counts = np.zeros(cfg["pair_capacity"], np.int64)
    for round_ in stream.load():
        for _source, batch, _bid in round_:
            np.add.at(counts, batch.keys, batch.weights)
    ref = mod.Reference(stream)
    loaded = stream.used
    for i in range(600):
        for lane in range(lanes):
            m = stream.next(lane)
            np.add.at(counts, m.delta.keys, m.delta.weights)
            ref.apply(m.ref)
        if i == 300:
            # asked in mid-stream, the reference must not disturb it
            ref.expected()
    assert stream.used > loaded            # articles got new pairs
    keys, want = ref.expected()["tf"]
    assert len(np.unique(keys)) == len(keys)
    got = np.zeros_like(counts)
    got[keys] = want.astype(np.int64)
    assert np.array_equal(got, counts)
    terms, pids = stream.filed()
    assert all(later is None for later in stream.doc_later)
    for t, p in zip(terms, pids):
        assert len(t) == len(p) and np.all(t[1:] > t[:-1])
    every = np.concatenate(pids)
    assert len(every) == stream.used == len(np.unique(every))


def test_tfidf_batches_equal_the_plain_formulation():
    """``Stream.next`` writes ``np.unique`` / ``np.bincount`` out as one
    sort and keeps an article's later pairs in a dict; here the same
    edit is worked out the plain way (``Corpus.edit``'s: unique terms,
    summed signs, a dict from ``(article, term)`` to the pair's id, the
    next id for a pair not seen) and every array must be equal."""
    cfg, traffic, mod = tiny_cell("tfidf-wiki", "edits-backlog")
    lanes = traffic["producers"]
    stream = mod.Stream(cfg, 2**31 + 10, lanes)
    pair = {}
    for round_ in stream.load():
        for _source, batch, _bid in round_:
            for pid, (term, doc) in zip(batch.keys.tolist(),
                                        batch.values.tolist()):
                pair[int(doc), int(term)] = pid
    docs = list(stream.docs)
    for _ in range(400):
        for lane in range(lanes):
            m = stream.next(lane)
            doc, start, take, put = m.ref
            old = docs[doc]
            gone = old[start:start + take]
            docs[doc] = np.concatenate([old[:start], put,
                                        old[start + take:]])
            sign = np.ones(len(put) + len(gone), np.int64)
            sign[len(put):] = -1
            terms, inv = np.unique(np.concatenate([put, gone]),
                                   return_inverse=True)
            wgt = np.bincount(inv, weights=sign, minlength=len(terms)
                              ).astype(np.int64)
            terms, wgt = terms[wgt != 0].astype(np.int64), wgt[wgt != 0]
            pids = [pair.setdefault((doc, t), len(pair))
                    for t in terms.tolist()]
            assert m.rows == len(terms) > 0
            assert np.array_equal(m.delta.keys, np.array(pids, np.int64))
            assert np.array_equal(m.delta.weights, wgt)
            assert m.delta.values.dtype == np.float32
            assert np.array_equal(m.delta.values[:, 0], terms)
            assert np.all(m.delta.values[:, 1] == doc)
    assert stream.used == len(pair)
