"""A plain reference for the k-NN re-index, for tier-1: NumPy only,
importing nothing of the program. It keeps the corpus table (the rows as
they were sent, and a live mask) by applying every sent row in order,
and computes each standing query's top-k by brute force over it, in the
arithmetic the graph states. ``benchmarks/configs/knn-1m768.py`` holds
its copy for the cell (``Reference``); ``test_knn_served.py`` holds the
two to each other.
"""

import numpy as np

NEG = float(np.finfo(np.float32).min)


def bf16(x):
    """float32 rounded to the nearest bfloat16 (ties to even), kept as
    float32."""
    b = np.ascontiguousarray(x, np.float32).view(np.uint32)
    b = (b + np.uint32(0x7FFF) + ((b >> np.uint32(16)) & np.uint32(1))
         ) & np.uint32(0xFFFF0000)
    return b.view(np.float32)


#: the rows a sweep of the fold kernel covers (``kernels.topk._BQ``)
ROW_BLOCK = 8


def sweeps_rule(vals, s, k):
    """What ``kernels.topk.fold_topk`` counts when it folds the chunk
    ``s [Q, C]`` into the carry ``vals [Q, k]``: per block of 8 rows,
    the most chunk columns any of its rows has that strictly beat the
    carry's k-th score, at most k."""
    q = s.shape[0]
    beat = np.zeros(-(-q // ROW_BLOCK) * ROW_BLOCK, np.int64)
    beat[:q] = np.minimum((s > vals.min(axis=1, keepdims=True)).sum(axis=1),
                          k)
    return beat.reshape(-1, ROW_BLOCK).max(axis=1)


def scan_sweeps(chunks, q, k, neg):
    """The rule summed over a rescan: ``chunks`` are its ``[q, C]``
    score chunks in scan order, the carry starts at ``neg``."""
    carry, total = np.full((q, k), neg, np.float32), 0
    for s in chunks:
        total += int(sweeps_rule(carry, s, k).sum())
        carry = -np.sort(-np.concatenate([carry, s], axis=1), axis=1)[:, :k]
    return total


def _unit(v):
    v = np.asarray(v, np.float32)
    n = np.sqrt(np.sum(v * v, axis=-1, keepdims=True))
    return np.where(n > 0, v / np.maximum(n, 1e-30), 0.0).astype(np.float32)


class KnnReference:
    """``doc_dtype`` int8: rows are ``round(unit * 127)`` and are scored
    as ``bf16(row * bf16(1/127))`` against queries normalised in float32
    and held in bfloat16. ``doc_dtype`` float32: rows and queries are
    normalised in float32 and scored as they are."""

    def __init__(self, slots, dim, k, doc_dtype):
        self.k, self.int8 = k, np.dtype(doc_dtype) == np.int8
        self.table = np.zeros((slots, dim), doc_dtype)
        self.live = np.zeros(slots, bool)
        self.queries = {}

    def apply_queries(self, keys, values, weights):
        for key, vec, w in zip(keys, values, weights):
            if w > 0:
                self.queries[int(key)] = np.asarray(vec, np.float32)
            elif w < 0:
                self.queries.pop(int(key), None)

    def apply(self, keys, values, weights):
        """Row by row, in order: the last row of an id decides."""
        for key, vec, w in zip(keys, values, weights):
            if w > 0:
                self.table[int(key)] = vec
                self.live[int(key)] = True
            elif w < 0:
                self.live[int(key)] = False

    def topk(self):
        """{query id: [k, 2] (doc id, score)}, best first, ties to the
        lowest id, padded with (-1, NEG)."""
        if self.int8:
            d = bf16(self.table.astype(np.float32)
                     * bf16(np.float32(1.0 / 127.0)))
        else:
            d = _unit(self.table)
        ids = np.flatnonzero(self.live)
        out = {}
        for qid, q in self.queries.items():
            q = bf16(_unit(bf16(q))) if self.int8 else _unit(q)
            row = np.full((self.k, 2), NEG, np.float64)
            row[:, 0] = -1
            s = d[ids].astype(np.float64) @ q.astype(np.float64)
            best = np.lexsort((ids, -s))[:self.k]
            row[:len(best), 0] = ids[best]
            row[:len(best), 1] = s[best]
            out[qid] = row
        return out
