"""Benchmark config 4: k-NN re-index on embedding deltas.

BASELINE.md: "k-NN re-index on 1Mx768 embedding deltas (vmapped cosine,
Pallas top-k)". The graph is two sources (queries, corpus) feeding a
:class:`~reflow_tpu.ops.KnnIndex` op; the maintained collection is each
query's top-k corpus ids by cosine similarity, re-indexed incrementally as
embedding deltas arrive. The host driver streams batches of corpus
insertions (the re-index flow) and occasional retractions (which trigger
the chunked full corpus rescan on device).
"""

from __future__ import annotations

import dataclasses
from typing import Optional, Tuple

import numpy as np

from reflow_tpu.delta import DeltaBatch, Spec
from reflow_tpu.graph import FlowGraph, Node


@dataclasses.dataclass
class KnnGraph:
    graph: FlowGraph
    queries: Node
    docs: Node
    index: Node   # read_table -> {query_id: [k, 2] (doc_id, score) rows}


def build_graph(n_queries: int, n_docs: int, dim: int, k: int,
                *, scan_chunk: int = 8192, dtype=np.float32,
                doc_dtype=None, precision: str = "highest") -> KnnGraph:
    """``dtype`` is the embedding storage/transfer dtype. ``bfloat16``
    halves corpus HBM residency and the per-tick host->device upload
    (the bandwidth-bound cost of streaming inserts) at ~1e-3 relative
    score error — scoring still accumulates in float32 on the MXU; pair
    it with ``precision="default"`` so the MXU takes bf16 inputs
    natively instead of upcasting.

    ``doc_dtype=jnp.int8`` (ROADMAP r4 #6 / VERDICT r4 #3a) halves the
    corpus wire+HBM cost AGAIN vs bf16: the host sends
    ``quantize_int8(vecs)`` — ``round(unit_vec * 127)``, 1 byte/dim —
    and scoring dequantizes to bf16 on chip (``kernels.topk.score_form``;
    per-vector scale folds away because cosine only needs direction).
    ~0.4% component error; recall bound tested in tests/test_knn.py.
    Queries keep ``dtype`` (their upload is negligible)."""
    g = FlowGraph("knn")
    q = g.source("queries", Spec((dim,), dtype, key_space=n_queries))
    d = g.source("docs", Spec((dim,), doc_dtype if doc_dtype is not None
                              else dtype, key_space=n_docs))
    idx = g.knn(q, d, k, dim, name="index", scan_chunk=scan_chunk,
                precision=precision)
    return KnnGraph(g, q, d, idx)


def quantize_int8(vals: np.ndarray) -> np.ndarray:
    """Host-side int8 embedding encoding: normalize each row, scale by
    127, round. The device stores these RAW (re-normalizing would
    truncate at int8) and dequantizes at score time."""
    vals = np.asarray(vals, np.float32)
    n = np.linalg.norm(vals, axis=1, keepdims=True)
    u = vals / np.maximum(n, 1e-30)
    return np.clip(np.round(u * 127.0), -127, 127).astype(np.int8)


def preload_chunk(rows: int, dim: int, n_docs: int, doc_dtype):
    """Jitted ``(seed, base) -> DeviceDelta`` minting one ``rows``-row
    corpus insert batch with the on-chip RNG (ids ``base..base+rows``
    mod ``n_docs``): a device-resident corpus preload."""
    import jax
    import jax.numpy as jnp

    from reflow_tpu.executors.device_delta import DeviceDelta

    @jax.jit
    def gen_chunk(seed, base):
        kk = jax.random.fold_in(jax.random.PRNGKey(3), seed)
        vals = jax.random.normal(kk, (rows, dim), jnp.float32)
        keys = (base + jnp.arange(rows, dtype=jnp.int32)) % n_docs
        if doc_dtype == jnp.int8:
            # device-side form of quantize_int8
            nrm = jnp.sqrt(jnp.sum(vals * vals, axis=1, keepdims=True))
            unit = vals / jnp.maximum(nrm, 1e-30)
            out = jnp.clip(jnp.round(unit * 127.0), -127, 127
                           ).astype(jnp.int8)
        else:
            out = jnp.asarray(vals, doc_dtype)
        return DeviceDelta(keys, out, jnp.ones((rows,), jnp.int32))

    return gen_chunk


# -- host-side data + churn driver ----------------------------------------

@dataclasses.dataclass
class EmbeddingStore:
    """Host mirror of the corpus for generating deltas + the oracle."""

    dim: int
    rng: np.random.Generator
    vecs: dict  # id -> raw (unnormalized) vector

    @staticmethod
    def create(dim: int, seed: int = 0) -> "EmbeddingStore":
        return EmbeddingStore(dim, np.random.default_rng(seed), {})

    def _random(self, n: int) -> np.ndarray:
        return self.rng.normal(size=(n, self.dim)).astype(np.float32)

    def insert_batch(self, ids: np.ndarray, *,
                     quantize: bool = False) -> DeltaBatch:
        """``quantize=True`` sends int8-encoded rows (1 byte/dim wire
        cost — what ``build_graph(doc_dtype=jnp.int8)`` needs: an int8
        source refuses float rows at the host boundary rather than
        truncating them to zeros); the host mirror keeps the raw f32
        vectors for the oracle either way."""
        vals = self._random(len(ids))
        for i, v in zip(ids, vals):
            self.vecs[int(i)] = v
        wire = quantize_int8(vals) if quantize else vals
        return DeltaBatch(np.asarray(ids, np.int64), wire,
                          np.ones(len(ids), np.int64))

    def retract_batch(self, ids: np.ndarray, *,
                      quantize: bool = False) -> DeltaBatch:
        """Retraction rows in the wire dtype asked for, as
        :meth:`insert_batch` sends them: ``quantize=True`` for an int8
        source (which refuses float rows at the host boundary)."""
        vals = np.stack([self.vecs.pop(int(i)) for i in ids])
        wire = quantize_int8(vals) if quantize else vals
        return DeltaBatch(np.asarray(ids, np.int64), wire,
                          -np.ones(len(ids), np.int64))

    def reference_topk(self, queries: np.ndarray, k: int
                       ) -> Tuple[np.ndarray, np.ndarray]:
        """Brute-force float64 oracle -> (ids [Q,k], scores [Q,k])."""
        ids = np.array(sorted(self.vecs), np.int64)
        if not len(ids):
            return (np.full((len(queries), k), -1, np.int64),
                    np.full((len(queries), k), -np.inf))
        mat = np.stack([self.vecs[int(i)] for i in ids]).astype(np.float64)
        mat /= np.maximum(np.linalg.norm(mat, axis=1, keepdims=True), 1e-30)
        qn = queries.astype(np.float64)
        qn /= np.maximum(np.linalg.norm(qn, axis=1, keepdims=True), 1e-30)
        s = qn @ mat.T
        take = np.argsort(-s, axis=1, kind="stable")[:, :k]
        out_ids = np.full((len(queries), k), -1, np.int64)
        out_s = np.full((len(queries), k), -np.inf)
        m = min(k, len(ids))
        out_ids[:, :m] = ids[take[:, :m]]
        out_s[:, :m] = np.take_along_axis(s, take, 1)[:, :m]
        return out_ids, out_s
