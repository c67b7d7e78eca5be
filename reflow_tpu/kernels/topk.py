"""Top-k: Pallas TPU kernel, ``jax.lax.top_k`` off-TPU (SURVEY.md §7.10).

The k-NN workload's hot op: row-wise top-k over a scores matrix. On TPU a
Pallas kernel keeps the whole row block in VMEM and does k unrolled
(max, first-argmax, mask) sweeps on the VPU, so the scores never
round-trip to HBM between sweeps. Off-TPU (the CPU-mesh test harness)
the default is ``jax.lax.top_k``, which implements the same tie-break
(first index wins). The choice follows the backend alone: on a TPU the
kernel is compiled by Mosaic or the call fails — nothing catches a
compile error and substitutes the XLA op.

``chunked_corpus_topk`` is the streaming form for corpora whose scores
matrix would not fit memory: matmul one corpus chunk at a time on the MXU
and fold it into a running (values, ids) top-k carry.
"""

from __future__ import annotations

import functools
from typing import Optional, Tuple

import jax
import jax.numpy as jnp

__all__ = ["topk", "chunked_corpus_topk", "NEG", "KERNEL_NAME"]

#: the Pallas kernel's fixed name: a device trace lists every call of it
#: under this one operation name, whatever program it was compiled into
KERNEL_NAME = "reflow_topk"


#: sentinel for "no candidate" — finite so arithmetic/compares stay clean
NEG = float(jnp.finfo(jnp.float32).min)

_BQ = 8  # rows per grid step (f32 sublane tile)


def _topk_kernel(x_ref, vals_ref, idx_ref, *, k: int):
    x = x_ref[...].astype(jnp.float32)                     # [BQ, N]
    bq, n = x.shape
    col = jax.lax.broadcasted_iota(jnp.int32, (bq, n), 1)
    for i in range(k):                                     # k static, unrolled
        m = jnp.max(x, axis=1, keepdims=True)              # [BQ, 1]
        first = jnp.min(jnp.where(x >= m, col, n), axis=1, keepdims=True)
        vals_ref[:, i] = m[:, 0]
        idx_ref[:, i] = first[:, 0].astype(jnp.int32)
        x = jnp.where(col == first, NEG, x)


def _topk_pallas(scores: jax.Array, k: int,
                 interpret: bool = False) -> Tuple[jax.Array, jax.Array]:
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    q, n = scores.shape
    if n % 128:
        pad = 128 - n % 128
        scores = jnp.pad(scores, ((0, 0), (0, pad)), constant_values=NEG)
        n += pad
    grid = (pl.cdiv(q, _BQ),)
    vals, idx = pl.pallas_call(
        functools.partial(_topk_kernel, k=k),
        grid=grid,
        in_specs=[pl.BlockSpec((_BQ, n), lambda i: (i, 0),
                               memory_space=pltpu.VMEM)],
        out_specs=[
            pl.BlockSpec((_BQ, k), lambda i: (i, 0),
                         memory_space=pltpu.VMEM),
            pl.BlockSpec((_BQ, k), lambda i: (i, 0),
                         memory_space=pltpu.VMEM),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((q, k), jnp.float32),
            jax.ShapeDtypeStruct((q, k), jnp.int32),
        ],
        interpret=interpret,
        name=KERNEL_NAME,
    )(scores)
    return vals, idx


def topk(scores: jax.Array, k: int,
         use_pallas: Optional[bool] = None) -> Tuple[jax.Array, jax.Array]:
    """Row-wise top-k of ``scores [Q, N]`` -> ``(values, ids) [Q, k]``.

    Ties resolve to the lowest column index on both paths. ``use_pallas``
    unset picks the kernel exactly when the backend is a TPU. Requesting
    the Pallas path off-TPU runs the kernel in interpreter mode (test
    coverage of the kernel logic on the CPU mesh).
    """
    on_tpu = jax.default_backend() == "tpu"
    if use_pallas is None:
        use_pallas = on_tpu
    if use_pallas:
        return _topk_pallas(scores, k, interpret=not on_tpu)
    vals, idx = jax.lax.top_k(scores, k)
    return vals, idx.astype(jnp.int32)


#: int8 embedding encoding: wire/table value is round(unit_vec * 127);
#: cosine only needs direction, so the per-vector scale folds away
INT8_EMBED_SCALE = 127.0


def score_form(v: jax.Array) -> jax.Array:
    """Compute-form of stored embeddings: int8 tables dequantize to bf16
    at score time (wire/HBM stay 1 byte/dim); float tables pass
    through."""
    if v.dtype == jnp.int8:
        return jnp.asarray(v, jnp.bfloat16) * jnp.bfloat16(
            1.0 / INT8_EMBED_SCALE)
    return v


def chunked_corpus_topk(qvec: jax.Array, dvec: jax.Array, dlive: jax.Array,
                        k: int, chunk: int = 8192,
                        use_pallas: Optional[bool] = None,
                        precision=None) -> Tuple[jax.Array, jax.Array]:
    """Top-k of ``qvec @ dvec.T`` without materializing the full [Q, D]
    scores matrix: stream the corpus in chunks through the MXU and fold
    each chunk into a running top-k carry.

    ``dlive`` masks dead corpus slots to NEG. D must be a multiple of the
    chunk (or <= chunk, in which case one pass covers it).
    """
    q, _dim = qvec.shape
    d = dvec.shape[0]
    chunk = min(chunk, d)
    if d % chunk:
        raise ValueError(f"corpus size {d} must be a multiple of the "
                         f"scan chunk {chunk}")

    def step(c, carry):
        vals, ids = carry
        lo = c * chunk
        blk = jax.lax.dynamic_slice_in_dim(dvec, lo, chunk, 0)
        live = jax.lax.dynamic_slice_in_dim(dlive, lo, chunk, 0)
        with jax.named_scope("knn.score"):
            s = jnp.dot(score_form(qvec), score_form(blk).T,
                        preferred_element_type=jnp.float32,
                        precision=precision)
            s = jnp.where(live[None, :], s, NEG)
        with jax.named_scope("knn.topk"):
            cand_vals = jnp.concatenate([vals, s], axis=1)
            cand_ids = jnp.concatenate(
                [ids, jnp.broadcast_to(
                    lo + jnp.arange(chunk, dtype=jnp.int32), (q, chunk))],
                axis=1)
            vals, sel = topk(cand_vals, k, use_pallas)
            ids = jnp.take_along_axis(cand_ids, sel, axis=1)
        return vals, ids

    init = (jnp.full((q, k), NEG, jnp.float32),
            jnp.full((q, k), -1, jnp.int32))
    return jax.lax.fori_loop(0, d // chunk, step, init)
