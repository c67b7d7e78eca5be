"""Top-k: Pallas TPU kernels, ``jax.lax.top_k`` off-TPU (SURVEY.md §7.10).

The k-NN workload's hot op: row-wise top-k over a scores matrix. On TPU a
Pallas kernel keeps the whole row block in VMEM and does k unrolled
(max, first-argmax, mask) sweeps on the VPU, so the scores never
round-trip to HBM between sweeps. Off-TPU (the CPU-mesh test harness)
the default is ``jax.lax.top_k``, which implements the same tie-break
(first index wins). The choice follows the backend alone: on a TPU the
kernel is compiled by Mosaic or the call fails — nothing catches a
compile error and substitutes the XLA op.

One sweep routine under two contracts; which runs follows from what the
caller's candidates are:

- ``topk(scores, k)``: one matrix in, column indices out, for callers
  whose candidates carry arbitrary ids (the incremental merge, the
  sharded ring's pairwise merge: ids sorted first, fetched afterwards).
- ``fold_topk(vals, ids, s, lo, k)``: a sorted ``[Q, k]`` carry and a
  score chunk whose column ``j`` is corpus id ``lo + j``, ids out. The
  kernel takes the two blocks as they are — no concatenate, and at a
  lane-multiple chunk no pad — and a winner's id is computed from where
  it stood: ``lo + j`` for a chunk column, the carried id (a one-hot
  select over k lanes) for a carry position. So a rescan builds no
  ``[Q, k + chunk]`` id block beside the scores and gathers from nothing.

``chunked_corpus_topk`` is the streaming form for corpora whose scores
matrix would not fit memory: matmul one corpus chunk at a time on the MXU
and ``fold_topk`` it into a running (values, ids) carry.
"""

from __future__ import annotations

import functools
from typing import Optional, Tuple

import jax
import jax.numpy as jnp

__all__ = ["topk", "fold_topk", "chunked_corpus_topk", "NEG",
           "KERNEL_NAME"]

#: the Pallas kernel's fixed name: a device trace lists every call of it
#: under this one operation name, whatever program it was compiled into
KERNEL_NAME = "reflow_topk"


#: sentinel for "no candidate" — finite so arithmetic/compares stay clean
NEG = float(jnp.finfo(jnp.float32).min)

_BQ = 8  # rows per grid step (f32 sublane tile)

#: what a sweep leaves where a winner stood: below every score and below
#: NEG, so a column wins once, as ``lax.top_k``'s distinct indices do
_TAKEN = float("-inf")

_NO_ID = int(jnp.iinfo(jnp.int32).min)  # the one-hot id select's filler


def _sweep(blocks, cols):
    """One (max, first-argmax, mask) sweep over ``blocks``, the column
    blocks of one candidate row in priority order: on equal scores an
    earlier block wins, and the lowest column inside a block.

    Returns ``(m, firsts, blocks)``: the winning score ``[BQ, 1]``, per
    block the winner's column (the block's width where the winner is
    not in it), and the blocks with the winner masked out.
    """
    m = functools.reduce(
        jnp.maximum, [jnp.max(x, axis=1, keepdims=True) for x in blocks])
    firsts, out, taken = [], [], None
    for x, col in zip(blocks, cols):
        n = x.shape[1]
        first = jnp.min(jnp.where(x >= m, col, n), axis=1, keepdims=True)
        if taken is not None:
            first = jnp.where(taken, n, first)
            taken = taken | (first < n)
        else:
            taken = first < n
        firsts.append(first)
        out.append(jnp.where(col == first, _TAKEN, x))
    return m, firsts, out


def _cols(x):
    return jax.lax.broadcasted_iota(jnp.int32, x.shape, 1)


def _topk_kernel(x_ref, vals_ref, idx_ref, *, k: int):
    x = x_ref[...].astype(jnp.float32)                     # [BQ, N]
    col = _cols(x)
    for i in range(k):                                     # k static, unrolled
        m, (first,), (x,) = _sweep([x], [col])
        vals_ref[:, i] = m[:, 0]
        idx_ref[:, i] = first[:, 0]


def _fold_kernel(lo_ref, cv_ref, ci_ref, s_ref, vals_ref, ids_ref, *,
                 k: int):
    """The carry block and the score block as they are: the sweeps run
    over both, and a winner is named from its column — carry position
    ``p`` is the carried id (a one-hot select over the k lanes), chunk
    column ``j`` is corpus id ``lo + j``."""
    cv, ci = cv_ref[...], ci_ref[...]                      # [BQ, k]
    x = s_ref[...]                                         # [BQ, C]
    ccol, col = _cols(cv), _cols(x)
    lo = lo_ref[0]
    for i in range(k):
        m, (p, j), (cv, x) = _sweep([cv, x], [ccol, col])
        carried = jnp.max(jnp.where(ccol == p, ci, _NO_ID), axis=1,
                          keepdims=True)
        vals_ref[:, i] = m[:, 0]
        ids_ref[:, i] = jnp.where(p < k, carried, lo + j)[:, 0]


def _pad_lanes(scores: jax.Array) -> jax.Array:
    """Columns up to a lane multiple, filled with NEG (never a winner
    ahead of a real column: ties go to the lower one)."""
    pad = -scores.shape[1] % 128
    if pad:
        scores = jnp.pad(scores, ((0, 0), (0, pad)), constant_values=NEG)
    return scores


def _row_blocks(*widths):
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    return [pl.BlockSpec((_BQ, w), lambda i, *_: (i, 0),
                         memory_space=pltpu.VMEM) for w in widths]


def _topk_pallas(scores: jax.Array, k: int,
                 interpret: bool = False) -> Tuple[jax.Array, jax.Array]:
    from jax.experimental import pallas as pl

    scores = _pad_lanes(scores)
    q, n = scores.shape
    vals, idx = pl.pallas_call(
        functools.partial(_topk_kernel, k=k),
        grid=(pl.cdiv(q, _BQ),),
        in_specs=_row_blocks(n),
        out_specs=_row_blocks(k, k),
        out_shape=[
            jax.ShapeDtypeStruct((q, k), jnp.float32),
            jax.ShapeDtypeStruct((q, k), jnp.int32),
        ],
        interpret=interpret,
        name=KERNEL_NAME,
    )(scores)
    return vals, idx


def _fold_pallas(vals, ids, s, lo, k: int, interpret: bool = False):
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    s = _pad_lanes(s)
    q, c = s.shape
    vals, ids = pl.pallas_call(
        functools.partial(_fold_kernel, k=k),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,                         # lo, in SMEM
            grid=(pl.cdiv(q, _BQ),),
            in_specs=_row_blocks(k, k, c),
            out_specs=_row_blocks(k, k),
        ),
        out_shape=[
            jax.ShapeDtypeStruct((q, k), jnp.float32),
            jax.ShapeDtypeStruct((q, k), jnp.int32),
        ],
        # the new carry takes the old one's buffers (operand 0 is lo)
        input_output_aliases={1: 0, 2: 1},
        interpret=interpret,
        name=KERNEL_NAME,
    )(jnp.reshape(lo, (1,)).astype(jnp.int32), vals, ids, s)
    return vals, ids


def _which(use_pallas: Optional[bool]) -> Tuple[bool, bool]:
    """``(kernel?, interpreted?)``: unset, the kernel exactly when the
    backend is a TPU; asked for off-TPU, the kernel interpreted (test
    coverage of its logic on the CPU mesh)."""
    on_tpu = jax.default_backend() == "tpu"
    return (on_tpu if use_pallas is None else use_pallas), not on_tpu


def topk(scores: jax.Array, k: int,
         use_pallas: Optional[bool] = None) -> Tuple[jax.Array, jax.Array]:
    """Row-wise top-k of ``scores [Q, N]`` -> ``(values, ids) [Q, k]``.

    Ties resolve to the lowest column index on both paths. ``use_pallas``
    unset picks the kernel exactly when the backend is a TPU; requesting
    it off-TPU runs the kernel in interpreter mode.
    """
    use_pallas, interpret = _which(use_pallas)
    if use_pallas:
        return _topk_pallas(scores, k, interpret)
    vals, idx = jax.lax.top_k(scores, k)
    return vals, idx.astype(jnp.int32)


def fold_topk(vals: jax.Array, ids: jax.Array, s: jax.Array, lo, k: int,
              use_pallas: Optional[bool] = None
              ) -> Tuple[jax.Array, jax.Array]:
    """Fold a score chunk into a top-k carry: the row-wise top-k of
    ``[vals ‖ s]`` as ``(values, ids) [Q, k]``.

    ``vals, ids [Q, k]`` is the carry; column ``j`` of ``s [Q, C]`` is
    corpus id ``lo + j`` (``lo`` may be traced). On equal scores the
    carry wins over the chunk and the lower position wins inside either
    — the lowest-column rule on ``[carry ‖ chunk]``, and, for a scan
    that visits ids in ascending order, ties to the lowest id. A
    winner's id is computed from its column, never fetched: no id block
    beside the scores, no gather. ``use_pallas`` as in :func:`topk`.
    """
    use_pallas, interpret = _which(use_pallas)
    if use_pallas:
        return _fold_pallas(vals, ids, s, lo, k, interpret)
    vals, sel = jax.lax.top_k(jnp.concatenate([vals, s], axis=1), k)
    sel = sel.astype(jnp.int32)
    carried = jnp.max(
        jnp.where(sel[:, :, None] == jnp.arange(k, dtype=jnp.int32),
                  ids[:, None, :], _NO_ID), axis=2)
    return vals, jnp.where(sel < k, carried, lo + sel - k)


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
    each chunk into a running top-k carry (:func:`fold_topk`). The loop
    carries ``[Q, k]`` values and ids and nothing else: the chunk at
    ``lo`` is ids ``lo .. lo + chunk``, so no id array accompanies the
    scores. Chunks come in ascending id order and the carry wins ties,
    so equal scores resolve to the lowest id.

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
        lo = c * chunk
        blk = jax.lax.dynamic_slice_in_dim(dvec, lo, chunk, 0)
        live = jax.lax.dynamic_slice_in_dim(dlive, lo, chunk, 0)
        with jax.named_scope("knn.score"):
            s = jnp.dot(score_form(qvec), score_form(blk).T,
                        preferred_element_type=jnp.float32,
                        precision=precision)
            s = jnp.where(live[None, :], s, NEG)
        with jax.named_scope("knn.topk"):
            return fold_topk(*carry, s, lo, k, use_pallas)

    init = (jnp.full((q, k), NEG, jnp.float32),
            jnp.full((q, k), -1, jnp.int32))
    return jax.lax.fori_loop(0, d // chunk, step, init)
