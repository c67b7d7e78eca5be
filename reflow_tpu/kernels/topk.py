"""Top-k: Pallas TPU kernels, ``jax.lax.top_k`` off-TPU (SURVEY.md §7.10).

The k-NN workload's hot op: row-wise top-k over a scores matrix. On TPU a
Pallas kernel keeps a block of 8 rows in VMEM and picks winners by (max,
first-argmax, mask) sweeps on the VPU, so the scores never round-trip to
HBM between sweeps. Off-TPU (the CPU-mesh test harness) the default is
``jax.lax.top_k``, which implements the same tie-break (first index
wins). The choice follows the backend alone: on a TPU the kernel is
compiled by Mosaic or the call fails — nothing catches a compile error
and substitutes the XLA op.

Two contracts; which runs follows from what the caller's candidates are:

- ``topk(scores, k)``: one matrix in, column indices out, for callers
  whose candidates carry arbitrary ids and no carry to gate on (the
  incremental merge, the sharded ring's pairwise merge: ids sorted
  first, fetched afterwards). k unrolled sweeps over the block.
- ``fold_topk(vals, ids, sweeps, s, lo, k)``: a sorted ``[Q, k]`` carry
  and a score chunk whose column ``j`` is corpus id ``lo + j``, ids out.
  The kernel takes the two blocks as they are — no concatenate, and at
  a lane-multiple chunk no pad — and a winner's id is ``lo + j``,
  computed from where it stood: a rescan builds no ``[Q, k + chunk]``
  id block beside the scores and gathers from nothing. Its work follows
  what the chunk holds, not k: a column can enter only by strictly
  beating the carry's k-th score (the carry wins ties), so per 8-row
  block one pass counts those columns, and the most any row has, ``n``
  (at most k), is the trip count of a loop of sweeps over the chunk,
  each winner inserted into the carry on a single vreg. ``n == 0``
  writes the carry through. After a scan's first chunks almost every
  block is that: in an order that does not know the scores, the chunk
  after the c-th holds ``k / c`` columns above a query's k-th score in
  expectation. The trip count is data; there is no flag. The order
  that costs most is a corpus whose scores rise with the id: every
  chunk replaces the whole carry, k sweeps a block as before plus the
  gate and the insertions, + 17 % on the rescan (PERF.md §6, PR 30).
  The answers are the same on every order, bit for bit.

``fold_topk`` also counts: ``sweeps`` (int32, one entry per 8-row block)
comes back with the sweeps the fold ran added. It is defined by the
inputs (per block, the most columns of a row that beat its k-th score,
at most k), so the XLA body reports the same number by the same rule.

``chunked_corpus_topk`` is the streaming form for corpora whose scores
matrix would not fit memory: matmul one corpus chunk at a time on the MXU
and ``fold_topk`` it into a running (values, ids, sweeps) carry.
"""

from __future__ import annotations

import functools
from typing import Optional, Tuple

import jax
import jax.numpy as jnp

__all__ = ["topk", "fold_topk", "chunked_corpus_topk", "sweep_blocks",
           "NEG", "KERNEL_NAME"]

#: the Pallas kernel's fixed name: a device trace lists every call of it
#: under this one operation name, whatever program it was compiled into
KERNEL_NAME = "reflow_topk"


#: sentinel for "no candidate" — finite so arithmetic/compares stay clean
NEG = float(jnp.finfo(jnp.float32).min)

_BQ = 8  # rows per grid step (f32 sublane tile)
_LANES = 128

#: what a sweep leaves where a winner stood: below every score and below
#: NEG, so a column wins once, as ``lax.top_k``'s distinct indices do
_TAKEN = float("-inf")

_NO_ID = int(jnp.iinfo(jnp.int32).min)  # the one-hot id select's filler


def _rowmax(x):
    return jnp.max(x, axis=1, keepdims=True)


def _take(x, m, col):
    """The lowest column of ``x`` that holds the row's maximum ``m``
    ``[BQ, 1]``, and ``x`` with that column masked out."""
    first = jnp.min(jnp.where(x >= m, col, x.shape[1]), axis=1,
                    keepdims=True)
    return first, jnp.where(col == first, _TAKEN, x)


def _cols(x):
    return jax.lax.broadcasted_iota(jnp.int32, x.shape, 1)


def _topk_kernel(x_ref, vals_ref, idx_ref, *, k: int):
    x = x_ref[...].astype(jnp.float32)                     # [BQ, N]
    col = _cols(x)
    for i in range(k):                                     # k static, unrolled
        m = _rowmax(x)
        first, x = _take(x, m, col)
        vals_ref[:, i] = m[:, 0]
        idx_ref[:, i] = first[:, 0]


def _fold_kernel(lo_ref, cv_ref, ci_ref, n_ref, s_ref,
                 vals_ref, ids_ref, nout_ref, x_ref, wv_ref, wi_ref, *,
                 k: int, q: int):
    """One 8-row block: sweep the chunk once per column that can enter.

    The carry is sorted, so a chunk column can enter only by strictly
    beating the carry's k-th score (the carry wins ties, and a NEG
    filler in a young carry is beaten by every live column). The gate is
    one pass over the chunk that counts those columns per row; ``n``,
    the most any of the block's rows has (at most k), is the trip count
    of a loop of (first-argmax, mask, max) sweeps, each followed by the
    insertion of its winner into the carry on one vreg (the carry
    widened to the 128 lanes, so that the shift is a lane rotate). A
    row with fewer than ``n`` such columns yields columns at or under
    its k-th score, which fall off the carry's end. ``n == 0`` writes
    the carry through.
    """
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    b = pl.program_id(0)
    lo = lo_ref[0]
    cv = cv_ref[...]                                       # [BQ, k]
    kth = jnp.min(cv, axis=1, keepdims=True)
    beat = jnp.sum((s_ref[...] > kth).astype(jnp.int32), axis=1,
                   keepdims=True)
    if q % _BQ:     # rows past the last query hold the block's padding
        row = jax.lax.broadcasted_iota(jnp.int32, beat.shape, 0)
        beat = jnp.where(b * _BQ + row < q, beat, 0)
    n = jnp.minimum(jnp.max(beat), k)
    nout_ref[b] = n_ref[b] + n

    @pl.when(n == 0)
    def _():
        vals_ref[...] = cv
        ids_ref[...] = ci_ref[...]

    @pl.when(n > 0)
    def _():
        wv_ref[...] = jnp.full(wv_ref.shape, _TAKEN, jnp.float32)
        wv_ref[:, :k] = cv
        wi_ref[:, :k] = ci_ref[...]
        lane = _cols(wv_ref)
        col = _cols(s_ref)

        def sweep(src_ref, state):
            wv, wi = state
            x = src_ref[...]
            m = _rowmax(x)
            j, x = _take(x, m, col)
            x_ref[...] = x
            # (m, lo + j) goes in behind the p scores it does not beat;
            # at p == k that is off the carry's end
            p = jnp.sum((wv >= m).astype(jnp.int32), axis=1, keepdims=True)
            wv = jnp.where(lane < p, wv, jnp.where(
                lane == p, m, pltpu.roll(wv, 1, 1)))
            wv = jnp.where(lane < k, wv, _TAKEN)
            wi = jnp.where(lane < p, wi, jnp.where(
                lane == p, lo + j, pltpu.roll(wi, 1, 1)))
            return wv, wi

        # the first sweep reads the chunk where it lies, the others the
        # copy the sweep before left with its winners masked
        state = sweep(s_ref, (wv_ref[...], wi_ref[...]))
        wv, wi = jax.lax.fori_loop(
            1, n, lambda _, state: sweep(x_ref, state), state)
        vals_ref[...] = wv[:, :k]
        ids_ref[...] = wi[:, :k]


def _pad_lanes(scores: jax.Array) -> jax.Array:
    """Columns up to a lane multiple, filled with NEG (never a winner
    ahead of a real column: ties go to the lower one)."""
    pad = -scores.shape[1] % _LANES
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


def _fold_pallas(vals, ids, sweeps, s, lo, k: int, interpret: bool = False):
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    if k > _LANES:
        raise ValueError(f"fold_topk holds the carry in one vreg: k {k} "
                         f"is over its {_LANES} lanes")
    s = _pad_lanes(s)
    q, c = s.shape
    smem = pl.BlockSpec(memory_space=pltpu.SMEM)
    return pl.pallas_call(
        functools.partial(_fold_kernel, k=k, q=q),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,                         # lo, in SMEM
            grid=(pl.cdiv(q, _BQ),),
            in_specs=_row_blocks(k, k) + [smem] + _row_blocks(c),
            out_specs=_row_blocks(k, k) + [smem],
            scratch_shapes=[pltpu.VMEM((_BQ, c), jnp.float32),
                            pltpu.VMEM((_BQ, _LANES), jnp.float32),
                            pltpu.VMEM((_BQ, _LANES), jnp.int32)],
        ),
        out_shape=[
            jax.ShapeDtypeStruct((q, k), jnp.float32),
            jax.ShapeDtypeStruct((q, k), jnp.int32),
            jax.ShapeDtypeStruct(sweeps.shape, jnp.int32),
        ],
        # the new carry takes the old one's buffers (operand 0 is lo)
        input_output_aliases={1: 0, 2: 1, 3: 2},
        interpret=interpret,
        name=KERNEL_NAME,
    )(jnp.reshape(lo, (1,)).astype(jnp.int32), vals, ids, sweeps, s)


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


def sweep_blocks(q: int) -> int:
    """How many row blocks a ``[q, ...]`` fold has: the length of the
    ``sweeps`` vector :func:`fold_topk` carries."""
    return -(-q // _BQ)


def fold_topk(vals: jax.Array, ids: jax.Array, sweeps: jax.Array,
              s: jax.Array, lo, k: int, use_pallas: Optional[bool] = None
              ) -> Tuple[jax.Array, jax.Array, jax.Array]:
    """Fold a score chunk into a top-k carry: the row-wise top-k of
    ``[vals ‖ s]`` as ``(values, ids) [Q, k]``, and the sweeps it took.

    ``vals, ids [Q, k]`` is the carry, sorted by descending score;
    column ``j`` of ``s [Q, C]`` is corpus id ``lo + j`` (``lo`` may be
    traced). On equal scores the carry wins over the chunk and the lower
    position wins inside either — the lowest-column rule on ``[carry ‖
    chunk]``, and, for a scan that visits ids in ascending order, ties
    to the lowest id. A winner's id is computed from its column, never
    fetched: no id block beside the scores, no gather.

    ``sweeps`` is int32 ``[sweep_blocks(Q)]`` and comes back with, per
    block of 8 rows, the chunk sweeps this fold ran added: the most
    columns any of the block's rows has that strictly beat its carry's
    k-th score, at most k. The XLA body counts by the same rule.
    ``use_pallas`` as in :func:`topk`.
    """
    use_pallas, interpret = _which(use_pallas)
    if use_pallas:
        return _fold_pallas(vals, ids, sweeps, s, lo, k, interpret)
    kth = jnp.min(vals, axis=1, keepdims=True)
    beat = jnp.sum((s > kth).astype(jnp.int32), axis=1)
    beat = jnp.pad(jnp.minimum(beat, k), (0, -beat.shape[0] % _BQ))
    vals, sel = jax.lax.top_k(jnp.concatenate([vals, s], axis=1), k)
    sel = sel.astype(jnp.int32)
    carried = jnp.max(
        jnp.where(sel[:, :, None] == jnp.arange(k, dtype=jnp.int32),
                  ids[:, None, :], _NO_ID), axis=2)
    return (vals, jnp.where(sel < k, carried, lo + sel - k),
            sweeps + jnp.max(beat.reshape(-1, _BQ), axis=1))


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
                        precision=None
                        ) -> Tuple[jax.Array, jax.Array, jax.Array]:
    """Top-k of ``qvec @ dvec.T`` without materializing the full [Q, D]
    scores matrix: stream the corpus in chunks through the MXU and fold
    each chunk into a running top-k carry (:func:`fold_topk`). The loop
    carries ``[Q, k]`` values and ids and the fold's sweep counts, and
    nothing else: the chunk at ``lo`` is ids ``lo .. lo + chunk``, so no
    id array accompanies the scores. Chunks come in ascending id order
    and the carry wins ties, so equal scores resolve to the lowest id.
    Returns ``(values, ids, sweeps)``: ``sweeps`` is the scan's chunk
    sweeps (:func:`fold_topk`), summed once here, an int32 scalar.

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
            jnp.full((q, k), -1, jnp.int32),
            jnp.zeros((sweep_blocks(q),), jnp.int32))
    vals, ids, sweeps = jax.lax.fori_loop(0, d // chunk, step, init)
    return vals, ids, jnp.sum(sweeps)
