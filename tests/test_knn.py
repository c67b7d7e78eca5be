"""k-NN re-index (config 4): kernel parity, CPU-vs-TPU differential, and
the incremental insert path vs the full-rescan path."""

import numpy as np
import pytest

from reflow_tpu import DeltaBatch, DirtyScheduler
from reflow_tpu.executors import CpuExecutor, get_executor
from reflow_tpu.workloads import knn

from knn_reference import ROW_BLOCK, scan_sweeps, sweeps_rule

Q, D, DIM, K = 16, 256, 32, 4


def _drive(executor, seed=0, retract=True):
    kg = knn.build_graph(Q, D, DIM, K, scan_chunk=D)
    sched = DirtyScheduler(kg.graph, executor)
    store = knn.EmbeddingStore.create(DIM, seed=seed)
    rng = np.random.default_rng(seed + 100)
    qvecs = rng.normal(size=(Q, DIM)).astype(np.float32)
    sched.push(kg.queries, DeltaBatch(np.arange(Q), qvecs))
    sched.push(kg.docs, store.insert_batch(np.arange(0, 64)))
    sched.tick()
    # pure insert tick (incremental path on device)
    sched.push(kg.docs, store.insert_batch(np.arange(64, 128)))
    sched.tick()
    if retract:
        # retraction tick (full rescan path on device)
        sched.push(kg.docs, store.retract_batch(np.arange(10, 30)))
        sched.tick()
        sched.push(kg.docs, store.insert_batch(np.arange(128, 160)))
        sched.tick()
    return sched, kg, store, qvecs


def _ids_table(sched, kg):
    return {q: row[:, 0].astype(np.int64)
            for q, row in sched.read_table(kg.index).items()}


def test_cpu_matches_bruteforce_oracle():
    sched, kg, store, qvecs = _drive(CpuExecutor())
    ref_ids, _ = store.reference_topk(qvecs, K)
    table = _ids_table(sched, kg)
    for q in range(Q):
        np.testing.assert_array_equal(table[q], ref_ids[q])


def test_tpu_matches_bruteforce_oracle():
    sched, kg, store, qvecs = _drive(get_executor("tpu"))
    ref_ids, ref_s = store.reference_topk(qvecs, K)
    table = _ids_table(sched, kg)
    for q in range(Q):
        np.testing.assert_array_equal(table[q], ref_ids[q])


def test_cpu_tpu_views_match():
    s_cpu, kg_cpu, _, _ = _drive(CpuExecutor(), seed=3)
    s_tpu, kg_tpu, _, _ = _drive(get_executor("tpu"), seed=3)
    t_cpu = s_cpu.read_table(kg_cpu.index)
    t_tpu = s_tpu.read_table(kg_tpu.index)
    assert set(t_cpu) == set(t_tpu)
    for q in t_cpu:
        np.testing.assert_array_equal(
            t_cpu[q][:, 0].astype(np.int64),
            t_tpu[q][:, 0].astype(np.int64))
        np.testing.assert_allclose(t_cpu[q][:, 1], t_tpu[q][:, 1],
                                   atol=1e-5)


def test_incremental_vs_full_oracle_property():
    """Rebuilding from scratch on the accumulated corpus equals the
    incrementally maintained index (SURVEY.md §4b, for knn)."""
    sched, kg, store, qvecs = _drive(get_executor("tpu"), seed=7)
    # fresh graph fed the *current* corpus in one shot
    kg2 = knn.build_graph(Q, D, DIM, K, scan_chunk=D)
    sched2 = DirtyScheduler(kg2.graph, get_executor("tpu"))
    sched2.push(kg2.queries, DeltaBatch(np.arange(Q),
                                        qvecs))
    ids = np.array(sorted(store.vecs), np.int64)
    vals = np.stack([store.vecs[int(i)] for i in ids])
    sched2.push(kg2.docs, DeltaBatch(ids, vals))
    sched2.tick()
    a, b = _ids_table(sched, kg), _ids_table(sched2, kg2)
    assert set(a) == set(b)
    for q in a:
        np.testing.assert_array_equal(a[q], b[q])


def test_query_retraction_removes_row():
    ex = get_executor("tpu")
    sched, kg, store, qvecs = _drive(ex, retract=False)
    sink_view_before = len(sched.read_table(kg.index))
    assert sink_view_before == Q
    sched.push(kg.queries, DeltaBatch(np.arange(3), qvecs[:3],
                                      -np.ones(3, np.int64)))
    sched.tick()
    assert len(sched.read_table(kg.index)) == Q - 3


def test_sharded_knn_matches_single_device():
    """VERDICT r2 item 7: corpus row-sharded k-NN on the 8-device mesh —
    per-shard chunked scan + all_gather candidate merge — must reproduce
    the single-device tables exactly (incremental AND rescan paths)."""
    from reflow_tpu.parallel import make_mesh
    from reflow_tpu.parallel.shard import ShardedTpuExecutor

    mesh = make_mesh(8)
    s_sh, kg_sh, store, qvecs = _drive(ShardedTpuExecutor(mesh), seed=6)
    s_tp, kg_tp, _, _ = _drive(get_executor("tpu"), seed=6)
    t_sh = s_sh.read_table(kg_sh.index)
    t_tp = s_tp.read_table(kg_tp.index)
    assert set(t_sh) == set(t_tp)
    for q in t_tp:
        a, b = np.asarray(t_sh[q]), np.asarray(t_tp[q])
        np.testing.assert_array_equal(a[:, 0], b[:, 0])  # ids exact
        # scores: per-shard contraction order differs by ~1 ulp
        np.testing.assert_allclose(a[:, 1], b[:, 1], rtol=1e-5)
    ref_ids, _ = store.reference_topk(qvecs, K)
    for q in range(Q):
        np.testing.assert_array_equal(
            np.asarray(t_sh[q])[:, 0].astype(np.int64), ref_ids[q])


def test_bf16_embeddings_high_recall():
    """bf16 embedding storage (halved HBM + halved per-tick upload, the
    bandwidth-bound cost of config 4) must keep near-perfect recall vs
    the f32 brute-force oracle — scoring still accumulates in f32."""
    import jax.numpy as jnp

    kg = knn.build_graph(Q, D, DIM, K, scan_chunk=D,
                         dtype=jnp.bfloat16, precision="default")
    sched = DirtyScheduler(kg.graph, get_executor("tpu"))
    store = knn.EmbeddingStore.create(DIM, seed=3)
    rng = np.random.default_rng(103)
    qvecs = rng.normal(size=(Q, DIM)).astype(np.float32)
    sched.push(kg.queries, DeltaBatch(np.arange(Q), qvecs))
    sched.push(kg.docs, store.insert_batch(np.arange(0, 64)))
    sched.tick()
    sched.push(kg.docs, store.insert_batch(np.arange(64, 160)))
    sched.tick()

    ref_ids, ref_s = store.reference_topk(qvecs, K)
    table = _ids_table(sched, kg)
    hits = total = 0
    for q in range(Q):
        hits += len(set(table[q]) & set(ref_ids[q]))
        total += K
    assert hits / total >= 0.95, f"bf16 recall {hits/total:.3f}"


def test_in_place_doc_update_matches_oracle():
    """Re-inserting a LIVE doc id with a new vector is an in-place
    update; the stale score may sit in emitted top-k rows, so the device
    path must take the full rescan (the incremental merge would keep the
    stale candidate alive forever)."""
    kg = knn.build_graph(Q, D, DIM, K, scan_chunk=D)
    sched = DirtyScheduler(kg.graph, get_executor("tpu"))
    store = knn.EmbeddingStore.create(DIM, seed=9)
    rng = np.random.default_rng(109)
    qvecs = rng.normal(size=(Q, DIM)).astype(np.float32)
    sched.push(kg.queries, DeltaBatch(np.arange(Q), qvecs))
    sched.push(kg.docs, store.insert_batch(np.arange(0, 64)))
    sched.tick()
    # overwrite docs 0..16 with fresh vectors via plain inserts
    sched.push(kg.docs, store.insert_batch(np.arange(0, 16)))
    sched.tick()
    ref_ids, _ = store.reference_topk(qvecs, K)
    table = _ids_table(sched, kg)
    for q in range(Q):
        np.testing.assert_array_equal(table[q], ref_ids[q])


def test_device_retraction_never_consults_values():
    """ADVICE r3: bench config 4 fabricates ZERO-valued retraction rows,
    relying on the device lowering's contract that a doc retraction only
    clears the live bit (lowerings._fold_vectors) and never reads the
    row's value. Pin that contract: retracting with garbage (NaN) values
    must behave exactly like retracting with the true vectors."""
    ex_true = get_executor("tpu")
    ex_junk = get_executor("tpu")
    tables = []
    for ex, junk in ((ex_true, False), (ex_junk, True)):
        kg = knn.build_graph(Q, D, DIM, K, scan_chunk=D)
        sched = DirtyScheduler(kg.graph, ex)
        store = knn.EmbeddingStore.create(DIM, seed=9)
        rng = np.random.default_rng(42)
        qvecs = rng.normal(size=(Q, DIM)).astype(np.float32)
        sched.push(kg.queries, DeltaBatch(np.arange(Q), qvecs))
        sched.push(kg.docs, store.insert_batch(np.arange(0, 96)))
        sched.tick()
        ids = np.arange(16, 48)
        if junk:
            vals = np.full((len(ids), DIM), np.nan, np.float32)
            batch = DeltaBatch(ids, vals, -np.ones(len(ids), np.int64))
        else:
            batch = store.retract_batch(ids)
        sched.push(kg.docs, batch)
        sched.tick()
        tables.append(sched.read_table(kg.index))
    a, b = tables
    assert set(a) == set(b)
    for q in a:
        np.testing.assert_array_equal(np.asarray(a[q]), np.asarray(b[q]))


def test_preload_chunk_is_the_device_form_of_quantize_int8():
    import jax
    import jax.numpy as jnp

    rows, dim, n_docs = 8, 16, 100
    dd = knn.preload_chunk(rows, dim, n_docs, jnp.int8)(
        np.int32(5), np.int32(95))
    # ids wrap around the corpus, every row is one insert
    assert np.array_equal(dd.keys, (95 + np.arange(rows)) % n_docs)
    assert np.array_equal(dd.weights, np.ones(rows))
    draws = jax.random.normal(
        jax.random.fold_in(jax.random.PRNGKey(3), 5), (rows, dim),
        jnp.float32)
    want = knn.quantize_int8(np.asarray(draws)).astype(np.int32)
    got = np.asarray(dd.values)
    assert got.dtype == np.int8
    # the two roundings may part by one step at a .5 boundary
    assert np.abs(got.astype(np.int32) - want).max() <= 1
    wide = knn.preload_chunk(rows, dim, n_docs, jnp.bfloat16)(
        np.int32(5), np.int32(0))
    assert wide.values.dtype == jnp.bfloat16


def test_int8_embeddings_high_recall():
    """int8 quantized ingest (VERDICT r4 #3a): round(unit_vec * 127) on
    the wire — 1 byte/dim, halving the upload AGAIN vs bf16 — must keep
    near-perfect recall vs the f64 brute-force oracle. Scoring
    dequantizes to bf16 on chip (kernels.topk.score_form); retractions
    and in-place updates exercise both the rescan and incremental
    paths at int8."""
    import jax.numpy as jnp

    kg = knn.build_graph(Q, D, DIM, K, scan_chunk=D,
                         doc_dtype=jnp.int8, precision="default")
    sched = DirtyScheduler(kg.graph, get_executor("tpu"))
    store = knn.EmbeddingStore.create(DIM, seed=5)
    rng = np.random.default_rng(105)
    qvecs = rng.normal(size=(Q, DIM)).astype(np.float32)
    sched.push(kg.queries, DeltaBatch(np.arange(Q), qvecs))
    sched.push(kg.docs, store.insert_batch(np.arange(0, 64),
                                           quantize=True))
    sched.tick()
    # incremental insert path at int8
    sched.push(kg.docs, store.insert_batch(np.arange(64, 160),
                                           quantize=True))
    sched.tick()
    # retraction (full rescan path at int8): wire replays the SAME
    # quantized rows
    gone = np.arange(10, 20)
    raw = np.stack([store.vecs.pop(int(i)) for i in gone])
    sched.push(kg.docs, DeltaBatch(gone.astype(np.int64),
                                   knn.quantize_int8(raw),
                                   -np.ones(len(gone), np.int64)))
    sched.tick()

    ref_ids, _ = store.reference_topk(qvecs, K)
    table = _ids_table(sched, kg)
    hits = total = 0
    for q in range(Q):
        hits += len(set(table[q]) & set(ref_ids[q]))
        total += K
    assert hits / total >= 0.95, f"int8 recall {hits/total:.3f}"


@pytest.mark.parametrize("q,n,k", [(256, 8208, 16), (16, 300, 4)])
def test_pallas_topk_interpreted_equals_lax_top_k(q, n, k):
    """The Pallas kernel's logic, interpreted (what ``use_pallas=True``
    means off-TPU), against ``jax.lax.top_k`` on the same scores — at
    the config-4 rescan shape ``[256, 16 + 8192]`` (lane-padded to 8320
    inside) and at a ragged one. Values and ids exactly: ties (planted
    here) go to the first index on both. The compiled kernel gets the
    same comparison on the chip in ``chip_smoke.py``."""
    import jax
    import jax.numpy as jnp

    from reflow_tpu.kernels.topk import topk

    rng = np.random.default_rng(7)
    s = rng.normal(size=(q, n)).astype(np.float32)
    s[:, ::7] = np.round(s[:, ::7], 1)          # plant exact ties
    s = jnp.asarray(s)
    vals, ids = topk(s, k, use_pallas=True)
    ref_vals, ref_ids = jax.lax.top_k(s, k)
    assert np.array_equal(np.asarray(ids), np.asarray(ref_ids))
    assert np.array_equal(np.asarray(vals), np.asarray(ref_vals))


# -- the rescan's fold step (kernels.topk.fold_topk) ------------------------

def _fold_oracle(vals, ids, s, lo, k):
    """The formulation ``chunked_corpus_topk`` had before the fold step
    was carry-aware, kept as the oracle: one candidate matrix, an id
    block beside it, ``lax.top_k`` for columns, a gather for ids."""
    import jax
    import jax.numpy as jnp

    q, c = s.shape
    cand_vals = jnp.concatenate([vals, s], axis=1)
    cand_ids = jnp.concatenate(
        [ids, jnp.broadcast_to(lo + jnp.arange(c, dtype=jnp.int32),
                               (q, c))], axis=1)
    vals, sel = jax.lax.top_k(cand_vals, k)
    return vals, jnp.take_along_axis(cand_ids, sel, axis=1)


def _fold_case(name, q, c, k, lo):
    """``(vals, ids, s)``: a sorted carry and a score chunk at one
    decimal (so equal scores abound everywhere), shaped by ``name``."""
    from reflow_tpu.kernels.topk import NEG

    rng = np.random.default_rng(sum(map(ord, name)) + c)
    s = np.round(rng.normal(size=(q, c)), 1).astype(np.float32)
    vals = -np.sort(-np.round(rng.normal(size=(q, k)), 1), axis=1)
    vals = vals.astype(np.float32)
    ids = rng.integers(0, max(lo, 1), size=(q, k)).astype(np.int32)
    if name == "ties":
        # the carry's scores again in the chunk, twice each (carry
        # against chunk, and inside the chunk), and a run inside the carry
        vals[:, 1] = vals[:, 0]
        vals[:, 2:] = np.minimum(vals[:, 2:], vals[:, :1])
        w = min(k, c // 2)
        s[:, :w] = vals[:, :w]
        s[:, c - w:] = vals[:, :w]
    elif name == "first_step":
        vals[:], ids[:] = NEG, -1
    elif name == "dead_slots":
        s[:, ::3] = NEG
        s[1] = NEG                       # a query that sees nothing live
        vals[:, k // 2:], ids[:, k // 2:] = NEG, -1
    elif name == "chunk_below_carry":
        vals += 100.0
    elif name == "one_entrant_beside_none":
        # the first 8-row block sweeps nothing; in the second, one row
        # takes one column and the other seven none
        vals += 100.0
        s[ROW_BLOCK + 2, c // 2] = vals[ROW_BLOCK + 2, k - 1] + 0.5
    elif name == "one_row_takes_k":
        # one row of a block replaces its whole carry (more than k
        # columns beat it), the block's other seven take nothing
        vals += 100.0
        s[3] += 200.0
    elif name == "equal_to_the_kth":
        # columns that tie the carry's k-th score: the carry wins, none
        # enters; one row also has a column strictly above it
        vals += 100.0
        s[:, ::2] = vals[:, k - 1:]
        s[5, 1] = vals[5, k - 1] + 0.1
    elif name == "young_carry":
        vals[:, k // 2:], ids[:, k // 2:] = NEG, -1
    elif name == "chunk_above_carry":
        # what an ascending-score corpus hands the fold at every chunk:
        # all of the chunk beats all of the carry
        vals -= 100.0
    else:
        assert name == "plain"
    return vals, ids, s


@pytest.mark.parametrize("use_pallas", [True, False],
                         ids=["kernel_interpreted", "xla_body"])
@pytest.mark.parametrize("name,q,c,k,lo", [
    ("ties", 256, 8192, 16, 8192 * 37),
    ("plain", 256, 8192, 16, 0),
    ("first_step", 16, 8192, 16, 0),
    ("dead_slots", 16, 300, 4, 600),
    ("ties", 16, 300, 4, 600),
    ("chunk_below_carry", 16, 300, 4, 300),
    ("first_step", 16, 300, 4, 0),
    ("first_step", 12, 3, 8, 0),
    ("dead_slots", 12, 3, 8, 9),
    ("ties", 8, 6, 16, 60),
    ("one_entrant_beside_none", 16, 8192, 16, 8192 * 5),
    ("one_row_takes_k", 16, 8192, 16, 8192 * 5),
    ("equal_to_the_kth", 16, 8192, 16, 8192 * 5),
    ("young_carry", 16, 8192, 16, 8192),
    ("chunk_above_carry", 16, 8192, 16, 8192 * 5),
    ("one_entrant_beside_none", 16, 300, 4, 600),
    ("one_row_takes_k", 16, 300, 4, 600),
    ("equal_to_the_kth", 12, 300, 4, 600),
    ("chunk_above_carry", 12, 300, 4, 600),
    ("young_carry", 12, 3, 8, 9),
    ("chunk_above_carry", 12, 3, 8, 9),
])
def test_fold_topk_equals_the_id_block_formulation(name, q, c, k, lo,
                                                   use_pallas):
    """``fold_topk`` — the Pallas kernel interpreted, and the XLA body —
    against the id-block formulation on the same ``(vals, ids, s, lo)``:
    values and ids exactly, every query, every rank. At the rescan's
    shape ``[256, 16] + [256, 8192]``, at a ragged chunk (the kernel's
    pad branch) and at a chunk narrower than k; ``lo`` traced, as the
    scan passes it. The kernel sweeps a block as often as a row of it
    has columns above its carry's k-th score, so the cases also deal
    those every way: none in a block beside one in the next, one row
    that takes k beside seven that take nothing, columns that only tie
    the k-th score, a carry half unfilled, a chunk that replaces the
    whole carry. The sweeps it reports are the rule's
    (``knn_reference.sweeps_rule``). The compiled kernel gets the same
    comparison on the chip in ``chip_smoke.py``."""
    import jax
    import jax.numpy as jnp

    from reflow_tpu.kernels.topk import fold_topk, sweep_blocks

    vals, ids, s = _fold_case(name, q, c, k, lo)
    had = np.arange(sweep_blocks(q), dtype=np.int32)
    got_v, got_i, got_n = jax.jit(
        lambda v, i, n, x, at: fold_topk(v, i, n, x, at, k, use_pallas)
    )(vals, ids, had, s, jnp.int32(lo))
    ref_v, ref_i = _fold_oracle(jnp.asarray(vals), jnp.asarray(ids),
                                jnp.asarray(s), lo, k)
    assert np.array_equal(np.asarray(got_i), np.asarray(ref_i))
    assert np.array_equal(np.asarray(got_v), np.asarray(ref_v))
    assert np.array_equal(np.asarray(got_n), had + sweeps_rule(vals, s, k))


@pytest.mark.parametrize("use_pallas", [True, False],
                         ids=["kernel_interpreted", "xla_body"])
@pytest.mark.parametrize("d,chunk,law", [
    (1024, 128, "random"), (200, 8192, "random"),
    (1024, 128, "ascending"), (200, 8192, "ascending"), (6, 8192, "random"),
])
def test_chunked_corpus_topk_equals_bruteforce_lowest_id(d, chunk, law,
                                                         use_pallas):
    """The whole rescan over several chunks (and over one ragged one,
    and one narrower than k) against NumPy: all scores at once, ordered
    by (score descending, id ascending). Quarter-integer vectors make
    the scores exact and equal in droves; a fifth of the corpus is dead.
    ``ascending`` is the order that costs the gated kernel most: a
    query's scores rise with the id (in runs of four equal ones), so
    every chunk replaces its whole carry; the queries whose scores fall
    with the id instead take nothing after the first chunk. The scan's
    sweeps are the rule's, chunk by chunk."""
    import jax.numpy as jnp

    from reflow_tpu.kernels.topk import NEG, chunked_corpus_topk

    q, dim, k = 16, 8, 8
    rng = np.random.default_rng(d)
    qv = rng.integers(-2, 3, size=(q, dim)).astype(np.float32) / 4
    dv = rng.integers(-2, 3, size=(d, dim)).astype(np.float32) / 4
    if law == "ascending":
        dv[:, 1:] = 0
        dv[:, 0] = (np.arange(d) // 4) / 4
        qv[::3, 0], qv[1::3, 0] = 0.5, -0.25
    live = rng.random(d) > 0.2
    vals, ids, sweeps = chunked_corpus_topk(
        jnp.asarray(qv), jnp.asarray(dv), jnp.asarray(live), k, chunk,
        use_pallas=use_pallas)
    scores = np.where(live[None, :], qv @ dv.T, NEG)
    order = np.lexsort((np.broadcast_to(np.arange(d), (q, d)), -scores),
                       axis=1)[:, :k]
    assert len(np.unique(scores[0])) <= max(d // 4 + 1, 6)  # ties planted
    best = np.take_along_axis(scores, order, axis=1)
    if d < k:           # the carry's unfilled places stay (NEG, -1), and
        order = np.where(best > NEG, order, -1)    # they win ties with
        pad = ((0, 0), (0, k - d))                 # dead documents
        order = np.pad(order, pad, constant_values=-1)
        best = np.pad(best, pad, constant_values=NEG)
    assert np.array_equal(np.asarray(ids), order)
    assert np.array_equal(np.asarray(vals), best)
    want = scan_sweeps((scores[:, lo:lo + chunk].astype(np.float32)
                        for lo in range(0, d, min(chunk, d))), q, k, NEG)
    assert int(sweeps) == want
    if law == "ascending" and d > chunk:
        assert want == k * (d // chunk) * (q // ROW_BLOCK)


def test_rescan_lowers_without_id_block_or_gather():
    """Structural, a count and never a speed: the rescan's program (XLA
    body, CPU) holds no ``[Q, k + chunk]`` integer array — the id block
    the fold step used to build beside the scores — and gathers from
    nothing; the ids come from column arithmetic."""
    import re

    import jax
    import jax.numpy as jnp

    from reflow_tpu.kernels.topk import chunked_corpus_topk

    q, d, dim, k, chunk = 16, 1024, 8, 4, 256
    lowered = jax.jit(
        lambda qv, dv, live: chunked_corpus_topk(qv, dv, live, k, chunk,
                                                 use_pallas=False)[:2]
    ).lower(jnp.zeros((q, dim)), jnp.zeros((d, dim)),
            jnp.ones((d,), bool))
    for text in (lowered.as_text(), lowered.compile().as_text()):
        assert "while" in text                          # the scan is there
        assert not re.search(r"\bgather\b", text)
        assert f"{q}x{k + chunk}xi32" not in text       # StableHLO
        assert f"s32[{q},{k + chunk}]" not in text      # HLO
