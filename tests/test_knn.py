"""k-NN re-index (config 4): kernel parity, CPU-vs-TPU differential, and
the incremental insert path vs the full-rescan path."""

import numpy as np
import pytest

from reflow_tpu import DeltaBatch, DirtyScheduler
from reflow_tpu.executors import CpuExecutor, get_executor
from reflow_tpu.workloads import knn

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
