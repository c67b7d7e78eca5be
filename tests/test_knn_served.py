"""The k-NN re-index on the served path (PR 26): row order inside a merged
feed, the host boundary's refusal of a lossy cast, byte-heavy int8
batches through RPC / admission / WAL / fused windows / recovery, the
KnnIndex node's device counters, and the benchmark's own reference,
control and op / byte counts for the ``knn-1m768`` configuration.

Three parties are held to each other: the plain NumPy reference
(``tests/knn_reference.py``), the host oracle (``ops/knn.py``, the
specification) and the device lowering (the ``tpu`` executor, here on the
CPU backend).
"""

from __future__ import annotations

import importlib.util
import os
import sys

import jax.numpy as jnp
import numpy as np
import pytest

from knn_reference import KnnReference, scan_sweeps
from reflow_tpu import DeltaBatch, DirtyScheduler, obs
from reflow_tpu.executors import get_executor
from reflow_tpu.net import LoopbackTransport
from reflow_tpu.obs import MetricsRegistry
from reflow_tpu.obs import trace as trace_mod
from reflow_tpu.serve import (APPLIED, REJECTED, CoalesceWindow,
                              IngestFrontend, RemoteProducer,
                              RpcIngestServer)
from reflow_tpu.utils.faults import DeliveryError
from reflow_tpu.wal import DurableScheduler, recover, scan_wal
from reflow_tpu.workloads import knn

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
BENCH = os.path.join(ROOT, "benchmarks")
Q, D, DIM, K, CHUNK = 8, 128, 32, 4, 64


def _graph(wire):
    if wire == "int8":
        return knn.build_graph(Q, D, DIM, K, scan_chunk=CHUNK,
                               dtype=jnp.bfloat16, doc_dtype=jnp.int8,
                               precision="default")
    return knn.build_graph(Q, D, DIM, K, scan_chunk=CHUNK)


def _rows(rng, n, wire):
    v = rng.normal(size=(n, DIM)).astype(np.float32)
    return knn.quantize_int8(v) if wire == "int8" else v


def _ins(ids, vals):
    return DeltaBatch(np.asarray(ids, np.int64), vals,
                      np.ones(len(ids), np.int64))


def _ret(ids, vals):
    return DeltaBatch(np.asarray(ids, np.int64), vals,
                      -np.ones(len(ids), np.int64))


# -- merged feeds: reference == host oracle == device ----------------------

def _case(name, rng, wire, base):
    """Two micro-batches of one source that the frontend would merge
    into one tick's delta, and what they do to id 5 / 40 / 7."""
    a, b = _rows(rng, 1, wire), _rows(rng, 1, wire)
    if name == "insert_then_delete":        # a fresh id: ends dead
        return [_ins([40], a), _ret([40], a)]
    if name == "delete_then_insert":        # a live id: ends live, new
        return [_ret([5], base[5:6]), _ins([5], a)]
    if name == "two_updates":               # the second vector stays
        return [_ins([7], a), _ins([7], b)]
    if name == "update_in_consecutive_batches":
        return [DeltaBatch.concat([_ret([7], base[7:8]), _ins([7], a)]),
                DeltaBatch.concat([_ret([7], a), _ins([7], b)])]
    raise AssertionError(name)


CASES = ["insert_then_delete", "delete_then_insert", "two_updates",
         "update_in_consecutive_batches"]


@pytest.mark.parametrize("wire", ["int8", "float32"])
@pytest.mark.parametrize("case", CASES)
def test_merged_feed_row_order(case, wire):
    rng = np.random.default_rng(CASES.index(case) + 10 * (wire == "int8"))
    qv = rng.normal(size=(Q, DIM)).astype(np.float32)
    base = _rows(rng, 32, wire)
    parts = _case(case, rng, wire, base)
    merged = DeltaBatch.concat(parts)           # serve/coalesce.py's merge

    ref = KnnReference(D, DIM, K, base.dtype)
    ref.apply_queries(np.arange(Q), qv, np.ones(Q))
    ref.apply(np.arange(32), base, np.ones(32))
    ref.apply(merged.keys, merged.values, merged.weights)

    tables = {}
    for name, ex in (("oracle", None), ("device", get_executor("tpu"))):
        kg = _graph(wire)
        sched = DirtyScheduler(kg.graph, ex)
        sched.push(kg.queries, _ins(np.arange(Q), qv))
        sched.push(kg.docs, _ins(np.arange(32), base))
        sched.tick()
        for p in parts:                  # one tick's delta, in order
            sched.push(kg.docs, p)
        sched.tick()
        tables[name] = sched.read_table(kg.index)
        if name == "oracle":
            docs = sched.executor.states[kg.index.id]["docs"]
        else:
            st = sched.executor.states[kg.index.id]
    want_live = np.flatnonzero(ref.live)

    # liveness and vectors: all three agree
    assert sorted(docs) == want_live.tolist()
    np.testing.assert_array_equal(np.asarray(st["dlive"]), ref.live)
    dvec = np.asarray(st["dvec"])
    for i in want_live:
        if wire == "int8":
            np.testing.assert_array_equal(dvec[i], ref.table[i])
            unit = ref.table[i] / np.linalg.norm(ref.table[i].astype(float))
        else:
            unit = ref.table[i] / np.linalg.norm(ref.table[i])
            np.testing.assert_allclose(dvec[i], unit, atol=1e-6)
        np.testing.assert_allclose(docs[int(i)], unit, atol=1e-6)

    # the served top-k: the device against the reference in the graph's
    # own arithmetic; against the oracle wherever float32 is stated
    want = ref.topk()
    for q in range(Q):
        got = np.asarray(tables["device"][q])
        np.testing.assert_array_equal(got[:, 0], want[q][:, 0])
        np.testing.assert_allclose(got[:, 1], want[q][:, 1], atol=2e-5)
        if wire == "float32":
            np.testing.assert_array_equal(
                got[:, 0], np.asarray(tables["oracle"][q])[:, 0])


def test_frontend_merges_two_updates_of_one_id_into_one_tick(tmp_path):
    """The served form of the last case: two batches queued while the
    pump is paused coalesce into ONE feed (``coalesced_with`` says so),
    and the table holds the second update's vector."""
    rng = np.random.default_rng(3)
    kg = _graph("int8")
    sched = DurableScheduler(kg.graph, get_executor("tpu"),
                             wal_dir=str(tmp_path / "wal"), fsync="tick",
                             committer="thread")
    base, a, b = (_rows(rng, 16, "int8"), _rows(rng, 1, "int8"),
                  _rows(rng, 1, "int8"))
    sched.push(kg.docs, _ins(np.arange(16), base))
    sched.tick()
    fe = IngestFrontend(sched, depth=2, window=CoalesceWindow(
        max_rows=64, max_ticks=2, max_latency_s=0.002))
    try:
        fe.pause()
        t1 = fe.submit(kg.docs, DeltaBatch.concat(
            [_ret([7], base[7:8]), _ins([7], a)]), batch_id="u1")
        t2 = fe.submit(kg.docs, DeltaBatch.concat(
            [_ret([7], a), _ins([7], b), _ins([20], a), _ret([20], a)]),
            batch_id="u2")
        fe.resume()
        r1, r2 = t1.result(30), t2.result(30)
        assert r1.status == r2.status == APPLIED
        assert r1.tick == r2.tick and r1.coalesced_with == 1
        fe.flush(timeout=30)
        st = sched.executor.states[kg.index.id]
        np.testing.assert_array_equal(np.asarray(st["dvec"])[7], b[0])
        live = np.asarray(st["dlive"])
        assert live[7] and not live[20] and live.sum() == 16
    finally:
        fe.close()
        sched.close()


# -- the host boundary refuses a lossy cast ---------------------------------

@pytest.mark.parametrize("where", ["push", "durable_push", "submit", "rpc"])
def test_float_rows_to_an_int8_source_are_refused(where, tmp_path):
    rng = np.random.default_rng(1)
    unit = rng.normal(size=(4, DIM)).astype(np.float32)
    unit /= np.linalg.norm(unit, axis=1, keepdims=True)
    bad = _ins(np.arange(4), unit)              # would become all zeros
    good = _ins(np.arange(4), knn.quantize_int8(unit))
    kg = _graph("int8")
    if where == "push":
        sched = DirtyScheduler(kg.graph, get_executor("tpu"))
        with pytest.raises(DeliveryError, match="float32.*int8"):
            sched.push(kg.docs, bad, batch_id="b")
        assert sched.push(kg.docs, good, batch_id="b")     # id not burnt
        return
    sched = DurableScheduler(kg.graph, get_executor("tpu"),
                             wal_dir=str(tmp_path / "wal"), fsync="tick",
                             committer="thread")
    try:
        if where == "durable_push":
            with pytest.raises(DeliveryError):
                sched.push(kg.docs, bad, batch_id="b")
            sched.wal.sync()
            assert not [r for _p, r in scan_wal(str(tmp_path / "wal"))[0]
                        if r.get("kind") == "push"]       # nothing logged
            return
        fe = IngestFrontend(sched, depth=2)
        lt = LoopbackTransport()
        srv = RpcIngestServer(fe, lt).start()
        prod = RemoteProducer(lt, srv.address, name="p0")
        try:
            sub = prod if where == "rpc" else fe
            res = sub.submit(kg.docs if where == "submit" else "docs", bad,
                             batch_id="b").result(30)
            assert res.status == REJECTED and "int8" in res.reason
            assert fe.rejected == 1
            # the pump lives, and the same id is admitted once corrected
            res = sub.submit(kg.docs if where == "submit" else "docs", good,
                             batch_id="b").result(30)
            assert res.status == APPLIED
            fe.flush(timeout=30)
            assert np.asarray(
                sched.executor.states[kg.index.id]["dlive"]).sum() == 4
        finally:
            prod.close()
            srv.close()
            fe.close()
    finally:
        sched.close()


def test_casts_that_lose_nothing_pass():
    """Integers to a float source, float64 to float32, int64 rows to an
    int8 source: what the boundary has always cast."""
    from reflow_tpu.delta import Spec, lossy_value_cast

    f32, i8 = Spec((2,), np.float32, 8), Spec((2,), np.int8, 8)
    ok = [(f32, np.int64), (f32, np.float64), (i8, np.int64), (i8, np.int8),
          (i8, np.bool_), (None, np.float32)]
    for spec, dt in ok:
        assert lossy_value_cast(
            spec, DeltaBatch([0], np.zeros((1, 2), dt))) is None
    for dt in (np.float32, np.float64, np.float16):
        assert "int8" in lossy_value_cast(
            i8, DeltaBatch([0], np.zeros((1, 2), dt)))
    import ml_dtypes
    assert lossy_value_cast(
        i8, DeltaBatch([0], np.zeros((1, 2), ml_dtypes.bfloat16)))


# -- served end to end: RPC -> admission -> WAL -> fused windows -> recover --

def test_served_end_to_end_int8_wal_and_recovery(tmp_path):
    rng = np.random.default_rng(5)
    wal_dir = str(tmp_path / "wal")
    kg = _graph("int8")
    sched = DurableScheduler(kg.graph, get_executor("tpu"), wal_dir=wal_dir,
                             fsync="tick", committer="thread")
    qv = rng.normal(size=(Q, DIM)).astype(np.float32)
    base = _rows(rng, 64, "int8")
    ref = KnnReference(D, DIM, K, np.int8)
    ref.apply_queries(np.arange(Q), qv, np.ones(Q))
    ref.apply(np.arange(64), base, np.ones(64))
    sched.push(kg.queries, _ins(np.arange(Q), qv), batch_id="load/q")
    sched.push(kg.docs, _ins(np.arange(64), base), batch_id="load/d")
    sched.tick()

    fe = IngestFrontend(sched, depth=2, window=CoalesceWindow(
        max_rows=16, max_ticks=3, max_latency_s=0.002))
    lt = LoopbackTransport()
    srv = RpcIngestServer(fe, lt).start()
    prod = RemoteProducer(lt, srv.address, name="p0")
    sent = {}
    try:
        fe.pause()
        tickets = []
        for i in range(12):         # update 4, insert 4, delete 4: 16 rows
            upd = rng.choice(np.flatnonzero(ref.live), 8, replace=False)
            upd, gone = upd[:4], upd[4:]
            new = rng.choice(np.flatnonzero(~ref.live), 4, replace=False)
            fresh = _rows(rng, 8, "int8")
            b = DeltaBatch.concat([
                _ret(upd, ref.table[upd]), _ins(upd, fresh[:4]),
                _ins(new, fresh[4:]), _ret(gone, ref.table[gone])])
            ref.apply(b.keys, b.values, b.weights)
            sent[f"b{i}"] = b
            tickets.append(prod.submit("docs", b, batch_id=f"b{i}"))
        fe.resume()
        assert all(t.result(60).status == APPLIED for t in tickets)
        fe.flush(timeout=60)
        assert sched.megatick_fallbacks == 0
        assert sched.megatick_windows >= 4 and fe.windows_pipelined >= 1
        st = sched.executor.states[kg.index.id]
        table, live = np.asarray(st["dvec"]), np.asarray(st["dlive"])
        np.testing.assert_array_equal(live, ref.live)
        np.testing.assert_array_equal(table[ref.live], ref.table[ref.live])
        served = sched.read_table(kg.index)
        want = ref.topk()
        for q in range(Q):
            np.testing.assert_array_equal(
                np.asarray(served[q])[:, 0], want[q][:, 0])
        counters = sched.executor.op_counters()["index"]
        assert counters["rescans"] == 13 and counters["incremental"] == 0
        assert counters["rows_folded"] == Q + 64 + 12 * 12
    finally:
        prod.close()
        srv.close()
        fe.close()
        sched.close()

    # int8 rows byte-identical in the log
    logged = {}
    for _pos, rec in scan_wal(wal_dir)[0]:
        if rec.get("kind") == "push":
            for bid in rec.get("batch_ids") or [rec["batch_id"]]:
                logged[bid] = rec
    for bid, b in sent.items():
        vals = np.asarray(logged[bid]["values"])
        assert vals.dtype == np.int8
        assert vals.tobytes() == b.values.tobytes()

    # recovery into a fresh executor: the same table
    kg2 = _graph("int8")
    sched2 = DurableScheduler(kg2.graph, get_executor("tpu"),
                              wal_dir=wal_dir, fsync="tick")
    try:
        report = recover(sched2, wal_dir)
        assert report.replayed_pushes == 14
        st2 = sched2.executor.states[kg2.index.id]
        np.testing.assert_array_equal(np.asarray(st2["dlive"]), live)
        np.testing.assert_array_equal(np.asarray(st2["dvec"])[live],
                                      table[live])
        again = sched2.read_table(kg2.index)
        for q in range(Q):
            np.testing.assert_array_equal(np.asarray(again[q]),
                                          np.asarray(served[q]))
    finally:
        sched2.close()


# -- the node's device counters -----------------------------------------------

def test_knn_counters_count_and_are_published():
    rng = np.random.default_rng(2)
    kg = _graph("float32")
    sched = DirtyScheduler(kg.graph, get_executor("tpu"))
    reg = MetricsRegistry()
    key = sched.publish_metrics(reg)
    rows = _rows(rng, 40, "float32")
    steps = [
        (kg.queries, _ins(np.arange(Q), rng.normal(size=(Q, DIM))), "full"),
        (kg.docs, _ins(np.arange(16), rows[:16]), "incr"),   # fresh ids
        (kg.docs, _ins(np.arange(16, 32), rows[16:32]), "incr"),
        (kg.docs, _ret([3], rows[3:4]), "full"),             # a live id
        (kg.docs, _ins([4], rows[33:34]), "full"),           # an update
        (kg.docs, _ret([100], rows[0:1]), "incr"),           # never live
        (kg.docs, DeltaBatch.concat(                         # fresh, gone
            [_ins([50], rows[34:35]), _ret([50], rows[34:35])]), "incr"),
        (kg.queries, _ret([0], np.zeros((1, DIM), np.float32)), "incr"),
    ]
    full = incr = rows_n = sweeps = 0
    for source, batch, path in steps:
        sched.push(source, batch)
        sched.tick()
        full += path == "full"
        incr += path == "incr"
        rows_n += len(set(batch.keys.tolist()))      # winning rows
        got = sched.executor.op_counters()["index"]
        # the fourth counter, the rescans' chunk sweeps: an incremental
        # tick adds none, a rescan of a corpus with live rows in it some
        # (the first rescan meets an empty one); the exact count is held
        # in tests/test_knn.py
        swept = got.pop("sweeps") - sweeps
        assert (swept > 0) == (path == "full" and full > 1), (path, swept)
        sweeps += swept
        assert list(got.items()) == [
            ("rescans", full), ("incremental", incr),
            ("rows_folded", rows_n)], (path, got)
    snap = reg.snapshot()["gauges"]
    assert snap[f"{key}.index.rescans"] == 3
    assert snap[f"{key}.index.incremental"] == 5
    assert snap[f"{key}.index.rows_folded"] == rows_n
    assert snap[f"{key}.index.sweeps"] == sweeps
    assert sched.executor.counter_names() == {
        "index": ("rescans", "incremental", "rows_folded", "sweeps")}
    # a graph without such a node publishes none, and reads {}
    from reflow_tpu.workloads import wordcount
    g, *_ = wordcount.build_graph()
    assert getattr(DirtyScheduler(g).executor, "op_counters", dict)() == {}


def test_sweeps_counter_is_the_rule_and_stands_still_on_incremental_ticks():
    """The node's ``sweeps`` after a rescan of a two-chunk corpus: the
    tick program's count (XLA body here) == the kernel's, interpreted on
    the node's own state == the rule in NumPy over the same score
    chunks. Counts, never speeds. A tick that takes the incremental
    merge adds 0."""
    import jax

    from reflow_tpu.kernels.topk import (NEG, chunked_corpus_topk,
                                         score_form)

    rng = np.random.default_rng(6)
    kg = _graph("int8")
    sched = DirtyScheduler(kg.graph, get_executor("tpu"))
    sched.push(kg.queries, _ins(np.arange(Q), rng.normal(size=(Q, DIM))))
    sched.push(kg.docs, _ins(np.arange(100), _rows(rng, 100, "int8")))
    sched.tick()
    st = sched.executor.states[kg.index.id]
    got = sched.executor.op_counters()["index"]
    assert got["rescans"] == 1 and got["sweeps"] > 0

    *_, kernel = chunked_corpus_topk(st["qvec"], st["dvec"], st["dlive"],
                                     K, CHUNK, use_pallas=True)
    rule = scan_sweeps((np.asarray(jnp.where(
        st["dlive"][lo:lo + CHUNK][None, :],
        jnp.dot(score_form(st["qvec"]),
                score_form(st["dvec"][lo:lo + CHUNK]).T,
                preferred_element_type=jnp.float32,
                precision=jax.lax.Precision.DEFAULT), NEG))
        for lo in range(0, D, CHUNK)), Q, K, NEG)
    assert got["sweeps"] == int(kernel) == rule

    sched.push(kg.docs, _ins(np.arange(100, 108), _rows(rng, 8, "int8")))
    sched.tick()
    after = sched.executor.op_counters()["index"]
    assert after["incremental"] == 1 and after["sweeps"] == got["sweeps"]


def test_traced_windows_carry_the_counters(monkeypatch):
    """Under tracing the window program's completion token also holds
    the counters, and each ``window_device`` span says what its window
    left them at; untraced, the program has no token at all."""
    obs.disable()
    trace_mod.reset()
    monkeypatch.setattr(trace_mod, "SAMPLE_EVERY", 1)
    obs.enable()
    try:
        rng = np.random.default_rng(4)
        kg = _graph("int8")
        sched = DirtyScheduler(kg.graph, get_executor("tpu"))
        sched.push(kg.queries, _ins(np.arange(Q), rng.normal(size=(Q, DIM))))
        sched.tick()
        fe = IngestFrontend(sched, depth=2, window=CoalesceWindow(
            max_rows=8, max_ticks=2, max_latency_s=0.002))
        fe.pause()
        tickets = [fe.submit(kg.docs, _ins(np.arange(8 * i, 8 * i + 8),
                                           _rows(rng, 8, "int8")))
                   for i in range(6)]
        fe.resume()
        assert all(t.result(60).applied for t in tickets)
        fe.flush(timeout=60)
        sched.executor.drain_device_watch()
        seen = [e["args"]["counters"]["index"] for e in obs.chrome_events()
                if e.get("ph") == "X" and e["name"] == "window_device"]
        fe.close()
        assert sched.executor.device_watch_error is None
        assert len(seen) == sched.megatick_windows == 3
        # four values a node; the one rescan met an empty corpus, and
        # the incremental ticks sweep nothing
        assert seen == [[1, 2, Q + 16, 0], [1, 4, Q + 32, 0],
                        [1, 6, Q + 48, 0]]
        assert sched.executor.op_counters()["index"]["incremental"] == 6
    finally:
        obs.disable()
        trace_mod.reset()


# -- the benchmark's files for the configuration --------------------------------

def _bench(name, path):
    for p in (BENCH, ROOT):
        if p not in sys.path:
            sys.path.insert(0, p)
    spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def _tiny_cfg():
    import json
    with open(os.path.join(BENCH, "configs", "knn-1m768.json")) as f:
        cfg = json.load(f)
    tiny = cfg.pop("tiny")
    cfg.update(tiny)
    return cfg


@pytest.fixture(scope="module")
def bench_cfg():
    return _bench("bench_knn_cfg",
                  os.path.join(BENCH, "configs", "knn-1m768.py"))


@pytest.mark.parametrize("seed", [11, 2**31 + 5])
def test_benchmark_reference_equals_the_tests_reference(bench_cfg, seed):
    """The cell's ``Reference`` and ``tests/knn_reference.py`` are two
    copies of one plain reference: same table, same top-k, on a stream
    of the cell's own batches; and a sound table passes ``compare``."""
    cfg = _tiny_cfg()
    stream = bench_cfg.Stream(cfg, seed, lanes=2)
    load = stream.load()
    ref = bench_cfg.Reference(stream)
    mine = KnnReference(cfg["doc_slots"], cfg["dim"], cfg["k"], np.int8)
    for tick in load:
        for source, b, _bid in tick:
            (mine.apply_queries if source == "queries" else mine.apply)(
                b.keys, b.values, b.weights)
    for i in range(30):
        m = stream.next(i % 2)
        d = m.delta
        assert m.rows == len(d) == 16 and d.values.dtype == np.int8
        assert len(set(d.keys.tolist())) == 10       # an id, one operation
        assert (d.keys % 2 == i % 2).all()           # a lane's own ids
        ref.apply(m.ref)
        mine.apply(d.keys, d.values, d.weights)
    want = ref.expected()
    assert want["live"].sum() == cfg["corpus"]
    np.testing.assert_array_equal(want["live"], mine.live)
    np.testing.assert_array_equal(want["table"][mine.live],
                                  mine.table[mine.live])
    top = mine.topk()
    for q in range(cfg["queries"]):
        np.testing.assert_array_equal(want["ids"][q], top[q][:, 0])
        np.testing.assert_allclose(want["scores"][q], top[q][:, 1],
                                   atol=1e-12)
    assert all(c.ok for c in bench_cfg.compare(cfg, want, want))


@pytest.mark.parametrize("seed", [11, 2**31 + 5, 77])
def test_lower_precision_control_fails_compare(bench_cfg, seed):
    """Scores accumulated in bfloat16, the nearest precision below the
    float32 the configuration states, are not ``correct``: by the score
    limit, not by the exact table checks."""
    cfg = dict(_tiny_cfg(), dim=768, corpus=4096, doc_slots=8192,
               clusters=16, queries=16, k=16, load_rows_per_tick=2048)
    stream = bench_cfg.Stream(cfg, seed, lanes=2)
    stream.load()
    ref = bench_cfg.Reference(stream)
    for i in range(6):
        ref.apply(stream.next(i % 2).ref)
    want = ref.expected()
    control = {c.name: c for c in bench_cfg.compare(
        cfg, ref.expected("bfloat16"), want)}
    assert control["corpus_rows_mismatch"].ok and control["live_mismatch"].ok
    err = control["topk_score_max_abs_err"]
    assert not err.ok and err.value > 3 * err.limit
    # and a dropped row is caught exactly
    short = bench_cfg.Reference(stream)
    short.table, short.live = ref.table.copy(), ref.live.copy()
    short.live[np.flatnonzero(short.live)[0]] = False
    bad = {c.name: c.ok for c in bench_cfg.compare(cfg, short.expected(),
                                                   want)}
    assert not bad["live_mismatch"]


def test_op_and_byte_counts_against_hand_counts():
    model = _bench("bench_knn_model", os.path.join(BENCH, "knn_model.py"))
    import json
    with open(os.path.join(BENCH, "configs", "knn-1m768.json")) as f:
        cfg = json.load(f)
    # 256 queries x 2^20 slots x 768 terms, a multiply and an add each
    assert model.rescan_flops(cfg) == 2 * 256 * 1048576 * 768 \
        == 412_316_860_416
    # the int8 table, a live byte a slot, the bf16 queries
    assert model.rescan_bytes(cfg) == 805_306_368 + 1_048_576 + 393_216
    v5e = model.peaks("TPU v5 lite")
    assert v5e == {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}
    assert model.rescan_floor_s(cfg, "TPU v5 lite") == pytest.approx(
        412_316_860_416 / 197e12) == pytest.approx(2.093e-3, rel=1e-3)
    assert 806_748_160 / 819e9 < 412_316_860_416 / 197e12   # MXU-bound
    # [256, 16 + 8192] float32 in; [256, 16] float32 + int32 out
    assert model.topk_call_bytes(cfg) == 256 * 8208 * 4 + 256 * 16 * 8 \
        == 8_437_760
    with pytest.raises(ValueError, match="no peaks"):
        model.peaks("TPU v9")
