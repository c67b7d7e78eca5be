"""The ``tpch-q3`` configuration's stream (a seed gives the same rows
again, lanes own disjoint order keys, a batch deletes nothing it
inserts, a pair fits a tick) and the cell's counter readers on small
hand-made lists of ``window_device`` and ``join_reindex`` spans:
``join_reindexes_per_window`` and ``join_retracted_rows_pct`` (PR 43),
on the parent of PR 43, whose joins keep nine counters and record no
reindex span, and across a compaction, where the arenas' level falls.
The readers that need the device trace are held by the traced rehearsal
(``test_rehearsal.py``), which runs every cell of the manifest."""

import types

import numpy as np
import pytest

import manifest as mf
from conftest import tiny_cell
from run import Run

CELL = "tpch-q3.refresh-backlog"
NEW = ("tpch_tick_ms", "tpch_tick_roofline_pct", "join_reindex_share_pct",
       "join_reindex_roofline_pct", "join_reindexes_per_window",
       "join_retracted_rows_pct")


def _reader(name):
    cell = mf.Cell(mf.load_manifest(), CELL)
    return mf.load_module(cell.reader_file(name), name)


# -- the stream -------------------------------------------------------------


def test_stream_is_seeded_and_lanes_are_disjoint():
    cfg, traffic, mod = tiny_cell("tpch-q3", "refresh-backlog")
    lanes = traffic["producers"]
    a, b = (mod.Stream(cfg, 2**31 + 21, lanes) for _ in range(2))
    other = mod.Stream(cfg, 2**31 + 22, lanes)
    per_lane = cfg["order_keys"] // lanes
    live = [set() for _ in range(lanes)]
    for i in range(200):
        for lane in range(lanes):
            m, m2, m3 = a.next(lane), b.next(lane), other.next(lane)
            assert np.array_equal(m.delta.values, m2.delta.values)
            assert np.array_equal(m.delta.keys, m2.delta.keys)
            assert not np.array_equal(m.delta.values[:4],
                                      m3.delta.values[:4])
            assert m.rows == len(m.delta) <= cfg["batch_rows"]
            assert m.delta.values.shape[1] * 4 == 112
            v, w = m.delta.values, m.delta.weights
            assert set(np.unique(w)) == {-1, 1}
            assert ((v[:, 1] - 1) // per_lane == lane).all()
            orders = v[:, 0] == mod.ORDERS
            ins = set(v[orders & (w > 0), 1].tolist())
            dele = set(v[orders & (w < 0), 1].tolist())
            assert len(ins) == len(dele) > 0 and not ins & dele
            assert not ins & live[lane]
            live[lane] |= ins
            live[lane] -= dele
            # every lineitem travels with its order, inserted or deleted
            lines = v[:, 0] == mod.LINEITEM
            assert set(v[lines, 1].tolist()) <= ins | dele
            assert lines.sum() + orders.sum() == len(v)


def test_history_is_customers_then_orders_in_key_order():
    cfg, traffic, mod = tiny_cell("tpch-q3", "refresh-backlog")
    stream = mod.Stream(cfg, 5, traffic["producers"])
    keys, custs = [], 0
    for tick in stream.load():
        for source, batch, _bid in tick:
            assert source == "changes" and (batch.weights == 1).all()
            v = batch.values
            custs += int((v[:, 0] == mod.CUSTOMER).sum())
            assert (v[:, 0] == mod.CONT).sum() == (
                v[:, 0] == mod.CUSTOMER).sum()
            keys.append(v[v[:, 0] == mod.ORDERS, 1])
    keys = np.concatenate(keys)
    assert custs == cfg["customers"] and len(keys) == cfg["orders"]
    assert (np.diff(keys) > 0).all() and keys.max() < cfg["order_keys"]
    assert ((keys - 1) % 32 < 8).all()         # 8 of every 32 keys


# -- the counter readers ------------------------------------------------------

_RUNS = []


def _run(spans):
    _RUNS.append(Run(spans=spans, t_open=100.0, t_close=200.0, trace=None,
                     joined=types.SimpleNamespace(batches=[], windows=[])))
    return _RUNS[-1]


def _done(t, ticks, orders, lines, n=10):
    """A ``window_device`` span that ended at ``t``: each join's
    (arena_rows, index_rebuilds, retracted) at these levels."""
    def vec(rows, rebuilds, retracted):
        v = [7, 1, rows, rebuilds, rebuilds, 0, 0, 0, 0, retracted]
        return v[:n]
    return {"name": "window_device", "t0": t - 0.1, "t1": t,
            "track": "device/tpch",
            "args": {"ticks": ticks, "counters": {
                "q3_orders": vec(*orders), "q3_join": vec(*lines)}}}


def _reindex(t, node, before, after):
    return {"name": "join_reindex", "t0": t, "t1": t + 0.5,
            "track": "pump", "args": {"node": node, "rows_before": before,
                                      "rows_after": after}}


def test_reindexes_and_retracted_share_across_a_compaction():
    """From the last window done before the window opened (99) to the
    last inside it (180): the lineitem join rebuilt twice and the orders
    join once, over 81 s; the arenas took in 300 + 100 rows more than
    their level shows, because a compaction took them out."""
    spans = [
        _done(99.0, 8, (1000, 0, 40), (5000, 1, 400)),
        _done(120.0, 8, (1100, 0, 90), (5600, 1, 700)),
        _reindex(121.0, "q3_join", 5600, 5300),
        _done(150.0, 8, (1200, 0, 140), (5900, 2, 1000)),
        _reindex(151.0, "q3_orders", 1200, 1100),
        _reindex(152.0, "q3_join", 5900, 5400),
        _done(180.0, 8, (1200, 1, 190), (6000, 3, 1300)),
        _done(201.0, 8, (9999, 9, 9999), (9999, 9, 9999)),
    ]
    run = _run(spans)
    assert _reader("join_reindexes_per_window").read(
        run) == pytest.approx(40.0 * 3 / 81.0)
    appended = (1200 - 1000 + 100) + (6000 - 5000 + 300 + 500)
    assert _reader("join_retracted_rows_pct").read(
        run) == pytest.approx(100.0 * (150 + 900) / appended)


def test_the_parent_reads_none():
    """Nine counters a join and no reindex span (the parent of PR 43),
    no span at all, one span: no metric, nothing raised; the readers
    that need a trace return None without one."""
    parent = [_done(99.0, 8, (1000, 0, 0), (5000, 0, 0), n=9),
              _done(150.0, 8, (1100, 0, 0), (5600, 0, 0), n=9)]
    for spans in ([], parent[:1], parent):
        run = _run(spans)
        for name in NEW:
            assert _reader(name).read(run) is None
    change = [_done(99.0, 8, (1000, 0, 40), (5000, 1, 400))]
    assert _reader("join_reindexes_per_window").read(_run(change)) is None


def test_manifest_lists_the_cell_and_its_metrics():
    man = mf.load_manifest()
    assert mf.problems(man) == []
    by = {m["name"]: m for m in man["per_layer"]}
    for name in NEW:
        assert by[name]["workloads"] == [CELL]
        assert by[name]["moves"] == "rows_per_s"
        assert by[name]["layer"] == by["nexmark_tick_ms"]["layer"]
    cell = mf.Cell(man, CELL)
    assert {m["name"] for m in cell.end_to_end} == {"rows_per_s", "setup_s"}
    config = {c["name"]: c for c in man["configs"]}[cell.config_name]
    assert config["reduced"] == ["orders"] and cell.chips == 1
