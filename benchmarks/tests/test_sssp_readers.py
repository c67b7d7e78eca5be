"""The SSSP cell's counter readers on small hand-made lists of
``window_device`` spans: ``minmax_merged_slots_per_pass`` (PR 42) on a
run whose minimum keeps four counters, across a wrap of the int32
``merged_slots``, and on the parent of PR 42, whose minimum keeps three
and on which it reads ``None`` while the accepted readers of the same
spans read on."""

import types

import pytest

import manifest as mf
from run import Run

CELL = "sssp-graph500.inserts-backlog"


def _reader(name):
    cell = mf.Cell(mf.load_manifest(), CELL)
    return mf.load_module(cell.reader_file(name), name)


#: ``sssp_model`` remembers what it computed by a run's ``id``: every
#: run made here is kept, so that no two share one
_RUNS = []


def _run(spans):
    _RUNS.append(Run(spans=spans, t_open=100.0, t_close=200.0, trace=None,
                     joined=types.SimpleNamespace(batches=[])))
    return _RUNS[-1]


def _done(t, passes, ticks, best, swept=0, pairs=1):
    """A ``window_device`` span that ended at ``t`` with the loop's, the
    join's and the minimum's counters at these levels."""
    join = [pairs, pairs, 0, 0, 0, 0, passes - ticks, swept, pairs]
    return {"name": "window_device", "t0": t - 0.4, "t1": t,
            "track": "device/sssp", "args": {"counters": {
                "dist": [passes, ticks, 0], "relax": join,
                "best": list(best)}}}


def test_merged_slots_over_the_windows_passes():
    """The last span the device finished before the window opened to
    the last inside it: 3 ticks of 19 passes; 17 on the first rung, one
    on the second, one on the third."""
    K = 1 << 16
    spans = [
        _done(60.0, 10, 1, (5, 0, 10, 10 * K)),
        _done(99.0, 30, 4, (9, 0, 30, 40 * K)),
        _done(130.0, 37, 5, (12, 0, 37, 47 * K)),
        _done(160.0, 43, 6, (15, 1, 44, 47 * K + 5 * K + 4 * K)),
        _done(199.0, 49, 7, (20, 1, 53, 56 * K + 5 * K + 16 * K)),
        _done(201.0, 99, 8, (99, 9, 99, 999 * K)),
    ]
    run = _run(spans)
    assert _reader("minmax_merged_slots_per_pass").read(
        run) == pytest.approx((17 + 4 + 16) * K / 19)
    assert _reader("fixpoint_passes_per_tick").read(
        run) == pytest.approx(19 / 3)


def test_merged_slots_across_a_wrap_of_the_counter():
    """int32 as the span carries it: a hub's tick of whole-delta merges
    takes the counter past 2^31 (negative) and past 2^32 (small again);
    each step is differenced modulo 2^32."""
    C = 1 << 22

    def i32(x):
        x %= 1 << 32
        return x - (1 << 32) if x >= 1 << 31 else x

    at = (1 << 31) - 3 * C
    spans = [
        _done(99.0, 10, 1, (1, 0, 1, i32(at))),
        _done(140.0, 20, 2, (2, 0, 2, i32(at + 10 * C))),
        _done(180.0, 530, 3, (3, 0, 3, i32(at + 520 * C))),
        _done(199.0, 540, 4, (4, 0, 4, i32(at + 520 * C + 10 * 65536))),
    ]
    assert spans[1]["args"]["counters"]["best"][3] < 0
    assert 0 <= spans[2]["args"]["counters"]["best"][3] < at
    assert _reader("minmax_merged_slots_per_pass").read(
        _run(spans)) == pytest.approx((520 * C + 10 * 65536) / 530)


def test_the_parent_reads_none_and_the_accepted_readers_read_on():
    """Three counters on the minimum (the parent of PR 42): no such
    metric, nothing raised; so with no spans at all, and with one span
    of the window's still short of the fourth counter. The readers the
    cell already had are not disturbed by the fourth entry."""
    read = _reader("minmax_merged_slots_per_pass").read
    parent = [_done(99.0, 30, 4, (9, 0, 30), swept=1000, pairs=10),
              _done(150.0, 44, 6, (15, 1, 44), swept=9400, pairs=24)]
    for spans in ([], parent[:1], parent):
        assert read(_run(spans)) is None
    mixed = [parent[0], _done(150.0, 44, 6, (15, 1, 44, 777),
                              swept=9400, pairs=24)]
    assert read(_run(mixed)) is None
    change = [_done(99.0, 30, 4, (9, 0, 30, 640), swept=1000, pairs=10),
              mixed[1]]
    for spans in (parent, change):
        run = _run(spans)
        assert _reader("fixpoint_passes_per_tick").read(
            run) == pytest.approx(7.0)
        assert _reader("loop_join_swept_rows_per_pair").read(
            run) == pytest.approx(600.0)
    assert read(_run(change)) == pytest.approx(137 / 14)


def test_manifest_lists_the_metric_in_its_cell_alone():
    man = mf.load_manifest()
    assert mf.problems(man) == []
    by = {m["name"]: m for m in man["per_layer"]}
    m = by["minmax_merged_slots_per_pass"]
    assert man["per_layer"][-1] is m
    assert m == dict(by["loop_join_swept_rows_per_pair"],
                     name="minmax_merged_slots_per_pass", unit="slots")
    assert m["workloads"] == [CELL]
