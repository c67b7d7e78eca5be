"""``loop_join_probed_pct`` (PR 44) on small hand-made lists of
``window_device`` spans: on a run whose join keeps eleven counters,
across a wrap of the int32 ``probes``, and on the parent of PR 44, whose
join keeps ten and on which it reads ``None`` while the accepted readers
of the same spans read on."""

import pytest

import manifest as mf
from test_sssp_readers import CELL, _reader, _run


def _done(t, passes, ticks, sweeps, probes=None, swept=0, pairs=1):
    """A ``window_device`` span that ended at ``t`` with the loop's, the
    join's and the minimum's counters at these levels; ``probes`` None:
    a join of ten counters."""
    join = [pairs, pairs, 0, 0, 0, 0, sweeps, swept, pairs, 0]
    if probes is not None:
        join.append(probes)
    return {"name": "window_device", "t0": t - 0.4, "t1": t,
            "track": "device/sssp", "args": {"counters": {
                "dist": [passes, ticks, 0], "relax": join,
                "best": [1, 0, 1, 1]}}}


def test_probed_share_over_the_windows_passes():
    """The last span the device finished before the window opened to
    the last inside it: 16 passes with a left delta, 14 through the
    view and 2 swept."""
    spans = [
        _done(60.0, 10, 1, 5, 4),
        _done(99.0, 30, 4, 9, 17),
        _done(130.0, 37, 5, 9, 23),
        _done(160.0, 43, 6, 11, 26),
        _done(199.0, 49, 7, 11, 31),
        _done(201.0, 99, 8, 50, 41),
    ]
    run = _run(spans)
    assert _reader("loop_join_probed_pct").read(run) == pytest.approx(
        100 * 14 / 16)
    assert _reader("fixpoint_passes_per_tick").read(
        run) == pytest.approx(19 / 3)


def test_probes_across_a_wrap_of_the_counter():
    def i32(x):
        x %= 1 << 32
        return x - (1 << 32) if x >= 1 << 31 else x

    at = (1 << 31) - 5
    spans = [_done(99.0, 10, 1, 3, i32(at)),
             _done(150.0, 30, 2, 4, i32(at + 19)),
             _done(199.0, 60, 3, 4, i32(at + 49))]
    assert spans[1]["args"]["counters"]["relax"][10] < 0
    assert _reader("loop_join_probed_pct").read(
        _run(spans)) == pytest.approx(100 * 49 / 50)


def test_the_parent_reads_none_and_the_accepted_readers_read_on():
    """Ten counters on the join (the parent of PR 44): no such metric,
    nothing raised; so with no spans at all, and with one span of the
    window's still short of the eleventh counter; no pass with a left
    delta in the window reads None too. The readers the cell already
    had are not disturbed by the eleventh entry."""
    read = _reader("loop_join_probed_pct").read
    parent = [_done(99.0, 30, 4, 26, swept=1000, pairs=10),
              _done(150.0, 44, 6, 38, swept=9400, pairs=24)]
    for spans in ([], parent[:1], parent):
        assert read(_run(spans)) is None
    mixed = [parent[0], _done(150.0, 44, 6, 27, 11, swept=9400, pairs=24)]
    assert read(_run(mixed)) is None
    change = [_done(99.0, 30, 4, 26, 0, swept=1000, pairs=10), mixed[1]]
    for spans in (parent, change):
        run = _run(spans)
        assert _reader("fixpoint_passes_per_tick").read(
            run) == pytest.approx(7.0)
        assert _reader("loop_join_swept_rows_per_pair").read(
            run) == pytest.approx(600.0)
    assert read(_run(change)) == pytest.approx(100 * 11 / 12)
    idle = [_done(99.0, 30, 4, 26, 5), _done(150.0, 32, 6, 26, 5)]
    assert read(_run(idle)) is None


def test_manifest_lists_the_metric_in_its_cell_alone():
    man = mf.load_manifest()
    assert mf.problems(man) == []
    by = {m["name"]: m for m in man["per_layer"]}
    m = by["loop_join_probed_pct"]
    assert m == dict(by["sssp_pass_roofline_pct"],
                     name="loop_join_probed_pct", source="program_counter")
    assert m["workloads"] == [CELL]
