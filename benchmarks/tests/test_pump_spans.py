"""The readers of the program's own pump, device and RPC spans (PR 24):
each on a small hand-made span list, and the join of the span clock with
a profiler trace on one recorded on a TPU v5e with clock anchors in it
(``record_trace_anchors.py`` says how)."""

import json
import os
import types

import pytest

import manifest as mf
import pump_spans as ps
from run import Run

HERE = os.path.dirname(os.path.abspath(__file__))
RECORDED = os.path.join(HERE, "data", "recorded_anchors.xplane.pb.gz")
SPANS = os.path.join(HERE, "data", "recorded_anchors.spans.json")


def _reader(name):
    cell = mf.Cell(mf.load_manifest(), "tfidf-wiki.edits-paced")
    return mf.load_module(cell.reader_file(name), name)


def _span(name, t0, t1, track="pump", **args):
    return {"name": name, "t0": t0, "t1": t1, "track": track, "args": args}


def _run(spans, batches=(), trace=None):
    return Run(spans=spans, t_open=100.0, t_close=200.0, trace=trace,
               joined=types.SimpleNamespace(batches=list(batches)))


def _cycle(t, win):
    """One 10 ms pump cycle that starts at ``t``: tiled but for 1 ms."""
    return [
        _span("pump_turn", t, t + 0.001, cpu_s=0.0005),
        _span("host_merge", t + 0.001, t + 0.002, cpu_s=0.001, win=win),
        _span("window_stage", t + 0.002, t + 0.005, cpu_s=0.001, win=win),
        _span("queue_write", t + 0.003, t + 0.004, cpu_s=0.0, win=win),
        _span("pump_execute", t + 0.005, t + 0.007, cpu_s=0.0015, win=win),
        _span("window", t + 0.001, t + 0.0075, win=win),     # umbrella
        _span("pump_wait", t + 0.007, t + 0.009, cpu_s=0.0),
        # [t + 0.009, t + 0.010] is under no span
    ]


def test_pump_readers_on_hand_made_cycles():
    spans = [s for k in range(5) for s in _cycle(110.0 + 0.010 * k, k + 1)]
    spans += _cycle(50.0, 0)                 # outside the window: ignored
    run = _run(spans)
    assert _reader("pump_cycle_ms.paced").read(run) == pytest.approx(10.0)
    # 5 cycles, the last one's uncovered tail lies past the last span:
    # 4 ms of 49 ms are under no span
    assert _reader("pump_untiled_pct.paced").read(run) == pytest.approx(
        100.0 * 0.004 / 0.049)
    # outermost working spans a cycle: turn 1 ms (0.5 cpu), merge 1 (1),
    # stage 3 (1), execute 2 (1.5): 4 of 7 ms on the CPU; queue_write is
    # nested, pump_wait and the umbrella are left out
    assert _reader("pump_offcpu_pct.backlog").read(run) == pytest.approx(
        100.0 * 3.0 / 7.0)
    outer = ps.outermost(ps.pump_spans(run))
    assert {s["name"] for s in outer} == {
        "pump_turn", "host_merge", "window_stage", "pump_execute",
        "pump_wait"}


def test_device_rpc_and_ticket_readers():
    ids = ["a", "b", "c"]
    batches = [{"id": i, "due": 120.0 + k} for k, i in enumerate(ids)]
    batches.append({"id": "late", "due": 250.0})
    spans = [_span("window_device", 120.0 + k, 120.0 + k + 0.001 * (k + 3),
                   track="device/default", win=k, queued_s=0.0005)
             for k in range(3)]
    spans += [_span("rpc_serve", 130.0 + k, 130.0 + k + 0.001 * (k + 1),
                    track=f"rpc-serve/{k}", batch_id=i)
              for k, i in enumerate(ids)]
    for k, i in enumerate(ids + ["late"]):
        t = 140.0 + k
        spans += [
            _span("fsync", t, t + 0.025, track=f"ticket/{i}"),
            _span("wire_wait", t, t + 0.020 + 0.001 * k,
                  track=f"ticket/{i}"),
            _span("admit_lock_wait", t - 1, t - 1 + 0.0002 * (k + 1),
                  track=f"ticket/{i}")]
    run = _run(spans, batches)
    assert _reader("window_device_ms.paced").read(run) == pytest.approx(4.0)
    assert _reader("rpc_serve_ms.paced").read(run) == pytest.approx(2.0)
    # the ticket due outside the window does not count
    assert _reader("retire_wait_ms.paced").read(run) == pytest.approx(21.0)
    assert _reader("admit_lock_wait_ms.paced").read(run) == pytest.approx(
        0.4)
    assert _reader("retire_wait_ms.paced").read(run) <= 25.0


def test_readers_find_nothing_on_a_program_without_the_spans():
    """The parent of PR 24 records none of these spans: every reader
    returns None (the metric is left out of the line), none raises."""
    old = [_span("window_stage", 110.0, 110.01), _span("fsync", 111, 112,
                                                       track="ticket/a")]
    for spans in ([], old):
        run = _run(spans, [{"id": "a", "due": 111.0}],
                   trace={"busy_s": 1.0, "window_s": 2.0})
        for name in ("window_device_ms.paced", "retire_wait_ms.paced",
                     "rpc_serve_ms.paced", "admit_lock_wait_ms.paced",
                     "pump_offcpu_pct.backlog",
                     "idle_unexplained_pct.paced"):
            assert _reader(name).read(run) is None, name
    assert _reader("pump_cycle_ms.paced").read(_run([])) is None
    assert _reader("pump_untiled_pct.paced").read(_run([])) is None
    assert ps.clock_offset([(1, 1.0)]) is None


def test_clock_offset_median_and_spread():
    anchors = [(int(1e9 * (10.0 + k)), 510.0 + k + 1e-6 * k)
               for k in range(11)]
    off = ps.clock_offset(anchors)
    assert off["n"] == 11
    assert off["median_s"] == pytest.approx(500.000005, abs=1e-9)
    assert off["spread_s"] == pytest.approx(8e-6, abs=1e-9)


def test_recorded_tpu_trace_with_anchors():
    with open(SPANS) as f:
        spans = json.load(f)
    anchors, busy, (lo, hi) = ps.read_trace(RECORDED)
    assert list(busy) == ["/device:TPU:0"]
    assert len(anchors) == 6
    off = ps.clock_offset(anchors)
    assert off["spread_s"] < 0.5e-3
    run = types.SimpleNamespace(spans=spans)
    got = ps.idle_by_span(run, RECORDED)
    by = got["by_span"]
    assert got["idle_s"] == pytest.approx(sum(by.values()), rel=1e-9)
    assert got["idle_s"] + sum(e - s for s, e in busy["/device:TPU:0"]) \
        == pytest.approx(hi - lo, rel=1e-9)
    # six 2 ms sleeps under pump_wait, five 1 ms sleeps (the sixth ends
    # the stretch) under nothing: both are found, at about their sizes
    assert 0.011 < by["pump_wait"] < 0.016
    assert 0.004 < by["unexplained"] < 0.009
    assert by["pump_execute"] > 0 and by["window_stage"] > 0
    # pinned from the recording (see RECORDED_READINGS below)
    assert off["median_s"] == pytest.approx(
        RECORDED_READINGS["offset_median_s"], abs=1e-9)
    assert off["spread_s"] == pytest.approx(
        RECORDED_READINGS["offset_spread_s"], abs=1e-9)
    assert by["unexplained"] / got["idle_s"] == pytest.approx(
        RECORDED_READINGS["unexplained_share"], rel=1e-6)


RECORDED_READINGS = {"offset_median_s": -32.7042000215,
                     "offset_spread_s": 7.060000015712831e-07,
                     "unexplained_share": 0.20972450470154583}
