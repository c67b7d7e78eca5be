"""The join of the generator's log with the device-completion log."""

import pytest

import measure


def _logs():
    # two windows of two batches; the device finishes each 3 s after its
    # dispatch, the acks come 10 ms after it: an ack is not a result
    windows = [
        {"ix": 0, "tick_lo": 0, "tick_hi": 2, "dispatch0": 10.0,
         "dispatch1": 10.1, "ready": 13.0},
        {"ix": 1, "tick_lo": 2, "tick_hi": 4, "dispatch0": 10.2,
         "dispatch1": 10.3, "ready": 16.0},
    ]
    batches = [
        {"id": f"b{i}", "rows": 100, "due": 9.0 + 0.1 * i, "sent": 9.0,
         "ack": 10.11 if i < 2 else 10.31, "status": "applied",
         "tick": i + 1} for i in range(4)]
    return batches, windows


def test_an_early_ack_does_not_shorten_freshness():
    batches, windows = _logs()
    j = measure.join(batches, windows)
    assert [b["window"] for b in j.batches] == [0, 0, 1, 1]
    # done is the device completion, not the ack that came 2.9 s earlier
    assert [b["done"] for b in j.batches] == [13.0, 13.0, 16.0, 16.0]
    fresh = measure.freshness_ms(j, 0.0, 100.0)
    assert fresh == pytest.approx([4000.0, 3900.0, 6800.0, 6700.0])
    # had it ended at the ack, the first would read 1110 ms
    assert min(fresh) > 1e3 * (10.11 - 9.0)


def test_a_late_ack_counts_too():
    batches, windows = _logs()
    batches[1]["ack"] = 14.5            # after its window was ready
    j = measure.join(batches, windows)
    assert j.batches[1]["done"] == 14.5
    assert j.windows[0]["done"] == 14.5


def test_rate_runs_between_completions_not_acks():
    batches, windows = _logs()
    j = measure.join(batches, windows)
    r = measure.completion_rate(j, 0.0, 100.0)
    # rows of the windows done in (13, 16] over 3 s: window 1 only
    assert r["rows"] == 200 and r["span_s"] == pytest.approx(3.0)
    assert r["rows_per_s"] == pytest.approx(200 / 3.0)
    # a rate from acks would have been 200 rows / 0.2 s
    assert r["rows_per_s"] < 100
    assert measure.completion_rate(j, 0.0, 14.0) is None   # one window


def test_a_stall_outside_the_two_completions_shows_as_the_edge():
    """The rate does not move when the run stalls before its first or
    after its last completion; ``edge_s`` against ``median_gap_s`` is
    what the run is held to instead."""
    batches, windows = _logs()
    j = measure.join(batches, windows)
    steady = measure.completion_rate(j, 10.0, 17.0)
    stalled = measure.completion_rate(j, 10.0, 40.0)     # nothing after 16
    assert stalled["rows_per_s"] == steady["rows_per_s"]
    assert steady["median_gap_s"] == pytest.approx(3.0)
    assert steady["edge_s"] == pytest.approx(3.0)        # 10 -> 13
    assert stalled["edge_s"] == pytest.approx(24.0)      # 16 -> 40
    assert stalled["edge_s"] > 2 * stalled["median_gap_s"] + 0.05 * 30


def test_unapplied_and_uncompleted_batches_are_never_done():
    batches, windows = _logs()
    batches[0]["ack"] = None
    windows[1]["ready"] = None
    j = measure.join(batches, windows)
    assert [b["done"] for b in j.batches] == [None, 13.0, None, None]
    assert measure.freshness_ms(j, 0.0, 100.0) == pytest.approx([3900.0])


def test_percentile_is_numpys_linear_rule():
    np = pytest.importorskip("numpy")
    xs = [5.0, 1.0, 9.0, 3.0, 7.0, 2.0]
    for q in (0, 10, 50, 90, 100):
        assert measure.percentile(xs, q) == pytest.approx(
            float(np.percentile(xs, q)))
    with pytest.raises(ValueError):
        measure.percentile([], 50)
