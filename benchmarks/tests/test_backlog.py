"""The depth of the backlog mixes: the reader that says how far a cell
is from running dry, and that deepening a mix leaves its head as it
was (``test_manifest.py`` holds that a backlog divides by its lanes)."""

from types import SimpleNamespace

import numpy as np
import pytest

import manifest as mf
import measure
import traffic_plan as tp
from conftest import tiny_cell


def _run(minted, inside, after=0, arrivals="closed", producers=4):
    """A run whose windows hold one batch each: ``inside`` complete
    inside the window [100, 140], ``after`` once it has closed."""
    wins = [{"ix": i, "n_batches": 1, "rows": 512,
             "done": 100.0 + 40.0 * (i + 1) / max(inside, 1)}
            for i in range(inside)]
    wins += [{"ix": inside + i, "n_batches": 1, "rows": 512,
              "done": 141.0 + i} for i in range(after)]
    wins.append({"ix": len(wins), "n_batches": 1, "rows": 512,
                 "done": None})          # never completed: never counted
    traffic = {"arrivals": arrivals, "producers": producers,
               "batches": minted, "rate_per_s": 100.0}
    return SimpleNamespace(traffic=traffic, seconds=40.0, t_open=100.0,
                           t_close=140.0,
                           joined=measure.Joined([], wins))


def _reader():
    name = "backlog_headroom_x.backlog"
    cell = mf.Cell(mf.load_manifest(), "knn-1m768.reembed-backlog")
    return mf.load_module(cell.reader_file(name), name)


def test_headroom_is_minted_over_completed_inside():
    read = _reader().read
    assert read(_run(9600, 1800)) == pytest.approx(9600 / 1800)
    # what completes after the close is not the window's
    assert read(_run(9600, 1800, after=50)) == pytest.approx(9600 / 1800)
    # per lane as per_lane counts them: a remainder is not minted
    assert read(_run(9602, 1800)) == pytest.approx(9600 / 1800)
    assert read(_run(256, 213, arrivals="prefilled", producers=1)
                ) == pytest.approx(256 / 213)


def test_headroom_of_nothing_is_nothing():
    read = _reader().read
    assert read(_run(9600, 0)) is None
    assert read(_run(9600, 0, after=10)) is None
    assert read(_run(9600, 1800, arrivals="poisson")) is None


@pytest.mark.parametrize("config,mix", [
    ("knn-1m768", "reembed-backlog"), ("tfidf-wiki", "edits-backlog")])
def test_a_lanes_first_batches_do_not_depend_on_how_many_follow(config,
                                                                  mix):
    """Deepening a mix leaves what today's program is sent unchanged:
    mint 8 and 32 a lane at one seed, the first 8 are equal array for
    array (and so whoever mints a tail later has this to keep)."""
    cfg, traffic, mod = tiny_cell(config, mix)

    def mint(n):
        t = dict(traffic, batches=n * traffic["producers"])
        stream = mod.Stream(cfg, 2**31 + 5, t["producers"])
        stream.load()
        tp.plan_warm(stream, t)
        return tp.mint_traffic(stream, t, 2.0)

    short, long = mint(8), mint(32)
    assert [len(x) for x in short] == [8] * traffic["producers"]
    assert [len(x) for x in long] == [32] * traffic["producers"]
    for a_lane, b_lane in zip(short, long):
        for a, b in zip(a_lane, b_lane):
            assert a.rows == b.rows
            for col in ("keys", "values", "weights"):
                x, y = getattr(a.delta, col), getattr(b.delta, col)
                assert x.dtype == y.dtype and np.array_equal(x, y)
