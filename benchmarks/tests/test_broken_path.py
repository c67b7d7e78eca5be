"""Drive a whole run with the timed path broken underneath and see
``correct`` come out false: the executor leaves out a part of one
window's batch on its way into the device queue. The WAL still holds
what was acknowledged, so it is the comparison with the reference that
has to catch it."""

import argparse
import os

import pytest

import manifest as mf

CELLS = [w["name"] for w in mf.load_manifest()["workloads"]]


def _args(cell):
    return argparse.Namespace(workload=cell, seed=31, seconds=1.0, trace=0,
                              tiny=True)


@pytest.mark.parametrize("cell", [CELLS[0], CELLS[1]])
def test_a_window_that_drops_rows_is_not_correct(cell, capsys):
    if os.environ.get("JAX_PLATFORMS") != "cpu":
        pytest.skip("the rehearsal needs JAX_PLATFORMS=cpu stated")
    import run

    seen = {"n": 0, "dropped": 0}

    def tamper(fe, sched, ex, srv):
        stage = ex.stage_window

        def broken(plan, feeds, max_iters):
            seen["n"] += 1
            if seen["n"] == 4:          # after the warm-up windows
                feeds = [dict(f) for f in feeds]
                nid, b = next(iter(feeds[0].items()))
                cut = type(b)(b.keys, b.values, b.weights * 0)
                feeds[0][nid] = cut
                seen["dropped"] = len(b)
            return stage(plan, feeds, max_iters)

        ex.stage_window = broken

    res = run.run_cell(_args(cell), tamper=tamper)
    out = capsys.readouterr().out
    assert seen["dropped"] > 0
    assert res["correct"] is False
    assert "check acked_batches_not_in_wal: 0.0 (limit 0.0) -> ok" in out
    assert "-> FAIL" in out


def test_a_link_reset_inside_the_window_is_delivery_at_work(capsys):
    """Every link is cut while the window is open. The producers dial
    again and send again what was in flight; the leader refuses the
    second copies (DEDUPED). That is the delivery guarantee working: no
    batch counts as failed, every one is in the log once, the refused
    tickets are joined to their windows through the log, and the state
    equals the reference. A closed-loop lane stands still while its
    link is down: it may not run through what it minted with nothing on
    the wire (which once left the leader fed by one long resubmission
    and the rate without its last seconds)."""
    if os.environ.get("JAX_PLATFORMS") != "cpu":
        pytest.skip("the rehearsal needs JAX_PLATFORMS=cpu stated")
    import threading
    import time

    import run

    cut = {"links": 0}

    def tamper(fe, sched, ex, srv):
        def cutter():
            start = None
            while cut["links"] == 0:
                time.sleep(0.002)
                lanes = list(srv._conns)
                if len(lanes) < 3:          # the generator has not dialled
                    continue
                start = start or fe.admitted
                if fe.admitted - start > 200:
                    for c in lanes:
                        c.close()
                    cut["links"] = len(lanes)

        threading.Thread(target=cutter, daemon=True).start()

    res = run.run_cell(_args(CELLS[1]), tamper=tamper)
    out = capsys.readouterr().out
    assert cut["links"] >= 3
    assert "sent twice after a link reset" in out, out[-3000:]
    assert "the log's ticks agree" in out
    assert res["failed"] == 0 and res["attempted"] > 0
    assert "sent every minted batch" not in out
    for name in ("tf_mismatches", "df_mismatches", "ndocs_mismatches",
                 "acked_batches_not_in_wal", "batches_logged_twice",
                 "tickets_not_applied",
                 "batches_without_device_completion"):
        assert f"check {name}: 0.0 (limit 0.0) -> ok" in out, name
