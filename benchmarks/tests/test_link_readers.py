"""The two readers of the ingest link's counters (PR 40), each on a
small hand-made list of recorded ``rpc_ops`` events: the ``link`` tables
differenced a handler track and summed, a handler born mid-window
counted from zero, a cell where every fate went by ``resolve``, and a
program whose events have no such table (the parent of PR 40), on which
both read ``None`` and the accepted ``rpc_ops`` readers read on."""

import types

import pytest

import manifest as mf
import rpc_link
from run import Run


def _reader(name, cell="tfidf-wiki.edits-backlog"):
    cell = mf.Cell(mf.load_manifest(), cell)
    return mf.load_module(cell.reader_file(name), name)


def _run(spans):
    return Run(spans=spans, t_open=100.0, t_close=200.0, trace=None,
               joined=types.SimpleNamespace(batches=[]))


def _ops(t, track, since, link=None, submit=(10, 0.1, 0.01, 10)):
    args = {"since": since, "ops": {"submit": list(submit)}}
    if link is not None:
        args["link"] = dict(zip(rpc_link.KEYS, link))
    return {"name": "rpc_ops", "t0": t, "t1": t, "track": track,
            "args": args}


def _events():
    return [
        # there before the window: the last table inside it less the
        # table as the window opened
        _ops(60.0, "rpc-serve/1", 20.0, (900, 100, 0, 90)),
        _ops(99.5, "rpc-serve/1", 20.0, (1000, 120, 2, 100)),
        _ops(150.0, "rpc-serve/1", 20.0, (2500, 620, 350, 180)),
        _ops(199.5, "rpc-serve/1", 20.0, (4000, 1120, 802, 200)),
        _ops(200.5, "rpc-serve/1", 20.0, (9999, 9999, 9999, 9999)),
        # born mid-window, after a link reset: from zero, one event is
        # enough
        _ops(180.0, "rpc-serve/17", 160.0, (640, 200, 150, 50)),
        # a single event and a table older than the window: no
        # difference to take
        _ops(120.0, "rpc-serve/2", 30.0, (7777, 777, 77, 7)),
        # no event before the window: last less first inside
        _ops(101.0, "rpc-serve/3", 40.0, (30, 10, 1, 4)),
        _ops(190.0, "rpc-serve/3", 40.0, (390, 130, 51, 54)),
    ]


def test_link_readers_sum_tracks_and_count_a_late_handler_from_zero(capsys):
    run = _run(_events())
    m = rpc_link.link_moved(run)
    assert m == {"sock_calls": 3000 + 640 + 360,
                 "frames_in": 1000 + 200 + 120,
                 "fates_on_ack": 800 + 150 + 50,
                 "fates_by_resolve": 100 + 50 + 50}
    assert _reader("rpc_sock_calls_per_request.backlog").read(
        run) == pytest.approx(4000 / 1320)
    assert _reader("rpc_fates_on_ack_pct.backlog").read(
        run) == pytest.approx(100.0 * 1000 / 1200)
    assert _reader("rpc_fates_on_ack_pct.paced",
                   "tfidf-wiki.edits-paced").read(run) == pytest.approx(
        100.0 * 1000 / 1200)
    said = capsys.readouterr().out
    # said once, whoever reads first
    assert said.count("rpc link over 3 handler tracks: 4000 socket calls "
                      "for 1320 requests; 1200 fates of pending tickets, "
                      "1000 on a later submit's ack and 200 by resolve") == 1


def test_every_fate_by_resolve_no_fate_and_no_request():
    # a lane that only polls: 0 %, not nothing
    polled = [_ops(99.0, "rpc-serve/1", 20.0, (10, 2, 0, 0)),
              _ops(190.0, "rpc-serve/1", 20.0, (310, 102, 0, 60))]
    run = _run(polled)
    assert _reader("rpc_fates_on_ack_pct.backlog").read(run) == 0.0
    assert _reader("rpc_sock_calls_per_request.backlog").read(
        run) == pytest.approx(3.0)
    # no ticket was ever pending (every ack terminal): a share of nothing
    none = [_ops(99.0, "rpc-serve/1", 20.0, (10, 2, 0, 0)),
            _ops(190.0, "rpc-serve/1", 20.0, (40, 12, 0, 0))]
    run = _run(none)
    assert _reader("rpc_fates_on_ack_pct.backlog").read(run) is None
    assert _reader("rpc_sock_calls_per_request.backlog").read(
        run) == pytest.approx(3.0)
    # nobody served inside the window (producers prefilled before it):
    # only the 0.2 s poll slices' calls, over no request
    idle = [_ops(99.0, "rpc-serve/1", 20.0, (800, 256, 0, 0)),
            _ops(190.0, "rpc-serve/1", 20.0, (1250, 256, 0, 0))]
    assert _reader("rpc_sock_calls_per_request.backlog").read(
        _run(idle)) is None


def test_parent_program_reads_none_and_the_accepted_readers_read_on():
    """The parent of PR 40 records ``rpc_ops`` with no ``link`` table:
    both new readers return ``None`` and raise nothing, and the accepted
    readers of the same events are not disturbed by the new key."""
    bare = [_ops(99.0, "rpc-serve/1", 20.0, submit=(0, 0.0, 0.0, 0)),
            _ops(190.0, "rpc-serve/1", 20.0, submit=(800, 8.0, 0.02, 100))]
    names = ("rpc_sock_calls_per_request.backlog",
             "rpc_fates_on_ack_pct.backlog")
    for spans in ([], bare):
        run = _run(spans)
        assert rpc_link.link_moved(run) is None
        assert [_reader(n).read(run) for n in names] == [None, None]
    assert _reader("rpc_submit_cpu_us.backlog").read(
        _run(bare)) == pytest.approx(200.0)
    keyed = [_ops(99.0, "rpc-serve/1", 20.0, (1, 1, 0, 0),
                  submit=(0, 0.0, 0.0, 0)),
             _ops(190.0, "rpc-serve/1", 20.0, (2401, 801, 700, 90),
                  submit=(800, 8.0, 0.02, 100))]
    run = _run(keyed)
    assert _reader("rpc_submit_cpu_us.backlog").read(run) == pytest.approx(
        200.0)
    assert _reader("rpc_sock_calls_per_request.backlog").read(
        run) == pytest.approx(3.0)


def test_manifest_lists_the_link_metrics_where_they_are_read():
    man = mf.load_manifest()
    assert mf.problems(man) == []
    by = {m["name"]: m for m in man["per_layer"]}
    for stem, better, unit in (("rpc_sock_calls_per_request", "lower",
                                "calls"),
                               ("rpc_fates_on_ack_pct", "higher", "%")):
        b, p = by[stem + ".backlog"], by[stem + ".paced"]
        # the cells whose producers submit inside the window
        assert b["workloads"] == by["rpc_submit_cpu_us.backlog"]["workloads"]
        assert p["workloads"] == ["tfidf-wiki.edits-paced"]
        assert (b["moves"], p["moves"]) == ("rows_per_s", "fresh_p50_ms")
        assert b["better"] == p["better"] == better
        assert b["unit"] == p["unit"] == unit
        assert b["source"] == p["source"] == "program_counter"
        assert b["layer"] == p["layer"] == "ingest RPC"
    # appended: nothing that was there moved
    names = [m["name"] for m in man["per_layer"]]
    assert names[-4:] == ["rpc_sock_calls_per_request.backlog",
                          "rpc_sock_calls_per_request.paced",
                          "rpc_fates_on_ack_pct.backlog",
                          "rpc_fates_on_ack_pct.paced"]
