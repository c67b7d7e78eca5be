"""The four readers of the program's thread ledger (PR 39), each on a
small hand-made list of recorded events: the differences between the
first and the last ``thread_ledger`` event inside the window, a ledger
with CPU seconds alone (gVisor: no ``schedstat``), a role and a handler
track that appear mid-window, CPU clocks read for one request in eight,
and a program that records none of it (the parent of PR 39)."""

import types

import pytest

import manifest as mf
import thread_ledger as tl
from run import Run

PUMP, HANDLERS = tl.PUMP, tl.HANDLERS


def _reader(name):
    cell = mf.Cell(mf.load_manifest(), "tfidf-wiki.edits-backlog")
    return mf.load_module(cell.reader_file(name), name)


def _span(name, t0, t1, track="pump", **args):
    return {"name": name, "t0": t0, "t1": t1, "track": track, "args": args}


def _run(spans):
    return Run(spans=spans, t_open=100.0, t_close=200.0, trace=None,
               joined=types.SimpleNamespace(batches=[]))


def _row(n, cpu_s, runq_s, vol, invol):
    r = {"n": n, "cpu_s": cpu_s}
    if runq_s is not None:
        r.update(runq_s=runq_s, vol=vol, invol=invol)
    return r


def _ledger(t, roles, process_cpu_s):
    return _span("thread_ledger", t, t, track="proc", roles=roles,
                 process_cpu_s=process_cpu_s, switch_interval_s=0.005,
                 cores=13, read_s=0.002)


def _ledgers(runq=True):
    """Three events inside the window and one before it. Between the
    first and the last inside, 80 s apart: the pump 16 s of CPU, 0.8 s
    of run-queue wait and 8 000 voluntary switches; three handlers 48 s
    together; the committer, born after the first event, 4 s; native
    12 s. The roles' 80 s close on ``process_cpu_s``."""
    q = (lambda x: x) if runq else (lambda x: None)
    early = {PUMP: _row(1, 1.0, q(0.0), 10, 0),
             HANDLERS: _row(3, 2.0, q(0.0), 50, 0),
             "native": {"cpu_s": 3.0}}
    first = {PUMP: _row(1, 2.0, q(0.1), 1000, 3),
             HANDLERS: _row(3, 6.0, q(0.2), 9000, 10),
             "native": {"cpu_s": 5.0}}
    mid = {PUMP: _row(1, 10.0, q(0.5), 5000, 5),
           HANDLERS: _row(3, 30.0, q(0.6), 50000, 30),
           "reflow-wal-committer": _row(1, 2.0, q(0.0), 400, 0),
           "native": {"cpu_s": 11.0}}
    last = {PUMP: _row(1, 18.0, q(0.9), 9000, 7),
            HANDLERS: _row(2, 54.0, q(1.0), 90000, 50),
            "reflow-wal-committer": _row(1, 4.0, q(0.1), 800, 0),
            "native": {"cpu_s": 17.0}}
    return [_ledger(90.0, early, 6.0), _ledger(110.0, first, 13.0),
            _ledger(150.0, mid, 53.0), _ledger(190.0, last, 93.0)]


def _pump_cycles():
    """400 windows of 200 ms between the two events: a stage of 100 ms
    and an execute of 60 ms, 20 ms of CPU each, and a 40 ms idle wait
    that is no working time."""
    spans = []
    for k in range(400):
        t = 110.0 + 0.2 * k
        spans += [
            _span("window_stage", t, t + 0.1, cpu_s=0.02, win=k),
            _span("queue_write", t + 0.01, t + 0.09, cpu_s=0.0, win=k),
            _span("pump_execute", t + 0.1, t + 0.16, cpu_s=0.02, win=k),
            _span("window", t, t + 0.16, win=k),
            _span("pump_wait", t + 0.16, t + 0.2, cpu_s=0.0)]
    # before the first event and after the last: not the ledger's stretch
    spans += [_span("window_stage", 105.0, 105.1, cpu_s=0.02, win=-1),
              _span("window_stage", 195.0, 195.1, cpu_s=0.02, win=-2)]
    return spans


def test_ledger_readers_difference_first_and_last_event_inside(capsys):
    run = _run(_ledgers() + _pump_cycles())
    m = tl.moved(run)
    assert m["t0"] == 110.0 and m["t1"] == 190.0 and m["events"] == 3
    assert m["process_cpu_s"] == pytest.approx(80.0)
    assert sum(r["cpu_s"] for r in m["roles"].values()) == pytest.approx(80.0)
    # a role that began after the first event counts from zero
    assert m["roles"]["reflow-wal-committer"]["cpu_s"] == pytest.approx(4.0)
    # every role but native: 16 + 48 + 4 of 80 s
    assert _reader("python_cpu_pct.backlog").read(run) == pytest.approx(85.0)
    assert _reader("rpc_handlers_cpu_pct.paced").read(run) == pytest.approx(
        60.0)
    said = capsys.readouterr().out
    # one measurement a thread, one a span: 400 x (20 + 20) ms of cpu_s
    assert "pump CPU by the ledger 16.0000 s, by its outermost spans " \
        "16.0000 s (+0.00 %)" in said
    # where the kernel keeps them: 0.8 s of run-queue wait of the 64 s
    # the working spans cover, 8 000 switches over 400 windows
    assert "runnable and waiting for a core 0.800, blocked 47.200; " \
        "20.00 voluntary and 0.01 involuntary switches a window over " \
        "400 windows" in said


def test_cpu_alone_no_events_and_one_event():
    # gVisor: CPU seconds alone; what needs nothing else is still read
    run = _run(_ledgers(runq=False) + _pump_cycles())
    assert _reader("python_cpu_pct.backlog").read(run) == pytest.approx(85.0)
    assert set(tl.moved(run)["roles"][PUMP]) == {"cpu_s"}
    names = ("python_cpu_pct.backlog", "rpc_handlers_cpu_pct.backlog",
             "rpc_submit_cpu_us.backlog", "rpc_resolve_cpu_pct.backlog")
    # the parent of PR 39 records no such event; one event is no stretch
    for spans in (_pump_cycles(), _pump_cycles() + _ledgers()[1:2]):
        run = _run(spans)
        assert [_reader(n).read(run) for n in names] == [None] * 4
    # a ledger with no handlers in it (nobody served)
    bare = _ledgers()
    for s in bare:
        s["args"]["roles"] = {k: v for k, v in s["args"]["roles"].items()
                              if k != HANDLERS}
    run = _run(bare)
    assert _reader("rpc_handlers_cpu_pct.backlog").read(run) is None
    assert _reader("python_cpu_pct.backlog").read(run) == pytest.approx(
        100.0 * 20.0 / 80.0)


def _ops(t, track, since, submit, resolve, other=(2, 0.002, 0.001)):
    """Rows ``(n, busy_s, cpu_s)`` with the clock read for every
    request, or ``(n, busy_s, cpu_s, n_cpu)``."""
    def row(r):
        return list(r) if len(r) == 4 else list(r) + [r[0]]
    return _span("rpc_ops", t, t, track=track, since=since,
                 ops={"submit": row(submit), "resolve": row(resolve),
                      "other": row(other)})


def test_rpc_ops_readers_sum_tracks_and_count_a_late_handler_from_zero(capsys):
    spans = [
        # a handler that was there before the window: the last table
        # inside it less the table as the window opened
        _ops(60.0, "rpc-serve/1", 20.0, (90, 0.9, 0.09), (40, 4.0, 0.01)),
        _ops(99.5, "rpc-serve/1", 20.0, (100, 1.0, 0.10), (50, 5.0, 0.02)),
        _ops(150.0, "rpc-serve/1", 20.0, (600, 6.0, 0.60), (300, 30., 0.12)),
        _ops(199.5, "rpc-serve/1", 20.0, (1100, 11., 1.10), (550, 55., 0.22)),
        _ops(200.5, "rpc-serve/1", 20.0, (1200, 12., 1.20), (600, 60., 0.24)),
        # one born mid-window, after a link reset: from zero, and its
        # one event is enough
        _ops(180.0, "rpc-serve/17", 160.0, (200, 2.0, 0.30), (100, 9.0, 0.03),
             other=(1, 0.001, 0.001)),
        # one with a single event and a table older than the window: no
        # difference to take
        _ops(120.0, "rpc-serve/2", 30.0, (999, 9.0, 9.0), (9, 9.0, 9.0)),
        # one with no event before the window: last less first inside
        _ops(101.0, "rpc-serve/3", 40.0, (10, 0.1, 0.01), (5, 0.5, 0.002)),
        _ops(190.0, "rpc-serve/3", 40.0, (110, 1.1, 0.11), (55, 5.5, 0.022)),
    ]
    run = _run(spans)
    ops = tl.ops_moved(run)
    assert ops["submit"] == pytest.approx([1300, 13.0, 1.40, 1300])
    assert ops["resolve"] == pytest.approx([650, 64.0, 0.25, 650])
    assert ops["other"] == pytest.approx([1, 0.001, 0.001, 1])
    assert _reader("rpc_submit_cpu_us.backlog").read(run) == pytest.approx(
        1e6 * 1.40 / 1300)
    assert _reader("rpc_resolve_cpu_pct.paced").read(run) == pytest.approx(
        100.0 * 0.25 / 1.651)
    # no submit inside the window (producers prefilled before it): the
    # cost of one cannot be read, the shares can
    idle = [_ops(101.0, "rpc-serve/1", 20.0, (256, 1.0, 0.2), (10, 1.0, 0.01)),
            _ops(190.0, "rpc-serve/1", 20.0, (256, 1.0, 0.2), (99, 9.0, 0.05))]
    run = _run(idle)
    assert _reader("rpc_submit_cpu_us.backlog").read(run) is None
    assert _reader("rpc_resolve_cpu_pct.backlog").read(run) == pytest.approx(
        100.0)
    # the handlers' CPU by the ledger against their tables'
    run = _run(_ledgers() + spans)
    assert _reader("rpc_handlers_cpu_pct.backlog").read(
        run) == pytest.approx(60.0)
    assert "48.000 s by the ledger, 1.651 s inside requests" in \
        capsys.readouterr().out
    # the clock read for one request in eight: a row's CPU is its
    # requests times the CPU a request of those
    eighth = [_ops(99.0, "rpc-serve/1", 20.0, (0, 0.0, 0.0, 0),
                   (0, 0.0, 0.0, 0), other=(1, 0.001, 0.001, 1)),
              _ops(190.0, "rpc-serve/1", 20.0, (800, 8.0, 0.02, 100),
                   (400, 40.0, 0.005, 50), other=(1, 0.001, 0.001, 1))]
    run = _run(eighth)
    assert _reader("rpc_submit_cpu_us.backlog").read(run) == pytest.approx(
        200.0)
    assert tl.ops_cpu_s(tl.ops_moved(run)) == pytest.approx(
        {"submit": 0.16, "resolve": 0.04})
    assert _reader("rpc_resolve_cpu_pct.backlog").read(run) == pytest.approx(
        20.0)


def test_manifest_lists_the_new_metrics_where_they_are_read():
    man = mf.load_manifest()
    assert mf.problems(man) == []
    by = {m["name"]: m for m in man["per_layer"]}
    backlog = {w["name"] for w in man["workloads"]
               if w["name"] != "tfidf-wiki.edits-paced"}
    stems = ("python_cpu_pct", "rpc_handlers_cpu_pct", "rpc_submit_cpu_us",
             "rpc_resolve_cpu_pct")
    for stem in stems[:2]:
        assert set(by[stem + ".backlog"]["workloads"]) == backlog
    for stem in stems[2:]:      # producers inside the window
        assert set(by[stem + ".backlog"]["workloads"]) == backlog - {
            "pagerank-1m.churn-backlog"}
    for stem in stems:
        b, p = by[stem + ".backlog"], by[stem + ".paced"]
        assert p["workloads"] == ["tfidf-wiki.edits-paced"]
        assert (b["moves"], p["moves"]) == ("rows_per_s", "fresh_p50_ms")
        assert b["better"] == p["better"] == "lower"
        assert b["source"] == p["source"] == "program_counter"
        assert b["layer"] == p["layer"]
    assert by["python_cpu_pct.backlog"]["layer"] == "leader process"
