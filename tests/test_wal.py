"""Write-ahead delta log: framing, rotation, truncation, and the
crash-recovery differential — a killed/torn/recovered run's sink views
must equal an uninterrupted clean run's (exactly-once across process
death), extending the lossy-transport property of
``test_aux.test_fault_injection_exactly_once`` to crashes."""

import json
import os
import subprocess
import sys

import numpy as np
import pytest

from reflow_tpu import DirtyScheduler
from reflow_tpu.utils.checkpoint import save_checkpoint
from reflow_tpu.utils.faults import (CrashInjector, CrashPoint,
                                     DeliveryError, FaultyChannel,
                                     tear_wal_tail)
from reflow_tpu.utils.metrics import summarize, summarize_wal
from reflow_tpu.wal import (DurableScheduler, WalError, WriteAheadLog,
                            recover, scan_wal)
from reflow_tpu.wal.log import LogPosition, list_segments
from reflow_tpu.workloads import wordcount

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


# -- feed / drive helpers ---------------------------------------------------

def make_feed(seed: int, n_ticks: int = 10):
    """Deterministic per-tick [(batch_id, DeltaBatch)] lists, with
    retraction batches mixed in so the differential exercises the full
    delta algebra, not just inserts."""
    rng = np.random.default_rng(seed)
    feed = []
    for t in range(n_ticks):
        batches = []
        for j in range(int(rng.integers(1, 3))):
            words = " ".join(
                f"w{int(x)}" for x in rng.integers(0, 25,
                                                   int(rng.integers(2, 8))))
            weight = -1 if (t > 2 and rng.random() < 0.2) else 1
            batches.append((f"t{t}b{j}",
                            wordcount.ingest_lines([words], weight=weight)))
        feed.append(batches)
    return feed


def clean_run(feed):
    g, src, sink = wordcount.build_graph()
    sched = DirtyScheduler(g)
    for batches in feed:
        for bid, b in batches:
            sched.push(src, b, batch_id=bid)
        sched.tick()
    return dict(sched.view(sink.name))


def drive(sched, src, feed):
    for batches in feed:
        for bid, b in batches:
            sched.push(src, b, batch_id=bid)
        sched.tick()


def resume_from_cursor(sched, src, feed):
    """What a restarted upstream does: re-send EVERYTHING from its own
    cursor with the same batch ids; the dedup window keeps replayed
    batches from folding twice."""
    drive(sched, src, feed)


# -- log mechanics ----------------------------------------------------------

def test_append_scan_roundtrip(tmp_path):
    wal = WriteAheadLog(str(tmp_path), fsync="record")
    b = wordcount.ingest_lines(["a b a"])
    p0 = wal.append({"kind": "push", "tick": 0, "node": 0,
                     "node_name": "words", "batch_id": "b0",
                     "keys": b.keys, "values": b.values,
                     "weights": b.weights})
    p1 = wal.append({"kind": "tick", "tick": 1})
    wal.close()
    records, torn = scan_wal(str(tmp_path))
    assert torn is None
    assert [pos for pos, _ in records] == [p0, p1]
    assert records[0][1]["batch_id"] == "b0"
    assert list(records[0][1]["keys"]) == list(b.keys)
    assert records[1][1] == {"kind": "tick", "tick": 1}
    assert wal.appends == 2 and wal.fsyncs >= 2 and wal.bytes_written > 0


def test_segment_rotation_and_truncate(tmp_path):
    wal = WriteAheadLog(str(tmp_path), fsync="os", segment_bytes=256)
    for i in range(64):
        wal.append({"kind": "tick", "tick": i})
    wal.close()
    segs = list_segments(str(tmp_path))
    assert len(segs) > 1, "256-byte segments must have rotated"
    records, torn = scan_wal(str(tmp_path))
    assert torn is None
    assert [r["tick"] for _p, r in records] == list(range(64))

    # truncation drops sealed segments strictly before the position
    cut = segs[2][0]
    wal2 = WriteAheadLog(str(tmp_path), fsync="os")
    removed = wal2.truncate_until(LogPosition(cut, 8))
    wal2.close()
    assert len(removed) == 2
    assert all(seq >= cut for seq, _ in list_segments(str(tmp_path)))
    kept, _ = scan_wal(str(tmp_path))
    assert [r["tick"] for _p, r in kept if r["kind"] == "tick"] \
        == [r["tick"] for p, r in records
            if p.segment >= cut and r["kind"] == "tick"]


def test_torn_tail_tolerated_but_sealed_corruption_raises(tmp_path):
    # tear the last record: tolerated, scan stops at the tear
    torn_dir = str(tmp_path / "torn")
    wal = WriteAheadLog(torn_dir, fsync="os")
    for i in range(10):
        wal.append({"kind": "tick", "tick": i})
    wal.close()
    full, _ = scan_wal(torn_dir)
    assert tear_wal_tail(torn_dir, 5) is not None
    records, torn = scan_wal(torn_dir)
    assert torn is not None and "truncated" in torn.reason
    assert len(records) == len(full) - 1

    # flip a byte inside a SEALED (non-final) segment: real corruption
    sealed_dir = str(tmp_path / "sealed")
    wal = WriteAheadLog(sealed_dir, fsync="os", segment_bytes=200)
    for i in range(40):
        wal.append({"kind": "tick", "tick": i})
    wal.close()
    seg0 = list_segments(sealed_dir)[0][1]
    with open(seg0, "rb+") as f:
        f.seek(20)
        byte = f.read(1)
        f.seek(20)
        f.write(bytes([byte[0] ^ 0xFF]))
    with pytest.raises(WalError):
        scan_wal(sealed_dir)


def test_fresh_writer_never_appends_to_existing_segment(tmp_path):
    wal = WriteAheadLog(str(tmp_path), fsync="os")
    wal.append({"kind": "tick", "tick": 1})
    wal.close()
    tear_wal_tail(str(tmp_path), 3)  # crashed process left a torn tail
    wal2 = WriteAheadLog(str(tmp_path), fsync="os")
    wal2.append({"kind": "tick", "tick": 2})
    wal2.close()
    # the torn record is confined to the old segment; the new record
    # lives in a fresh segment and still parses
    records, torn = scan_wal(str(tmp_path))
    assert torn is None  # tear is not in the LAST segment...
    assert [r["tick"] for _p, r in records] == [2]


# -- crash-recovery differential (the acceptance property) -----------------

@pytest.mark.parametrize("seed", range(6))
def test_crash_recovery_differential(tmp_path, seed):
    """Kill at an arbitrary instrumented seam (including between push
    and tick), optionally tear the final record, recover, resume from
    the upstream cursor: sink views == clean run, no batch folded
    twice."""
    feed = make_feed(seed)
    want = clean_run(feed)
    rng = np.random.default_rng(1000 + seed)

    wal_dir = str(tmp_path / "wal")
    g, src, sink = wordcount.build_graph()
    crash = CrashInjector(int(rng.integers(1, 60)))
    sched = DurableScheduler(g, wal_dir=wal_dir, fsync="record",
                             crash=crash)
    with pytest.raises(CrashPoint):
        drive(sched, src, feed)
        raise CrashPoint("end-of-feed")  # feed exhausted before the kill
    sched.wal.drain()  # settle the committer: frames enqueued before the
    # "kill" land in the page cache, as a real death would leave them
    if crash.fired and rng.random() < 0.5:
        tear_wal_tail(wal_dir, int(rng.integers(1, 24)))

    g2, src2, sink2 = wordcount.build_graph()
    sched2 = DurableScheduler(g2, wal_dir=wal_dir, fsync="record")
    report = recover(sched2, wal_dir)
    resume_from_cursor(sched2, src2, feed)
    assert dict(sched2.view(sink2.name)) == want, (
        f"seed {seed}: crashed at {crash.seams[-1] if crash.seams else '?'} "
        f"after {len(crash.seams)} seams; report={report.as_dict()}")


@pytest.mark.parametrize("seam", ["before_append", "after_append",
                                  "after_push", "before_tick_mark"])
def test_crash_at_each_seam(tmp_path, seam):
    """Pin the kill to each seam class — the push-vs-tick windows the
    ISSUE calls out — instead of relying on the fuzz to land there."""
    feed = make_feed(99)
    want = clean_run(feed)
    wal_dir = str(tmp_path / seam)
    g, src, sink = wordcount.build_graph()
    crash = CrashInjector(7, only=seam)
    sched = DurableScheduler(g, wal_dir=wal_dir, fsync="tick", crash=crash)
    with pytest.raises(CrashPoint):
        drive(sched, src, feed)
    sched.wal.drain()  # deterministic page-cache state for the replay
    g2, src2, sink2 = wordcount.build_graph()
    sched2 = DurableScheduler(g2, wal_dir=wal_dir, fsync="tick")
    recover(sched2, wal_dir)
    resume_from_cursor(sched2, src2, feed)
    assert dict(sched2.view(sink2.name)) == want


@pytest.mark.parametrize("seed", range(4))
def test_checkpoint_plus_tail_recovery(tmp_path, seed):
    """Acceptance: after a checkpoint, sealed segments are dropped, and
    recovery from (checkpoint + remaining tail) still equals the clean
    run — with replayed pre-checkpoint pushes deduped, not re-folded."""
    feed = make_feed(200 + seed, n_ticks=12)
    want = clean_run(feed)
    rng = np.random.default_rng(300 + seed)
    wal_dir = str(tmp_path / "wal")
    ckpt_dir = str(tmp_path / "ckpt")

    g, src, sink = wordcount.build_graph()
    sched = DurableScheduler(g, wal_dir=wal_dir, fsync="tick",
                             segment_bytes=512)
    ckpt_at = int(rng.integers(3, 9))
    for t, batches in enumerate(feed):
        for bid, b in batches:
            sched.push(src, b, batch_id=bid)
        sched.tick()
        if t == ckpt_at:
            save_checkpoint(sched, ckpt_dir)
            # sealed pre-checkpoint segments are gone; the live segment
            # (and any later ones) remain
            import pickle
            with open(os.path.join(ckpt_dir, "meta.pkl"), "rb") as f:
                wal_pos = pickle.load(f)["wal_pos"]
            assert all(s >= wal_pos[0]
                       for s, _p in list_segments(wal_dir))
        if t == ckpt_at + 2:
            break  # simulated kill two ticks after the save
    sched.wal.drain()
    if rng.random() < 0.5:
        tear_wal_tail(wal_dir, int(rng.integers(1, 16)))

    g2, src2, sink2 = wordcount.build_graph()
    sched2 = DurableScheduler(g2, wal_dir=wal_dir, fsync="tick")
    report = recover(sched2, wal_dir, ckpt_dir)
    assert report.checkpoint_loaded and report.checkpoint_tick == ckpt_at + 1
    resume_from_cursor(sched2, src2, feed)
    assert dict(sched2.view(sink2.name)) == want, report.as_dict()


def test_recovery_without_resume_matches_prefix(tmp_path):
    """Recovery alone (no upstream re-send) reproduces every COMMITTED
    tick's view: the log is authoritative for accepted input."""
    feed = make_feed(7)
    wal_dir = str(tmp_path / "wal")
    g, src, sink = wordcount.build_graph()
    sched = DurableScheduler(g, wal_dir=wal_dir, fsync="record")
    drive(sched, src, feed)
    want = dict(sched.view(sink.name))

    g2, src2, sink2 = wordcount.build_graph()
    sched2 = DirtyScheduler(g2)  # recovery also works on a plain scheduler
    report = recover(sched2, wal_dir)
    assert report.replayed_pushes > 0 and report.replayed_ticks == len(feed)
    assert dict(sched2.view(sink2.name)) == want
    assert sched2._tick == sched._tick


def test_auto_minted_ids_replay_once(tmp_path):
    """Pushes without caller batch ids get durable auto ids: recovery
    folds them exactly once, and a resumed writer mints past them."""
    wal_dir = str(tmp_path / "wal")
    g, src, sink = wordcount.build_graph()
    sched = DurableScheduler(g, wal_dir=wal_dir, fsync="record")
    sched.push(src, wordcount.ingest_lines(["a b"]))
    sched.push(src, wordcount.ingest_lines(["b c"]))
    sched.tick()
    want = dict(sched.view(sink.name))

    g2, src2, sink2 = wordcount.build_graph()
    sched2 = DurableScheduler(g2, wal_dir=wal_dir, fsync="record")
    recover(sched2, wal_dir)
    assert dict(sched2.view(sink2.name)) == want
    # the resumed writer must not mint an id the replayed window holds
    assert sched2.push(src2, wordcount.ingest_lines(["d"]))
    sched2.tick()
    assert dict(sched2.view(sink2.name)) != want


def test_wal_metrics_and_summary(tmp_path):
    feed = make_feed(3, n_ticks=5)
    wal_dir = str(tmp_path / "wal")
    g, src, sink = wordcount.build_graph()
    sched = DurableScheduler(g, wal_dir=wal_dir, fsync="tick")
    drive(sched, src, feed)
    wm = summarize_wal(sched.wal)
    assert wm.fsync_policy == "tick"
    assert wm.appends == sched.wal.appends > len(feed)  # pushes + marks
    assert wm.fsyncs == len(feed)  # one barrier per tick
    assert wm.append_p95_s >= wm.append_p50_s > 0.0

    g2, src2, _ = wordcount.build_graph()
    sched2 = DurableScheduler(g2, wal_dir=wal_dir, fsync="tick")
    report = recover(sched2, wal_dir)
    wm2 = summarize_wal(sched2.wal, recovery=report)
    assert wm2.replayed_pushes == report.replayed_pushes > 0
    assert wm2.replayed_ticks == len(feed)


def test_wal_inspect_tool(tmp_path):
    feed = make_feed(5, n_ticks=4)
    wal_dir = str(tmp_path / "wal")
    g, src, _sink = wordcount.build_graph()
    sched = DurableScheduler(g, wal_dir=wal_dir, fsync="os")
    drive(sched, src, feed)
    sched.close()
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    out = subprocess.run(
        [sys.executable, os.path.join(REPO, "tools", "wal_inspect.py"),
         wal_dir, "--json"], capture_output=True, text=True, env=env)
    assert out.returncode == 0, out.stderr
    summary = json.loads(out.stdout)
    assert summary["record_kinds"]["tick"] == len(feed)
    assert summary["record_kinds"]["push"] == sum(len(t) for t in feed)
    assert summary["torn_tail"] is None

    tear_wal_tail(wal_dir, 4)
    out = subprocess.run(
        [sys.executable, os.path.join(REPO, "tools", "wal_inspect.py"),
         wal_dir, "--json", "--verify"],
        capture_output=True, text=True, env=env)
    assert out.returncode == 0, out.stderr  # torn tail is NOT corruption
    assert json.loads(out.stdout)["torn_tail"] is not None


# -- satellite: faults raise loudly even under python -O -------------------

def test_flush_raises_on_rejected_first_delivery():
    g, src, _sink = wordcount.build_graph()
    sched = DirtyScheduler(g)
    sched.push(src, wordcount.ingest_lines(["a"]), batch_id="b0")
    chan = FaultyChannel(sched, src, drop_p=0.0, dup_p=0.0, seed=1)
    # the transport still holds b0 (never delivered by IT), but the
    # scheduler's window already claims the id: flush must fail loudly
    chan._unacked.append(("b0", wordcount.ingest_lines(["a"])))
    with pytest.raises(DeliveryError):
        chan.flush()


def test_pump_raises_when_duplicate_accepted():
    g, src, _sink = wordcount.build_graph()
    sched = DirtyScheduler(g)
    sched.push = lambda *a, **k: True  # a scheduler that lost its dedup
    chan = FaultyChannel(sched, src, drop_p=0.0, dup_p=1.0, seed=0)
    with pytest.raises(DeliveryError):
        # dup_p=1: the pump retransmits b0 right after delivering it;
        # the dedup-less scheduler accepts the duplicate -> loud error
        chan.send(wordcount.ingest_lines(["a"]), "b0")


# -- satellite: empty-history summary stays field-aligned ------------------

def test_empty_history_summary_keyword_constructed():
    s = summarize([])
    assert s.ticks == 0 and s.delta_ops == 0
    assert s.quiesced_all is True and s.forced_syncs == 0


# -- group commit (fsync="record") ------------------------------------------

def test_append_group_one_fsync_covers_the_group(tmp_path):
    wal = WriteAheadLog(str(tmp_path), fsync="record")
    fsyncs0 = wal.fsyncs
    poss = wal.append_group([{"kind": "tick", "tick": i} for i in range(5)])
    assert len(poss) == 5
    assert wal.fsyncs == fsyncs0 + 1
    assert wal.group_sizes[-1] == 5
    wal.close()
    records, torn = scan_wal(str(tmp_path))
    assert torn is None
    assert [r["tick"] for _p, r in records] == list(range(5))


def test_individual_appends_record_group_size_one(tmp_path):
    wal = WriteAheadLog(str(tmp_path), fsync="record")
    for i in range(3):
        wal.append({"kind": "tick", "tick": i})
    wal.close()
    assert list(wal.group_sizes) == [1, 1, 1]
    assert wal.fsyncs >= 3


def test_group_commit_survives_rotation(tmp_path):
    # a group large enough to rotate mid-group must still land every
    # record durably and scan back in order
    wal = WriteAheadLog(str(tmp_path), fsync="record", segment_bytes=256)
    wal.append_group([{"kind": "tick", "tick": i} for i in range(64)])
    wal.close()
    assert len(list_segments(str(tmp_path))) > 1
    records, torn = scan_wal(str(tmp_path))
    assert torn is None
    assert [r["tick"] for _p, r in records] == list(range(64))


def test_append_group_rotation_mid_window_atomic_replay(tmp_path):
    """A coalesced macro-tick whose ``append_group`` starts in one
    segment and rotates mid-window: the sealed segment must be fsynced
    AT the rotation (even under the lazy ``"tick"`` policy — the crash
    here dies before any tick marker, so rotation is the only barrier),
    and every ``batch_ids`` replay unit must stay all-or-nothing across
    the segment boundary."""
    wal_dir = str(tmp_path / "wal")
    g, src, sink = wordcount.build_graph()
    crash = CrashInjector(at=1, only="after_append")
    sched = DurableScheduler(g, wal_dir=wal_dir, fsync="tick",
                             segment_bytes=1024, crash=crash)
    feeds, feed_ids = [], []
    for t in range(8):
        lines = [" ".join(f"w{(t * 7 + k) % 13}" for k in range(40))]
        feeds.append({src: wordcount.ingest_lines(lines)})
        feed_ids.append({src: [f"t{t}a", f"t{t}b"]})
    with pytest.raises(CrashPoint):
        sched.tick_many(feeds, feed_ids=feed_ids)
    sched.wal.drain()  # the enqueued window + its rotations hit disk
    segs = list_segments(wal_dir)
    assert len(segs) > 1, "window did not span a rotation; shrink segments"
    # the "tick" policy alone would have fsynced NOTHING yet (no tick
    # mark was reached): every fsync on the books is a rotation sealing
    # a full segment
    assert sched.wal.fsyncs == len(segs) - 1
    records, torn = scan_wal(wal_dir)
    assert torn is None and len(records) == 8

    g2, src2, sink2 = wordcount.build_graph()
    fresh = DurableScheduler(g2, wal_dir=wal_dir, fsync="tick")
    report = recover(fresh, wal_dir)
    # the crash died before execution, so no tick marker landed: the
    # replayed units sit as pending backlog until the next tick
    fresh.tick()
    fresh.close()
    assert report.replayed_pushes == 8
    g3, src3, sink3 = wordcount.build_graph()
    want = DirtyScheduler(g3)
    for feed in feeds:
        for _src, batch in feed.items():
            want.push(src3, batch)
        want.tick()
    assert dict(fresh.view(sink2.name)) == dict(want.view(sink3.name))

    # all-or-nothing across the boundary: pre-seeding ONE id of a
    # mid-log unit dedups that whole unit and only it
    g4, src4, sink4 = wordcount.build_graph()
    again = DurableScheduler(g4, wal_dir=wal_dir, fsync="tick")
    again._register_batch_id("t4a")
    report2 = recover(again, wal_dir)
    again.tick()
    again.close()
    assert report2.replayed_pushes == 7
    assert report2.deduped_pushes == 1


def test_empty_group_is_a_noop(tmp_path):
    wal = WriteAheadLog(str(tmp_path), fsync="record")
    fsyncs0 = wal.fsyncs
    assert wal.append_group([]) == []
    assert wal.fsyncs == fsyncs0 and wal.appends == 0
    wal.close()


def test_wal_metrics_report_group_shape(tmp_path):
    wal = WriteAheadLog(str(tmp_path), fsync="record")
    wal.append({"kind": "tick", "tick": 0})
    wal.append_group([{"kind": "tick", "tick": i} for i in range(1, 5)])
    wal.close()
    wm = summarize_wal(wal)
    assert wm.group_commits == len(wal.group_sizes)
    assert wm.group_max == 4.0
    assert wm.as_dict()["group_p50"] >= 1.0


def test_coalesced_batch_ids_replay_all_or_nothing(tmp_path):
    """A frontend-coalesced push record carries the merged micro-batch
    ids; its macro-tick committed them atomically, so replay must fold
    the merged batch once if NO id is known, and never if ANY is."""
    g, src, sink = wordcount.build_graph()
    sched = DurableScheduler(g, wal_dir=str(tmp_path / "wal"))
    sched.tick_many(
        [{src: wordcount.ingest_lines(["a b"])},
         {src: wordcount.ingest_lines(["b c"])}],
        feed_ids=[{src: ["m0", "m1"]}, {src: ["m2"]}])
    want = dict(sched.view(sink.name))
    sched.close()

    g2, src2, sink2 = wordcount.build_graph()
    fresh = DurableScheduler(g2, wal_dir=str(tmp_path / "wal"))
    report = recover(fresh, str(tmp_path / "wal"))
    fresh.close()
    assert dict(fresh.view(sink2.name)) == want
    assert report.replayed_pushes == 2
    # all three micro-ids are back in the dedup window after replay
    for bid in ("m0", "m1", "m2"):
        assert bid in fresh._seen_batch_ids

    g3, src3, sink3 = wordcount.build_graph()
    again = DurableScheduler(g3, wal_dir=str(tmp_path / "wal"))
    # pre-seed ONE of the merged ids: the whole record must dedup
    again._register_batch_id("m1")
    report2 = recover(again, str(tmp_path / "wal"))
    again.close()
    assert report2.deduped_pushes >= 1


def test_window_replays_as_its_own_ticks_not_one_merged_tick(tmp_path):
    """A K-tick window's push records carry their feed index, and replay
    ticks up to it before folding one: the window re-runs as the K ticks
    the leader ran. Merged into one tick it would be K times the size —
    past what a device executor's arenas are sized for (the recover()
    arena overflow chip_smoke.py hit at 16 x 20 000-row churn)."""
    g, src, sink = wordcount.build_graph()
    sched = DurableScheduler(g, wal_dir=str(tmp_path / "wal"))
    lines = [["a b"], ["b c d"], ["a"]]
    sched.tick_many([{src: wordcount.ingest_lines(ln)} for ln in lines],
                    feed_ids=[{src: [f"m{t}"]} for t in range(3)])
    want = dict(sched.view(sink.name))
    sched.close()

    records, _ = scan_wal(str(tmp_path / "wal"))
    pushes = [r for _p, r in records if r["kind"] == "push"]
    assert [r.get("feed", 0) for r in pushes] == [0, 1, 2]
    assert len({r["tick"] for r in pushes}) == 1     # the window's start

    g2, src2, sink2 = wordcount.build_graph()
    fresh = DirtyScheduler(g2)
    report = recover(fresh, str(tmp_path / "wal"))
    assert dict(fresh.view(sink2.name)) == want and fresh._tick == 3
    # one feed per tick, in order — not 6 rows in tick 1 and two no-ops
    assert [r.deltas_in for r in fresh.history] == [2, 3, 1]
    assert report.replayed_ticks == 3 and report.skipped_ticks == 2


# -- asynchronous committer pipeline ---------------------------------------

PIPELINE_SEAMS = ["wal_enqueue", "wal_before_write", "wal_after_write",
                  "wal_before_fsync", "wal_after_fsync"]


@pytest.mark.parametrize("seam", PIPELINE_SEAMS)
def test_committer_seam_crash_replays_exactly_once(tmp_path, seam):
    """Kill the durability pipeline at each of its own seams — frame
    enqueued but not written, written but not fsynced, fsynced but the
    acknowledgement path dead — then recover and resume from the
    upstream cursor: the sink view matches the clean run, nothing folds
    twice. ``wal_enqueue`` dies on the appending thread; the other four
    kill the committer itself, and the death must surface as the
    original CrashPoint from the next append/wait."""
    import contextlib

    feed = make_feed(7)
    want = clean_run(feed)
    wal_dir = str(tmp_path / seam)
    g, src, sink = wordcount.build_graph()
    crash = CrashInjector(3, only=seam)
    sched = DurableScheduler(g, wal_dir=wal_dir, fsync="record",
                             crash=crash)
    with pytest.raises(CrashPoint):
        drive(sched, src, feed)
    assert crash.fired
    with contextlib.suppress(CrashPoint):
        # settle surviving writes; a dead committer re-raises its cause
        sched.wal.drain()

    g2, src2, sink2 = wordcount.build_graph()
    sched2 = DurableScheduler(g2, wal_dir=wal_dir, fsync="record")
    recover(sched2, wal_dir)
    resume_from_cursor(sched2, src2, feed)
    assert dict(sched2.view(sink2.name)) == want


def test_committer_death_fails_waiters_and_callbacks(tmp_path):
    """A committer that dies before the fsync must (a) fail every
    registered ``when_durable`` continuation with its cause — no ticket
    may hang unresolved — and (b) re-raise that cause from later
    ``wait_durable``/``append`` calls instead of accepting writes it
    can never commit."""
    import threading

    crash = CrashInjector(1, only="wal_before_fsync")
    wal = WriteAheadLog(str(tmp_path), fsync="record", crash=crash)
    b = wordcount.ingest_lines(["a b"])
    rec = {"kind": "push", "tick": 0, "node": 0, "node_name": "w",
           "batch_id": "b0", "keys": b.keys, "values": b.values,
           "weights": b.weights}
    got = []
    fired = threading.Event()

    wal.append(rec, wait=False)
    lsn = wal.last_lsn()
    try:
        pending = wal.when_durable(
            lsn, lambda err: (got.append(err), fired.set()))
    except CrashPoint:
        pending = False  # death already visible at registration time
    if pending:
        assert fired.wait(timeout=10.0), "continuation never resolved"
        assert isinstance(got[0], CrashPoint)
    with pytest.raises(CrashPoint):
        wal.wait_durable(lsn)
    with pytest.raises(CrashPoint):
        wal.append(rec, wait=False)


def test_drain_is_write_barrier_not_fsync_barrier(tmp_path):
    """``drain()`` settles every enqueued frame into the segment file
    (the scan sees them) without spending an fsync or moving the
    durability watermark — the page-cache state a process death at that
    instant would leave behind."""
    wal = WriteAheadLog(str(tmp_path), fsync="tick")
    b = wordcount.ingest_lines(["a b a"])
    for j in range(3):
        wal.append({"kind": "push", "tick": 0, "node": 0,
                    "node_name": "w", "batch_id": f"b{j}",
                    "keys": b.keys, "values": b.values,
                    "weights": b.weights}, wait=False)
    fsyncs0 = wal.fsyncs
    wal.drain()
    assert wal.queue_depth() == 0
    records, torn = scan_wal(str(tmp_path))
    assert torn is None and len(records) == 3
    assert wal.fsyncs == fsyncs0          # no fsync spent
    assert wal.durable_lsn() < wal.last_lsn()  # ...so not durable yet
    wal.note_tick()
    wal.wait_durable(wal.last_lsn())
    assert wal.durable_lsn() == wal.last_lsn()
    wal.close()


def test_when_durable_fires_in_lsn_order(tmp_path):
    """Continuations fire in LSN order once the watermark passes them,
    each with ``None`` (success); already-durable LSNs report False so
    the caller resolves inline."""
    wal = WriteAheadLog(str(tmp_path), fsync="tick")
    b = wordcount.ingest_lines(["x"])
    lsns = []
    for j in range(4):
        wal.append({"kind": "push", "tick": 0, "node": 0,
                    "node_name": "w", "batch_id": f"b{j}",
                    "keys": b.keys, "values": b.values,
                    "weights": b.weights}, wait=False)
        lsns.append(wal.last_lsn())
    fired = []
    for lsn in lsns:
        assert wal.when_durable(lsn, lambda err, lsn=lsn:
                                fired.append((lsn, err)))
    wal.note_tick()
    wal.wait_durable(lsns[-1])
    assert fired == [(lsn, None) for lsn in lsns]
    # the watermark already covers them now: registration declines
    assert wal.when_durable(lsns[-1], lambda err: None) is False
    wal.close()


def test_idle_tick_and_seal_skip_fsync(tmp_path):
    """An idle tick boundary (nothing appended since the last barrier)
    and an already-durable seal must not pay a no-op fsync."""
    wal = WriteAheadLog(str(tmp_path), fsync="tick")
    b = wordcount.ingest_lines(["a b"])
    wal.append({"kind": "push", "tick": 0, "node": 0, "node_name": "w",
                "batch_id": "b0", "keys": b.keys, "values": b.values,
                "weights": b.weights}, wait=False)
    wal.note_tick()
    n = wal.fsyncs
    wal.note_tick()  # idle: watermark already covers every append
    wal.note_tick()
    assert wal.fsyncs == n
    wal.close()      # seal with no new bytes: no extra fsync either
    assert wal.fsyncs == n
