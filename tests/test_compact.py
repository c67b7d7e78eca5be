"""Key-level WAL compaction (``reflow_tpu.wal.compact``): folded
segments must replay to exact state parity with the original history
(the bounded-history half of O(state) recovery), crashes anywhere in
the write-new → manifest-flip → swap → unlink sequence must leave a
replay-equivalent log, eligibility must respect the checkpoint anchor
and every attached follower's cursor, and a follower whose cursor
predates a compacted range must re-anchor through the checkpoint and
converge — the PR-10 leader-truncation re-anchor extended to
rewritten-in-place segments."""

import os

import numpy as np
import pytest

from reflow_tpu import DirtyScheduler
from reflow_tpu.serve import (ControlConfig, ControlPlane, ReplicaScheduler,
                              ServeTier)
from reflow_tpu.utils.checkpoint import CheckpointChain
from reflow_tpu.utils.faults import CrashInjector, CrashPoint
from reflow_tpu.wal import (DurableScheduler, SegmentShipper, WalCompactor,
                            WalError, recover)
from reflow_tpu.wal.compact import COMPACT_MANIFEST_FILE, read_compact_manifest
from reflow_tpu.wal.log import _MAGIC, list_segments, scan_wal
from reflow_tpu.wal.recovery import replay_records
from reflow_tpu.workloads import wordcount


# -- helpers ----------------------------------------------------------------

def make_feed(seed, n_ticks, tag=""):
    """Deterministic per-tick [(batch_id, batch)] lists with retractions
    mixed in, so folding exercises weight cancellation (zero rows must
    vanish), not just inserts. ``tag`` keeps ids disjoint when one
    scheduler consumes several feeds (a repeated id is deduped at push,
    silently shrinking the feed)."""
    rng = np.random.default_rng(seed)
    feed = []
    for t in range(n_ticks):
        batches = []
        for j in range(int(rng.integers(1, 3))):
            words = " ".join(
                f"w{int(x)}" for x in rng.integers(0, 25,
                                                   int(rng.integers(2, 8))))
            weight = -1 if (t > 2 and rng.random() < 0.2) else 1
            batches.append((f"{tag}t{t}b{j}",
                            wordcount.ingest_lines([words], weight=weight)))
        feed.append(batches)
    return feed


def build_log(wal_dir, feed, segment_bytes=1 << 12):
    """Drive a durable leader over ``feed`` (small segments force many
    rotations) and return its final live view."""
    g, src, sink = wordcount.build_graph()
    sched = DurableScheduler(g, wal_dir=wal_dir, fsync="tick",
                             segment_bytes=segment_bytes)
    for batches in feed:
        for bid, b in batches:
            sched.push(src, b, batch_id=bid)
        sched.tick()
    view = dict(sched.view(sink.name))
    tick = sched._tick
    sched.close()
    return view, tick


def recovered_view(wal_dir, ckpt_dir=None):
    g, _src, sink = wordcount.build_graph()
    sched = DirtyScheduler(g)
    rep = recover(sched, wal_dir, ckpt_dir)
    return dict(sched.view(sink.name)), sched._tick, rep


# -- fold parity ------------------------------------------------------------

def test_fold_replay_parity_and_manifest(tmp_path):
    wal_dir = str(tmp_path / "wal")
    oracle, tick = build_log(wal_dir, make_feed(7, 30))
    comp = WalCompactor(wal_dir=wal_dir, min_segments=2, keep_segments=1)
    assert comp.reclaimable_bytes() > 0
    ev = comp.compact_once()
    assert ev is not None and ev["kind"] == "wal_compact"
    assert ev["records_out"] < ev["records_in"]
    assert ev["reclaimed_bytes"] > 0
    m = read_compact_manifest(wal_dir)
    assert m["gen"] == 1 and len(m["ranges"]) == 1
    ent = m["ranges"][0]
    assert ent["out"] == ent["covers"][0] == ev["out"]
    # the folded log replays through the UNCHANGED recovery path to the
    # exact oracle state — same views, same tick counter
    got, got_tick, _rep = recovered_view(wal_dir)
    assert got == oracle and got_tick == tick
    # superseded originals are gone; the out segment holds stamped
    # folded records carrying every original batch id
    seqs = [s for s, _ in list_segments(wal_dir)]
    assert ent["covers"][1] not in seqs or ent["covers"][1] == ent["out"]
    records, _ = scan_wal(wal_dir)
    folded = [r for _p, r in records if r.get("compacted")]
    assert folded and all(r["kind"] == "push" for r in folded)
    assert any(len(r.get("batch_ids", [])) > 1 for r in folded)


def test_refold_extends_previous_range(tmp_path):
    wal_dir = str(tmp_path / "wal")
    build_log(wal_dir, make_feed(7, 30))
    comp = WalCompactor(wal_dir=wal_dir, min_segments=2, keep_segments=1)
    ev1 = comp.compact_once()
    assert ev1 is not None
    # extend the log (a restarted leader appends fresh segments after
    # the folded prefix), then fold again: the out segment re-folds
    # together with the new history under a bumped generation
    g, src, sink = wordcount.build_graph()
    sched = DurableScheduler(g, wal_dir=wal_dir, fsync="tick",
                             segment_bytes=1 << 12)
    recover(sched, wal_dir)
    for batches in make_feed(11, 40, tag="x"):
        for bid, b in batches:
            sched.push(src, b, batch_id=bid)
        sched.tick()
    oracle2 = dict(sched.view(sink.name))
    tick2 = sched._tick
    sched.close()
    ev2 = comp.compact_once()
    assert ev2 is not None
    m = read_compact_manifest(wal_dir)
    assert m["gen"] == 2
    assert ev2["covers"][0] == ev1["covers"][0]
    assert ev2["covers"][1] > ev1["covers"][1]
    got, got_tick, _rep = recovered_view(wal_dir)
    assert got == oracle2 and got_tick == tick2


def test_zero_weight_rows_vanish_from_fold(tmp_path):
    # insert-then-retract the same rows: the folded record must not
    # carry the cancelled keys at all (that is the O(state) bound)
    wal_dir = str(tmp_path / "wal")
    g, src, sink = wordcount.build_graph()
    sched = DurableScheduler(g, wal_dir=wal_dir, fsync="tick",
                             segment_bytes=1 << 10)
    for t in range(12):
        sched.push(src, wordcount.ingest_lines(["gone forever"]),
                   batch_id=f"in{t}")
        sched.tick()
    for t in range(12):
        sched.push(src, wordcount.ingest_lines(["gone forever"],
                                               weight=-1),
                   batch_id=f"out{t}")
        sched.tick()
    sched.push(src, wordcount.ingest_lines(["kept"]), batch_id="keep")
    sched.tick()
    oracle = dict(sched.view(sink.name))
    sched.close()
    comp = WalCompactor(wal_dir=wal_dir, min_segments=1, keep_segments=0)
    ev = comp.compact_once()
    assert ev is not None
    records, _ = scan_wal(wal_dir)
    folded = [r for _p, r in records if r.get("compacted")]
    assert folded
    for r in folded:
        assert all(w != 0 for w in r["weights"])
        assert not any("gone" in str(k) for k in r["keys"])
    got, _t, _rep = recovered_view(wal_dir)
    assert got == oracle


# -- crash seams ------------------------------------------------------------

@pytest.mark.parametrize("seam", ["compact_before_flip",
                                  "compact_after_flip",
                                  "compact_before_unlink",
                                  "compact_after_unlink"])
def test_compact_crash_seam_differential(tmp_path, seam):
    # kill the pass at each seam of write-new → flip → swap → unlink:
    # the raw crashed layout must ALREADY replay to the oracle (folded
    # records carry the covered batch ids, so surviving originals dedup
    # away), and the next pass's roll-forward/back must too
    wal_dir = str(tmp_path / "wal")
    oracle, tick = build_log(wal_dir, make_feed(3, 30))
    crash = CrashInjector(1, only=seam)
    comp = WalCompactor(wal_dir=wal_dir, min_segments=2, keep_segments=1,
                        crash=crash)
    with pytest.raises(CrashPoint):
        comp.compact_once()
    got, got_tick, _rep = recovered_view(wal_dir)
    assert got == oracle and got_tick == tick, f"{seam}: raw layout diverged"
    comp2 = WalCompactor(wal_dir=wal_dir, min_segments=2, keep_segments=1)
    comp2.compact_once()
    assert not [f for f in os.listdir(wal_dir) if f.endswith(".compact")]
    got, got_tick, _rep = recovered_view(wal_dir)
    assert got == oracle and got_tick == tick, f"{seam}: recovery diverged"


def test_interrupted_tmp_rolled_back(tmp_path):
    # a stray tmp with no manifest entry (crash before the flip) and a
    # torn tmp WITH an entry (flip landed, write was lied about) must
    # both roll back to the authoritative originals
    wal_dir = str(tmp_path / "wal")
    oracle, tick = build_log(wal_dir, make_feed(5, 20))
    seqs = [s for s, _ in list_segments(wal_dir)]
    stray = os.path.join(wal_dir, f"wal-{seqs[0]:08d}.log.compact")
    with open(stray, "wb") as f:
        f.write(b"garbage, not a segment")
    comp = WalCompactor(wal_dir=wal_dir, min_segments=64)  # fold nothing
    comp.compact_once()
    assert not os.path.exists(stray)
    got, got_tick, _rep = recovered_view(wal_dir)
    assert got == oracle and got_tick == tick

    # now a torn tmp alongside a manifest entry claiming it: the entry
    # must be dropped with the tmp (bytes mismatch -> not rolled forward)
    import json

    with open(stray, "wb") as f:
        f.write(_MAGIC + b"\x00" * 7)
    with open(os.path.join(wal_dir, COMPACT_MANIFEST_FILE), "w") as f:
        json.dump({"schema": "reflow.wal_compact/1", "gen": 1,
                   "reclaimed_bytes": 0,
                   "ranges": [{"out": seqs[0],
                               "covers": [seqs[0], seqs[1]], "gen": 1,
                               "bytes": 12345, "orig_bytes": 0,
                               "records_in": 0, "records_out": 0,
                               "tick_lo": None, "tick_hi": None}]}, f)
    comp.compact_once()
    assert not os.path.exists(stray)
    assert read_compact_manifest(wal_dir)["ranges"] == []
    got, got_tick, _rep = recovered_view(wal_dir)
    assert got == oracle and got_tick == tick


# -- eligibility ------------------------------------------------------------

def test_eligibility_respects_checkpoint_anchor(tmp_path):
    # records before the newest checkpoint anchor belong to the
    # checkpoint; a fold must start AT the anchor, never below it
    wal_dir = str(tmp_path / "wal")
    ckpt_dir = str(tmp_path / "ckpt")
    g, src, sink = wordcount.build_graph()
    sched = DurableScheduler(g, wal_dir=wal_dir, fsync="tick",
                             segment_bytes=1 << 12)
    chain = CheckpointChain(ckpt_dir, delta_every=4)
    for t, batches in enumerate(make_feed(9, 30)):
        for bid, b in batches:
            sched.push(src, b, batch_id=bid)
        sched.tick()
        if t == 14:
            chain.save(sched)
    oracle = dict(sched.view(sink.name))
    tick = sched._tick
    sched.close()
    from reflow_tpu.utils.checkpoint import chain_head_wal_pos

    anchor = chain_head_wal_pos(ckpt_dir)
    assert anchor is not None
    comp = WalCompactor(wal_dir=wal_dir, ckpt_dir=ckpt_dir,
                        min_segments=1, keep_segments=1)
    rng = comp.eligible_range()
    assert rng is not None and rng[0] >= anchor[0]
    ev = comp.compact_once()
    assert ev is not None and ev["covers"][0] >= anchor[0]
    got, got_tick, rep = recovered_view(wal_dir, ckpt_dir)
    assert got == oracle and got_tick == tick
    assert rep.checkpoint_loaded


def test_eligibility_min_and_keep_segments(tmp_path):
    wal_dir = str(tmp_path / "wal")
    build_log(wal_dir, make_feed(5, 20))
    n_sealed = len(list_segments(wal_dir)) - 1
    assert n_sealed >= 2
    # min_segments above the sealed count: nothing to do
    comp = WalCompactor(wal_dir=wal_dir, min_segments=n_sealed + 10,
                        keep_segments=0)
    assert comp.eligible_range() is None
    assert comp.compact_once() is None
    # keep_segments holds the newest sealed segments out of the fold
    comp2 = WalCompactor(wal_dir=wal_dir, min_segments=1, keep_segments=2)
    rng = comp2.eligible_range()
    seqs = [s for s, _ in list_segments(wal_dir)]
    assert rng is not None
    assert set(rng).isdisjoint(seqs[-3:])  # open + 2 kept sealed


def test_eligibility_respects_attached_follower_cursor(tmp_path):
    # an attached follower still mid-fetch pins the fold floor: the
    # compactor must never rewrite bytes an attached cursor still needs
    sched_dir = tmp_path
    g, src, sink = wordcount.build_graph()
    sched = DurableScheduler(g, wal_dir=str(sched_dir / "wal"),
                             fsync="tick", segment_bytes=1 << 12)
    ship = SegmentShipper(sched.wal, leader_tick=lambda: sched._tick,
                          max_chunk_bytes=1 << 10)
    g2, _s2, _k2 = wordcount.build_graph()
    replica = ReplicaScheduler(g2, str(sched_dir / "r0"), name="r0")
    ship.attach(replica)
    for batches in make_feed(2, 25):
        for bid, b in batches:
            sched.push(src, b, batch_id=bid)
        sched.tick()
    sched.wal.sync()
    ship.pump_once()  # one small chunk: cursor parked low in the log
    floor = ship.min_cursor()
    assert floor is not None
    comp = WalCompactor(sched.wal, shipper=ship, min_segments=1,
                        keep_segments=0)
    rng = comp.eligible_range()
    if rng is not None:
        assert max(rng) < floor.segment
    ev = comp.compact_once()
    if ev is not None:
        assert ev["covers"][1] < floor.segment
    sched.close()


# -- follower re-anchor across a compacted range (extends PR 10) ------------

def test_follower_cursor_in_compacted_range_reanchors(tmp_path):
    # a follower detaches mid-catch-up with its cursor parked inside a
    # range that is later compacted; on re-attach the shipper must
    # detect the stale-generation cursor, re-anchor it through the
    # checkpoint-anchored bootstrap (which RESETS replica state — a
    # folded record is all-or-nothing against the dedup window), and
    # converge to exact parity
    wal_dir = str(tmp_path / "wal")
    ckpt_dir = str(tmp_path / "ckpt")
    g, src, sink = wordcount.build_graph()
    sched = DurableScheduler(g, wal_dir=wal_dir, fsync="tick",
                             segment_bytes=1 << 12)
    chain = CheckpointChain(ckpt_dir, delta_every=4)
    chain.save(sched)  # anchor at the log head
    ship = SegmentShipper(sched.wal, ckpt_dir=ckpt_dir,
                          leader_tick=lambda: sched._tick)
    g2, _s2, sink2 = wordcount.build_graph()
    replica = ReplicaScheduler(g2, str(tmp_path / "r0"), name="r0")
    ship.attach(replica)
    # a few ticks only: the synced watermark — and thus the caught-up
    # cursor — parks MID-segment inside the anchor segment, which a
    # later pass rewrites in place (the out segment of the fold)
    for batches in make_feed(4, 3):
        for bid, b in batches:
            sched.push(src, b, batch_id=bid)
        sched.tick()
    sched.wal.sync()
    ship.pump_once()
    stale = replica.subscribe()
    assert stale is not None and stale[1] > len(_MAGIC)
    ship.detach("r0")
    # leader keeps going, then compacts the range the cursor sits in
    for batches in make_feed(6, 30, tag="x"):
        for bid, b in batches:
            sched.push(src, b, batch_id=bid)
        sched.tick()
    sched.wal.sync()
    comp = WalCompactor(sched.wal, ckpt_dir=ckpt_dir, min_segments=1,
                        keep_segments=1)
    ev = comp.compact_once()
    assert ev is not None
    assert ev["covers"][0] == stale[0], \
        "test setup: stale cursor must sit in the rewritten out segment"
    # re-attach: the persisted cursor names a pre-compaction era
    ship.attach(replica)
    sched.wal.sync()
    for _ in range(200):
        ship.pump_once()
        if replica.published_horizon() == sched._tick:
            break
    assert ship.compact_reanchors >= 1
    assert replica.published_horizon() == sched._tick
    h, got = replica.view_at(sink2.name)
    want = {kv: w for kv, w in sched.view(sink.name).items() if w != 0}
    assert h == sched._tick and got == want  # max_abs_diff == 0
    sched.close()


def test_bounded_history_leg_equals_unbounded_and_cold_starts(tmp_path):
    # two identically-fed leaders — one keeps its whole history, one
    # cuts chain elements and folds its sealed tail as it goes. The
    # bounded leg must end on the same view, crash-recover from
    # {chain + compacted tail} to it, bootstrap a FRESH replica from
    # the directories alone to it, and hold fewer bytes on disk
    def du(path):
        return sum(os.path.getsize(os.path.join(b, f))
                   for b, _d, fs in os.walk(path) for f in fs)

    def feed(t):
        # every odd tick retracts its predecessor: live state stays
        # small while history grows — the compactor's whole case
        rng = np.random.default_rng(t - t % 2)
        words = " ".join(f"w{int(x)}" for x in rng.integers(0, 60, 16))
        return f"t{t}", wordcount.ingest_lines(
            [words], weight=-1 if t % 2 else 1)

    legs = {}
    for label in ("full", "bounded"):
        wal_dir = str(tmp_path / f"wal-{label}")
        root = str(tmp_path / "ckpt") if label == "bounded" else None
        g, src, sink = wordcount.build_graph()
        sched = DurableScheduler(g, wal_dir=wal_dir, fsync="tick",
                                 segment_bytes=1 << 12)
        chain = comp = None
        if root:
            chain = CheckpointChain(root, delta_every=3)
            comp = WalCompactor(sched.wal, ckpt_dir=root,
                                min_segments=2, keep_segments=1)
        for t in range(121):
            bid, b = feed(t)
            sched.push(src, b, batch_id=bid)
            sched.tick()
            if chain is not None and t in (29, 59):
                chain.save(sched)       # the rest is the replay tail
                comp.compact_once()
        sched.wal.sync()
        if comp is not None:
            while comp.compact_once() is not None:
                pass            # fold the sealed tail completely
            assert comp.folds >= 1
            comp.close()
        legs[label] = (wal_dir, root, {kv: w for kv, w
                                       in sched.view(sink.name).items()
                                       if w != 0}, sched._tick)
        sched.close()
    wal_dir, root, view, tick = legs["bounded"]
    assert view == legs["full"][2] and view     # same fold, exactly
    got, got_tick, rep = recovered_view(wal_dir, root)
    assert {kv: w for kv, w in got.items() if w != 0} == view
    assert got_tick == tick and rep.checkpoint_loaded
    ship = SegmentShipper(wal_dir=wal_dir, ckpt_dir=root)
    g2, _s2, sink2 = wordcount.build_graph()
    replica = ReplicaScheduler(g2, str(tmp_path / "boot"), name="boot")
    ship.attach(replica)
    assert replica.bootstraps == 1
    for _ in range(200):
        if replica.published_horizon() == tick:
            break
        ship.pump_once()
    assert replica.view_at(sink2.name) == (tick, view)
    ship.close()
    replica.close()
    assert du(wal_dir) + du(root) < du(legs["full"][0])


def test_compacted_record_partial_dedup_fails_loud(tmp_path):
    # a folded record whose batch ids are PARTIALLY in the restorer's
    # dedup window has no per-id slice to apply — silent divergence is
    # the one forbidden outcome, so replay must raise
    g, src, _sink = wordcount.build_graph()
    sched = DirtyScheduler(g)
    sched.push(src, wordcount.ingest_lines(["alpha"]), batch_id="a")
    sched.tick()
    b = wordcount.ingest_lines(["alpha beta"])
    rec = {"kind": "push", "tick": 0, "node": src.id,
           "node_name": src.name, "batch_id": "a", "compacted": True,
           "batch_ids": ["a", "b"], "keys": b.keys, "values": b.values,
           "weights": b.weights}
    with pytest.raises(WalError, match="folded range"):
        replay_records(sched, [(None, rec)])
    # fully-seen and fully-fresh folded records stay fine
    assert replay_records(sched, [(None, dict(rec, batch_ids=["a"],
                                              batch_id="a"))]) \
        == (0, 1, 0, 0)
    assert replay_records(sched, [(None, dict(rec, batch_ids=["x", "y"],
                                              batch_id="x"))]) \
        == (1, 0, 0, 0)


# -- control-plane supervision ----------------------------------------------

def test_control_plane_supervises_compactor(tmp_path):
    # the ControlPlane boots a cold compactor for free, surfaces pass
    # events as wal_compact actions, respawns a dead thread within the
    # budget, and fails fast past it (respawn-or-fail-fast, same stance
    # as the WAL committer)
    wal_dir = str(tmp_path / "wal")
    build_log(wal_dir, make_feed(8, 30))
    comp = WalCompactor(wal_dir=wal_dir, interval_s=3600.0,
                        min_segments=2, keep_segments=1)
    tier = ServeTier(max_bytes=1 << 20, pump_threads=1)
    cp = ControlPlane(tier, config=ControlConfig(max_compactor_restarts=2),
                      compactor=comp, sampler=lambda now: {"graphs": {}})
    try:
        cp.step(0.0)
        assert comp.alive  # free boot, no budget spent
        ev = comp.compact_once()  # synchronous pass queues an event
        assert ev is not None
        actions = cp.step(1.0)
        compacts = [a for a in actions if a["kind"] == "wal_compact"]
        assert len(compacts) == 1
        assert compacts[0]["covers"] == ev["covers"]
        assert compacts[0]["reclaimed_bytes"] == ev["reclaimed_bytes"]
        # kill the thread twice: budgeted respawns
        for i in (1, 2):
            comp.stop()
            acts = cp.step(1.0 + i)
            assert [a["kind"] for a in acts] == ["compactor_restart"]
            assert comp.alive
        # third death exhausts the budget: fail fast, stay failed
        comp.stop()
        acts = cp.step(10.0)
        assert [a["kind"] for a in acts] == ["compactor_failed"]
        assert not comp.alive
        assert cp.step(11.0) == []
    finally:
        cp.stop()
        comp.close()
        tier.close()


def test_compactor_metrics_publish_and_close(tmp_path):
    from reflow_tpu.obs import MetricsRegistry

    wal_dir = str(tmp_path / "wal")
    build_log(wal_dir, make_feed(1, 20))
    reg = MetricsRegistry()
    comp = WalCompactor(wal_dir=wal_dir, min_segments=2, keep_segments=1)
    comp.publish_metrics(reg)
    comp.compact_once()
    assert reg.value("compact.folds") == 1
    assert reg.value("compact.reclaimed_bytes") > 0
    assert reg.value("compact.log_bytes") == comp.log_bytes()
    comp.close()
    assert reg.value("compact.folds") is None  # unregistered on close
