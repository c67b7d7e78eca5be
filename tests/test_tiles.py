"""Key-range tiled maintenance (``REFLOW_TILE_BYTES``): the bucket/plan
partition must be deterministic and never split a bucket; tiled
compaction must fold to exact replay parity, survive a crash at either
per-tile seam and resume finished tiles instead of refolding them; a
torn final *tiled* delta element must fall back one element with the
WAL covering the gap; an untiled reader must restore a tiled
checkpoint (the knob is write-side only); replica snapshots must reuse
clean tiles by identity (zero-copy) and rebuild only dirty ones; and
the tile-unit bootstrap protocol must NACK-and-retry a single corrupt
unit, fall back whole when retries exhaust, and never stage a
traversal or an incomplete transfer."""

import glob
import os

import numpy as np
import pytest

from reflow_tpu import DirtyScheduler
from reflow_tpu.serve import ReplicaScheduler
from reflow_tpu.utils import tiles
from reflow_tpu.utils.checkpoint import CheckpointChain
from reflow_tpu.utils.faults import CrashInjector, CrashPoint
from reflow_tpu.wal import (DurableScheduler, SegmentShipper, WalCompactor,
                            recover)
from reflow_tpu.wal.compact import read_compact_manifest
from reflow_tpu.wal.log import _MAGIC
from reflow_tpu.workloads import wordcount


# -- helpers ----------------------------------------------------------------

def make_feed(seed, n_ticks, tag="", vocab=25):
    """Deterministic per-tick [(batch_id, batch)] lists with retractions
    mixed in (same shape as the compaction tests')."""
    rng = np.random.default_rng(seed)
    feed = []
    for t in range(n_ticks):
        batches = []
        for j in range(int(rng.integers(1, 3))):
            words = " ".join(
                f"w{int(x)}" for x in rng.integers(0, vocab,
                                                   int(rng.integers(2, 8))))
            weight = -1 if (t > 2 and rng.random() < 0.2) else 1
            batches.append((f"{tag}t{t}b{j}",
                            wordcount.ingest_lines([words], weight=weight)))
        feed.append(batches)
    return feed


def build_log(wal_dir, feed, segment_bytes=1 << 12):
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
    recover(sched, wal_dir, ckpt_dir)
    return dict(sched.view(sink.name)), sched._tick


def live_view(sched, sink):
    return {kv: w for kv, w in sched.view(sink.name).items() if w != 0}


# -- bucketing / planning primitives ----------------------------------------

def test_bucket_of_stable_across_processes():
    # crc32-based, NOT hash(): these exact values are what every other
    # process (leader, compactor, replica, shipper) computes — a change
    # here silently scatters tiles, so the constants are pinned
    assert tiles.bucket_of("alpha") == 22
    assert tiles.bucket_of(("w1", "w1")) == 3
    assert tiles.bucket_of(7) == 2
    assert tiles.bucket_of((b"x", 3.5)) == 24


def test_bucket_of_numpy_scalar_matches_python():
    # a replayed key often comes back as np.int64 where the live one
    # was int: scalarization must land both in the same bucket
    assert tiles.bucket_of(np.int64(7)) == tiles.bucket_of(7)
    arr = np.arange(3, dtype=np.float32)
    assert tiles.bucket_of(arr) == tiles.bucket_of(arr.copy())


def test_approx_row_bytes_estimates():
    assert tiles.approx_row_bytes("abc", None) == 3 + 16
    arr = np.arange(3, dtype=np.float32)
    assert tiles.approx_row_bytes(arr, None) == arr.nbytes + 16
    assert tiles.approx_row_bytes("ab", "cd") == 2 + 2 + 16


def test_plan_tiles_contiguous_cover_never_splits_bucket():
    rng = np.random.default_rng(0)
    hist = [float(x) for x in rng.integers(1, 200, tiles.N_BUCKETS)]
    plan = tiles.plan_tiles(hist, 400)
    assert len(plan) > 1
    assert plan[0][0] == 0 and plan[-1][1] == tiles.N_BUCKETS
    for (_, a_hi), (b_lo, _) in zip(plan, plan[1:]):
        assert a_hi == b_lo  # contiguous, no gap, no overlap
    assert all(hi > lo for lo, hi in plan)
    # an oversized bucket becomes its OWN tile rather than being split
    hot = [1.0] * tiles.N_BUCKETS
    hot[10] = 10_000.0
    plan = tiles.plan_tiles(hot, 100)
    i = tiles.owning_tile(plan, 10)
    assert plan[i] == (10, 11)


def test_plan_budget_zero_is_monolithic_and_owning_tile_raises():
    assert tiles.plan_tiles([1.0] * tiles.N_BUCKETS, 0) \
        == [(0, tiles.N_BUCKETS)]
    with pytest.raises(KeyError):
        tiles.owning_tile([(0, 32)], 40)


# -- tiled compaction -------------------------------------------------------

def test_tiled_fold_parity_and_manifest(tmp_path):
    # straddling keys: every tile folds its own bucket slice of every
    # source record, and the union replays to the exact oracle
    wal_dir = str(tmp_path / "wal")
    oracle, tick = build_log(wal_dir, make_feed(7, 30))
    comp = WalCompactor(wal_dir=wal_dir, min_segments=2, keep_segments=1,
                        tile_bytes=512)
    assert comp.compact_once() is not None
    while comp.compact_once() is not None:
        pass
    m = read_compact_manifest(wal_dir)
    ent = next(e for e in m["ranges"] if "tiles" in e)
    ti = ent["tiles"]
    assert ti["n"] >= 2 and ti["n"] == len(ti["plan"])
    assert ti["plan"][0][0] == 0 \
        and ti["plan"][-1][1] == tiles.N_BUCKETS
    assert all(g >= 1 for g in ti["gens"])
    assert 0 < ti["peak_tile_bytes"] <= 2 * 512
    got, got_tick = recovered_view(wal_dir)
    assert got == oracle and got_tick == tick


@pytest.mark.parametrize("seam", ["compact_tile_before_progress",
                                  "compact_tile_after_progress"])
def test_tiled_fold_crash_resumes_finished_tiles(tmp_path, seam):
    wal_dir = str(tmp_path / "wal")
    oracle, tick = build_log(wal_dir, make_feed(3, 30))
    inj = CrashInjector(2, only=seam)
    comp = WalCompactor(wal_dir=wal_dir, min_segments=2, keep_segments=1,
                        tile_bytes=512, crash=inj)
    with pytest.raises(CrashPoint):
        comp.compact_once()
    assert inj.fired_seam == seam
    # the originals are untouched mid-pass: recovery BEFORE the resume
    # sees exact parity (the tmp segment + sidecar are invisible)
    got, got_tick = recovered_view(wal_dir)
    assert got == oracle and got_tick == tick
    # a fresh compactor (new process) resumes: finished tiles are kept
    # from the sidecar, only the rest refold under attempt 2
    comp2 = WalCompactor(wal_dir=wal_dir, min_segments=2, keep_segments=1,
                         tile_bytes=512)
    ev = comp2.compact_once()
    assert ev is not None
    ti = read_compact_manifest(wal_dir)["ranges"][-1]["tiles"]
    assert ti["attempts"] == 2
    if seam == "compact_tile_after_progress":
        # two tiles were recorded done before the crash; their gen-1
        # output survives verbatim while the rest carry gen 2
        assert ti["resumed_tiles"] >= 1
        assert set(ti["gens"]) == {1, 2}
    got, got_tick = recovered_view(wal_dir)
    assert got == oracle and got_tick == tick


# -- tiled checkpoint chains ------------------------------------------------

def drive_chain(tmp_path, saves=3, per_save=5):
    """Leader + chain with a save every ``per_save`` ticks, plus an
    unsaved tail; returns (wal_dir, root, final view, tick, chain)."""
    wal_dir = str(tmp_path / "wal")
    root = str(tmp_path / "ckpt")
    g, src, sink = wordcount.build_graph()
    sched = DurableScheduler(g, wal_dir=wal_dir, fsync="tick",
                             segment_bytes=1 << 12)
    chain = CheckpointChain(root, delta_every=4)
    t = 0
    for _ in range(saves):
        for batches in make_feed(t, per_save, tag=f"s{t}"):
            for bid, b in batches:
                sched.push(src, b, batch_id=bid)
            sched.tick()
        t += per_save
        chain.save(sched)
    for batches in make_feed(99, 2, tag="tail"):
        for bid, b in batches:
            sched.push(src, b, batch_id=bid)
        sched.tick()
    view = live_view(sched, sink)
    tick = sched._tick
    sched.close()
    return wal_dir, root, view, tick, chain


def test_torn_final_tiled_delta_falls_back_one_element(
        tmp_path, monkeypatch):
    monkeypatch.setenv("REFLOW_TILE_BYTES", "512")
    wal_dir, root, view, tick, chain = drive_chain(tmp_path)
    assert chain.tile_count >= 2  # the elements really tiled
    deltas = sorted(glob.glob(os.path.join(root, "delta-*.ckd")))
    assert deltas
    with open(deltas[-1], "rb+") as f:
        f.truncate(os.path.getsize(deltas[-1]) - 4)  # tear a tile frame
    # validation happens before a single frame is applied, so the torn
    # element mutates nothing; truncation lags one element, so the WAL
    # tail still covers the dropped window — exact parity
    got, got_tick = recovered_view(wal_dir, root)
    assert {kv: w for kv, w in got.items() if w != 0} == view
    assert got_tick == tick


@pytest.mark.parametrize("seam", ["ckpt_tile_full_append",
                                  "ckpt_tile_append"])
def test_tiled_chain_crash_seam_recovers(tmp_path, monkeypatch, seam):
    # kill the element writer between tile appends: the chain manifest
    # never flipped, so recovery restores the previous element (or
    # replays from scratch) plus the untruncated WAL tail
    monkeypatch.setenv("REFLOW_TILE_BYTES", "512")
    wal_dir = str(tmp_path / "wal")
    root = str(tmp_path / "ckpt")
    g, src, sink = wordcount.build_graph()
    sched = DurableScheduler(g, wal_dir=wal_dir, fsync="tick",
                             segment_bytes=1 << 12)
    inj = CrashInjector(2, only=seam)
    chain = CheckpointChain(root, delta_every=4, crash=inj)
    fired = False
    for i in range(4):
        for batches in make_feed(20 + i, 5, tag=f"c{i}"):
            for bid, b in batches:
                sched.push(src, b, batch_id=bid)
            sched.tick()
        if not fired:
            try:
                chain.save(sched)
            except CrashPoint:
                fired = True
    assert fired and inj.fired_seam == seam
    view = live_view(sched, sink)
    tick = sched._tick
    sched.close()
    got, got_tick = recovered_view(wal_dir, root)
    assert {kv: w for kv, w in got.items() if w != 0} == view
    assert got_tick == tick


def test_untiled_reader_restores_tiled_chain(tmp_path, monkeypatch):
    # the knob is write-side only: a reader with REFLOW_TILE_BYTES
    # unset walks the same manifest and streams the same frames
    monkeypatch.setenv("REFLOW_TILE_BYTES", "512")
    wal_dir, root, view, tick, chain = drive_chain(tmp_path)
    assert chain.tile_count >= 2
    assert glob.glob(os.path.join(root, "*", "tiles", "*.ckt"))
    monkeypatch.delenv("REFLOW_TILE_BYTES")
    got, got_tick = recovered_view(wal_dir, root)
    assert {kv: w for kv, w in got.items() if w != 0} == view
    assert got_tick == tick


def test_tiled_chain_io_peaks_stay_under_twice_the_budget(
        tmp_path, monkeypatch):
    # at state >= 8x the tile budget no checkpoint frame — pickled on
    # a save or unpickled on a restore — may exceed 2x the budget: the
    # chain writer and reader never hold the whole state
    from reflow_tpu.utils.checkpoint import (TILE_IO_STATS,
                                             reset_tile_io_stats)

    budget = 512
    monkeypatch.setenv("REFLOW_TILE_BYTES", str(budget))
    reset_tile_io_stats()
    wal_dir = str(tmp_path / "wal")
    root = str(tmp_path / "ckpt")
    g, src, sink = wordcount.build_graph()
    sched = DurableScheduler(g, wal_dir=wal_dir, fsync="tick",
                             segment_bytes=1 << 12)
    chain = CheckpointChain(root, delta_every=4)
    for i in range(3):
        for batches in make_feed(40 + i, 6, tag=f"p{i}", vocab=400):
            for bid, b in batches:
                sched.push(src, b, batch_id=bid)
            sched.tick()
        chain.save(sched)                   # one full + two deltas
    view = live_view(sched, sink)
    tick = sched._tick
    sched.close()
    state = sum(tiles.approx_row_bytes(kv, w) for kv, w in view.items())
    assert state >= 8 * budget, "state too small to prove the bound"
    assert chain.tile_count >= 4
    assert 0 < TILE_IO_STATS["writer_peak_frame_bytes"] <= 2 * budget
    reset_tile_io_stats()
    got, got_tick = recovered_view(wal_dir, root)
    assert {kv: w for kv, w in got.items() if w != 0} == view
    assert got_tick == tick
    assert 0 < TILE_IO_STATS["reader_peak_frame_bytes"] <= 2 * budget


# -- tiled replica snapshots ------------------------------------------------

def make_pair(tmp_path, tile_bytes=512):
    g, src, sink = wordcount.build_graph()
    sched = DurableScheduler(g, wal_dir=str(tmp_path / "wal"),
                             fsync="tick")
    ship = SegmentShipper(sched.wal, leader_tick=lambda: sched._tick)
    g2, _s2, _k2 = wordcount.build_graph()
    rep = ReplicaScheduler(g2, str(tmp_path / "r0"), name="r0",
                           tile_bytes=tile_bytes)
    ship.attach(rep)
    return sched, src, sink, ship, rep


def pump(sched, ship, rep):
    sched.wal.sync()
    for _ in range(100):
        ship.pump_once()
        if rep.published_horizon() == sched._tick:
            return
    raise AssertionError("replica stuck")


def test_snapshot_reuses_clean_tiles_by_identity(tmp_path):
    sched, src, sink, ship, rep = make_pair(tmp_path)
    for batches in make_feed(5, 12):
        for bid, b in batches:
            sched.push(src, b, batch_id=bid)
        sched.tick()
    pump(sched, ship, rep)
    s1 = rep._snapshot(sink.name)
    assert len(s1.plan) >= 2
    # one tick touching one key: only the owning tile may rebuild
    sched.push(src, wordcount.ingest_lines(["w3 w3"]), batch_id="hot")
    sched.tick()
    pump(sched, ship, rep)
    s2 = rep._snapshot(sink.name)
    assert s2.plan == s1.plan and s2.horizon > s1.horizon
    reused = sum(1 for a, b in zip(s1.tiles, s2.tiles) if a is b)
    assert reused >= 1  # zero-copy: same array objects, same gen
    assert reused < len(s2.tiles)  # but the dirty tile DID rebuild
    for a, b in zip(s1.tiles, s2.tiles):
        assert (b.gen == a.gen) if (a is b) else (b.gen == a.gen + 1)
    assert rep.snapshot_tiles_reused >= reused
    h, got = rep.view_at(sink.name)
    assert h == sched._tick and got == live_view(sched, sink)
    sched.close()
    rep.close()


def test_snapshot_empty_window_reuses_whole_tuple(tmp_path):
    sched, src, sink, ship, rep = make_pair(tmp_path)
    for batches in make_feed(6, 8):
        for bid, b in batches:
            sched.push(src, b, batch_id=bid)
        sched.tick()
    pump(sched, ship, rep)
    s1 = rep._snapshot(sink.name)
    sched.tick()  # an empty tick: horizon advances, no sink delta
    pump(sched, ship, rep)
    s2 = rep._snapshot(sink.name)
    assert s2.horizon == s1.horizon + 1
    assert s2.tiles is s1.tiles  # the whole tuple carried by identity
    sched.close()
    rep.close()


def test_tiled_reads_match_an_untiled_snapshot_oracle(tmp_path):
    # same leader, same WAL, same horizon — only snapshot publication
    # differs (tile_bytes=0 forces monolithic arrays): top_k and lookup
    # off per-tile arrays must answer what one global array answers
    sched, src, sink, ship, rep = make_pair(tmp_path)
    g3, _s3, _k3 = wordcount.build_graph()
    oracle = ReplicaScheduler(g3, str(tmp_path / "r1"), name="r1",
                              tile_bytes=0)
    ship.attach(oracle)
    for batches in make_feed(13, 14, vocab=120):
        for bid, b in batches:
            sched.push(src, b, batch_id=bid)
        sched.tick()
    pump(sched, ship, rep)
    pump(sched, ship, oracle)
    assert len(rep._snapshot(sink.name).plan) >= 2
    view = live_view(sched, sink)
    for by in ("weight", "value"):
        h_t, top_t = rep.top_k(sink.name, 10, by=by)
        h_o, top_o = oracle.top_k(sink.name, 10, by=by)
        assert h_t == h_o == sched._tick
        # tie order may differ between a per-tile merge and one global
        # argpartition: compare the rank sequence, then every member
        rank = (lambda kv, w: w) if by == "weight" else \
            (lambda kv, w: kv[1])
        assert [rank(kv, w) for kv, w in top_t] \
            == [rank(kv, w) for kv, w in top_o]
        assert all(view.get(kv) == w for kv, w in top_t)
    for kv in list(view)[::7] + [("w-never-seen", None)]:
        assert rep.lookup(sink.name, kv) == oracle.lookup(sink.name, kv)
    sched.close()
    rep.close()
    oracle.close()


def test_replica_tile_gauges_lifecycle(tmp_path):
    from reflow_tpu.obs import MetricsRegistry

    sched, src, sink, ship, rep = make_pair(tmp_path)
    reg = MetricsRegistry()
    rep.publish_metrics(reg)
    for batches in make_feed(8, 6):
        for bid, b in batches:
            sched.push(src, b, batch_id=bid)
        sched.tick()
    pump(sched, ship, rep)
    rep._snapshot(sink.name)
    assert reg.value("replica.r0.snapshot_tiles") >= 2
    assert reg.value("replica.r0.snapshot_tiles_reused") >= 0
    rep.close()
    assert reg.value("replica.r0.snapshot_tiles") is None
    sched.close()


# -- tile-unit bootstrap protocol -------------------------------------------

def tiled_leader_with_chain(tmp_path, monkeypatch):
    monkeypatch.setenv("REFLOW_TILE_BYTES", "512")
    g, src, sink = wordcount.build_graph()
    sched = DurableScheduler(g, wal_dir=str(tmp_path / "wal"),
                             fsync="tick", segment_bytes=1 << 12)
    chain = CheckpointChain(str(tmp_path / "ckpt"), delta_every=4)
    for batches in make_feed(11, 10):
        for bid, b in batches:
            sched.push(src, b, batch_id=bid)
        sched.tick()
    chain.save(sched)
    sched.wal.sync()
    assert chain.tile_count >= 2
    return sched, src, sink, str(tmp_path / "ckpt")


class FlakyTransport:
    """Delegating replica proxy that corrupts the first N tile units in
    flight (payload flipped after the CRC was stamped)."""

    def __init__(self, inner, corrupt_first=1):
        self.inner = inner
        self.left = corrupt_first

    def __getattr__(self, name):
        return getattr(self.inner, name)

    def receive_ckpt_tile(self, unit):
        if self.left > 0 and unit.get("payload"):
            self.left -= 1
            unit = dict(unit)
            unit["payload"] = b"\xff" + unit["payload"][1:]
        return self.inner.receive_ckpt_tile(unit)


def test_tile_unit_corrupt_in_flight_nacked_and_retried(
        tmp_path, monkeypatch):
    sched, src, sink, root = tiled_leader_with_chain(tmp_path, monkeypatch)
    ship = SegmentShipper(sched.wal, ckpt_dir=root,
                          leader_tick=lambda: sched._tick)
    g2, _s2, _k2 = wordcount.build_graph()
    rep = ReplicaScheduler(g2, str(tmp_path / "r0"), name="r0")
    ship.attach(FlakyTransport(rep))
    # the corrupt unit was NACKed (per-unit CRC) and ONLY that unit was
    # re-sent; the transfer completed as a tile bootstrap, not whole
    assert rep.crc_rejects == 1
    assert ship.tile_unit_retries == 1
    assert ship.tile_bootstraps == 1
    assert ship.tile_units_shipped > 2
    pump(sched, ship, rep)
    h, got = rep.view_at(sink.name)
    assert h == sched._tick and got == live_view(sched, sink)
    sched.close()
    rep.close()


def test_tile_unit_retries_exhaust_falls_back_whole(tmp_path, monkeypatch):
    monkeypatch.setenv("REFLOW_TILE_SHIP_RETRIES", "2")
    sched, src, sink, root = tiled_leader_with_chain(tmp_path, monkeypatch)
    ship = SegmentShipper(sched.wal, ckpt_dir=root,
                          leader_tick=lambda: sched._tick)
    g2, _s2, _k2 = wordcount.build_graph()
    rep = ReplicaScheduler(g2, str(tmp_path / "r0"), name="r0")
    ship.attach(FlakyTransport(rep, corrupt_first=10 ** 6))
    # every attempt NACKs -> the shipper gives up on the unit protocol
    # and the plain whole-directory bootstrap still anchors the replica
    assert ship.tile_bootstraps == 0
    assert ship.tile_unit_retries == 2
    pump(sched, ship, rep)
    h, got = rep.view_at(sink.name)
    assert h == sched._tick and got == live_view(sched, sink)
    sched.close()
    rep.close()


def test_receive_ckpt_tile_rejects_bad_units(tmp_path):
    import zlib

    g, _s, _k = wordcount.build_graph()
    rep = ReplicaScheduler(g, str(tmp_path / "r0"), name="r0")
    assert rep.receive_ckpt_tile({"schema": "nope"})["ok"] is False
    body = b"payload"
    unit = {"schema": "reflow.tile_ship/1", "rel": "../evil", "idx": 0,
            "total": 2, "payload": body,
            "crc": zlib.crc32(body) & 0xFFFFFFFF, "last": False}
    resp = rep.receive_ckpt_tile(unit)
    assert resp["ok"] is False and "relpath" in resp["reason"]
    assert not os.path.exists(str(tmp_path / "evil"))
    # a "last" unit arriving before every index staged is an incomplete
    # transfer: NACK whole, nothing anchors
    unit = {"schema": "reflow.tile_ship/1", "rel": "meta.pkl", "idx": 1,
            "total": 3, "payload": body,
            "crc": zlib.crc32(body) & 0xFFFFFFFF, "last": True}
    resp = rep.receive_ckpt_tile(unit)
    assert resp["ok"] is False and "incomplete" in resp["reason"]
    rep.close()


def test_follower_reanchor_into_tile_compacted_range(tmp_path, monkeypatch):
    # the PR-10 stale-cursor re-anchor, with the rewritten segment now
    # holding per-tile part records: the re-anchored follower replays
    # cover + parts through the checkpoint bootstrap and converges
    monkeypatch.setenv("REFLOW_TILE_BYTES", "512")
    wal_dir = str(tmp_path / "wal")
    ckpt_dir = str(tmp_path / "ckpt")
    g, src, sink = wordcount.build_graph()
    sched = DurableScheduler(g, wal_dir=wal_dir, fsync="tick",
                             segment_bytes=1 << 12)
    chain = CheckpointChain(ckpt_dir, delta_every=4)
    chain.save(sched)
    ship = SegmentShipper(sched.wal, ckpt_dir=ckpt_dir,
                          leader_tick=lambda: sched._tick)
    g2, _s2, sink2 = wordcount.build_graph()
    replica = ReplicaScheduler(g2, str(tmp_path / "r0"), name="r0")
    ship.attach(replica)
    for batches in make_feed(4, 3):
        for bid, b in batches:
            sched.push(src, b, batch_id=bid)
        sched.tick()
    sched.wal.sync()
    ship.pump_once()
    stale = replica.subscribe()
    assert stale is not None and stale[1] > len(_MAGIC)
    ship.detach("r0")
    for batches in make_feed(6, 30, tag="x"):
        for bid, b in batches:
            sched.push(src, b, batch_id=bid)
        sched.tick()
    sched.wal.sync()
    comp = WalCompactor(sched.wal, ckpt_dir=ckpt_dir, min_segments=1,
                        keep_segments=1)
    ev = comp.compact_once()
    assert ev is not None and ev["covers"][0] == stale[0]
    ti = read_compact_manifest(wal_dir)["ranges"][-1]["tiles"]
    assert ti["n"] >= 2  # the range really was rewritten tile-wise
    ship.attach(replica)
    sched.wal.sync()
    for _ in range(200):
        ship.pump_once()
        if replica.published_horizon() == sched._tick:
            break
    assert ship.compact_reanchors >= 1
    h, got = replica.view_at(sink2.name)
    assert h == sched._tick and got == live_view(sched, sink)
    sched.close()
    replica.close()
