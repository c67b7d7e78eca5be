#!/usr/bin/env python3
"""Per-stage latency breakdown + critical path of a reflow trace.

Usage::

    python tools/trace_inspect.py trace.json           # human report
    python tools/trace_inspect.py trace.json --json    # machine summary

Input is the Chrome trace-event JSON written by
``reflow_tpu.obs.export_chrome_trace()`` (either the
``{"traceEvents": [...]}`` object or a bare event array). The report
has two halves:

- **spans**: p50/p99/total for every named span across all tracks
  (windows, ticks, WAL appends/fsyncs, device dispatches), plus the
  **durability pipeline** split: ``wal_fsync`` spans on the
  ``wal-committer`` track ran off the dispatch path (the asynchronous
  committer), spans on the ``wal`` track ran on it (inline barriers) —
  ``offpath_fsync_frac`` is the share of fsync time the pipeline moved
  off the pump, ``fsync_covered_mean`` the group-commit fan-in;
- **per-device**: ``device_dispatch`` busy time grouped by the executing
  device (from the placement/sharding tags on dispatch spans) — the
  placement-skew view of a spread-placed serving tier;
- **tickets**: the sampled tickets' end-to-end latency decomposed into
  the six pipeline stages (admission → coalesce → sched_delay →
  execute → fsync → resolve), with the **critical path** — stages
  ranked by their mean share of end-to-end latency — and the worst
  decomposition deviation (stage sums are tiled, so this should sit at
  ~0%; large values mean a clock or export bug).

A trace of a serving pump carries the **pump cycle**: the pump
thread's top-level spans (``pump_wait``, ``pump_turn``, ``host_merge``,
``window_stage``, ``pump_execute``, ``window_retire``) tile its wall,
so the report gives the time by span with the share of it the thread
spent off the CPU (``dur - cpu_s``: waiting for the interpreter lock, a
lock, or the device inside a slot write), what no span covers, the
cycle (gap between ``window_stage`` starts), and the device's busy
share from the executor's own ``window_device`` completion spans.

A trace recorded under WAL shipping (``wal/ship.py`` +
``serve/replica.py``) carries ``ship_segment`` spans on the
``wal-shipper`` track and ``replica_replay`` spans on per-replica
tracks; the report folds them into a **replication** section — per
follower byte flow and NACKs, per replica applied records, replay time,
and the published-horizon lag after each window.

A trace whose spans carry **causality tokens**
(``obs.trace.mint_cause`` stamped onto writes, shipments, and delta
frames while tracing is on) gets a **causal chains** section: spans
sharing a token in ``args.cause`` / ``args.causes`` are stitched into
cross-process chains, and tokens co-occurring on one span (a chunk's
own token beside the write tokens it carries) are bridged into one
group — so a write's journey ``producer_submit`` → ``rpc_admit`` →
``admission`` → ``wal_append`` → ``ship_segment`` → ``net_send`` →
``replica_replay`` → ``sub_fanout`` → ``sub_deliver`` reads as a
single chain even though no single process saw it whole. Groups
carrying all nine links are **full chains** and feed the **freshness**
section: ack→delta-visible latency tiled into admission / durability /
ship / apply / fanout / deliver, with the worst tiling deviation.
Passing several trace files merges them onto one timeline via their
``baseTimeS`` anchors (same-host processes share the monotonic
clock). ``--require-chain a,b,c`` makes the exit status assert that
at least one causal group carries all the named spans (the fleet
bench's smoke check).

A trace recorded across a **leader failover** (``serve/failover.py``)
carries ``failover_elect`` / ``failover_replay`` spans on the
``failover`` and replica tracks and ``fence_reject`` spans wherever a
zombie write was turned away; the report folds them into a **failover**
section — promotions, elect/replay time, and fence rejects by kind
(append vs shipment) — the promotion timeline an operator reads after
pulling a leader.

A trace recorded under a live ``ControlPlane`` also carries its
actuations as zero-duration ``control.<action>`` spans on the
``control`` track; the report surfaces them as **control actions** —
counts per action (brownout steps/recoveries, respawns, breaker
opens/probes/closes, scale events, floor reclaims) — so an operator can
line the controller's interventions up against the data-path spans they
reacted to.
"""

from __future__ import annotations

import argparse
import json
import os
import sys
from collections import defaultdict

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from reflow_tpu.obs.export import ticket_timelines  # noqa: E402
from reflow_tpu.obs.trace import STAGES  # noqa: E402
from reflow_tpu.utils.metrics import percentile  # noqa: E402

#: the canonical follow-the-write chain, producer keystroke to
#: subscriber-visible answer; a causal group carrying all nine links
#: is a *full chain* and feeds the freshness decomposition
FULL_CHAIN = ("producer_submit", "rpc_admit", "admission", "wal_append",
              "ship_segment", "net_send", "replica_replay",
              "sub_fanout", "sub_deliver")

#: ack→push freshness stages; each tiles between two chain boundaries
FRESHNESS_STAGES = ("admission", "durability", "ship", "apply",
                    "fanout", "deliver")

#: span kinds ONE write's token must itself carry for its ack→deliver
#: freshness decomposition (the cut points in ``_chain_freshness``).
#: Deliberately narrower than FULL_CHAIN: ``net_send`` joins a write's
#: group only through the shipped chunk's own token, and a bridged
#: group can blob MANY writes together — decomposing over group bounds
#: would mix cut points from different writes and break the tiling.
FRESHNESS_SPANS = ("producer_submit", "rpc_admit", "wal_append",
                   "replica_replay", "sub_fanout", "sub_deliver")


#: the spans that tile a pump thread's wall (the umbrella ``window``
#: overlaps them all and is left out)
PUMP_SPANS = ("pump_wait", "pump_turn", "host_merge", "window_stage",
              "pump_execute", "window_retire")


def _pump_cycle(events, tid_names):
    """The pump-cycle section: per pump track (one that recorded
    ``pump_execute``) the time by top-level span with its CPU seconds,
    the off-CPU share of the working spans, the untiled share of the
    wall and the cycle; and the device's busy share from
    ``window_device``. None when the trace holds no pump."""
    by_track: dict = defaultdict(list)
    device = []
    for ev in events:
        if ev.get("ph") != "X":
            continue
        name = ev.get("name")
        if name in PUMP_SPANS:
            by_track[ev.get("tid")].append(ev)
        elif name == "window_device":
            device.append(ev)
    by_track = {t: evs for t, evs in by_track.items()
                if any(e["name"] == "pump_execute" for e in evs)}
    if not by_track and not device:
        return None
    by_span: dict = defaultdict(lambda: {"count": 0, "ms": 0.0,
                                         "cpu_ms": 0.0})
    wall = covered = 0.0
    cycles = []
    for evs in by_track.values():
        evs.sort(key=lambda e: e["ts"])
        wall += max(e["ts"] + e["dur"] for e in evs) - evs[0]["ts"]
        edge = evs[0]["ts"]
        for e in evs:
            end = e["ts"] + e["dur"]
            if end > edge:
                covered += end - max(e["ts"], edge)
                edge = end
            d = by_span[e["name"]]
            d["count"] += 1
            d["ms"] += e["dur"] / 1e3
            d["cpu_ms"] += 1e3 * float(
                (e.get("args") or {}).get("cpu_s", 0.0))
        starts = [e["ts"] for e in evs if e["name"] == "window_stage"]
        cycles += [b - a for a, b in zip(starts, starts[1:])]
    work_ms = sum(d["ms"] for n, d in by_span.items() if n != "pump_wait")
    work_cpu = sum(d["cpu_ms"] for n, d in by_span.items()
                   if n != "pump_wait")
    out = {
        "tracks": sorted(str(tid_names.get(t, t)) for t in by_track),
        "wall_ms": round(wall / 1e3, 3),
        "untiled_frac": round(1.0 - covered / wall, 6) if wall else 0.0,
        "offcpu_frac": (round(1.0 - work_cpu / work_ms, 4)
                        if work_ms else 0.0),
        "cycle_p50_us": round(percentile(cycles, 50), 3),
        "by_span": {
            n: {"count": d["count"], "ms": round(d["ms"], 3),
                "cpu_ms": round(d["cpu_ms"], 3),
                "share": round(1e3 * d["ms"] / wall, 4) if wall else 0.0}
            for n, d in sorted(by_span.items(),
                               key=lambda kv: -kv[1]["ms"])},
        "device": None,
    }
    if device:
        t0 = min(e["ts"] for e in device)
        t1 = max(e["ts"] + e["dur"] for e in device)
        busy = sum(e["dur"] for e in device)
        out["device"] = {
            "windows": len(device),
            "busy_ms": round(busy / 1e3, 3),
            "window_p50_us": round(
                percentile([e["dur"] for e in device], 50), 3),
            "busy_frac": round(busy / (t1 - t0), 4) if t1 > t0 else 0.0}
    return out


def load_events(path: str) -> list:
    with open(path) as f:
        raw = json.load(f)
    return raw["traceEvents"] if isinstance(raw, dict) else raw


def load_traces(paths) -> tuple:
    """Load + merge one or more trace files onto a shared timeline.

    Every ``export_chrome_trace`` file carries ``baseTimeS`` — the
    ``perf_counter()`` instant its ``ts=0`` maps to. Processes on one
    host share that clock, so shifting each file's events by
    ``(baseTimeS - min(baseTimeS)) * 1e6`` puts all spans on directly
    comparable microseconds. Per-file ``tid`` namespaces are kept
    disjoint by rewriting tids to ``(file_index, tid)`` pairs. Returns
    ``(events, files)`` where ``files`` records each path's base and
    node id."""
    loaded, files = [], []
    for i, path in enumerate(paths):
        with open(path) as f:
            raw = json.load(f)
        if isinstance(raw, dict):
            events = raw.get("traceEvents", [])
            base = float(raw.get("baseTimeS") or 0.0)
            node = raw.get("node")
        else:
            events, base, node = raw, 0.0, None
        files.append({"path": path, "base_time_s": base, "node": node})
        loaded.append((i, events, base))
    base0 = min((f["base_time_s"] for f in files), default=0.0)
    merged = []
    for i, events, base in loaded:
        off_us = (base - base0) * 1e6
        for ev in events:
            e = dict(ev)
            if "tid" in e:
                e["tid"] = (i, e["tid"])
            if e.get("ph") == "X":
                e["ts"] = float(e.get("ts", 0.0)) + off_us
            merged.append(e)
    return merged, files


def read_report(obj: dict) -> dict:
    """Normalize a ``--json`` report across schema versions: a
    ``reflow.trace_inspect/1`` report (single file, token-keyed chains,
    no freshness section) reads back with the /2 keys defaulted, so
    downstream consumers can be written once against /2."""
    out = dict(obj)
    out.setdefault("schema", "reflow.trace_inspect/1")
    out.setdefault("freshness", None)
    if not out.get("trace_files"):
        tf = out.get("trace_file")
        out["trace_files"] = [tf] if tf else []
    ca = out.get("causal")
    if ca is not None:
        ca.setdefault("groups", ca.get("chains", 0))
        ca.setdefault("full_chains", 0)
    return out


def _chain_freshness(bounds) -> tuple:
    """One full chain's ack→deliver decomposition from its per-span
    time bounds: ``(stage_durs_us, e2e_us, deviation_frac)``. The six
    stages tile the boundaries producer_submit.start → first
    rpc_admit.end (a lost ack's dedup re-admit lands later) →
    wal_append.end → replica_replay.start → min(replica_replay.end,
    sub_fanout.end) → sub_fanout.end → sub_deliver.end; a stage going
    negative (clock
    skew between merged files) is clamped to 0 and shows up in the
    deviation instead of silently corrupting a neighbor."""
    def _first_end(b):
        # min end when tracked (3-element bounds); a 2-element bound
        # (older report data, hand-built tests) falls back to max end
        return b[2] if len(b) > 2 else b[1]

    # the hub fans out synchronously inside the replay batch's span
    # (the window-retire callback), so replica_replay can CLOSE after
    # the push — even after the subscriber recorded delivery. The
    # first completed push therefore bounds apply completion from
    # above; taking the min keeps the cut sequence monotone instead
    # of charging the replay span's trailing bookkeeping to a
    # negative fanout stage.
    apply_done = min(bounds["replica_replay"][1],
                     bounds["sub_fanout"][1])
    cuts = (bounds["producer_submit"][0],
            _first_end(bounds["rpc_admit"]),
            bounds["wal_append"][1],
            bounds["replica_replay"][0],
            apply_done,
            bounds["sub_fanout"][1],
            bounds["sub_deliver"][1])
    raw = {name: cuts[i + 1] - cuts[i]
           for i, name in enumerate(FRESHNESS_STAGES)}
    stages = {name: max(0.0, v) for name, v in raw.items()}
    e2e = cuts[-1] - cuts[0]
    dev = (abs(sum(stages.values()) - e2e) / e2e) if e2e > 0 else 0.0
    return stages, e2e, dev, raw


def _freshness_summary(full_chains):
    """Aggregate the per-write decompositions of every ``(token, chain)``
    whose chain carries all of ``FRESHNESS_SPANS``; None when no write's
    chain is complete enough to decompose. ``worst`` names the chain
    with the largest tiling deviation and its unclamped stage deltas —
    a negative raw delta fingers the cut whose ordering broke."""
    if not full_chains:
        return None
    per_stage: dict = {s: [] for s in FRESHNESS_STAGES}
    e2e_list, devs = [], []
    worst = None
    for tok, ch in full_chains:
        stages, e2e, dev, raw = _chain_freshness(ch["bounds"])
        for s, v in stages.items():
            per_stage[s].append(v)
        e2e_list.append(e2e)
        devs.append(dev)
        if worst is None or dev > worst["dev_frac"]:
            worst = {"token": tok, "e2e_us": round(e2e, 3),
                     "dev_frac": round(dev, 6),
                     "raw_stage_us": {s: round(v, 3)
                                      for s, v in raw.items()}}
    mean_e2e = sum(e2e_list) / len(e2e_list)
    out_stages = {}
    for s in FRESHNESS_STAGES:
        vals = per_stage[s]
        mean = sum(vals) / len(vals)
        out_stages[s] = {
            "p50_us": round(percentile(vals, 50), 3),
            "p99_us": round(percentile(vals, 99), 3),
            "mean_share": (round(mean / mean_e2e, 4)
                           if mean_e2e else 0.0)}
    return {"chains": len(full_chains),
            "stages": out_stages,
            "e2e_p50_us": round(percentile(e2e_list, 50), 3),
            "e2e_p99_us": round(percentile(e2e_list, 99), 3),
            "max_dev_frac": round(max(devs), 6),
            "worst": worst}


def inspect(path, require_chain=None) -> dict:
    """Summarize one trace file (or a list of them, merged onto a
    shared timeline via ``baseTimeS``); the dict is the ``--json``
    output. ``require_chain`` (a list of span names) additionally
    reports, as ``causal.required_chains``, how many causal groups
    carry *all* of the named spans — the assertable form of "the
    end-to-end path survived"."""
    paths = [path] if isinstance(path, str) else list(path)
    events, files = load_traces(paths)
    by_name: dict = defaultdict(list)
    tracks = set()
    # numeric tid -> track name, from the thread_name metadata events
    tid_names = {ev.get("tid"): ev["args"]["name"] for ev in events
                 if ev.get("ph") == "M"
                 and ev.get("name") == "thread_name"}
    fsync_on, fsync_off, covered = [], [], []
    # executing-device busy time, from the device tag placement/sharding
    # stamps onto dispatch-side spans ("(default)" = untagged executor)
    dev_busy: dict = defaultdict(float)
    dev_dispatches: dict = defaultdict(int)
    # pipelined staging: window_stage spans carry args.inflight (windows
    # already dispatched when this stage began) — inflight > 0 means the
    # host staging wall overlapped device compute
    stage_total, stage_overlapped = 0.0, 0.0
    # pump_execute spans carry args.depth (in-flight windows INCLUDING
    # the one being dispatched) — the occupancy histogram of the pipeline
    depth_counts: dict = defaultdict(int)
    # WAL shipping / replica replay (wal/ship.py, serve/replica.py):
    # ship_segment spans carry the per-follower byte flow, replica_replay
    # spans the applied windows and the lag the replica published after
    # each one — together the replica-lag breakdown
    ship_by_follower: dict = defaultdict(
        lambda: {"shipments": 0, "bytes": 0, "nacks": 0, "ship_ms": 0.0})
    replay_by_replica: dict = defaultdict(
        lambda: {"shipments": 0, "records_applied": 0, "replay_ms": 0.0,
                 "horizon": 0, "lag_ticks": 0, "max_lag_ticks": 0})
    # tiled maintenance (wal/compact.py, utils/checkpoint.py,
    # wal/ship.py): compact_tile per folded key-range tile (resident
    # fold bytes), ckpt_tile per checkpoint tile frame (full/delta),
    # tile_ship per checkpoint file shipped as a CRC-framed unit —
    # together the bounded-peak-memory evidence for a tiled pass
    tiles_acc = {
        "compact_tile": {"tiles": 0, "ms": 0.0, "parts": 0,
                         "max_resident_bytes": 0},
        "ckpt_tile": {"tiles": 0, "ms": 0.0, "full": 0, "delta": 0,
                      "max_bytes": 0},
        "tile_ship": {"units": 0, "ms": 0.0, "bytes": 0,
                      "retries": 0, "rejects": 0},
    }
    # failover (serve/failover.py, serve/replica.py, wal/log.py):
    # failover_elect marks the decision, failover_replay the winner's
    # mirrored-prefix replay, fence_reject every zombie write the new
    # epoch turned away — the promotion timeline, span by span
    failover_events: list = []
    fence_rejects: dict = defaultdict(int)
    # wire transport (net/client.py): net_send per roundtrip on the
    # net/<follower> track, net_reconnect per recovery attempt — the
    # per-link health breakdown
    net_by_link: dict = defaultdict(
        lambda: {"sends": 0, "send_failures": 0, "send_ms": 0.0,
                 "ops": defaultdict(int), "reconnect_attempts": 0,
                 "reconnects": 0, "reconnect_ms": 0.0,
                 "last_state": None})
    # causal chains (obs.trace.mint_cause): spans sharing one
    # args.cause token are one write's cross-process journey —
    # chains[token] = {span name -> [durs]}, per-name time bounds, and
    # the chain's overall span. A span may carry several tokens (one
    # args.cause plus an args.causes list — e.g. a shipped chunk's own
    # token alongside the write tokens it carries); tokens co-occurring
    # on one span are bridged into a single *group* (union-find), which
    # is how a write token meets the chunk token its bytes rode in
    # net_send.
    chains: dict = defaultdict(
        lambda: {"links": defaultdict(list), "bounds": {},
                 "t0": None, "t1": None})
    uf_parent: dict = {}

    def _find(t):
        r = t
        while uf_parent.setdefault(r, r) != r:
            r = uf_parent[r]
        while uf_parent[t] != r:
            uf_parent[t], t = r, uf_parent[t]
        return r

    def _union(a, b):
        ra, rb = _find(a), _find(b)
        if ra != rb:
            uf_parent[rb] = ra
    for ev in events:
        if ev.get("ph") == "X":
            by_name[ev.get("name", "?")].append(float(ev.get("dur", 0.0)))
            tracks.add(ev.get("tid"))
            a = ev.get("args") or {}
            tokens = []
            if a.get("cause"):
                tokens.append(a["cause"])
            for tok in a.get("causes") or ():
                if tok not in tokens:
                    tokens.append(tok)
            if tokens:
                ts = float(ev.get("ts", 0.0))
                dur = float(ev.get("dur", 0.0))
                name = ev.get("name", "?")
                for tok in tokens:
                    _find(tok)
                    ch = chains[tok]
                    ch["links"][name].append(dur)
                    b = ch["bounds"].get(name)
                    if b is None:
                        # [min start, max end, min end]: a resubmitted
                        # write can carry several same-name spans (the
                        # dedup re-admit after an ack was lost); cuts
                        # that mean "first time this happened" read
                        # the min end
                        ch["bounds"][name] = [ts, ts + dur, ts + dur]
                    else:
                        b[0] = min(b[0], ts)
                        b[1] = max(b[1], ts + dur)
                        b[2] = min(b[2], ts + dur)
                    ch["t0"] = ts if ch["t0"] is None \
                        else min(ch["t0"], ts)
                    ch["t1"] = (ts + dur if ch["t1"] is None
                                else max(ch["t1"], ts + dur))
                for tok in tokens[1:]:
                    _union(tokens[0], tok)
            if ev.get("name") == "device_dispatch":
                dev = (ev.get("args") or {}).get("device") or "(default)"
                dev_busy[dev] += float(ev.get("dur", 0.0))
                dev_dispatches[dev] += 1
            if ev.get("name") == "window_stage":
                dur = float(ev.get("dur", 0.0))
                stage_total += dur
                if int((ev.get("args") or {}).get("inflight", 0) or 0) > 0:
                    stage_overlapped += dur
            if ev.get("name") == "pump_execute":
                d = (ev.get("args") or {}).get("depth")
                if d is not None:
                    depth_counts[int(d)] += 1
            if ev.get("name") == "ship_segment":
                a = ev.get("args") or {}
                st = ship_by_follower[a.get("follower") or "?"]
                st["shipments"] += 1
                st["bytes"] += int(a.get("bytes", 0) or 0)
                st["ship_ms"] += float(ev.get("dur", 0.0)) / 1e3
                if not a.get("ack", True):
                    st["nacks"] += 1
            if ev.get("name") == "replica_replay":
                a = ev.get("args") or {}
                track = tid_names.get(ev.get("tid"), "replica/?")
                name = track.split("/", 1)[1] if "/" in track else track
                st = replay_by_replica[name]
                st["shipments"] += 1
                st["records_applied"] += int(a.get("applied", 0) or 0)
                st["replay_ms"] += float(ev.get("dur", 0.0)) / 1e3
                st["horizon"] = max(st["horizon"],
                                    int(a.get("horizon", 0) or 0))
                lag = int(a.get("lag_ticks", 0) or 0)
                st["lag_ticks"] = lag
                st["max_lag_ticks"] = max(st["max_lag_ticks"], lag)
            if ev.get("name") == "compact_tile":
                a = ev.get("args") or {}
                st = tiles_acc["compact_tile"]
                st["tiles"] += 1
                st["ms"] += float(ev.get("dur", 0.0)) / 1e3
                st["parts"] += int(a.get("parts", 0) or 0)
                st["max_resident_bytes"] = max(
                    st["max_resident_bytes"],
                    int(a.get("resident_bytes", 0) or 0))
            if ev.get("name") == "ckpt_tile":
                a = ev.get("args") or {}
                st = tiles_acc["ckpt_tile"]
                st["tiles"] += 1
                st["ms"] += float(ev.get("dur", 0.0)) / 1e3
                kind = a.get("kind")
                if kind in ("full", "delta"):
                    st[kind] += 1
                st["max_bytes"] = max(st["max_bytes"],
                                      int(a.get("bytes", 0) or 0))
            if ev.get("name") == "tile_ship":
                a = ev.get("args") or {}
                st = tiles_acc["tile_ship"]
                st["ms"] += float(ev.get("dur", 0.0)) / 1e3
                if a.get("ok", True):
                    st["units"] += 1
                    st["bytes"] += int(a.get("bytes", 0) or 0)
                else:
                    st["rejects"] += 1
                if int(a.get("attempt", 0) or 0) > 0:
                    st["retries"] += 1
            if ev.get("name") == "failover_elect":
                a = ev.get("args") or {}
                failover_events.append({
                    "event": "elect", "winner": a.get("winner"),
                    "epoch": a.get("epoch"), "reason": a.get("reason"),
                    "drained_bytes": a.get("drained_bytes"),
                    "ms": round(float(ev.get("dur", 0.0)) / 1e3, 3)})
            if ev.get("name") == "failover_replay":
                a = ev.get("args") or {}
                failover_events.append({
                    "event": "replay", "epoch": a.get("epoch"),
                    "horizon": a.get("horizon"),
                    "replayed_pushes": a.get("replayed_pushes"),
                    "replayed_ticks": a.get("replayed_ticks"),
                    "ms": round(float(ev.get("dur", 0.0)) / 1e3, 3)})
            if ev.get("name") == "fence_reject":
                kind = (ev.get("args") or {}).get("kind") or "?"
                fence_rejects[kind] += 1
            if ev.get("name") in ("net_send", "net_reconnect"):
                a = ev.get("args") or {}
                track = tid_names.get(ev.get("tid"), "net/?")
                link = track.split("/", 1)[1] if "/" in track else track
                st = net_by_link[link]
                if ev["name"] == "net_send":
                    st["sends"] += 1
                    st["send_ms"] += float(ev.get("dur", 0.0)) / 1e3
                    st["ops"][a.get("op") or "?"] += 1
                    if not a.get("ok", True):
                        st["send_failures"] += 1
                else:
                    st["reconnect_attempts"] += 1
                    st["reconnect_ms"] += float(ev.get("dur", 0.0)) / 1e3
                    if a.get("ok") and a.get("recovered"):
                        st["reconnects"] += 1
                if a.get("state"):
                    st["last_state"] = a["state"]
                elif a.get("ok"):
                    st["last_state"] = "healthy"
            if ev.get("name") == "wal_fsync":
                dur = float(ev.get("dur", 0.0))
                if tid_names.get(ev.get("tid")) == "wal-committer":
                    fsync_off.append(dur)
                    covered.append(
                        float((ev.get("args") or {}).get("covered", 0)))
                else:
                    fsync_on.append(dur)
    spans = {
        name: {"count": len(durs),
               "p50_us": round(percentile(durs, 50), 3),
               "p99_us": round(percentile(durs, 99), 3),
               "total_ms": round(sum(durs) / 1e3, 3)}
        for name, durs in sorted(by_name.items())}

    tickets = ticket_timelines(events)
    e2e = [t["e2e_us"] for t in tickets.values()]
    stage_durs = {s: [t["stages"].get(s, 0.0) for t in tickets.values()]
                  for s in STAGES}
    mean_e2e = sum(e2e) / len(e2e) if e2e else 0.0
    stage_summary = {}
    for s in STAGES:
        durs = stage_durs[s]
        mean = sum(durs) / len(durs) if durs else 0.0
        stage_summary[s] = {
            "p50_us": round(percentile(durs, 50), 3),
            "p99_us": round(percentile(durs, 99), 3),
            "mean_share": round(mean / mean_e2e, 4) if mean_e2e else 0.0,
        }
    critical_path = sorted(
        STAGES, key=lambda s: stage_summary[s]["mean_share"],
        reverse=True)
    max_dev = 0.0
    for t in tickets.values():
        if t["e2e_us"] > 0:
            max_dev = max(max_dev, abs(t["sum_us"] - t["e2e_us"])
                          / t["e2e_us"])
    control_actions = {
        name[len("control."):]: len(durs)
        for name, durs in sorted(by_name.items())
        if name.startswith("control.")}
    # mega-tick occupancy: how much of the commit-window wall was the
    # device dispatch itself — the compiled-window path drives this
    # toward 1.0 (dispatch-bound), the per-tick crank leaves it low
    dispatch_us = sum(by_name.get("device_dispatch", ()))
    window_us = (sum(by_name.get("window", ()))
                 or sum(by_name.get("tick_many", ())))
    window_dispatch_frac = (round(dispatch_us / window_us, 4)
                            if window_us else 0.0)
    fsync_total = sum(fsync_on) + sum(fsync_off)
    durability = {
        "onpath_fsyncs": len(fsync_on),
        "offpath_fsyncs": len(fsync_off),
        "onpath_fsync_ms": round(sum(fsync_on) / 1e3, 3),
        "offpath_fsync_ms": round(sum(fsync_off) / 1e3, 3),
        "offpath_fsync_frac": (round(sum(fsync_off) / fsync_total, 4)
                               if fsync_total else 0.0),
        "fsync_covered_mean": (round(sum(covered) / len(covered), 2)
                               if covered else 0.0),
    }
    dev_total = sum(dev_busy.values())
    per_device = {
        dev: {"dispatches": dev_dispatches[dev],
              "busy_ms": round(busy / 1e3, 3),
              "share": round(busy / dev_total, 4) if dev_total else 0.0}
        for dev, busy in sorted(dev_busy.items())}
    stage_overlap_frac = (round(stage_overlapped / stage_total, 4)
                          if stage_total else 0.0)
    dispatch_by_depth = {str(d): n for d, n in sorted(depth_counts.items())}
    replication = None
    if ship_by_follower or replay_by_replica:
        for st in ship_by_follower.values():
            st["ship_ms"] = round(st["ship_ms"], 3)
        for st in replay_by_replica.values():
            st["replay_ms"] = round(st["replay_ms"], 3)
        replication = {
            "ship": {k: dict(v)
                     for k, v in sorted(ship_by_follower.items())},
            "replicas": {k: dict(v)
                         for k, v in sorted(replay_by_replica.items())},
            "max_lag_ticks": max(
                (v["max_lag_ticks"] for v in replay_by_replica.values()),
                default=0),
            "final_lag_ticks": max(
                (v["lag_ticks"] for v in replay_by_replica.values()),
                default=0),
        }
    network = None
    if net_by_link:
        network = {}
        for link, st in sorted(net_by_link.items()):
            network[link] = {
                "sends": st["sends"],
                "send_failures": st["send_failures"],
                "send_ms": round(st["send_ms"], 3),
                "ops": dict(sorted(st["ops"].items())),
                "reconnect_attempts": st["reconnect_attempts"],
                "reconnects": st["reconnects"],
                "reconnect_ms": round(st["reconnect_ms"], 3),
                "last_state": st["last_state"],
            }
    causal = None
    freshness = None
    if chains:
        # fold token-keyed chains into bridged groups (union-find roots)
        groups: dict = {}
        for tok, ch in chains.items():
            g = groups.get(_find(tok))
            if g is None:
                groups[_find(tok)] = g = {
                    "links": defaultdict(list), "bounds": {},
                    "t0": None, "t1": None, "tokens": []}
            g["tokens"].append(tok)
            for name, durs in ch["links"].items():
                g["links"][name].extend(durs)
            for name, b in ch["bounds"].items():
                gb = g["bounds"].get(name)
                if gb is None:
                    g["bounds"][name] = list(b)
                else:
                    gb[0] = min(gb[0], b[0])
                    gb[1] = max(gb[1], b[1])
                    if len(gb) > 2 and len(b) > 2:
                        gb[2] = min(gb[2], b[2])
            if ch["t0"] is not None:
                g["t0"] = ch["t0"] if g["t0"] is None \
                    else min(g["t0"], ch["t0"])
                g["t1"] = ch["t1"] if g["t1"] is None \
                    else max(g["t1"], ch["t1"])
        # the canonical replication chain; a group carrying all three
        # links is "complete" — per-link attribution is computed over
        # those, so partial chains (dropped shipment, wrapped ring)
        # can't skew the hop shares
        chain_links = ("ship_segment", "net_send", "replica_replay")
        complete = [g for g in groups.values()
                    if all(name in g["links"] for name in chain_links)]
        full = [g for g in groups.values()
                if all(name in g["links"] for name in FULL_CHAIN)]
        link_us: dict = defaultdict(float)
        link_count: dict = defaultdict(int)
        e2e_us_list = []
        for g in complete:
            e2e_us_list.append((g["t1"] or 0.0) - (g["t0"] or 0.0))
            for name, durs in g["links"].items():
                link_us[name] += sum(durs)
                link_count[name] += len(durs)
        total_link_us = sum(link_us.values())
        causal = {
            "chains": len(chains),
            "groups": len(groups),
            "complete_chains": len(complete),
            "full_chains": len(full),
            "links": {
                name: {"spans": link_count[name],
                       "total_ms": round(us / 1e3, 3),
                       "share": (round(us / total_link_us, 4)
                                 if total_link_us else 0.0)}
                for name, us in sorted(link_us.items())},
            "chain_e2e_p50_us": round(percentile(e2e_us_list, 50), 3),
            "chain_e2e_p99_us": round(percentile(e2e_us_list, 99), 3),
            "span_names": sorted({name for ch in chains.values()
                                  for name in ch["links"]}),
        }
        if require_chain:
            causal["required_chains"] = sum(
                1 for g in groups.values()
                if all(name in g["links"] for name in require_chain))
        # freshness decomposes ONE write's journey, so it is computed
        # over token-keyed chains, never bridged groups (see
        # FRESHNESS_SPANS for why)
        freshness = _freshness_summary(
            [(tok, ch) for tok, ch in chains.items()
             if all(name in ch["links"] for name in FRESHNESS_SPANS)])
    tiles = None
    if any(st["tiles"] for k, st in tiles_acc.items()
           if "tiles" in st) or tiles_acc["tile_ship"]["units"] \
            or tiles_acc["tile_ship"]["rejects"]:
        for st in tiles_acc.values():
            st["ms"] = round(st["ms"], 3)
        tiles = tiles_acc
    failover = None
    if failover_events or fence_rejects:
        failover = {
            "promotions": sum(1 for e in failover_events
                              if e["event"] == "elect"),
            "elect_ms": round(sum(e["ms"] for e in failover_events
                                  if e["event"] == "elect"), 3),
            "replay_ms": round(sum(e["ms"] for e in failover_events
                                   if e["event"] == "replay"), 3),
            "fence_rejects": dict(sorted(fence_rejects.items())),
            "events": failover_events,
        }
    return {
        "schema": "reflow.trace_inspect/2",
        "trace_file": paths[0],
        "trace_files": paths,
        "files": files,
        "events": sum(len(d) for d in by_name.values()),
        "tracks": len(tracks),
        "freshness": freshness,
        "durability": durability,
        "failover": failover,
        "window_dispatch_frac": window_dispatch_frac,
        "stage_overlap_frac": stage_overlap_frac,
        "dispatch_by_depth": dispatch_by_depth,
        "pump_cycle": _pump_cycle(events, tid_names),
        "per_device": per_device,
        "replication": replication,
        "tiles": tiles,
        "network": network,
        "causal": causal,
        "control_actions": control_actions,
        "spans": spans,
        "tickets": len(tickets),
        "ticket_e2e_p50_us": round(percentile(e2e, 50), 3),
        "ticket_e2e_p99_us": round(percentile(e2e, 99), 3),
        "ticket_stages": stage_summary,
        "critical_path": critical_path,
        "decomposition_max_dev_frac": round(max_dev, 6),
    }


def _print_human(s: dict) -> None:
    print(f"{s['trace_file']}: {s['events']} span(s) on "
          f"{s['tracks']} track(s)")
    print(f"{'span':<16} {'count':>7} {'p50_us':>12} {'p99_us':>12} "
          f"{'total_ms':>10}")
    for name, d in s["spans"].items():
        print(f"{name:<16} {d['count']:>7} {d['p50_us']:>12.1f} "
              f"{d['p99_us']:>12.1f} {d['total_ms']:>10.2f}")
    dur = s["durability"]
    if dur["onpath_fsyncs"] or dur["offpath_fsyncs"]:
        print(f"durability: {dur['offpath_fsyncs']} fsync(s) off the "
              f"dispatch path ({dur['offpath_fsync_frac']:.0%} of fsync "
              f"time), {dur['onpath_fsyncs']} inline; mean group "
              f"coverage {dur['fsync_covered_mean']:.1f}")
    if s["window_dispatch_frac"]:
        print(f"window dispatch fraction: "
              f"{s['window_dispatch_frac']:.0%} of commit-window time "
              f"was device dispatch")
    if s.get("stage_overlap_frac"):
        print(f"stage overlap: {s['stage_overlap_frac']:.0%} of host "
              f"staging time ran while a window was in flight")
    if s.get("dispatch_by_depth"):
        occ = ", ".join(f"depth {d}: {n}"
                        for d, n in s["dispatch_by_depth"].items())
        print(f"dispatch occupancy: {occ}")
    pc = s.get("pump_cycle")
    if pc:
        if pc["by_span"]:
            print(f"pump cycle ({', '.join(pc['tracks'])}): "
                  f"{pc['wall_ms']:.2f}ms wall, cycle p50 "
                  f"{pc['cycle_p50_us'] / 1e3:.3f}ms, "
                  f"{pc['offcpu_frac']:.0%} of the working spans off "
                  f"the CPU, {pc['untiled_frac']:.2%} under no span")
            print(f"  {'span':<14} {'count':>7} {'ms':>10} {'cpu_ms':>10} "
                  f"{'share':>7}")
            for name, d in pc["by_span"].items():
                print(f"  {name:<14} {d['count']:>7} {d['ms']:>10.2f} "
                      f"{d['cpu_ms']:>10.2f} {100 * d['share']:>6.1f}%")
        dv = pc["device"]
        if dv:
            print(f"  device: {dv['windows']} window(s) completed, busy "
                  f"{dv['busy_ms']:.2f}ms = {dv['busy_frac']:.1%} of "
                  f"first launch to last completion, window p50 "
                  f"{dv['window_p50_us'] / 1e3:.3f}ms")
    if s.get("per_device"):
        print(f"{'device':<12} {'dispatches':>11} {'busy_ms':>10} "
              f"{'share':>8}")
        for dev, d in s["per_device"].items():
            print(f"{dev:<12} {d['dispatches']:>11} {d['busy_ms']:>10.2f} "
                  f"{100 * d['share']:>7.1f}%")
    rep = s.get("replication")
    if rep:
        print(f"replication: max lag {rep['max_lag_ticks']} tick(s), "
              f"final lag {rep['final_lag_ticks']} tick(s)")
        for name, d in rep["replicas"].items():
            print(f"  replica {name}: {d['shipments']} shipment(s) "
                  f"{d['records_applied']} record(s) applied in "
                  f"{d['replay_ms']:.2f}ms, horizon {d['horizon']}, "
                  f"lag {d['lag_ticks']} (max {d['max_lag_ticks']})")
        for name, d in rep["ship"].items():
            print(f"  ship->{name}: {d['shipments']} shipment(s) "
                  f"{d['bytes']} byte(s) in {d['ship_ms']:.2f}ms, "
                  f"{d['nacks']} nack(s)")
    ti = s.get("tiles")
    if ti:
        ct, kt, sh = ti["compact_tile"], ti["ckpt_tile"], ti["tile_ship"]
        if ct["tiles"]:
            print(f"tiles: compacted {ct['tiles']} tile(s) "
                  f"({ct['parts']} part record(s)) in {ct['ms']:.2f}ms, "
                  f"max resident {ct['max_resident_bytes']} byte(s)")
        if kt["tiles"]:
            print(f"tiles: checkpointed {kt['tiles']} tile frame(s) "
                  f"({kt['full']} full, {kt['delta']} delta) in "
                  f"{kt['ms']:.2f}ms, max frame {kt['max_bytes']} "
                  f"byte(s)")
        if sh["units"] or sh["rejects"]:
            print(f"tiles: shipped {sh['units']} ckpt unit(s) "
                  f"{sh['bytes']} byte(s) in {sh['ms']:.2f}ms, "
                  f"{sh['retries']} retried, {sh['rejects']} rejected")
    net = s.get("network")
    if net:
        for link, d in net.items():
            ops = ", ".join(f"{k}={v}" for k, v in d["ops"].items())
            print(f"  net/{link}: {d['sends']} send(s) "
                  f"({d['send_failures']} failed) in "
                  f"{d['send_ms']:.2f}ms [{ops}]; "
                  f"{d['reconnects']}/{d['reconnect_attempts']} "
                  f"reconnect(s) in {d['reconnect_ms']:.2f}ms; "
                  f"state={d['last_state']}")
    ca = s.get("causal")
    if ca:
        print(f"causal chains: {ca['complete_chains']}/"
              f"{ca.get('groups', ca['chains'])} replication-complete, "
              f"{ca.get('full_chains', 0)} full submit→deliver — "
              f"e2e p50 {ca['chain_e2e_p50_us']:.1f}us "
              f"p99 {ca['chain_e2e_p99_us']:.1f}us")
        for name, d in ca["links"].items():
            print(f"  link {name}: {d['spans']} span(s) "
                  f"{d['total_ms']:.2f}ms ({100 * d['share']:.1f}% of "
                  f"chain link time)")
    fr = s.get("freshness")
    if fr:
        print(f"freshness: {fr['chains']} full chain(s) — ack→deliver "
              f"p50 {fr['e2e_p50_us']:.1f}us p99 {fr['e2e_p99_us']:.1f}us "
              f"(tiling deviation max {100 * fr['max_dev_frac']:.2f}%)")
        for name in FRESHNESS_STAGES:
            d = fr["stages"][name]
            print(f"  {name:<12} p50 {d['p50_us']:>10.1f}us "
                  f"p99 {d['p99_us']:>10.1f}us "
                  f"{100 * d['mean_share']:>6.1f}%")
    fo = s.get("failover")
    if fo:
        rej = ", ".join(f"{v} {k}(s)"
                        for k, v in fo["fence_rejects"].items()) or "none"
        print(f"failover: {fo['promotions']} promotion(s) — elect "
              f"{fo['elect_ms']:.2f}ms, replay {fo['replay_ms']:.2f}ms; "
              f"fence rejects: {rej}")
        for e in fo["events"]:
            if e["event"] == "elect":
                print(f"  epoch {e['epoch']}: elected {e['winner']} "
                      f"({e['reason']}), drained "
                      f"{e['drained_bytes']} byte(s) in {e['ms']:.2f}ms")
            else:
                print(f"  epoch {e['epoch']}: replayed "
                      f"{e['replayed_pushes']} push(es) / "
                      f"{e['replayed_ticks']} tick(s) to horizon "
                      f"{e['horizon']} in {e['ms']:.2f}ms")
    if s["control_actions"]:
        acts = ", ".join(f"{k}={v}"
                         for k, v in s["control_actions"].items())
        print(f"control actions: {acts}")
    if not s["tickets"]:
        print("no sampled tickets in this trace "
              "(REFLOW_TRACE_SAMPLE too high, or no serve traffic)")
        return
    print(f"\n{s['tickets']} sampled ticket(s): end-to-end "
          f"p50 {s['ticket_e2e_p50_us']:.1f}us "
          f"p99 {s['ticket_e2e_p99_us']:.1f}us "
          f"(stage-sum deviation max "
          f"{100 * s['decomposition_max_dev_frac']:.2f}%)")
    print(f"{'stage':<12} {'p50_us':>12} {'p99_us':>12} {'share':>8}")
    for name in s["critical_path"]:
        d = s["ticket_stages"][name]
        print(f"{name:<12} {d['p50_us']:>12.1f} {d['p99_us']:>12.1f} "
              f"{100 * d['mean_share']:>7.1f}%")
    print(f"critical path: {' > '.join(s['critical_path'][:3])}")


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("trace", nargs="+",
                    help="trace file(s); several are merged onto one "
                         "timeline via their baseTimeS anchors")
    ap.add_argument("--json", action="store_true",
                    help="print the summary as one JSON line")
    ap.add_argument("--require-chain", metavar="SPANS",
                    help="comma-separated span names; exit 1 unless at "
                         "least one causal chain carries them all")
    args = ap.parse_args(argv)
    want = [w.strip() for w in (args.require_chain or "").split(",")
            if w.strip()]
    summary = inspect(args.trace, require_chain=want or None)
    if args.json:
        print(json.dumps(summary))
    else:
        _print_human(summary)
    if want:
        ca = summary.get("causal")
        got = ca.get("required_chains", 0) if ca else 0
        if not got:
            print(f"require-chain FAILED: no causal chain carries all "
                  f"of {want} (chains={ca['chains'] if ca else 0})",
                  file=sys.stderr)
            return 1
    return 0


if __name__ == "__main__":
    sys.exit(main())
