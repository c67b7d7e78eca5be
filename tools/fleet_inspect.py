#!/usr/bin/env python3
"""One-shot fleet view: query a TelemetryServer or read saved state.

Usage::

    python tools/fleet_inspect.py --connect HOST:PORT         # live query
    python tools/fleet_inspect.py fleet.json                  # saved snapshot
    ... --json                                                # machine form

``--connect`` dials a :class:`~reflow_tpu.obs.wire.TelemetryServer`
over TCP (or a saved ``reflow.fleet/1`` JSON file stands in for a live
aggregator) and prints the fleet: per-node lag / read QPS / link
states / epoch / staleness, the derived cross-node gauges, and the
alert lines. Exit status is 0 even when nodes are stale — staleness is
a *reported* condition, not a tool failure; ``--fail-on-alert`` makes
alerts fatal for CI smokes.
"""

from __future__ import annotations

import argparse
import json
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

FLEET_SCHEMA = "reflow.fleet/1"


def fetch_live(hostport: str, timeout_s: float = 2.0) -> dict:
    """Dial a TelemetryServer and fetch one fleet snapshot."""
    from reflow_tpu.net.transport import TcpTransport
    from reflow_tpu.obs.wire import TelemetryLink

    host, _, port = hostport.rpartition(":")
    link = TelemetryLink(TcpTransport(host or "127.0.0.1"),
                         (host or "127.0.0.1", int(port)),
                         node="fleet-inspect", io_timeout_s=timeout_s)
    try:
        snap = link.fetch_fleet()
    finally:
        link.close()
    if snap is None:
        raise SystemExit(f"fleet_inspect: no aggregator at {hostport} "
                         f"(link state={link.conn_state})")
    return snap


def load_snapshot(path: str) -> dict:
    with open(path) as f:
        snap = json.load(f)
    if snap.get("schema") != FLEET_SCHEMA:
        raise SystemExit(f"fleet_inspect: {path} is not a "
                         f"{FLEET_SCHEMA} snapshot "
                         f"(schema={snap.get('schema')!r})")
    return snap


def _print_fleet(snap: dict) -> None:
    g = snap.get("gauges", {})
    nodes = snap.get("nodes", {})
    print(f"fleet: {g.get('nodes_total', 0)} node(s), "
          f"{g.get('nodes_stale', 0)} stale; "
          f"{g.get('snapshots_total', 0)} snapshot(s) ingested")
    spread = g.get("lag_spread")
    qps = g.get("aggregate_read_qps")
    print(f"  lag spread: "
          f"{'n/a' if spread is None else int(spread)} tick(s)   "
          f"epochs: {g.get('epochs')} "
          f"({'agree' if g.get('epoch_agree') else 'DISAGREE'})   "
          f"read qps: {'n/a' if qps is None else qps}")
    if g.get("link_states"):
        states = ", ".join(f"{k}={v}" for k, v in
                           sorted(g["link_states"].items()))
        print(f"  links: {states}")
    debt = g.get("compact_debt_bytes")
    if debt is not None:
        print(f"  compaction debt: {int(debt)} byte(s)")
    tpeak = g.get("tile_peak_bytes")
    stiles = g.get("snapshot_tiles")
    if tpeak is not None or stiles is not None:
        print(f"  tiles: peak resident "
              f"{'n/a' if tpeak is None else int(tpeak)} byte(s)   "
              f"published snapshot tiles: "
              f"{'n/a' if stiles is None else int(stiles)}")
    if g.get("subs_active") is not None:
        rows = g.get("sub_rows_s")
        lag = g.get("sub_lag_windows")
        print(f"  subs: {int(g['subs_active'])} active   fan-out: "
              f"{'n/a' if rows is None else f'{rows:.1f}'} row/s   "
              f"slowest lag: "
              f"{'n/a' if lag is None else int(lag)} window(s)")
    f50 = g.get("subs.freshness_p50")
    f99 = g.get("subs.freshness_p99")
    fev = g.get("flight.events_total")
    if f50 is not None or f99 is not None or fev is not None:
        print(f"  freshness: p50 "
              f"{'n/a' if f50 is None else f'{f50 * 1e3:.1f}ms'}   p99 "
              f"{'n/a' if f99 is None else f'{f99 * 1e3:.1f}ms'}   "
              f"flight events: {'n/a' if fev is None else int(fev)}")
    hdr = (f"  {'node':<16} {'horizon':>8} {'lag':>5} {'qps':>8} "
           f"{'epoch':>6} {'age_s':>7}  state")
    print(hdr)
    for name, e in sorted(nodes.items()):
        conn = ",".join(sorted(set(e.get("conn_states", {}).values()))) \
            or "-"
        if e.get("stale"):
            conn += " STALE"
        qps = e.get("read_qps")
        print(f"  {name:<16} "
              f"{e.get('horizon') if e.get('horizon') is not None else '-':>8} "
              f"{e.get('lag_ticks') if e.get('lag_ticks') is not None else '-':>5} "
              f"{f'{qps:.1f}' if qps is not None else '-':>8} "
              f"{int(e['epoch']) if e.get('epoch') is not None else '-':>6} "
              f"{e.get('age_s', 0):>7.2f}  {conn}")
    for line in snap.get("alerts", []):
        print(f"  ALERT: {line}")


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("snapshot", nargs="?",
                    help="saved reflow.fleet/1 JSON file")
    ap.add_argument("--connect", metavar="HOST:PORT",
                    help="dial a live TelemetryServer instead")
    ap.add_argument("--json", action="store_true",
                    help="print the summary as one JSON line")
    ap.add_argument("--fail-on-alert", action="store_true",
                    help="exit 1 when the fleet has any alert line")
    args = ap.parse_args(argv)
    if args.connect:
        snap = fetch_live(args.connect)
    elif args.snapshot:
        snap = load_snapshot(args.snapshot)
    else:
        ap.error("need a snapshot file or --connect")
        return 2
    if args.json:
        print(json.dumps(snap))
    else:
        _print_fleet(snap)
    if args.fail_on_alert and snap.get("alerts"):
        return 1
    return 0


if __name__ == "__main__":
    sys.exit(main())
