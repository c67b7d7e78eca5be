#!/usr/bin/env python3
"""The load generator: one process of its own, never on JAX.

Producers are remote in every deployment of this system, and a generator
thread inside the leader would share one interpreter lock with the pump,
the RPC server threads and the WAL committer, which is the host path the
cells measure. So the leader (``run.py``) starts this file as a child.
It mints the cell's batches from the seed before the window, submits
them through ``RemoteProducer`` over TCP, one connection per lane, and
reports by batch id when each was due, sent, admitted and seen resolved
at the client, on CLOCK_MONOTONIC, the leader's clock too.

Protocol: JSON lines. stdout ``ready`` -> stdin ``connect`` -> stdout
``connected`` [-> stdin ``prefill`` -> stdout ``prefilled``] -> stdin
``go`` -> stdout ``done`` (the report is a file) -> stdin EOF.
"""

from __future__ import annotations

import argparse
import gc
import json
import os
import resource
import sys
import threading
import time
from collections import deque

os.environ["JAX_PLATFORMS"] = "cpu"   # as proc/__main__: a role never
#                                       takes the chip from the leader

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path[:0] = [HERE, os.path.dirname(HERE)]

from common import Heartbeat, now                                     # noqa: E402
from manifest import load_json, load_module, with_tiny     # noqa: E402
import traffic_plan as tp                                  # noqa: E402

#: a lane gives up on its tickets this long after the window closed
DRAIN_S = 240.0
#: and on one send or receive over its link after this long
LINK_WAIT_S = 60.0


def peak_rss_bytes() -> int:
    """This process's peak resident set (Linux counts it in KiB)."""
    return resource.getrusage(resource.RUSAGE_SELF).ru_maxrss * 1024


def emit(obj: dict) -> None:
    sys.stdout.write(json.dumps(obj) + "\n")
    sys.stdout.flush()


def read_cmd(want: str) -> dict:
    line = sys.stdin.readline()
    if not line:
        raise SystemExit("loadgen: leader went away")
    cmd = json.loads(line)
    if cmd.get("cmd") != want:
        raise SystemExit(f"loadgen: wanted {want!r}, got {cmd!r}")
    return cmd


class Lane:
    """One producer connection and its batches."""

    def __init__(self, lane: int, prod, source: str, minted, due):
        self.lane, self.prod, self.source = lane, prod, source
        self.minted, self.due_off = minted, due
        n = len(minted)
        self.due = [None] * n
        self.sent = [None] * n
        self.admitted = [None] * n
        self.ack = [None] * n
        self.status = [None] * n
        self.tick = [None] * n
        self.n_sent = 0
        self.pending = deque()          # (seq, ticket), FIFO per lane
        self.error = None

    def submit(self, seq: int, due: float) -> None:
        m = self.minted[seq]
        self.due[seq] = due
        self.sent[seq] = now()
        t = self.prod.submit(self.source, m.delta,
                             batch_id=tp.batch_id(self.lane, seq))
        self.admitted[seq] = now()
        self.n_sent += 1
        self.pending.append((seq, t))

    def poll(self, wait_s: float) -> None:
        """One resolve round trip (a long-poll of up to ``wait_s``),
        then stamp every ticket it decided. A lane's tickets resolve in
        order, so only the head is looked at."""
        if not self.pending:
            return
        try:
            self.pending[0][1].result(timeout=max(wait_s, 1e-4))
        except TimeoutError:
            pass
        t = now()
        while self.pending and self.pending[0][1].done():
            seq, ticket = self.pending.popleft()
            res = ticket.result(timeout=1e-4)
            self.ack[seq] = t
            self.status[seq] = res.status
            self.tick[seq] = res.tick

    def drain(self, deadline: float) -> None:
        while self.pending and now() < deadline:
            self.poll(0.05)

    # -- the three arrival kinds ---------------------------------------

    def prefill(self) -> None:
        for seq in range(len(self.minted)):
            self.submit(seq, now())

    def run_closed(self, t_close: float, poll_s: float) -> None:
        last = now()
        for seq in range(len(self.minted)):
            t = now()
            if t >= t_close:
                break
            self.submit(seq, t)
            # A closed loop is paced by admission: ``submit`` returns
            # when the leader has taken the batch. With the link down it
            # returns at once and the batch only waits in the client, so
            # a reset would let the lane run through everything it
            # minted in milliseconds and leave the leader to be fed by
            # one long resubmission that stamps no ack until it ends.
            # So the lane stands still until the link is up again and
            # what was in flight has been sent again, in order.
            while self.pending and self.prod.policy.failures > 0:
                self.poll(0.05)
                last = now()
            if t - last >= poll_s:
                self.poll(0.0)
                last = now()

    def run_paced(self, t_open: float, poll_s: float) -> None:
        last = now()
        for seq in range(len(self.minted)):
            due = t_open + float(self.due_off[seq])
            while True:
                t = now()
                gap = due - t
                if gap <= 0:
                    break
                if self.pending and t - last >= poll_s:
                    self.poll(0.0)
                    last = now()
                else:
                    time.sleep(min(gap, 0.0005))
            self.submit(seq, due)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--config-file", required=True)
    ap.add_argument("--traffic-file", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--out", required=True)
    ap.add_argument("--tiny", action="store_true")
    args = ap.parse_args(argv)

    t0 = now()
    cfg = with_tiny(load_json(args.config_file), args.tiny)
    traffic = with_tiny(load_json(args.traffic_file), args.tiny)
    mod = load_module(os.path.splitext(args.config_file)[0] + ".py",
                      cfg["name"])
    lanes_n = traffic["producers"]
    kind = traffic["arrivals"]
    if kind not in tp.ARRIVALS:
        raise SystemExit(f"loadgen: unknown arrivals {kind!r}")

    stream = mod.Stream(cfg, args.seed, lanes_n)
    stream.load()                       # advances the mirror; leader loads
    tp.plan_warm(stream, traffic)       # likewise; the leader warms
    minted = tp.mint_traffic(stream, traffic, args.seconds)
    dues = tp.due_times(traffic, args.seed, args.seconds)
    # the minted batches and the mirror are millions of objects that
    # live until exit: keep the collector from walking them inside the
    # window, where a full collection would stall every lane at once
    gc.collect()
    gc.freeze()
    emit({"ev": "ready", "mint_s": now() - t0,
          "batches": sum(len(m) for m in minted),
          "rss_bytes": peak_rss_bytes()})

    from reflow_tpu.net import TcpTransport
    from reflow_tpu.serve import APPLIED, RemoteProducer

    address = tuple(read_cmd("connect")["address"])
    # a link that waits out a stall on the leader reports it as latency;
    # the default 5 s would redial, resubmit and read DEDUPED for
    # batches that were applied once, which a run counts as failed
    lanes = [Lane(i, RemoteProducer(TcpTransport(), address, name=f"L{i}",
                                    io_timeout_s=LINK_WAIT_S),
                  stream.source, minted[i], dues[i])
             for i in range(lanes_n)]
    from reflow_tpu.delta import DeltaBatch
    for ln in lanes:
        # dial + hello now, not inside the first timed submit: an empty
        # batch resolves at admission and reaches no queue and no log
        m = ln.minted[0].delta
        empty = DeltaBatch(m.keys[:0], m.values[:0], m.weights[:0])
        res = ln.prod.submit(stream.source, empty,
                             batch_id=f"dial/{ln.lane}").result(timeout=30)
        if res.status != APPLIED:
            raise SystemExit(f"loadgen: dial on lane {ln.lane}: {res}")
    emit({"ev": "connected"})

    def guarded(fn, ln, *a):
        def body():
            try:
                fn(*a)
            except BaseException as e:  # noqa: BLE001 - reported, fatal
                ln.error = repr(e)
        return threading.Thread(target=body, name=f"lane-{ln.lane}")

    if kind == "prefilled":
        read_cmd("prefill")
        threads = [guarded(ln.prefill, ln) for ln in lanes]
        for t in threads:
            t.start()
        for t in threads:
            t.join()
        emit({"ev": "prefilled", "sent": sum(ln.n_sent for ln in lanes)})

    go = read_cmd("go")
    t_open, t_close = float(go["t_open"]), float(go["t_close"])
    poll_s = float(traffic["ack_poll_s"])
    deadline = t_close + DRAIN_S

    def lane_body(ln: Lane) -> None:
        if kind == "closed":
            ln.run_closed(t_close, poll_s)
        elif kind == "poisson":
            ln.run_paced(t_open, poll_s)
        ln.drain(deadline)

    beat = Heartbeat()
    threads = [guarded(lane_body, ln, ln) for ln in lanes]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    stall = beat.stop()
    for ln in lanes:
        ln.prod.close()

    report = {
        "lanes": [{"lane": ln.lane, "n_sent": ln.n_sent,
                   "exhausted": ln.n_sent == len(ln.minted),
                   "unresolved": len(ln.pending), "error": ln.error,
                   "rows": [m.rows for m in ln.minted[:ln.n_sent]],
                   "due": ln.due[:ln.n_sent], "sent": ln.sent[:ln.n_sent],
                   "admitted": ln.admitted[:ln.n_sent],
                   "ack": ln.ack[:ln.n_sent],
                   "status": ln.status[:ln.n_sent],
                   "tick": ln.tick[:ln.n_sent]} for ln in lanes],
        "jax_imported": "jax" in sys.modules,
        "stall": stall,
    }
    tmp = args.out + ".tmp"
    with open(tmp, "w") as f:
        json.dump(report, f)
    os.replace(tmp, args.out)
    emit({"ev": "done", "report": args.out,
          "errors": [ln.error for ln in lanes if ln.error]})
    sys.stdin.readline()                # EOF or a last word: then exit
    return 0


if __name__ == "__main__":
    sys.exit(main())
