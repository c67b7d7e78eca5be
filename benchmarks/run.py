#!/usr/bin/env python3
"""Run one cell of the benchmark once.

    python benchmarks/run.py --workload <cell> --seed <n> \
        --seconds <run_seconds> --trace <0|1> [--tiny]

The leader: the only process that imports JAX. It needs a TPU (there is
no fallback; ``--tiny`` with a stated ``JAX_PLATFORMS=cpu`` is the
rehearsal form and says ``cpu`` in ``device``). It builds the
configuration's graph and state on the device from ``--seed``, serves it
as a deployment does (``DurableScheduler`` -> ``IngestFrontend`` ->
``RpcIngestServer`` on TCP at 127.0.0.1) and starts the load generator
(``loadgen.py``) as a child process. Everything up to the opening of the
window is ``setup_s``; then it measures for ``--seconds``; then, outside
the window, it decides ``correct``. The last line of stdout is the one
JSON object of the contract; everything else comes before it. The
numbers compared, each beside its limit, are that object's last key
(``checks``) and the last lines of stderr.

Nothing here names a cell, a configuration or a mix: they are entries of
``BENCHMARK.json`` and files found by name (see README.md beside this).
"""

from __future__ import annotations

import time

T_START = time.monotonic()

import argparse          # noqa: E402
import json              # noqa: E402
import os                # noqa: E402
import shutil            # noqa: E402
import subprocess        # noqa: E402
import sys               # noqa: E402
import threading         # noqa: E402

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
sys.path[:0] = [HERE, ROOT]

from common import Check, Heartbeat, now                              # noqa: E402
import manifest as mf                                      # noqa: E402
import measure                                             # noqa: E402
import traffic_plan as tp                                  # noqa: E402

WAIT_S = 600.0          # bound on every blocking wait: fail, never hang
#: a traced run follows one ticket in eight through its six stages:
#: thousands a run, and medians are what is read from them. Following
#: every ticket costs the leader a seventh of its rate, which a cell
#: offered 0.8 of the knee does not have to spare
TRACE_SAMPLE = "8"


def say(msg: str) -> None:
    print(f"bench: [{time.monotonic() - T_START:7.2f}s] {msg}", flush=True)


class Failed(SystemExit):
    """The run cannot produce a result: non-zero exit, no result line."""

    def __init__(self, msg: str, code: int = 1):
        print(f"bench: FAILED: {msg}", file=sys.stderr, flush=True)
        super().__init__(code)


# -- the child ---------------------------------------------------------


class LoadGen:
    def __init__(self, cell: mf.Cell, args, run_dir: str):
        self.report_path = os.path.join(run_dir, "loadgen.json")
        cmd = [sys.executable, os.path.join(HERE, "loadgen.py"),
               "--config-file", cell.config_file,
               "--traffic-file", cell.traffic_file,
               "--seed", str(args.seed), "--seconds", str(args.seconds),
               "--out", self.report_path]
        if args.tiny:
            cmd.append("--tiny")
        env = dict(os.environ, JAX_PLATFORMS="cpu")
        if args.trace:
            # the producer decides sampling and the token carries it:
            # one ticket in TRACE_SAMPLE gets its six-stage timeline on
            # the leader
            env.update(REFLOW_TRACE="1", REFLOW_TRACE_SAMPLE=TRACE_SAMPLE)
        self.proc = subprocess.Popen(
            cmd, stdin=subprocess.PIPE, stdout=subprocess.PIPE, env=env,
            text=True, bufsize=1)

    def send(self, **cmd) -> None:
        self.proc.stdin.write(json.dumps(cmd) + "\n")
        self.proc.stdin.flush()

    def expect(self, ev: str, timeout: float = WAIT_S) -> dict:
        box = {}

        def read():
            box["line"] = self.proc.stdout.readline()

        t = threading.Thread(target=read, daemon=True)
        t.start()
        t.join(timeout)
        line = box.get("line")
        if not line:
            raise Failed(f"load generator gave no {ev!r} "
                         f"(exit {self.proc.poll()})")
        msg = json.loads(line)
        if msg.get("ev") != ev:
            raise Failed(f"load generator said {msg!r}, wanted {ev!r}")
        return msg

    def stop(self) -> None:
        """End the child and wait for it, whatever state it is in."""
        try:
            if self.proc.stdin and not self.proc.stdin.closed:
                self.proc.stdin.close()
            self.proc.wait(timeout=10)
        except (subprocess.TimeoutExpired, OSError):
            self.proc.kill()
            self.proc.wait(timeout=10)
        finally:
            if self.proc.stdout:
                self.proc.stdout.close()


# -- checks that need no configuration knowledge ------------------------


def require_resident(states, devices) -> None:
    """Every state leaf lives on ``devices`` and together they use all of
    them (``chip_smoke.require_resident``)."""
    import jax

    leaves = [x for x in jax.tree.leaves(states) if isinstance(x, jax.Array)]
    if not leaves:
        raise Failed("no device state was built")
    used = set().union(*(x.devices() for x in leaves))
    if used != set(devices):
        raise Failed(f"state leaves live on {sorted(map(str, used))}, "
                     f"want exactly {sorted(map(str, devices))}")


def wal_holds(wal_dir: str, sent: dict) -> tuple:
    """The guarantee as far as a run can show it: every batch id acked
    is found in the WAL's push records re-read from disk, each record's
    rows are exactly the rows of the batches it names, in order, and no
    id is logged twice. ``sent``: batch id -> the DeltaBatch that was
    submitted. Returns (ids missing or altered, ids logged more than
    once, id -> the tick its record commits at)."""
    import numpy as np
    from reflow_tpu.wal import scan_wal

    records, torn = scan_wal(wal_dir)
    if torn is not None:
        raise Failed(f"the sealed WAL has a torn tail: {torn}")
    ticks, bad, twice = {}, set(), set()
    for _pos, rec in records:
        if rec.get("kind") != "push":
            continue
        ids = list(rec.get("batch_ids") or [rec["batch_id"]])
        ours = [i for i in ids if i in sent]
        if not ours:
            continue
        if len(ours) != len(ids):
            bad.update(ours)
            continue
        parts = [sent[i] for i in ids]
        same = all(
            np.array_equal(np.asarray(rec[col]),
                           np.concatenate([getattr(p, col)
                                           for p in parts]))
            for col in ("keys", "values", "weights"))
        if not same:
            bad.update(ids)
        for i in ids:
            if i in ticks:
                twice.add(i)
            # a window's records carry its first tick and the feed's
            # place in it; the batch commits at the end of that feed
            ticks[i] = int(rec["tick"]) + int(rec.get("feed", 0)) + 1
    return sorted((set(sent) - set(ticks)) | bad), sorted(twice), ticks


# -- the run -------------------------------------------------------------


def run_cell(args, tamper=None) -> dict:
    """One run; returns the result object. ``tamper(deployment parts)``
    is for the tests that break the timed path underneath."""
    man = mf.load_manifest(ROOT)
    cell = mf.Cell(man, args.workload, ROOT)
    cfg = mf.with_tiny(mf.load_json(cell.config_file), args.tiny)
    traffic = mf.with_tiny(mf.load_json(cell.traffic_file), args.tiny)
    mod = mf.load_module(cell.config_module, cell.config_name)
    if traffic["arrivals"] not in tp.ARRIVALS:
        raise Failed(f"unknown arrivals {traffic['arrivals']!r}")
    if abs(time.perf_counter() - time.monotonic()) > 1e-3:
        raise Failed("perf_counter and monotonic are different clocks "
                     "here: the program's spans cannot be joined")

    if args.tiny and os.environ.get("JAX_PLATFORMS") != "cpu":
        raise Failed("--tiny is the CPU rehearsal: state JAX_PLATFORMS=cpu")
    if args.trace:
        os.environ["REFLOW_TRACE_SAMPLE"] = TRACE_SAMPLE
        os.environ["REFLOW_TRACE_RING"] = str(1 << 20)

    run_dir = os.path.join(
        ROOT, ".bench_runs", f"{cell.name}-{args.seed}-{os.getpid()}")
    shutil.rmtree(run_dir, ignore_errors=True)
    os.makedirs(run_dir)
    gen = LoadGen(cell, args, run_dir)       # mints while we build
    closers = [gen.stop]
    try:
        return _run(args, cell, cfg, traffic, mod, run_dir, gen, closers,
                    tamper)
    finally:
        for close in reversed(closers):
            try:
                close()
            except Exception as e:  # noqa: BLE001 - teardown must finish
                say(f"teardown: {e!r}")
        shutil.rmtree(run_dir, ignore_errors=True)


def _run(args, cell, cfg, traffic, mod, run_dir, gen, closers,
         tamper) -> dict:
    import jax

    try:
        devices = jax.devices()
    except RuntimeError as e:
        raise Failed(f"JAX found no device: {e}", 3)
    want = "cpu" if args.tiny else "tpu"
    if devices[0].platform != want:
        raise Failed(f"needs platform {want!r}: JAX resolved "
                     f"{devices[0].platform!r}; there is no fallback", 3)
    if len(devices) < cell.chips and not args.tiny:
        raise Failed(f"the cell asks for {cell.chips} chips, JAX has "
                     f"{len(devices)}", 3)
    use = devices[:cell.chips] if not args.tiny else devices[:1]

    from reflow_tpu import obs
    from reflow_tpu.executors import get_executor
    from reflow_tpu.net import TcpTransport
    from reflow_tpu.serve import (APPLIED, DEDUPED, CoalesceWindow,
                                  IngestFrontend,
                                  RemoteProducer, RpcIngestServer)
    from reflow_tpu.utils.runtime import (device_record,
                                          place_compile_cache)
    from reflow_tpu.wal import DurableScheduler

    from probe import CompileWatch, CompletionProbe

    cache_dir = place_compile_cache()
    # every program, however quick to compile, comes from the cache on a
    # cell's second run: set-up is paid by every run of every check
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    device = device_record()
    say(f"cell {cell.name} seed {args.seed} seconds {args.seconds} "
        f"trace {args.trace}; device {device}; compile cache {cache_dir}")
    compiles = CompileWatch()

    # -- data, graph, state -------------------------------------------
    kind = traffic["arrivals"]
    lanes = traffic["producers"]
    stream = mod.Stream(cfg, args.seed, lanes)
    load = stream.load()
    ref = mod.Reference(stream)
    dep = mod.build(cfg)
    ex = get_executor("tpu")
    g = cfg["guarantees"]
    wal_dir = os.path.join(run_dir, "wal")
    sched = DurableScheduler(dep.graph, ex, wal_dir=wal_dir,
                             fsync=g["fsync"], committer=g["committer"])
    closers.append(sched.close)
    require_resident(ex.states, use)
    t0 = now()
    for batches in load:                     # a tick each
        for source, batch, bid in batches:
            sched.push(dep.sources[source], batch, batch_id=bid)
        if not sched.tick().quiesced:
            raise Failed("a load tick did not quiesce")
    say(f"loaded {sum(len(b) for r in load for _, b, _ in r)} rows in "
        f"{len(load)} ticks, {now() - t0:.2f}s")
    del load

    # -- serve ---------------------------------------------------------
    if args.trace:
        obs.enable()
    w, adm = traffic["coalesce"], traffic["admission"]
    fe = IngestFrontend(
        sched, policy=adm["policy"], queue_batches=adm["queue_batches"],
        max_bytes=adm["max_bytes"], depth=adm["depth"],
        window=CoalesceWindow(max_rows=w["max_rows"],
                              max_ticks=w["max_ticks"],
                              max_latency_s=w["max_latency_s"]))
    closers.append(fe.close)
    probe = CompletionProbe(sched, fe, annotate=bool(args.trace))
    closers.append(probe.close)
    srv = RpcIngestServer(fe, TcpTransport("127.0.0.1")).start()
    closers.append(srv.close)
    if tamper is not None:
        tamper(fe=fe, sched=sched, ex=ex, srv=srv)

    # -- warm every shape the mix can produce, through the served path --
    sent = {}                    # batch id -> DeltaBatch, for the WAL check
    # a cold warm-up compiles for minutes with the interpreter lock held
    # in stretches: the default 5 s I/O timeout would redial, resubmit
    # and read DEDUPED for batches that were in fact applied
    warm = RemoteProducer(TcpTransport(), srv.address, name="warm",
                          io_timeout_s=WAIT_S)
    closers.append(warm.close)
    t0 = now()
    n_warm = 0
    for gi, group in enumerate(tp.plan_warm(stream, traffic)):
        fe.pause()
        tickets = []
        for m in group:
            bid = f"warm/{n_warm}"
            n_warm += 1
            sent[bid] = m.delta
            ref.apply(m.ref)
            tickets.append(warm.submit(stream.source, m.delta,
                                       batch_id=bid))
        fe.resume()
        # wait in-process first, with no frame in flight: loading or
        # compiling a window program can hold the interpreter lock for
        # seconds, the ingest server gives a frame 0.2 s to arrive whole
        # and resets the link otherwise, and the producer then resubmits
        # and reads DEDUPED for a batch that was applied once
        fe.flush(timeout=WAIT_S)
        probe.drain()
        for t in tickets:
            res = t.result(timeout=WAIT_S)
            if res.status not in (APPLIED, DEDUPED):
                raise Failed(f"warm batch {t.batch_id} resolved "
                             f"{res.status!r}")
    warm_windows = len(probe.windows)
    shapes = sorted({(x["k"], tuple(x["caps"])) for x in probe.windows})
    say(f"warmed {n_warm} batches in {warm_windows} windows, "
        f"{now() - t0:.2f}s; shapes (ticks, capacities): {shapes}")

    # -- hand over to the generator ------------------------------------
    ready = gen.expect("ready")
    gen.send(cmd="connect", address=list(srv.address))
    gen.expect("connected")
    if kind == "prefilled":
        fe.pause()
        gen.send(cmd="prefill")
        gen.expect("prefilled")
    probe.drain()
    say(f"generator minted {ready['batches']} batches in "
        f"{ready['mint_s']:.2f}s (peak RSS "
        f"{ready['rss_bytes'] / 1e9:.2f} GB); connected"
        + (" and prefilled" if kind == "prefilled" else ""))

    # -- the window ------------------------------------------------------
    profile_dir = os.path.join(run_dir, "profile")
    prof = {"error": None, "started": False}

    def profile(t_from: float, t_to: float) -> None:
        try:
            time.sleep(max(0.0, t_from - now()))
            opts = jax.profiler.ProfileOptions()
            opts.python_tracer_level = 0
            jax.profiler.start_trace(profile_dir, profiler_options=opts)
            prof["started"] = True
            time.sleep(max(0.0, t_to - now()))
            jax.profiler.stop_trace()
        except BaseException as e:  # noqa: BLE001 - fails the run below
            prof["error"] = e

    snap_open = _counters(fe, sched)
    t_open = now()
    t_close = t_open + args.seconds
    setup_s = t_open - T_START
    if kind == "prefilled":
        fe.resume()
    gen.send(cmd="go", t_open=t_open, t_close=t_close)
    beat = Heartbeat()
    tracer = None
    if args.trace:
        # the last 40 % of the window, and the profiler is stopped only
        # once the window has closed: stopping it writes the trace out,
        # which holds the leader for seconds
        tracer = threading.Thread(
            target=profile, name="bench-profiler",
            args=(t_open + 0.6 * args.seconds, t_close))
        tracer.start()
    time.sleep(max(0.0, t_close - now()))
    snap_close = _counters(fe, sched)
    stall = beat.stop()
    compiled = compiles.between(t_open, t_close)
    if tracer is not None:
        tracer.join(timeout=WAIT_S)
        if prof["error"] is not None or not prof["started"]:
            raise Failed(f"the profiler did not run: {prof['error']!r}")

    # -- after the window: drain, then decide `correct` -------------------
    done = gen.expect("done")
    if done["errors"]:
        raise Failed(f"load generator lanes failed: {done['errors']}")
    fe.flush(timeout=WAIT_S)
    probe.drain()
    report = mf.load_json(done["report"])
    if report["jax_imported"]:
        raise Failed("the load generator imported JAX")
    say(f"window closed; {len(probe.windows) - warm_windows} windows "
        f"dispatched since it opened")

    batches = []
    for ln in report["lanes"]:
        for seq in range(ln["n_sent"]):
            batches.append({
                "id": tp.batch_id(ln["lane"], seq), "lane": ln["lane"],
                "seq": seq, "rows": ln["rows"][seq], "due": ln["due"][seq],
                "sent": ln["sent"][seq], "admitted": ln["admitted"][seq],
                "ack": ln["ack"][seq], "status": ln["status"][seq],
                "tick": ln["tick"][seq]})

    ex.check_errors()
    got = mod.read_state(cfg, dep, sched)
    counters = {"fixpoint_engine": getattr(ex, "fixpoint_engine", None),
                "megatick_windows": sched.megatick_windows,
                "megatick_fallbacks": sched.megatick_fallbacks,
                "windows_staged": fe.windows_staged,
                "windows_pipelined": fe.windows_pipelined,
                "forced_syncs": getattr(sched, "forced_syncs", None)}
    require_resident(ex.states, use)
    peak = 0
    for d in use:
        stats = d.memory_stats() or {}
        peak = max(peak, int(stats.get("peak_bytes_in_use", 0)))
    spans = _spans(run_dir) if args.trace else []
    # seal: frontend, server, WAL -- before the log is read back
    warm.close()
    srv.close()
    fe.close()
    probe.close()

    # the reference: mint the generator's batches again from the seed
    # and fold in exactly the ones it sent (a prefix of every lane)
    t0 = now()
    n_sent = {ln["lane"]: ln["n_sent"] for ln in report["lanes"]}
    for seq in range(max(n_sent.values())):
        for lane in range(lanes):
            m = stream.next(lane)
            if seq < n_sent[lane]:
                ref.apply(m.ref)
                sent[tp.batch_id(lane, seq)] = m.delta
    expected = ref.expected()
    checks = list(mod.compare(cfg, got, expected))
    ref_s = now() - t0

    t0 = now()
    lost, twice, logged = wal_holds(wal_dir, sent)
    wal_s = now() - t0
    # A ticket that reads DEDUPED belongs to a batch its producer sent
    # again after a link reset: the first copy was applied, the second
    # refused, which is the delivery guarantee at work and not a fault.
    # Such a ticket carries no tick, so the log says where the batch
    # committed, and the client knew it durable when DEDUPED came back.
    # The log's ticks are trusted only if they agree with every ticket
    # that does carry one.
    agree = all(logged.get(b["id"]) == b["tick"] for b in batches
                if b["status"] == APPLIED)
    deduped = [b for b in batches if b["status"] == DEDUPED]
    if agree:
        for b in deduped:
            b["tick"] = logged.get(b["id"])
    joined = measure.join(batches, probe.windows[warm_windows:])
    _say_stalls(stall, report["stall"], batches, joined.windows, t_open,
                t_close)
    if deduped:
        say(f"{len(deduped)} batches were sent twice after a link reset "
            f"and refused the second time; the log's ticks "
            f"{'agree' if agree else 'DO NOT agree'} with the tickets'")
    not_applied = [b["id"] for b in batches
                   if b["status"] not in (APPLIED, DEDUPED)]
    unjoined = [b["id"] for b in joined.batches if b["done"] is None]
    checks += [
        Check("acked_batches_not_in_wal", float(len(lost)), 0.0, not lost),
        Check("batches_logged_twice", float(len(twice)), 0.0, not twice),
        Check("tickets_not_applied", float(len(not_applied)), 0.0,
              not not_applied),
        Check("batches_without_device_completion", float(len(unjoined)),
              0.0, not unjoined),
        Check("megatick_fallbacks", float(counters["megatick_fallbacks"]),
              0.0, counters["megatick_fallbacks"] == 0),
        Check("compiles_in_window", float(compiled), 0.0, compiled == 0),
    ]
    if "fixpoint_engine" in cfg:
        ok = counters["fixpoint_engine"] == cfg["fixpoint_engine"]
        checks.append(Check(
            f"engine_is_{cfg['fixpoint_engine']}", float(ok), 1.0, ok))
    rate = measure.completion_rate(joined, t_open, t_close)
    if rate is not None and any(m["name"] == "rows_per_s"
                                for m in cell.end_to_end):
        # the rate runs from the first to the last completion: a stall
        # before the one or after the other would not move it, so the
        # window's two ends are held to the run's own cadence
        edge = rate["edge_s"]
        limit = 2.0 * rate["median_gap_s"] + max(0.05 * args.seconds, 0.5)
        checks.append(Check("rate_edge_s", edge, limit, edge <= limit))
    for c in checks:
        say(c.line())
    say(f"reference and comparison {ref_s:.2f}s, WAL re-read "
        f"{wal_s:.2f}s; WAL held "
        f"{len(set(sent) - set(lost))} of {len(sent)} batch ids, its "
        f"ticks {'agree' if agree else 'DISAGREE'} with the tickets'; "
        f"counters {counters}")

    # -- metrics ---------------------------------------------------------
    in_window = [b for b in joined.batches
                 if t_open <= b["due"] < t_close] if kind == "poisson" \
        else joined.batches
    attempted = len(in_window)
    failed = sum(1 for b in in_window
                 if b["status"] not in (APPLIED, DEDUPED)
                 or b["done"] is None)
    run = Run(cell=cell, cfg=cfg, traffic=traffic, seconds=args.seconds,
              t_open=t_open, t_close=t_close, joined=joined,
              report=report, spans=spans, snap_open=snap_open,
              snap_close=snap_close, compiles_in_window=compiled,
              trace=None)
    values = {"setup_s": setup_s}
    if rate is not None:
        values["rows_per_s"] = rate["rows_per_s"]
        say(f"rate: {rate}; it spans "
            f"{rate['span_s'] / args.seconds:.3f} of the window")
    fresh = measure.freshness_ms(joined, t_open, t_close)
    if fresh and kind == "poisson":
        values["fresh_p50_ms"] = measure.percentile(fresh, 50)
        values["fresh_p90_ms"] = measure.percentile(fresh, 90)
        mid = 0.5 * (t_open + t_close)
        halves = [measure.percentile(
            measure.freshness_ms(joined, a, b) or [0.0], 50)
            for a, b in ((t_open, mid), (mid, t_close))]
        say(f"freshness over {len(fresh)} batches: p50 "
            f"{values['fresh_p50_ms']:.3f} ms, p90 "
            f"{values['fresh_p90_ms']:.3f} ms, max {max(fresh):.3f} ms; "
            f"p50 of the first / second half of the window "
            f"{halves[0]:.3f} / {halves[1]:.3f} ms (a growing backlog "
            f"would pull them apart)")
        step = args.seconds / 10.0
        tenths = [measure.freshness_ms(joined, t_open + i * step,
                                       t_open + (i + 1) * step)
                  for i in range(10)]
        say("p50 / max by tenth of the window: " + ", ".join(
            f"{measure.percentile(t, 50):.0f}/{max(t):.0f}" if t else "-"
            for t in tenths))
    exhausted = [ln["lane"] for ln in report["lanes"] if ln["exhausted"]]
    if exhausted and kind == "closed":
        say(f"note: lanes {exhausted} sent every minted batch before the "
            f"window closed; the rate ends at the last completion")

    metrics = {}
    dev = dict(device, memory_peak_bytes=peak)
    result = {"correct": all(c.ok for c in checks),
              "attempted": attempted, "failed": failed,
              "metrics": metrics, "device": dev}
    if args.trace:
        import xplane
        trace_path = xplane.find_trace(profile_dir)
        red = xplane.reduce_trace(trace_path)
        if red["on_cpu"] != bool(args.tiny):
            raise Failed("the trace has no device plane")
        if red["busy_s"] <= 0:
            raise Failed("no operation ran on the device in the trace")
        run.trace = red
        dev["busy_s"], dev["window_s"] = red["busy_s"], red["window_s"]
        result["breakdown"] = {"device_ops": red["device_ops"],
                               "idle_gaps": red["idle_gaps"]}
        say(f"trace: {json.dumps(red['per_device'])} "
            f"annotations {red['annotation_counts']}")
        for m in cell.per_layer:
            reader = mf.load_module(cell.reader_file(m["name"]), m["name"])
            v = reader.read(run)
            if v is not None:
                metrics[m["name"]] = {"value": float(v), "unit": m["unit"]}
    else:
        for m in cell.end_to_end:
            if m["name"] not in values:
                raise Failed(f"the run produced no {m['name']}")
            metrics[m["name"]] = {"value": float(values[m["name"]]),
                                  "unit": m["unit"]}
    # every number compared beside its limit, as the line's last key
    result["checks"] = {c.name: {"value": c.value, "limit": c.limit,
                                 "ok": c.ok} for c in checks}
    return result


class Run:
    """What a per-layer reader is given: the joined logs, the program's
    spans (absolute seconds on the shared clock), counter snapshots at
    the window's two ends, and the reduced device trace."""

    def __init__(self, **kw):
        self.__dict__.update(kw)

    def spans_named(self, name: str):
        """The program's spans of one name that began inside the window."""
        return [s for s in self.spans if s["name"] == name
                and self.t_open <= s["t0"] <= self.t_close]

    def stage_ms(self, stage: str):
        """Durations (ms) of one stage of the six-stage ticket timeline,
        over the tickets of batches due inside the window."""
        ids = {b["id"] for b in self.joined.batches
               if self.t_open <= b["due"] < self.t_close}
        return [1e3 * (s["t1"] - s["t0"]) for s in self.spans
                if s["name"] == stage and s["track"].startswith("ticket/")
                and s["track"][7:] in ids]

    def windows_inside(self):
        return [w for w in self.joined.windows
                if self.t_open <= w["dispatch0"] <= self.t_close]


def _say_stalls(leader, child, batches, windows, t_open, t_close) -> None:
    """Where a run stood still, if it did: the longest the two
    processes' heartbeats overslept, the longest gap between dispatches,
    the longest a window waited for the device and a submit for its
    admission, and what the tickets resolved to. Judged by nothing."""
    def at(t):
        return "-" if t is None else f"{t - t_open:+.2f}s"

    say(f"stalls: leader heartbeat {1e3 * leader['worst_s']:.0f} ms at "
        f"{at(leader['worst_at'])}, generator heartbeat "
        f"{1e3 * child['worst_s']:.0f} ms at {at(child['worst_at'])}")
    ins = [w for w in windows if t_open <= w["dispatch0"] <= t_close]
    gaps = [(b["dispatch0"] - a["dispatch1"], a["dispatch1"])
            for a, b in zip(ins, ins[1:])]
    lags = [(w["ready"] - w["dispatch1"], w["dispatch1"]) for w in ins
            if w.get("ready") is not None]
    waits = [(b["admitted"] - b["sent"], b["sent"]) for b in batches
             if b["admitted"] is not None]
    for what, xs in (("between dispatches", gaps),
                     ("dispatch to device completion", lags),
                     ("submit to admitted", waits)):
        if xs:
            worst, when = max(xs)
            say(f"stalls: longest {what} {1e3 * worst:.0f} ms at "
                f"{at(when)}")
    status = {}
    for b in batches:
        status[b["status"]] = status.get(b["status"], 0) + 1
    say(f"ticket statuses: {status}")


def _counters(fe, sched) -> dict:
    return {"t": now(), "windows_staged": fe.windows_staged,
            "stage_s_total": fe.stage_s_total, "admitted": fe.admitted,
            "applied": fe.applied, "fsyncs": sched.wal.fsyncs,
            "wal_bytes": sched.wal.bytes_written}


def _spans(run_dir: str) -> list:
    """The program's spans with absolute times (the export is relative
    to ``baseTimeS``, a perf_counter value)."""
    from reflow_tpu import obs

    path = obs.export_chrome_trace(os.path.join(run_dir, "spans.json"))
    with open(path) as f:
        doc = json.load(f)
    base = doc["baseTimeS"]
    tracks, out = {}, []
    for ev in doc["traceEvents"]:
        if ev.get("ph") == "M" and ev.get("name") == "thread_name":
            tracks[ev["tid"]] = ev["args"]["name"]
        elif ev.get("ph") == "M" and ev.get("name") == "dropped_events":
            say(f"note: span ring dropped {ev['args']}")
    for ev in doc["traceEvents"]:
        if ev.get("ph") == "X":
            t0 = base + ev["ts"] * 1e-6
            out.append({"name": ev["name"], "t0": t0,
                        "t1": t0 + ev["dur"] * 1e-6,
                        "track": tracks.get(ev["tid"], ""),
                        "args": ev.get("args", {})})
    return out


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--tiny", action="store_true",
                    help="the CPU rehearsal; needs JAX_PLATFORMS=cpu")
    args = ap.parse_args(argv)
    result = run_cell(args)
    for name, c in result["checks"].items():
        print(Check(name, **c).line(), file=sys.stderr)
    sys.stderr.flush()
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
