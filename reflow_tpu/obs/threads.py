"""The thread ledger: who had the CPU, by thread role.

A pump span's ``dur - cpu_s`` says how long the pump was off the CPU;
it cannot say who was on it, nor whether the pump was *runnable* and
waiting for a core or *blocked* (on the interpreter lock, a lock, the
device). :func:`ledger` reads every Python thread of the process and
sums, by **role**, cumulative since process start: ``n`` (live
threads), ``cpu_s`` and, where the kernel keeps them, ``runq_s``
(seconds runnable but waiting for a core), ``vol`` / ``invol``
(voluntary / involuntary context switches — a thread that blocks on the
interpreter lock gives the CPU up voluntarily). A role is the thread's
name up to its first ``/`` (a trailing ``-<n>`` of a pool worker
dropped): ``rpc-serve``, ``rpc-accept``, ``reflow-ingest-pump``,
``reflow-wal-committer``, ``reflow-device-watch``, ``MainThread``,
``bench-*`` as they are named, anything else ``other``. Beside them
``native``: what the Python roles leave of ``process_cpu_s``
(``time.process_time()``) — XLA's pools, the accelerator runtime, the
transfer threads — and once each ``switch_interval_s`` and ``cores``.

Two sources, by what they cost. ``cpu_s`` is each thread's POSIX CPU
clock (``time.pthread_getcpuclockid``): a plain system call that keeps
the interpreter lock, 6 - 15 us a thread on the TPU machines, so a read
of a leader's 25 threads is under half a millisecond. ``runq_s`` /
``vol`` / ``invol`` are ``/proc/self/task/<tid>/schedstat`` (its second
number) and the two ``ctxt_switches`` lines of ``.../status``: two small
files a thread, read only where ``/proc`` has ``schedstat`` (Linux
proper; gVisor, which the TPU machines run under, has neither it nor
the switch counts, and the keys are then left out). Every file read
gives the interpreter lock up and queues for it again — a hand-over
wait each in a leader whose lock is contended, the one this is for — so
that half costs a read milliseconds a thread where it exists. It is
also why the native tasks are not read one by one from
``/proc/self/task``: a TPU runtime brings 200 of them.

A thread that exits keeps what it had used when last read in its role's
total, so a role never runs backwards (RPC handlers come and go with
link resets); what it used after that read falls to ``native``.

Two readers, no thread of its own (docs/guide.md "Span catalog"): under
tracing the device watcher records the ledger as the instant event
``thread_ledger`` on track ``proc`` at most twice a second; for an
operator :func:`publish` registers it as the registry source
``proc.threads``, read at snapshot time on the snapshotter's thread,
tracing on or off. Nothing on any hot path.
"""

from __future__ import annotations

import os
import sys
import threading
import time
from typing import Any, Dict, List, Optional

from reflow_tpu.utils.runtime import named_lock

__all__ = ["SOURCE", "ledger", "role_of", "publish", "unpublish"]

#: the registry source's key: the process's, not a frontend's
SOURCE = "proc.threads"

_TASKS = "/proc/self/task"
_KEPT = ("rpc-", "reflow-", "bench-")

_lock = named_lock("obs.threads")
#: thread -> (role, [cpu_s, runq_s, vol, invol]) at the last read
_seen: Dict[threading.Thread, tuple] = {}
#: role -> what its exited threads had used when last read
_gone: Dict[str, List[float]] = {}
#: whether this kernel's ``/proc`` has ``schedstat``; asked once
_schedstat: Optional[bool] = None


def role_of(name: str) -> str:
    """The role a Python thread's name puts it in."""
    head = name.split("/", 1)[0]
    stem = head.rstrip("0123456789")
    if stem != head and stem.endswith("-"):
        head = stem[:-1]
    if head == "MainThread" or head.startswith(_KEPT):
        return head
    return "other"


def _slurp(path: str) -> bytes:
    fd = os.open(path, os.O_RDONLY)
    try:
        return os.read(fd, 8192)
    finally:
        os.close(fd)


def _has_schedstat() -> bool:
    global _schedstat
    if _schedstat is None:
        try:
            _slurp("/proc/self/schedstat")
            _schedstat = True
        except OSError:
            _schedstat = False
    return _schedstat


def _waits(tid: int) -> Optional[list]:
    """``[runq_s, vol, invol]`` of one task from ``/proc``; None once
    it has gone (or where ``status`` counts no switches)."""
    base = f"{_TASKS}/{tid}/"
    try:
        wait_ns = _slurp(base + "schedstat").split()[1]
        status = _slurp(base + "status")
        sw = status[status.index(b"\nvoluntary_ctxt_switches:"):].split()
        return [int(wait_ns) * 1e-9, int(sw[1]), int(sw[3])]
    except (OSError, ValueError, IndexError):
        return None


def _add(total: List[float], row: list) -> None:
    for i, v in enumerate(row):
        total[i] += v


def ledger() -> Dict[str, Any]:
    """``{"roles": {role: {n, cpu_s, [runq_s, vol, invol]}, "native":
    {cpu_s}}, "process_cpu_s", "switch_interval_s", "cores"}``,
    cumulative since process start. Thread-safe."""
    waits = _has_schedstat()
    with _lock:
        live: Dict[threading.Thread, tuple] = {}
        for t in threading.enumerate():
            # a finished thread's clock is not asked for: it is alive
            # the instant before, and needs the interpreter lock to end
            if t.ident is None or not t.is_alive():
                continue
            try:
                cpu_s = time.clock_gettime(
                    time.pthread_getcpuclockid(t.ident))
            except (OSError, ValueError, AttributeError):
                continue
            was = _seen.get(t)
            live[t] = (role_of(t.name),
                       [cpu_s] + (was[1][1:] if was else [0.0, 0, 0]))
        process_cpu_s = time.process_time()
        if waits:
            for t, (_role, row) in live.items():
                got = _waits(t.native_id) if t.native_id else None
                if got is not None:         # else: as it last read
                    row[1:] = got
        # a thread that has left since the last read keeps what it had
        # in its role
        for t, (role, row) in _seen.items():
            if t not in live:
                _add(_gone.setdefault(role, [0.0, 0.0, 0, 0]), row)
        _seen.clear()
        _seen.update(live)
        totals: Dict[str, List[float]] = {
            role: list(row) for role, row in _gone.items()}
        counts: Dict[str, int] = {}
        for role, row in live.values():
            _add(totals.setdefault(role, [0.0, 0.0, 0, 0]), row)
            counts[role] = counts.get(role, 0) + 1
    roles = {}
    for role, (cpu_s, runq_s, vol, invol) in sorted(totals.items()):
        r = roles[role] = {"n": counts.get(role, 0), "cpu_s": cpu_s}
        if waits:
            r.update(runq_s=runq_s, vol=vol, invol=invol)
    roles["native"] = {"cpu_s": max(0.0, process_cpu_s - sum(
        row[0] for row in totals.values()))}
    try:
        cores = len(os.sched_getaffinity(0))
    except (AttributeError, OSError):
        cores = os.cpu_count() or 1
    return {"roles": roles, "process_cpu_s": process_cpu_s,
            "switch_interval_s": sys.getswitchinterval(),
            "cores": cores}


def publish(registry=None) -> str:
    """Register :func:`ledger` as the source ``proc.threads``. The
    ledger is the process's: one key however many frontends publish
    (registering again changes nothing), and no frontend's ``close()``
    takes it away from the others — :func:`unpublish` does."""
    from reflow_tpu.obs.registry import REGISTRY
    reg = registry if registry is not None else REGISTRY
    return reg.register_source(SOURCE, ledger)


def unpublish(registry=None) -> None:
    from reflow_tpu.obs.registry import REGISTRY
    reg = registry if registry is not None else REGISTRY
    reg.unregister_source(SOURCE)
