"""What the leader observes about the device, from the benchmark's own
files: when each dispatched window *finished on the device*, and whether
anything was compiled while the window was open.

Completion. ``dispatch_staged`` returns when the window is enqueued; the
ticket path then waits for the WAL only, so nothing the program records
says when the chip was done. For a loop graph the window's ``TickResult``
carries program outputs one could wait on, for a loop-free graph it
carries none, and every state leaf is donated to the next window. So the
probe wraps the scheduler's ``dispatch_staged`` and, on the pump thread,
right after the dispatch and before anything can donate its outputs,
enqueues one tiny jitted read of the smallest state leaf the window
produced. A watcher thread waits on those reads in order: a read is ready
only when the window program that produced its input has finished. Cost:
one scalar dispatch per window. ``TickResult.block()`` is not used from
the watcher: it also runs ``check_errors``, which reads state leaves the
next dispatch may already have donated.
"""

from __future__ import annotations

import queue
import threading
from typing import List, Optional

from common import now

BACKEND_COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"


class CompileWatch:
    """Counts executables built or loaded (``jax.monitoring``: the event
    fires once per compile request, cache hit or not). A program that
    first appears inside the window costs seconds either way, and a
    number taken across it is not a number."""

    def __init__(self):
        import jax.monitoring

        self.times: List[float] = []
        jax.monitoring.register_event_duration_secs_listener(self._on)

    def _on(self, event: str, duration: float, **kw) -> None:
        if event == BACKEND_COMPILE_EVENT:
            self.times.append(now())

    def between(self, t0: float, t1: float) -> int:
        return sum(1 for t in self.times if t0 <= t <= t1)


class CompletionProbe:
    """Per dispatched window: ticks covered, dispatch wall, and the host
    time at which the device finished it."""

    def __init__(self, sched, frontend, annotate: bool = False):
        import jax

        self.sched = sched
        self._fe = frontend
        self.windows: List[dict] = []
        self._q: "queue.Queue[Optional[tuple]]" = queue.Queue()
        self._read = jax.jit(lambda x: x.reshape(-1)[0])
        self.error: Optional[BaseException] = None
        self._orig_dispatch = sched.dispatch_staged
        sched.dispatch_staged = self._dispatch
        if annotate:
            self._annotate("stage_window")
            self._annotate("retire_staged")
        self._annotated = annotate
        self._thread = threading.Thread(target=self._watch,
                                        name="bench-completion",
                                        daemon=True)
        self._thread.start()

    def _annotate(self, method: str) -> None:
        import jax

        orig = getattr(self.sched, method)

        def wrapped(*a, **kw):
            with jax.profiler.TraceAnnotation(f"bench.{method}"):
                return orig(*a, **kw)

        setattr(self.sched, method, wrapped)

    def _smallest_leaf(self):
        import jax

        leaves = [x for x in jax.tree.leaves(self.sched.executor.states)
                  if isinstance(x, jax.Array)]
        return min(leaves, key=lambda x: x.size)

    def _dispatch(self, handle):
        import jax

        fe = self._fe
        tick_lo = self.sched._tick
        t0 = now()
        if self._annotated:
            with jax.profiler.TraceAnnotation("bench.dispatch_staged"):
                result = self._orig_dispatch(handle)
        else:
            result = self._orig_dispatch(handle)
        t1 = now()
        marker = self._read(self._smallest_leaf())
        w = {"ix": len(self.windows), "tick_lo": tick_lo,
             "tick_hi": self.sched._tick, "k": self.sched._tick - tick_lo,
             "host_rows": getattr(handle, "host_rows", None),
             "caps": sorted(getattr(getattr(handle, "sw", None), "caps",
                                    {}).values()),
             "admitted": getattr(fe, "admitted", None),
             "staged_s": getattr(fe, "stage_s_total", None),
             "dispatch0": t0, "dispatch1": t1, "ready": None}
        self.windows.append(w)
        self._q.put((w, marker))
        return result

    def _watch(self) -> None:
        import jax

        while True:
            item = self._q.get()
            if item is None:
                return
            w, marker = item
            try:
                if self._annotated:
                    with jax.profiler.TraceAnnotation("bench.await_device"):
                        marker.block_until_ready()
                else:
                    marker.block_until_ready()
                w["ready"] = now()
            except BaseException as e:  # noqa: BLE001 - surfaced by drain
                self.error = e
            finally:
                self._q.task_done()

    def drain(self) -> None:
        """Wait until every dispatched window so far is ready."""
        self._q.join()
        if self.error is not None:
            raise RuntimeError(f"completion probe: {self.error!r}")

    def close(self) -> None:
        self._q.put(None)
        self._thread.join(timeout=30)
