"""Process-level runtime helpers: the persistent compile cache an entry
point places, and the opt-in lock-order detector.

Compile cache (:func:`place_compile_cache`): every device program this
repo builds is compiled at first use and compilation is the largest
share of a cold start, so entry points (``chip_smoke.py``,
``benchmarks/run.py``, the device tools) share one persistent JAX
compilation cache. Library code never calls it — ``import reflow_tpu``
configures nothing.

Lock-order detection (``REFLOW_LOCKCHECK=1``): every lock in the
serving/WAL stack is created through :func:`named_lock`. Off (the
default) that returns a plain ``threading.Lock``/``RLock`` — zero
overhead, byte-identical behavior. On, it returns a :class:`NamedLock`
wrapper that records per-thread acquisition stacks into the global
:data:`LOCK_MONITOR`, merges every acquisition into one held-before
graph, and raises :class:`LockOrderError` the moment an acquisition
would close a cycle (the classic AB/BA deadlock, caught on the FIRST
inverted acquisition, not the eventual hang). The static twin of this
check lives in ``reflow_tpu/analysis/locks.py``; the runtime detector
catches orders the AST can't see (callbacks, cross-module call
chains). ``tools/tier1.sh``'s RUN_BENCH leg runs the serve/tier/
failover suites under it.
"""

from __future__ import annotations

import os
import threading
import traceback
from typing import Dict, List, Set, Tuple

__all__ = ["LOCK_MONITOR", "LockOrderError", "LockOrderMonitor",
           "NamedLock", "device_record", "lockcheck_enabled", "named_lock",
           "place_compile_cache"]

#: the checkout root (the directory holding the ``reflow_tpu`` package)
_CHECKOUT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))


def place_compile_cache() -> str:
    """Point JAX's persistent compilation cache somewhere that outlives
    the process, and return the directory in use.

    Where ``JAX_COMPILATION_CACHE_DIR`` is set this does nothing: JAX
    reads the variable itself, and whoever runs the program chose the
    place. Where it is unset the cache goes to ``<checkout>/.jax_cache``:
    a fixed path derived from the package's location (a cache that moves
    between runs never hits), git-ignored, shared by every process one
    command starts. Call it from an entry point before the first
    compile."""
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    import jax

    path = os.path.join(_CHECKOUT, ".jax_cache")
    jax.config.update("jax_compilation_cache_dir", path)
    return path


def device_record() -> dict:
    """The device a result ran on, as JAX reports it — the one shape
    every entry point stamps into what it prints. Initialises the
    backend: call it from the process that owns the device."""
    import jax

    devs = jax.devices()
    return {"platform": devs[0].platform, "kind": devs[0].device_kind,
            "count": len(devs)}


class LockOrderError(RuntimeError):
    """An acquisition would close a cycle in the held-before graph —
    some other code path acquires the same locks in the opposite order,
    so the two paths can deadlock. Raised at acquire time by the
    ``REFLOW_LOCKCHECK=1`` wrapper, before any blocking happens."""


class LockOrderMonitor:
    """Process-global held-before graph over :class:`NamedLock`s.

    Per-thread state is the ordered list of held locks; each acquisition
    of ``B`` while holding ``A`` merges the edge ``A -> B`` (with a
    sample acquisition stack for diagnostics) into the graph. A new
    edge whose reverse direction is already reachable raises
    :class:`LockOrderError` carrying both acquisition stacks. Same-name
    edges (two *instances* of one named lock nested in a thread) count
    as cycles too: name-level order is the invariant the static pass
    checks, so instance-level inversions must not hide behind a shared
    name — give interacting instances distinct names.

    The monitor's own mutex is a leaf by construction: no callback or
    user code ever runs while it is held.
    """

    def __init__(self) -> None:
        # reflow-lint: waive lock-unnamed -- the monitor's own leaf mutex; a NamedLock here would recurse into the monitor
        self._mu = threading.Lock()
        #: name -> set of names acquired while it was held
        self._edges: Dict[str, Set[str]] = {}
        #: (a, b) -> sample stack (list of "file:line in fn" strings)
        self._sites: Dict[Tuple[str, str], List[str]] = {}
        self._tls = threading.local()
        self.cycles_checked = 0

    # -- per-thread held list ----------------------------------------------

    def _held(self) -> List[list]:
        held = getattr(self._tls, "held", None)
        if held is None:
            held = self._tls.held = []
        return held  # entries: [lock, recursion_count]

    def held_names(self) -> List[str]:
        return [e[0].name for e in self._held()]

    @staticmethod
    def _stack(limit: int = 6) -> List[str]:
        # drop the monitor/wrapper frames at the tail; keep callers
        frames = traceback.extract_stack(limit=limit + 3)[:-3]
        return [f"{os.path.basename(f.filename)}:{f.lineno} in {f.name}"
                for f in frames]

    # -- graph maintenance -------------------------------------------------

    def _reachable(self, src: str, dst: str) -> bool:
        # DFS under self._mu: is dst reachable from src?
        seen = {src}
        stack = [src]
        while stack:
            for nxt in self._edges.get(stack.pop(), ()):
                if nxt == dst:
                    return True
                if nxt not in seen:
                    seen.add(nxt)
                    stack.append(nxt)
        return False

    def on_acquire(self, lock: "NamedLock") -> None:
        held = self._held()
        for entry in held:
            if entry[0] is lock:      # RLock re-entry: no new edges
                entry[1] += 1
                return
        stack = self._stack()
        with self._mu:
            for entry in held:
                a, b = entry[0].name, lock.name
                if a == b:
                    # a DIFFERENT instance of the same name (identity
                    # re-entry returned above): name-level order can't
                    # arbitrate instance order, so this is a cycle —
                    # interacting instances need distinct names
                    raise LockOrderError(
                        f"lock-order cycle: acquiring a second "
                        f"{b!r} instance while one is already held "
                        f"({' <- '.join(stack)}); give interacting "
                        f"instances distinct named_lock() names")
                if b in self._edges.get(a, ()):
                    continue
                self.cycles_checked += 1
                if self._reachable(b, a):
                    first = self._sites.get(
                        (b, a)) or self._sites.get((b, b)) or []
                    raise LockOrderError(
                        f"lock-order cycle: acquiring {b!r} while "
                        f"holding {a!r}, but {b!r} -> {a!r} is already "
                        f"an established order.\n"
                        f"  this acquisition: {' <- '.join(stack)}\n"
                        f"  established at:   {' <- '.join(first)}\n"
                        f"  held here: {[e[0].name for e in held]}")
                self._edges.setdefault(a, set()).add(b)
                self._sites.setdefault((a, b), stack)
        held.append([lock, 1])

    def on_release(self, lock: "NamedLock", *, all_levels: bool = False,
                   ) -> int:
        """Pop one recursion level (or the whole entry for a
        Condition's ``_release_save``); returns the popped count."""
        held = self._held()
        for i in range(len(held) - 1, -1, -1):
            if held[i][0] is lock:
                if all_levels or held[i][1] <= 1:
                    return held.pop(i)[1]
                held[i][1] -= 1
                return 1
        return 0  # release of a lock acquired before lockcheck wrapped

    # -- introspection (tests, reports) ------------------------------------

    def edges(self) -> Dict[str, Set[str]]:
        with self._mu:
            return {k: set(v) for k, v in self._edges.items()}

    def reset(self) -> None:
        with self._mu:
            self._edges.clear()
            self._sites.clear()


#: the process-wide monitor every REFLOW_LOCKCHECK=1 NamedLock reports to
LOCK_MONITOR = LockOrderMonitor()


class NamedLock:
    """A named ``threading.Lock``/``RLock`` wrapper that reports every
    acquisition to a :class:`LockOrderMonitor`. Condition-compatible:
    ``threading.Condition(named_lock(...))`` works because the wrapper
    implements ``_release_save`` / ``_acquire_restore`` / ``_is_owned``
    (delegating recursion bookkeeping to the inner RLock when there is
    one, and keeping the monitor's held list balanced across a
    ``Condition.wait``)."""

    __slots__ = ("name", "_inner", "_mon")

    def __init__(self, name: str, inner, mon: LockOrderMonitor) -> None:
        self.name = name
        self._inner = inner
        self._mon = mon

    def acquire(self, blocking: bool = True, timeout: float = -1) -> bool:
        # order violations are checked BEFORE blocking on the inner
        # lock: a true inversion must raise, not deadlock
        self._mon.on_acquire(self)
        try:
            got = self._inner.acquire(blocking, timeout)
        except BaseException:
            self._mon.on_release(self)
            raise
        if not got:
            self._mon.on_release(self)
        return got

    def release(self) -> None:
        self._mon.on_release(self)
        self._inner.release()

    __enter__ = acquire

    def __exit__(self, *exc) -> None:
        self.release()

    def locked(self) -> bool:
        return self._inner.locked()

    # -- Condition protocol ------------------------------------------------

    def _is_owned(self) -> bool:
        inner = self._inner
        if hasattr(inner, "_is_owned"):
            return inner._is_owned()
        return any(e[0] is self for e in self._mon._held())

    def _release_save(self):
        count = self._mon.on_release(self, all_levels=True)
        inner = self._inner
        if hasattr(inner, "_release_save"):
            return (count, inner._release_save())
        inner.release()
        return (count, None)

    def _acquire_restore(self, state) -> None:
        count, inner_state = state
        inner = self._inner
        if hasattr(inner, "_acquire_restore"):
            inner._acquire_restore(inner_state)
        else:
            inner.acquire()
        # the wait dropped the lock, so the thread's other held locks
        # (if any) already have their edges recorded; restore the entry
        # without re-walking them (re-recording would be harmless but
        # this is the wait hot path)
        self._mon._held().append([self, max(1, count)])

    def __repr__(self) -> str:
        return f"NamedLock({self.name!r}, {self._inner!r})"


def lockcheck_enabled() -> bool:
    """True when the runtime lock-order detector is on. Read per call
    so a test can construct wrapped locks explicitly; module-level
    locks capture the value at import, so set ``REFLOW_LOCKCHECK=1``
    at process start for full coverage."""
    from reflow_tpu.utils.config import env_flag

    return env_flag("REFLOW_LOCKCHECK")


def named_lock(name: str, *, reentrant: bool = False):
    """The ONE way this project creates a lock on a concurrent path.

    Returns a plain ``threading.Lock`` / ``threading.RLock`` when
    ``REFLOW_LOCKCHECK`` is off (zero overhead, the production shape),
    or a monitor-wrapped :class:`NamedLock` when on. ``name`` is the
    node in the held-before graph; instances that can interact within
    one thread must use distinct names (e.g. ``serve.replica.<n>``).
    The static lint's lock pass keys its graph on the same names."""
    # reflow-lint: waive lock-unnamed -- named_lock() IS the factory; this is the inner lock it wraps
    inner = threading.RLock() if reentrant else threading.Lock()
    if not lockcheck_enabled():
        return inner
    return NamedLock(name, inner, LOCK_MONITOR)
