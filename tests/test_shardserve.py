"""Pod-scale serving (docs/guide.md "Sharded serving").

Two composable contracts on top of test_megatick.py's fused-window
semantics: (a) **tenant placement** — ``GraphConfig(device=...)`` /
``placement="spread"`` binds each tenant's executor to one mesh device
(distinct devices under spread, crash isolation and view parity
preserved), and (b) **sharded windows** — ``ShardedTpuExecutor`` runs
the SAME mega-tick window protocol with the ingress queue's stacked
buffers sharded along the capacity axis, view-identical to the CPU
per-tick oracle with zero fallbacks.
"""

import numpy as np
import pytest

from reflow_tpu import DirtyScheduler
from reflow_tpu.delta import DeltaBatch
from reflow_tpu.executors import get_executor
from reflow_tpu.graph import GraphError
from reflow_tpu.parallel import make_mesh
from reflow_tpu.parallel.shard import ShardedTpuExecutor
from reflow_tpu.serve import (CoalesceWindow, GraphConfig, PumpCrashed,
                              ServeTier)
from reflow_tpu.utils.faults import CrashInjector

from test_megatick import K_SPACE, _batch, _oracle, _small_graph, _table

WINDOW = CoalesceWindow(max_rows=256, max_ticks=8, max_latency_s=0.002)


def _mixed_ticks(seed, n_ticks=4, rows=6):
    """Ragged insert/retract feeds with integer-valued f32 payloads so
    every reduce sum is exact in f32 regardless of accumulation order
    (shard-local partial sums reorder the reduction)."""
    rng = np.random.default_rng(seed)
    ticks = []
    inserted = []
    for t in range(n_ticks):
        tick = {}
        for s_ix in (0, 1):
            if s_ix == 1 and t % 2 == 1:
                continue        # ragged: s1 absent on odd ticks
            rws = []
            for _ in range(rows):
                if inserted and rng.random() < 0.25:
                    k, v = inserted.pop(int(rng.integers(0, len(inserted))))
                    rws.append((k, v, -1))
                else:
                    k = int(rng.integers(0, K_SPACE))
                    v = float(rng.integers(0, 8))
                    rws.append((k, v, 1))
                    inserted.append((k, v))
            tick[s_ix] = rws
        ticks.append(tick)
    return ticks


def _sharded_window_drive(ticks, k, n):
    """Window drive of ``_small_graph`` on an ``n``-device mesh."""
    g, (s0, s1), r = _small_graph()
    sched = DirtyScheduler(g, ShardedTpuExecutor(make_mesh(n)))
    srcs = {0: s0, 1: s1}
    results = []
    for lo in range(0, len(ticks), k):
        feeds = [{srcs[s_ix]: _batch(rows) for s_ix, rows in tick.items()}
                 for tick in ticks[lo:lo + k]]
        results.append(sched.tick_many(feeds))
    for res in results:
        res.block()
    return _table(sched, r), sched


# -- sharded mega-tick windows: differential fuzz vs the CPU oracle --------

@pytest.mark.parametrize("n,k,seed", [(2, 2, 7), (2, 4, 8),
                                      (4, 2, 9), (4, 4, 10)])
def test_sharded_window_parity_fuzz(n, k, seed):
    """Mesh sizes x window sizes x seeds: the sharded window path must
    fuse (no fallback) and match the CPU per-tick oracle EXACTLY —
    inserts, retractions, and ragged zero-row padding included."""
    ticks = _mixed_ticks(seed, n_ticks=2 * k)
    want = _oracle(ticks)
    got, sched = _sharded_window_drive(ticks, k, n)
    assert got == want, f"n={n} k={k} seed={seed}"
    assert sched.megatick_fallbacks == 0
    assert sched.megatick_windows == 2
    assert sched.executor.device_label == f"mesh[{n}]"


def test_sharded_queue_buffers_are_sharded():
    """The ingress queue under a sharded executor must hold its stacked
    [K, cap] buffers with a NamedSharding along the capacity axis (not
    replicated): slot writes stay shard-local."""
    ticks = _mixed_ticks(31, n_ticks=2)
    _got, sched = _sharded_window_drive(ticks, k=2, n=2)
    qkeys = [key for key in sched.executor._cache
             if isinstance(key, tuple) and key and key[0] == "ingress_q"]
    assert len(qkeys) == 1
    queue = sched.executor._cache[qkeys[0]]
    stacked = queue.stacked()
    assert stacked, "queue holds no source buffers"
    axis = sched.executor.axis
    names = axis if isinstance(axis, tuple) else (axis,)
    for dd in stacked.values():
        sh = dd.keys.sharding
        spec_names = [p for p in sh.spec if p is not None]
        flat = []
        for p in spec_names:
            flat.extend(p if isinstance(p, tuple) else (p,))
        assert tuple(flat) == names, sh
        # leading axis (window slot K) stays unsharded
        assert sh.spec[0] is None, sh


def test_sharded_tenant_served_through_the_tier_pump():
    """One hot tenant spanning the mesh behind a ``ServeTier``: its
    submissions commit as fused sharded windows (no fallback) and the
    view equals the CPU per-tick oracle's exactly."""
    ticks = _mixed_ticks(23, n_ticks=8)
    want = _oracle(ticks)
    g, (s0, s1), r = _small_graph()
    hot = DirtyScheduler(g, ShardedTpuExecutor(make_mesh(4)))
    tier = ServeTier(max_bytes=8 << 20, pump_threads=2)
    try:
        h = tier.register("hot", hot, GraphConfig(window=WINDOW))
        srcs = {0: s0, 1: s1}
        tks = [h.submit(srcs[ix], _batch(rows))
               for tick in ticks for ix, rows in tick.items()]
        h.flush(timeout=60)
        assert all(t.result(timeout=60).applied for t in tks)
        assert _table(hot, r) == want
        assert hot.megatick_windows >= 1 and hot.megatick_fallbacks == 0
        assert hot.executor.device_label == "mesh[4]"
    finally:
        tier.close()


# -- tenant placement --------------------------------------------------------

def _tpu_graph():
    g, (s0, s1), r = _small_graph()
    return DirtyScheduler(g, get_executor("tpu")), s0, r


def test_spread_placement_lands_distinct_devices():
    """placement="spread" round-robins tenants across jax.devices();
    each tenant's views still match a bare per-tick loop."""
    import jax
    n = min(4, len(jax.devices()))
    tier = ServeTier(max_bytes=8 << 20, pump_threads=2)
    handles = []
    try:
        for i in range(n):
            sched, src, r = _tpu_graph()
            h = tier.register(
                f"g{i}", sched,
                GraphConfig(window=WINDOW, placement="spread"))
            handles.append((h, sched, src, r))
        labels = [h.device_label for h, *_ in handles]
        assert all(labels), labels
        assert len(set(labels)) == n, labels
        for i, (h, sched, src, r) in enumerate(handles):
            for j in range(4):
                assert h.submit(src, _batch(
                    [(j, float(i + 1), 1)])).result(10).applied
            h.flush(timeout=10)
            want = {j: float(2 * (i + 1)) for j in range(4)}  # map doubles
            assert _table(sched, r) == want
        scheds = [sched for _h, sched, *_ in handles]
        assert all(s.megatick_windows >= 1 and s.megatick_fallbacks == 0
                   for s in scheds)
        # identical tenants on different devices still trace their
        # window program once (the plan-signature cache)
        assert sum(s.executor.megatick_cache_hits for s in scheds) >= 1
    finally:
        tier.close()


def test_device_alone_implies_pin():
    import jax
    tier = ServeTier(max_bytes=8 << 20, pump_threads=1)
    try:
        sched, src, r = _tpu_graph()
        dev = jax.devices()[-1]
        h = tier.register("pin", sched, GraphConfig(window=WINDOW,
                                                    device=dev))
        assert h.device_label == f"{dev.platform}:{dev.id}"
        assert h.submit(src, _batch([(1, 3.0, 1)])).result(10).applied
        h.flush(timeout=10)
        assert _table(sched, r) == {1: 6.0}
    finally:
        tier.close()


def test_pin_accepts_device_index():
    """Integer device= pins by position in jax.devices()."""
    import jax
    tier = ServeTier(max_bytes=8 << 20, pump_threads=1)
    try:
        sched, _src, _r = _tpu_graph()
        h = tier.register("byix", sched,
                          GraphConfig(window=WINDOW, placement="pin",
                                      device=1))
        dev = jax.devices()[1]
        assert h.device_label == f"{dev.platform}:{dev.id}"
    finally:
        tier.close()


def test_placement_validation_errors():
    tier = ServeTier(max_bytes=8 << 20, pump_threads=1)
    try:
        sched, _s, _r = _tpu_graph()
        with pytest.raises(ValueError, match="placement"):
            tier.register("bad", sched,
                          GraphConfig(window=WINDOW, placement="stripe"))
        with pytest.raises(ValueError, match="device"):
            tier.register("bad", sched,
                          GraphConfig(window=WINDOW, placement="pin"))
        # an executor with no placement hook refuses loudly, not silently
        g, (_s0, _s1), _r2 = _small_graph()
        cpu_sched = DirtyScheduler(g, get_executor("cpu"))
        with pytest.raises(GraphError, match="place"):
            tier.register("cpu", cpu_sched,
                          GraphConfig(window=WINDOW, placement="spread"))
        assert "bad" not in tier.graphs()
        assert "cpu" not in tier.graphs()
    finally:
        tier.close()


def test_sharded_executor_refuses_single_device_placement():
    ex = ShardedTpuExecutor(make_mesh(2))
    with pytest.raises(GraphError, match="mesh"):
        ex.place(0)


def test_pinned_crash_isolates_to_its_device_tenant():
    """A pump crash on a pinned tenant leaves the sibling (pinned to a
    DIFFERENT device) applying — placement must not widen the blast
    radius of test_tier's crash-isolation contract."""
    crash = CrashInjector(at=1, only="pump_before_tick@doomed")
    tier = ServeTier(max_bytes=8 << 20, pump_threads=2, crash=crash)
    try:
        d_sched, d_src, _ = _tpu_graph()
        doomed = tier.register("doomed", d_sched,
                               GraphConfig(window=WINDOW, device=0))
        s_sched, s_src, s_r = _tpu_graph()
        sib = tier.register("sib", s_sched,
                            GraphConfig(window=WINDOW, device=1))
        assert doomed.device_label != sib.device_label
        t = doomed.submit(d_src, _batch([(1, 1.0, 1)]))
        with pytest.raises(PumpCrashed):
            t.result(timeout=10)
        assert crash.fired_seam == "pump_before_tick@doomed"
        assert sib.submit(s_src, _batch([(2, 4.0, 1)])).result(10).applied
        sib.flush(timeout=10)
        assert _table(s_sched, s_r) == {2: 8.0}
        tier.unregister("doomed", flush=False)
    finally:
        tier.close()


def test_placed_executor_runs_windows_on_its_device():
    """Direct executor-level check: place() moves state and the window
    path onto the chosen device, views unchanged."""
    import jax
    ticks = _mixed_ticks(17, n_ticks=4)
    want = _oracle(ticks)
    g, (s0, s1), r = _small_graph()
    ex = get_executor("tpu")
    ex.place(len(jax.devices()) - 1)
    sched = DirtyScheduler(g, ex)
    srcs = {0: s0, 1: s1}
    res = sched.tick_many(
        [{srcs[ix]: _batch(rows) for ix, rows in tick.items()}
         for tick in ticks])
    res.block()
    assert _table(sched, r) == want
    assert sched.megatick_fallbacks == 0
    dev = jax.devices()[-1]
    assert ex.device_label == f"{dev.platform}:{dev.id}"
    for v in ex.states.values():
        leaves = jax.tree.leaves(v)
        assert all(next(iter(l.devices())) == dev for l in leaves)
