"""Attribute the PageRank churn tick's wall time.

The linear-fixpoint tick program is one fused jit; its phases are closures
(executors/linear_fixpoint.py), so this tool attributes cost empirically
on the chip — one process, which holds the chip while it runs:

  T_zero   K zero-churn ticks in ONE device execution (tick_many): the
           churn batch carries only weight-0 rows, so phase A runs, the
           CSR cache validates (no appends -> the tail build is skipped),
           and the while_loop quiesces after its first predicate — i.e.
           the tick's FIXED cost.
  T_churn  K real churn ticks in one execution: fixed cost + the loop
           passes. (T_churn - T_zero) / passes = per-pass cost.
  T_csr    the full CSR REBUILD (argsort + scatter-count/cumsum bounds —
           since round 4 paid only on compaction/tail-overflow ticks, not
           per tick) reconstructed standalone and scanned K times in one
           execution; the obsolete searchsorted form alongside.

Timing protocol: every wall runs to ``jax.block_until_ready`` on what
the window produced; K-fold fusion amortizes the per-dispatch overhead
below the noise floor. The first line printed names the device — a
number from a CPU run is not a device time.

Usage:  python tools/profile_tick.py            # full scale, on the chip
        JAX_PLATFORMS=cpu python tools/profile_tick.py --tiny
"""

from __future__ import annotations

import argparse
import os
import sys
import time

import numpy as np

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def log(*a):
    print(*a, file=sys.stderr, flush=True)


def main():
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--tiny", action="store_true",
                    help="1k nodes / 10k edges: the CPU sanity size")
    tiny = ap.parse_args().tiny

    import jax
    import jax.numpy as jnp

    from reflow_tpu.delta import DeltaBatch
    from reflow_tpu.executors import get_executor
    from reflow_tpu.scheduler import DirtyScheduler
    from reflow_tpu.utils.runtime import place_compile_cache
    from reflow_tpu.workloads import pagerank

    place_compile_cache()
    dev = jax.devices()[0]
    print(f"platform={dev.platform} device_kind={dev.device_kind} "
          f"devices={len(jax.devices())}")

    n_nodes = 1_000 if tiny else 100_000
    n_edges = 10_000 if tiny else 1_000_000
    churn = 0.01
    K = 4 if tiny else 8

    pr = pagerank.build_graph(n_nodes, tol=1e-4, arena_capacity=(
        pagerank.churn_arena_capacity(n_edges, churn)))
    web = pagerank.WebGraph.random(n_nodes, n_edges, seed=7)
    ex = get_executor("tpu")
    sched = DirtyScheduler(pr.graph, ex)
    sched.push(pr.teleport, pagerank.teleport_batch(n_nodes))
    sched.push(pr.edges, web.initial_batch())
    sched.tick(sync=False)

    # absorb the churn-shape compile
    sched.push(pr.edges, web.churn(churn))
    sched.tick()
    jax.block_until_ready(ex.states)

    # churn batches are retract+insert pairs over m rewired edges; size
    # the zero batch the same WITHOUT calling churn() (churn mutates the
    # host WebGraph, and a discarded batch would desync host vs device)
    cap = 2 * max(1, int(n_edges * churn))

    def zero_batch():
        return DeltaBatch(np.zeros(cap, np.int64),
                          np.zeros((cap, 2), np.float32),
                          np.zeros(cap, np.int64))

    def window(feeds, tag):
        t0 = time.perf_counter()
        agg = sched.tick_many(feeds)
        jax.block_until_ready(ex.states)
        wall = time.perf_counter() - t0
        agg.block()
        log(f"{tag}: {wall:.3f}s for {len(feeds)} ticks "
            f"({wall / len(feeds) * 1e3:.1f} ms/tick, passes={agg.passes})")
        return wall / len(feeds), agg.passes

    # macro-tick compile absorption for both shapes
    window([{pr.edges: zero_batch()} for _ in range(K)], "warm zero")
    window([{pr.edges: web.churn(churn)} for _ in range(K)], "warm churn")

    t_zero, _ = window([{pr.edges: zero_batch()} for _ in range(K)],
                       "zero-churn (fixed+CSR)")
    t_churn, passes = window([{pr.edges: web.churn(churn)}
                              for _ in range(K)], "churn")
    loop_passes = max(1, (passes - 2 * K) / K)  # minus phase A + exit per tick

    # standalone CSR rebuild at the real arena shape
    jst = ex.states[pr.join.id]
    Rcap = jst["rkeys"].shape[0]
    Klc = pr.join.inputs[0].spec.key_space
    log(f"arena capacity {Rcap}, key space {Klc}")

    def time_scanned(name, once):
        """Scan ``once`` K times in one execution, timed to completion."""
        fn = jax.jit(lambda rk, rw: jax.lax.scan(
            once, (rk, rw), (), length=K)[0])
        jax.block_until_ready(fn(jst["rkeys"], jst["rw"]))  # compile + warm
        t0 = time.perf_counter()
        jax.block_until_ready(fn(jst["rkeys"], jst["rw"]))
        per = (time.perf_counter() - t0) / K
        log(f"{name}: {per * 1e3:.1f} ms")
        return per

    def use_order(rw, order):
        """Position-weighted sum: irreducibly consumes the FULL permutation
        (folding only order[0]/order[-1] lets XLA collapse the argsort
        into a min/max reduction and the timing lies)."""
        iota = jnp.arange(order.shape[0], dtype=jnp.int32)
        return jnp.sum(rw[order] * iota)

    def sort_only(c, _):
        rk, rw = c
        skey = jnp.where(rw != 0, rk, Klc)
        order = jnp.argsort(skey)
        return (rk ^ use_order(rw, order), rw ^ order[0]), ()

    def full_csr(c, _):
        rk, rw = c
        skey = jnp.where(rw != 0, rk, Klc)
        order = jnp.argsort(skey)
        sk = skey[order]
        bounds = jnp.searchsorted(
            sk, jnp.arange(Klc + 1, dtype=jnp.int32)).astype(jnp.int32)
        return (rk ^ bounds[0] ^ use_order(rw, order), rw ^ order[0]), ()

    def counts_csr(c, _):
        # searchsorted-free bounds: scatter-count + cumsum (the form
        # linear_fixpoint.py builds)
        rk, rw = c
        skey = jnp.where(rw != 0, rk, Klc)
        order = jnp.argsort(skey)
        deg = jnp.zeros((Klc + 1,), jnp.int32).at[skey].add(
            1, mode="drop")[:Klc]
        bounds = jnp.cumsum(deg) - deg
        return (rk ^ bounds[0] ^ use_order(rw, order), rw ^ order[0]), ()

    t_sort = time_scanned("argsort only", sort_only)
    time_scanned("CSR via searchsorted (obsolete form)", full_csr)
    # counts/cumsum is the rebuild-path form linear_fixpoint.py builds
    t_csr = time_scanned("CSR (argsort + counts/cumsum)", counts_csr)

    per_pass = (t_churn - t_zero) / loop_passes
    print(f"fixed         {t_zero * 1e3:8.1f} ms/tick")
    print(f"  CSR rebuild {t_csr * 1e3:8.1f} ms (argsort {t_sort * 1e3:.1f};"
          f" amortized over ticks between compactions)")
    print(f"loop          {(t_churn - t_zero) * 1e3:8.1f} ms/tick "
          f"({loop_passes:.1f} passes x {per_pass * 1e3:.1f} ms)")
    print(f"total         {t_churn * 1e3:8.1f} ms/tick")


if __name__ == "__main__":
    main()
