#!/usr/bin/env python3
"""Measure whether staged (topo-partitioned) execution can overlap on
the devices JAX has — the evidence behind the claim-bounding in
``parallel/topo.py``.

Two measurements:

1. **Raw runtime overlap**: dispatch one latency-bound program on device
   0, then the same program on devices 0 AND 1 back-to-back, and compare
   walls. Ratio ~1.0 = the runtime truly executes different devices'
   programs concurrently (pipelining can win); ratio ~2.0 = execution is
   serial across devices (no schedule can overlap anything).
2. **Framework staged-vs-single**: the two-stage compute-bound graph
   (heavy params-Map per stage -> keyed Reduce) driven for K streaming
   ticks on 1 device vs 2 devices via ``StagedTpuExecutor``.

Needs at least two devices and uses whatever backend JAX resolved — it
prints which. Virtual CPU devices
(``--xla_force_host_platform_device_count``) share the host's cores and
run device programs serially, so only a run on distinct chips says
whether the staged executor can win (PERF.md has the last such run;
ROADMAP D5).

Usage: python tools/staged_pipeline_probe.py       # one process, >= 2 chips
"""

import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402


def probe_raw_overlap(chain=400, d=64):
    def body(x):
        for _ in range(chain):
            x = jnp.tanh(x @ x)
        return x

    # jit follows its committed argument's device: one program, two inputs
    d0, d1 = jax.devices()[:2]
    f = jax.jit(body)
    x0 = jax.device_put(jnp.eye(d) * 0.5, d0)
    x1 = jax.device_put(jnp.eye(d) * 0.5, d1)
    f(x0).block_until_ready()
    f(x1).block_until_ready()
    t0 = time.perf_counter()
    f(x0).block_until_ready()
    one = time.perf_counter() - t0
    t0 = time.perf_counter()
    a, b = f(x0), f(x1)
    a.block_until_ready()
    b.block_until_ready()
    both = time.perf_counter() - t0
    return one, both, both / one


def probe_staged(n_dev, K=64, D=512, rows=256, ticks=10, chain=6):
    from reflow_tpu import DirtyScheduler, FlowGraph
    from reflow_tpu.delta import DeltaBatch, Spec
    from reflow_tpu.parallel.topo import StagedTpuExecutor

    def heavy(p, v):
        for _ in range(chain):
            v = jnp.tanh(v @ p)
        return v

    g = FlowGraph("pipe")
    src = g.source("x", Spec((D,), np.float32, key_space=K))
    rng = np.random.default_rng(0)
    W0 = (rng.standard_normal((D, D)) * 0.05).astype(np.float32)
    W1 = (rng.standard_normal((D, D)) * 0.05).astype(np.float32)
    m0 = g.map(src, heavy, vectorized=True, params=W0, name="m0")
    m1 = g.map(m0, heavy, vectorized=True, params=W1, name="m1")
    gb = g.group_by(m1, key_fn=lambda k, v: k % K, vectorized=True)
    red = g.reduce(gb, "sum", name="agg")
    m0.stage = 0
    for n in (m1, gb, red):
        n.stage = 1

    ex = StagedTpuExecutor(devices=jax.devices()[:n_dev])
    sched = DirtyScheduler(g, ex)
    rng = np.random.default_rng(7)

    def batch():
        return DeltaBatch(np.arange(rows) % K,
                          rng.standard_normal((rows, D)).astype(np.float32),
                          np.ones(rows, np.int64))

    sched.push(src, batch())
    sched.tick(sync=False)
    _ = sched.read_table(red)          # compile + barrier
    t0 = time.perf_counter()
    for _ in range(ticks):
        sched.push(src, batch())
        sched.tick(sync=False)
    _ = sched.read_table(red)          # barrier
    return time.perf_counter() - t0


def main():
    from reflow_tpu.utils.runtime import place_compile_cache

    place_compile_cache()
    devs = jax.devices()
    print(f"platform={devs[0].platform} device_kind={devs[0].device_kind} "
          f"devices={len(devs)}")
    if len(devs) < 2:
        raise SystemExit("staged_pipeline_probe: needs >= 2 devices")
    one, both, ratio = probe_raw_overlap()
    print(f"raw overlap: one-program {one*1e3:.1f}ms, two-device "
          f"{both*1e3:.1f}ms, ratio {ratio:.2f} "
          f"(1.0 = concurrent, 2.0 = serial)")
    w1 = probe_staged(1)
    w2 = probe_staged(2)
    print(f"staged compute shape: 1-device {w1:.3f}s, 2-device {w2:.3f}s, "
          f"speedup {w1 / w2:.2f}x")
    if ratio > 1.5:
        print("verdict: this runtime executes device programs SERIALLY "
              "across devices — no pipeline schedule can overlap; staged "
              "parity is the expected best case.")
    else:
        print("verdict: runtime overlaps across devices — staged "
              "pipelining can win on multi-stage compute-bound graphs.")


if __name__ == "__main__":
    main()
