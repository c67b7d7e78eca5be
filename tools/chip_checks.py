#!/usr/bin/env python3
"""Builder-run device checks that are not part of ``chip_smoke.py``.

Each subcommand is ONE command for the chip tool and keeps the
one-process-per-chip rule itself; each prints the device JAX reported,
fails with a non-zero exit on the first broken check, and claims no
performance number (what it prints goes into CHANGES.md / PERF.md as an
observation).

    python tools/chip_checks.py barrier   # 1 chip
        wall to ``block_until_ready`` vs wall to a host readback of the
        same ~1 s program: is ``block_until_ready`` the barrier here?
    python tools/chip_checks.py proc      # 1 chip
        a leader + two replica role processes (``python -m
        reflow_tpu.proc``, inheriting this machine's environment
        unchanged) never take the chip: this parent initialises the TPU
        while they are alive.
    python tools/chip_checks.py sharded   # 4 chips
        the served PageRank path of the smoke (WAL recovery included) on
        ``ShardedTpuExecutor(make_mesh(4))``.
    python tools/chip_checks.py spread    # 4 chips
        four PageRank tenants behind a ``ServeTier`` with
        ``placement="spread"``: one per device, none on a shared one.
    python tools/chip_checks.py ring      # 4 chips
        sharded k-NN (the ppermute ring merge's top-k shapes) against
        the single-device executor.

``--tiny`` shrinks the 4-chip checks for a CPU dry run and, like the
smoke's, has to be asked for together with ``JAX_PLATFORMS=cpu`` (plus
``XLA_FLAGS=--xla_force_host_platform_device_count=4``).
"""

from __future__ import annotations

import argparse
import json
import os
import shutil
import subprocess
import sys
import tempfile
import time

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)

from chip_smoke import (FULL, TINY, pagerank_phase, rel_err,  # noqa: E402
                        require, require_device, require_resident)


def say(msg: str) -> None:
    print(f"chip_checks: {msg}", flush=True)


def _device_line(tiny: bool = False):
    """Platform guard + the cache every device entry point places."""
    dev = require_device(tiny)
    import jax

    from reflow_tpu.utils.runtime import place_compile_cache

    say(f"platform={dev.platform} device_kind={dev.device_kind} "
        f"devices={len(jax.devices())} cache={place_compile_cache()}")
    return dev


# -- barrier ---------------------------------------------------------------

def check_barrier(args) -> dict:
    import jax
    import jax.numpy as jnp
    import numpy as np

    _device_line()

    @jax.jit
    def busy(x):        # ~1 s of dependent matmuls on a v5e
        return jax.lax.fori_loop(
            0, 11_000, lambda i, a: jnp.tanh(a @ a) * 0.5 + 0.1, x)

    x = jnp.ones((2048, 2048), jnp.bfloat16)
    busy(x).block_until_ready()                      # compile
    rows = []
    for _ in range(5):
        t0 = time.perf_counter()
        y = busy(x)
        t_dispatch = time.perf_counter() - t0
        y.block_until_ready()
        t_block = time.perf_counter() - t0
        np.asarray(y[0, 0])                          # host readback
        t_read = time.perf_counter() - t0
        rows.append((t_dispatch, t_block, t_read))
        say(f"dispatch {t_dispatch:.4f}s  block_until_ready {t_block:.4f}s"
            f"  readback {t_read:.4f}s")
    med = [float(np.median([r[i] for r in rows])) for i in range(3)]
    # after readbacks: do chained dispatches still pipeline?
    t0 = time.perf_counter()
    for _ in range(3):
        y = busy(x)
    y.block_until_ready()
    chained = (time.perf_counter() - t0) / 3
    say(f"median: block_until_ready {med[1]:.4f}s, readback {med[2]:.4f}s; "
        f"3 chained after the readbacks {chained:.4f}s each")
    require(med[1] > 0.5 * med[2],
            "block_until_ready returned long before the readback: it is "
            "not a barrier on this runtime")
    require(chained < 1.5 * med[1],
            "dispatch degraded after the first readback")
    return {"dispatch_s": med[0], "block_until_ready_s": med[1],
            "readback_s": med[2], "chained_after_readback_s": chained}


# -- proc ------------------------------------------------------------------

def _maps(pid: int) -> str:
    with open(f"/proc/{pid}/maps") as f:
        return f.read()


def check_proc(args) -> dict:
    """Stays off JAX until the children are up: then takes the chip."""
    require("jax" not in sys.modules, "parent imported jax too early")
    from reflow_tpu.proc import ProcHarness

    inherited = os.environ.get("JAX_PLATFORMS")
    root = tempfile.mkdtemp(prefix="reflow-chip-proc-")
    h = ProcHarness(root, fleet=False)     # no child_env: inherit as-is
    out = {"inherited_jax_platforms": inherited, "children": {}}
    try:
        h.spawn_leader()
        for nm in ("r0", "r1"):
            h.spawn_replica(nm)
        h.attach_replicas()
        h.spawn_producer("p0", index=0, pace_s=0.02)
        time.sleep(1.0)
        h.kill9("r0")                      # recovery over the mirrored WAL
        h.respawn("r0")
        h.attach_replicas(["r0"])
        horizons = h.barrier(timeout_s=60.0)
        st = h.child("p0").stop()
        require(st is not None and st["ok"] and st["in_doubt"] == [],
                f"producer did not drain cleanly: {st}")
        for nm in ("leader", "r0", "r1"):
            c = h.child(nm)
            maps = _maps(c.proc.pid)
            out["children"][nm] = {
                "jax_platforms": (c.ready or {}).get("jax_platforms"),
                "jaxlib_mapped": "jaxlib" in maps or "xla_extension" in maps,
                "libtpu_mapped": "libtpu" in maps,
                "horizon": horizons.get(nm),
            }
            require(out["children"][nm]["jax_platforms"] == "cpu",
                    f"{nm} did not pin itself to the cpu")
            require(not out["children"][nm]["libtpu_mapped"],
                    f"{nm} loaded libtpu: it can take the chip")
        say(f"inherited JAX_PLATFORMS={inherited!r}; children "
            f"{json.dumps(out['children'])}")

        # the children are alive; if one of them held the chip this
        # parent's backend initialisation would fail (or hang into the
        # tool's timeout)
        dev = _device_line()
        import jax.numpy as jnp

        val = float(jnp.ones((512, 512)).sum())
        require(val == 512 * 512, f"device sum wrong: {val}")
        out["parent_platform_with_children_alive"] = dev.platform

        # the rule itself, observed: a second process that touches JAX
        # unpinned while this one holds the chip does not get it
        t0 = time.perf_counter()
        try:
            p = subprocess.run(
                [sys.executable, "-c",
                 "import jax; print(jax.devices()[0].platform)"],
                capture_output=True, text=True, timeout=90)
            second = {"rc": p.returncode, "stdout": p.stdout.strip(),
                      "stderr_tail": p.stderr.strip().splitlines()[-1:]}
        except subprocess.TimeoutExpired:
            second = {"rc": None, "hung_s": 90}
        second["s"] = round(time.perf_counter() - t0, 1)
        out["second_unpinned_process"] = second
        say(f"second unpinned process while the chip is held: {second}")
        require(second.get("stdout") != "tpu",
                "a second process got the chip while this one held it")
    finally:
        h.close()
        shutil.rmtree(root, ignore_errors=True)
    return out


# -- mesh (4 chips) --------------------------------------------------------

def _knn_ring(cfg: dict, mesh) -> dict:
    """Sharded k-NN against the single-device executor on the same host
    batches: the ring merge runs ``topk`` at ``[Q, 2k]`` inside the
    shard_map region, the per-shard rescan folds ``[Q, k]`` + ``[Q, chunk]``."""
    import jax.numpy as jnp
    import numpy as np

    from reflow_tpu.delta import DeltaBatch
    from reflow_tpu.executors import get_executor
    from reflow_tpu.parallel.shard import ShardedTpuExecutor
    from reflow_tpu.scheduler import DirtyScheduler
    from reflow_tpu.workloads import knn

    c = cfg["knn"]
    Q, dim, k, chunk = c["Q"], c["dim"], c["k"], c["chunk"]
    n = mesh.devices.size
    D = 2 * n * chunk                       # two scan chunks per shard
    store = knn.EmbeddingStore.create(dim, seed=5)
    queries = DeltaBatch(np.arange(Q, dtype=np.int64), store._random(Q),
                         np.ones(Q, np.int64))
    inserts = [store.insert_batch(np.arange(i * chunk, (i + 1) * chunk),
                                  quantize=True) for i in range(2 * n)]
    ret = np.arange(0, D, 7, dtype=np.int64)
    retract = DeltaBatch(ret, np.zeros((len(ret), dim), np.int8),
                         -np.ones(len(ret), np.int64))

    def run(ex):
        kg = knn.build_graph(Q, D, dim, k, scan_chunk=chunk,
                             dtype=jnp.bfloat16, doc_dtype=jnp.int8,
                             precision="default")
        sched = DirtyScheduler(kg.graph, ex)
        sched.push(kg.queries, queries)
        for b in inserts:
            sched.push(kg.docs, b)
            sched.tick(sync=False)
        sched.push(kg.docs, retract)        # forces the rescan + ring merge
        sched.tick()
        table = sched.read_table(kg.index)
        return np.stack([table[q] for q in range(Q)])

    one = run(get_executor("tpu"))
    ring = run(ShardedTpuExecutor(mesh))
    require(np.isfinite(ring).all() and ring.shape == (Q, k, 2),
            f"sharded top-k table shape {ring.shape} / non-finite")
    ids_equal = bool(np.array_equal(one[:, :, 0], ring[:, :, 0]))
    val_diff = float(np.abs(one[:, :, 1] - ring[:, :, 1]).max())
    # ids exactly; scores may differ in the last bits (different programs
    # tile the same bf16 matmul differently)
    require(ids_equal, "sharded k-NN ids differ from the single device's")
    require(val_diff < 1e-5, f"sharded k-NN scores differ by {val_diff}")
    say(f"sharded k-NN Q {Q} dim {dim} k {k} chunk {chunk} corpus {D} over "
        f"{n} shards: ids == single device, max score diff {val_diff:.2e}")
    return {"Q": Q, "dim": dim, "k": k, "chunk": chunk, "corpus": D,
            "shards": n, "ids_equal": ids_equal, "max_score_diff": val_diff}


def _spread_tenants(cfg: dict, devices) -> dict:
    """Four PageRank tenants behind one ServeTier, ``placement="spread"``:
    each tenant's state on its own device, ranks against the reference."""
    import jax

    from reflow_tpu.executors import get_executor
    from reflow_tpu.scheduler import DirtyScheduler
    from reflow_tpu.serve import (APPLIED, CoalesceWindow, GraphConfig,
                                  ServeTier)
    from reflow_tpu.workloads import pagerank

    n, e, tol, k = cfg["nodes"], cfg["edges"], cfg["tol"], cfg["window_ticks"]
    bound = tol / (1.0 - pagerank.DAMPING)
    n_churn = 2 * max(1, int(cfg["churn"] * e))
    window = CoalesceWindow(max_rows=n_churn, max_ticks=k,
                            max_latency_s=0.005)
    tier = ServeTier(max_bytes=1 << 30, pump_threads=len(devices))
    tenants = []
    try:
        for i in range(len(devices)):
            pr = pagerank.build_graph(n, tol=tol, arena_capacity=(
                pagerank.churn_arena_capacity(e, cfg["churn"])))
            web = pagerank.WebGraph.random(n, e, seed=7 + i)
            init = web.initial_batch()
            churn = [web.churn(cfg["churn"]).padded(n_churn)
                     for _ in range(2 * k)]
            sched = DirtyScheduler(pr.graph, get_executor("tpu"))
            h = tier.register(f"pr{i}", sched, GraphConfig(
                window=window, placement="spread"))
            tenants.append((h, sched, pr, web, init, churn))
        # load every tenant at once (the placed builds compile side by
        # side on the pump pool), then churn them together
        t0 = time.perf_counter()
        loads = [(h, h.submit(pr.teleport, pagerank.teleport_batch(n)),
                  h.submit(pr.edges, init))
                 for h, sched, pr, web, init, churn in tenants]
        for h, t_tp, t_e in loads:
            h.flush()
            require(t_tp.result(900).status == APPLIED
                    and t_e.result(900).status == APPLIED,
                    f"{h.name}: load not applied")
        load_s = time.perf_counter() - t0
        t0 = time.perf_counter()
        tickets = [(h, h.submit(pr.edges, b))
                   for j in range(2 * k)
                   for h, sched, pr, web, init, churn in tenants
                   for b in [churn[j]]]
        for h, *_ in tenants:
            h.flush()
        for _h, sched, *_ in tenants:
            jax.block_until_ready(sched.executor.states)
        churn_s = time.perf_counter() - t0
        require(all(t.result(600).status == APPLIED for _h, t in tickets),
                "a tenant's churn ticket was not applied")
        placed = {}
        for i, (h, sched, pr, web, init, churn) in enumerate(tenants):
            ex = sched.executor
            require_resident(ex.states, [ex.device], f"tenant {h.name}")
            err = rel_err(pagerank.ranks_to_array(
                sched.read_table(pr.new_rank), n),
                pagerank.reference_ranks(web))
            require(err < bound, f"{h.name}: ranks off by {err:.3e}")
            placed[h.name] = {
                "device": str(ex.device), "platform": ex.device.platform,
                "max_rel_err": err, "engine": ex.fixpoint_engine,
                "megatick_windows": sched.megatick_windows,
                "megatick_fallbacks": sched.megatick_fallbacks}
        used = [p["device"] for p in placed.values()]
        require(len(set(used)) == len(devices),
                f"tenants share a device: {used}")
        require(set(used) == {str(d) for d in devices},
                f"tenants on {used}, devices are {list(map(str, devices))}")
        out = {"tenants": placed, "load_s": round(load_s, 2),
               "churn_s": round(churn_s, 3),
               "device_collisions": tier.device_collisions}
        say(f"spread tenants: {json.dumps(out)}")
        return out
    finally:
        tier.close()


def _mesh(args, n: int = 4):
    """(cfg, mesh, devices, header) for the 4-chip checks."""
    dev = _device_line(args.tiny)
    import jax

    from reflow_tpu.parallel import make_mesh

    require(len(jax.devices()) >= n,
            f"needs {n} devices, JAX has {len(jax.devices())}")
    mesh = make_mesh(n)
    devices = list(mesh.devices.ravel())
    say(f"mesh {[str(d) for d in devices]}")
    return (TINY if args.tiny else FULL, mesh, devices,
            {"platform": dev.platform, "kind": dev.device_kind,
             "devices": [str(d) for d in devices]})


def check_sharded(args) -> dict:
    cfg, mesh, devices, out = _mesh(args)
    from reflow_tpu.parallel import make_mesh
    from reflow_tpu.parallel.shard import ShardedTpuExecutor

    n = len(devices)
    root = tempfile.mkdtemp(prefix="reflow-chip-mesh-")
    try:
        return {**out, **pagerank_phase(
            cfg, devices, root, shards=n,
            make_executor=lambda: ShardedTpuExecutor(make_mesh(n)))}
    finally:
        shutil.rmtree(root, ignore_errors=True)


def check_spread(args) -> dict:
    cfg, _mesh_, devices, out = _mesh(args)
    return {**out, **_spread_tenants(cfg, devices)}


def check_ring(args) -> dict:
    cfg, mesh, _devices, out = _mesh(args)
    return {**out, **_knn_ring(cfg, mesh)}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    checks = {"barrier": check_barrier, "proc": check_proc,
              "sharded": check_sharded, "spread": check_spread,
              "ring": check_ring}
    ap.add_argument("check", choices=tuple(checks))
    ap.add_argument("--tiny", action="store_true",
                    help="4-chip checks only: small CPU form "
                         "(JAX_PLATFORMS=cpu)")
    args = ap.parse_args(argv)
    out = checks[args.check](args)
    print(json.dumps({"check": args.check, **out, "claim": None}),
          flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
