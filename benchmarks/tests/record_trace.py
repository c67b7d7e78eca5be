#!/usr/bin/env python3
"""How ``data/recorded.xplane.pb.gz`` was made (on one TPU v5e):

    python benchmarks/tests/record_trace.py chiprun_out/recorded

Six annotated dispatches of a small jitted program with a ``while`` in
it, 2 ms apart, traced with the options ``run.py`` uses. Prints what the
reducer reads from it; ``test_xplane.py`` pins those numbers."""

import gzip
import json
import os
import shutil
import sys
import time

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path[:0] = [os.path.dirname(HERE), os.path.dirname(os.path.dirname(HERE))]


def main(out_dir: str) -> None:
    import jax
    import jax.numpy as jnp

    import xplane

    def body(x):
        def step(c):
            i, a = c
            return i + 1, jnp.tanh(a @ a) * 0.5
        return jax.lax.while_loop(lambda c: c[0] < 8, step, (0, x))[1].sum()

    f = jax.jit(body)
    x = jnp.ones((512, 512), jnp.float32)
    f(x).block_until_ready()
    log = os.path.join(out_dir, "log")
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    jax.profiler.start_trace(log, profiler_options=opts)
    for k in range(6):
        with jax.profiler.TraceAnnotation("bench.dispatch_staged"):
            with jax.profiler.TraceAnnotation(f"reflow.window[{k}]"):
                y = f(x)
        with jax.profiler.TraceAnnotation("bench.await_device"):
            y.block_until_ready()
        time.sleep(0.002)
    jax.profiler.stop_trace()
    path = xplane.find_trace(log)
    gz = os.path.join(out_dir, "recorded.xplane.pb.gz")
    with open(path, "rb") as src, gzip.open(gz, "wb") as dst:
        shutil.copyfileobj(src, dst)
    from jax.profiler import ProfileData
    for plane in ProfileData.from_file(path).planes:
        print("plane", plane.name)
        for line in plane.lines:
            evs = list(line.events)
            print("   line", line.name, len(evs),
                  [(e.name, e.start_ns, e.duration_ns) for e in evs[:2]])
    print(jax.devices()[0].device_kind, os.path.getsize(gz), "bytes")
    print(json.dumps(xplane.reduce_trace(gz)))


if __name__ == "__main__":
    main(sys.argv[1])
