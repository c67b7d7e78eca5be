#!/usr/bin/env python3
"""How ``data/recorded_anchors.xplane.pb.gz`` and
``data/recorded_anchors.spans.json`` were made (on one TPU v5e):

    python benchmarks/tests/record_trace_anchors.py chiprun_out/recorded_anchors

``record_trace.py``'s six dispatches, each now also inside the
``reflow.clock[<perf_counter_ns>]`` annotation the program enters at a
traced window dispatch (PR 24), and around them a hand-made pump cycle
on the span clock (``perf_counter``): ``window_stage`` (a 0.3 ms sleep),
``pump_execute`` (dispatch and wait), ``pump_wait`` (a 2 ms sleep) and,
under no span at all, a 1 ms sleep. ``test_pump_spans.py`` maps the
spans onto the trace by the anchors and pins what it reads."""

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

    import pump_spans as ps
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
    spans = []

    def span(name, t0):
        spans.append({"name": name, "t0": t0, "t1": time.perf_counter(),
                      "track": "pump", "args": {}})

    jax.profiler.start_trace(log, profiler_options=opts)
    for k in range(6):
        t0 = time.perf_counter()
        time.sleep(0.0003)
        span("window_stage", t0)
        t0 = time.perf_counter()
        with jax.profiler.TraceAnnotation(f"reflow.window[{k}]"):
            with jax.profiler.TraceAnnotation(
                    f"reflow.clock[{time.perf_counter_ns()}]"):
                y = f(x)
        y.block_until_ready()
        span("pump_execute", t0)
        t0 = time.perf_counter()
        time.sleep(0.002)
        span("pump_wait", t0)
        time.sleep(0.001)                    # under no span
    jax.profiler.stop_trace()
    path = xplane.find_trace(log)
    gz = os.path.join(out_dir, "recorded_anchors.xplane.pb.gz")
    with open(path, "rb") as src, gzip.open(gz, "wb") as dst:
        shutil.copyfileobj(src, dst)
    with open(os.path.join(out_dir, "recorded_anchors.spans.json"),
              "w") as fh:
        json.dump(spans, fh)

    class Run:
        pass

    run = Run()
    run.spans = spans
    print(jax.devices()[0].device_kind, os.path.getsize(gz), "bytes")
    print(json.dumps(xplane.reduce_trace(gz)))
    print(json.dumps(ps.idle_by_span(run, gz)))


if __name__ == "__main__":
    main(sys.argv[1])
