"""Share of the device's idle seconds, in the run's own profiler trace,
that lie under no span of the pump thread (a ``pump_wait`` is an
explanation: the pump had no input). The program's spans are mapped onto
the trace's clock by the ``reflow.clock[<ns>]`` annotations it enters at
every traced dispatch (PR 24); with fewer than two of them there is no
mapping and no number. Prints the idle seconds by span and the anchors'
offset and spread."""

import pump_spans as ps


def read(run):
    if run.trace is None:
        return None
    path = ps.own_trace_path()
    if path is None:
        return None
    got = ps.idle_by_span(run, path)
    if got is None or got["idle_s"] <= 0:
        return None
    off = got["offset"]
    ps.say(f"clock anchors {off['n']}: trace - span clock median "
           f"{off['median_s']:.9f} s, p10-p90 spread "
           f"{1e3 * off['spread_s']:.4f} ms")
    ps.say(f"idle {got['idle_s']:.4f} s of {got['stretch_s']:.4f} s by "
           f"pump span: " + ", ".join(
               f"{k} {v:.4f}" for k, v in got["by_span"].items()))
    return 100.0 * got["by_span"].get("unexplained", 0.0) / got["idle_s"]
