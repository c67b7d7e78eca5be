"""Share of the pump's working time in which its thread was not running:
sum(dur - cpu_s) / sum(dur) over the pump thread's outermost spans
(nested ones are not counted twice) other than the umbrella ``window``
and ``pump_wait`` (idle by design). Off the CPU means waiting: for the
interpreter lock behind the RPC handler threads, for a lock, or for the
device inside a slot write."""

import pump_spans as ps


def read(run):
    spans = [s for s in ps.outermost(ps.pump_spans(run))
             if s["name"] != "pump_wait" and "cpu_s" in s["args"]]
    wall = sum(s["t1"] - s["t0"] for s in spans)
    if wall <= 0:
        return None
    cpu = sum(min(s["args"]["cpu_s"], s["t1"] - s["t0"]) for s in spans)
    by = {}
    for s in spans:
        d = by.setdefault(s["name"], [0.0, 0.0])
        d[0] += s["t1"] - s["t0"]
        d[1] += s["args"]["cpu_s"]
    ps.say("pump time by outermost span, wall s / cpu s: " + ", ".join(
        f"{k} {v[0]:.3f}/{v[1]:.3f}" for k, v in sorted(
            by.items(), key=lambda kv: -kv[1][0])))
    return 100.0 * (1.0 - cpu / wall)
