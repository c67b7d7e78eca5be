"""Share of the pump thread's wall, from its first to its last span
inside the window, that lies under no span of its own. The umbrella
``window`` (all of ``_run_window``) is left out, or it would cover
everything. The program tiles the private pump's wall by construction
(``pump_turn`` books every stretch between two named spans), so this
reads ~0 unless the ring dropped spans or a path records past the pump
clock."""

import pump_spans as ps
import xplane


def read(run):
    spans = ps.pump_spans(run)
    if not spans:
        return None
    wall = max(s["t1"] for s in spans) - spans[0]["t0"]
    if wall <= 0:
        return None
    covered = sum(e - s for s, e in xplane.union(
        [(s["t0"], s["t1"]) for s in spans]))
    return 100.0 * (1.0 - covered / wall)
