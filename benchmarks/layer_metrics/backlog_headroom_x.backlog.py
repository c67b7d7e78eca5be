"""How far the cell is from running dry: the batches the mix mints for
the window (``batches`` of the traffic file, per lane as
``traffic_plan.per_lane`` counts them) over the batches whose window
completed on the device inside the window, the same join and the same
completions ``rows_per_s`` is taken over (``measure.done_inside``). A lane that has sent its last
minted batch stops, the rate then ends at the last completion and
``rate_edge_s`` makes the run not ``correct``: so a program this many
times faster than the one measured would empty the backlog, and a
predicted gain above this number needs a ``benchmark`` step first.
``queue_depth_p10.backlog`` beside it says whether the generator kept up
so far; this says how long it can. Counts only: it reads the same in the
CPU rehearsal. A paced mix mints by its rate, not by a backlog: None."""

import measure
import traffic_plan as tp


def read(run):
    t = run.traffic
    if t["arrivals"] == "poisson":
        return None
    done = sum(w["n_batches"] for w in measure.done_inside(
        run.joined, run.t_open, run.t_close))
    if not done:
        return None
    return tp.per_lane(t, run.seconds) * t["producers"] / done
