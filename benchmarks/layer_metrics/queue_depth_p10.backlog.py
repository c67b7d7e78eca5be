"""Batches still queued in the frontend right after each window was
taken, 10th percentile over the windows dispatched inside the window.
The closed-loop cell is only valid while this stays above 0: at 0 the
pump ran dry and the generator, not the system, set the rate.

From the frontend's ``admitted`` counter read at each dispatch and the
batches each window held: depth_k = admitted_k - admitted_last + the
batches of the windows after k (every admitted batch is dispatched by
the last window)."""

from measure import percentile


def read(run):
    wins = run.joined.windows
    if not wins or any(w.get("admitted") is None for w in wins):
        return None
    last = wins[-1]["admitted"]
    after, depth = 0, {}
    for w in reversed(wins):
        depth[w["ix"]] = w["admitted"] - last + after
        after += w["n_batches"]
    inside = [depth[w["ix"]] for w in run.windows_inside()]
    return percentile(inside, 10) if inside else None
