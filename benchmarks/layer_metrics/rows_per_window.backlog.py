"""Real rows per dispatched window, mean over the windows dispatched
inside the window: how full the coalescer makes them."""


def read(run):
    wins = [w for w in run.windows_inside() if w["n_batches"]]
    return sum(w["rows"] for w in wins) / len(wins) if wins else None
