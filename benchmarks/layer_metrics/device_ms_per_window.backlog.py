"""Device-operation time in the traced stretch per window dispatched in
it (the benchmark's ``bench.dispatch_staged`` annotations count them)."""


def read(run):
    if run.trace is None:
        return None
    n = run.trace["annotation_counts"].get("bench.dispatch_staged", 0)
    return 1e3 * run.trace["busy_s"] / n if n else None
