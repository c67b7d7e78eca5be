"""Share of the traced stretch in which no operation ran on the device:
1 - union of device-op intervals / traced span, from the profiler trace."""


def read(run):
    if run.trace is None:
        return None
    return 100.0 * (1.0 - run.trace["busy_s"] / run.trace["window_s"])
