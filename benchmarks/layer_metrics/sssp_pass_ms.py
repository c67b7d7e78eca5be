"""Device time of one pass of the SSSP fixpoint: the device's busy time
in the traced stretch (profiler trace) over the passes of the windows
the device finished in it (the ``window_device`` spans that carry the
fixpoint program's counters). A tick's phase A counts as a pass and
sweeps nothing, so a loop pass costs ``passes / sweeps`` of this."""

import sssp_model


def read(run):
    m = sssp_model.traced(run)
    return None if m is None else m["pass_ms"]
