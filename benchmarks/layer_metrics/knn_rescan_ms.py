"""Device time of one corpus rescan: the device's busy time in the traced
stretch (profiler trace) over the ticks that rescanned in it (the
KnnIndex node's own device counters, read from the ``window_device``
spans). In a cell whose every tick rescans, 512 rows / this is the rate
the device allows."""

import knn_model


def read(run):
    return knn_model.rescan_ms(run)
