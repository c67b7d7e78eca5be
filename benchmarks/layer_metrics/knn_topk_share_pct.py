"""The Pallas top-k kernel's share of the device's busy time in the
traced stretch, by the kernel's operation name in the profiler trace."""

import knn_model


def read(run):
    got = knn_model.kernel_time(run)
    if got is None:
        return None
    return 100.0 * got[0] / run.trace["busy_s"]
