"""The Pallas top-k kernel against the memory roofline: the bytes its
calls must touch (the ``[queries, k]`` carry's values and the
``[queries, scan_chunk]`` float32 score chunk in, ``[queries, k]``
values and ids out, a call; the carry's ids in, 0.2 % of it, are not
counted: ``knn_model.topk_call_bytes``) at the HBM peak, over the time
the trace shows for them. The kernel does no MXU work and the
VPU has no published peak, so this is a share of the memory roofline
only, and small: k sweeps of compare-and-select over a block that is
read from HBM once. Only the rescan's calls are priced; if a run also
made calls of another width they are priced the same, which can only
lower the share in this cell (every tick rescans: 128 calls a tick)."""

import knn_model


def read(run):
    got = knn_model.kernel_time(run)
    if got is None:
        return None
    secs, calls = got
    bw = knn_model.peaks(knn_model.device_kind(run))["hbm_bytes_per_s"]
    return 100.0 * calls * knn_model.topk_call_bytes(run.cfg) / bw / secs
