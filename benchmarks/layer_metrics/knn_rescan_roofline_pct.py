"""A rescan's roofline share: the longer of its arithmetic
(2 x queries x slots x dim FLOP at the MXU's bf16 peak) and its bytes
(the int8 table, the live mask, the queries, once, at the HBM peak),
over the device time a rescan took (``knn_rescan_ms``). At 256 x 2^20 x
768 on a v5e the arithmetic is the longer: 403 GFLOP / 197 TFLOP/s =
2.05 ms. Everything else a rescanning tick does on the device (the
fold, the kernel, the emission) is in the denominator: this is the share
of the tick, not of the matmul."""

import knn_model


def read(run):
    ms = knn_model.rescan_ms(run)
    if ms is None:
        return None
    floor = knn_model.rescan_floor_s(run.cfg, knn_model.device_kind(run))
    return 100.0 * 1e3 * floor / ms
