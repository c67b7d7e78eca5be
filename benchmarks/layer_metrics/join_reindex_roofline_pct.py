"""A reindex's roofline share: one read and one write of the arena and
its index (``tpch_model.reindex_bytes``) at the HBM peak, over the
device time the program took, summed over the reindexes of both joins
that lie whole in the traced stretch. Where the stretch holds none, the
same over every ``join_reindex`` span of the window by the span's own
length (dispatch to the count read behind it: the device's time and a
dispatch). The program is a sort of the whole log by key and value and
a dozen gathers and scatters of its length, so this reads small."""

import tpch_model


def read(run):
    re = tpch_model.reindex_device(run)
    if re is None:
        return None
    by = re["by_node"]
    if not by:
        by = {}
        for s in tpch_model.reindexes(run, run.t_open, run.t_close):
            by.setdefault(s["args"]["node"], []).append(s["t1"] - s["t0"])
    secs = sum(sum(v) for v in by.values())
    if secs <= 0:
        return None
    floor = sum(len(v) * tpch_model.reindex_bytes(run.cfg, n)
                for n, v in by.items()) / tpch_model.hbm_bytes_per_s(run)
    return 100.0 * floor / secs
