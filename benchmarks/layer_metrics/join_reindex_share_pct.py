"""The reindex programs' share of the device's busy time in the traced
stretch: the busy seconds inside the ``join_reindex`` spans that lie
whole in it (``tpch_model.reindex_device``) over all busy seconds. 0
where the stretch holds no whole reindex."""

import tpch_model


def read(run):
    re = tpch_model.reindex_device(run)
    if re is None or not any(
            s["name"] == tpch_model.REINDEX for s in run.spans):
        return None
    return 100.0 * re["busy_s"] / run.trace["busy_s"]
