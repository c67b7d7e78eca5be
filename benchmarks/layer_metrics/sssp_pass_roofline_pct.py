"""A pass's share of the memory roofline: the bytes no implementation
can avoid (``sssp_model.floor_bytes_per_pass``: the arena row of every
edge relaxed read once, every improved distance written; counted from
below from the program's counters over the traced stretch) at the HBM
peak, over the device time a pass took (``sssp_pass_ms``). The swept
arena, the sorts and the candidate buffers are in the denominator only,
so this reads low: it is what a join that follows its frontier and a
merge sized by its keys would raise. The PR that brought it adds no
kernel; this is the share the fixpoint as a whole is owed."""

import sssp_model


def read(run):
    return sssp_model.roofline_pct(run)
