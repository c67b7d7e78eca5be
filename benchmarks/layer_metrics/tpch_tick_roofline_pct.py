"""A tick's roofline share: the bytes it cannot avoid
(``tpch_model.tick_floor_bytes``: its rows in, the arena rows appended
with their index entries, one matched row a pair gathered, the sum's
touched slots read and written; counted from the program's counters
over the traced stretch) at the HBM peak, over the device time a tick
took (``tpch_tick_ms``). The dense passes over the per-key tables and
every sort are in the denominator only, so this reads low: it says how
far those passes are from the work."""

import tpch_model


def read(run):
    ms = tpch_model.tick_ms(run)
    if ms is None:
        return None
    m = tpch_model.moved(run, *tpch_model._traced(run))
    floor = tpch_model.tick_floor_bytes(run, m)
    if floor is None:
        return None
    return 100.0 * 1e3 * floor / tpch_model.hbm_bytes_per_s(run) / ms
