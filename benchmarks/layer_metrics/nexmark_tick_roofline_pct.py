"""A tick's roofline share: the bytes it cannot avoid
(``nexmark_model.floor_bytes_per_tick``: event rows in, one matched row
a pair gathered, touched candidate buffers read and written, appended
arena rows and index entries written; counted from the program's
counters over the traced stretch) at the HBM peak, over the device time
a tick took (``nexmark_tick_ms``). Every sort pass and intermediate row
is in the denominator only, so this reads low: the tick is sorts and
small gathers, not a stream."""

import nexmark_model


def read(run):
    ms = nexmark_model.tick_ms(run)
    if ms is None:
        return None
    m = nexmark_model.moved(
        run, run.t_open + 0.6 * (run.t_close - run.t_open), run.t_close)
    return 100.0 * 1e3 * nexmark_model.floor_s(run, m) / ms
