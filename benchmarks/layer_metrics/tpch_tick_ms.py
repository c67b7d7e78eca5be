"""Device time of one TPC-H Q3 tick (one refresh pair through both
joins and the sum): the device's busy time in the traced stretch
(profiler trace) less the reindex programs' (the ``join_reindex`` spans
laid onto the trace), over the ticks of the windows the device finished
in it (the ``window_device`` spans that carry the joins' counters)."""

import tpch_model


def read(run):
    return tpch_model.tick_ms(run)
