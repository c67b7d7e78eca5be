"""Device time of one NEXmark tick (one block of events through both
queries): the device's busy time in the traced stretch (profiler trace)
over the ticks of the windows the device finished in it (the
``window_device`` spans that carry the joins' counters). ``batch_events``
/ this is the rate the device allows."""

import nexmark_model


def read(run):
    return nexmark_model.tick_ms(run)
