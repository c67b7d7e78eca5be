"""Distinct prices pushed out of an auction's candidate buffer per tick
(the maximum's ``evicted`` counter over the ticks): a hot auction sends
hundreds of bids through one 16-row buffer. Insert-only traffic cannot
ask for an evicted row back, so these cost sort width, not exactness."""

import nexmark_model


def read(run):
    m = nexmark_model.in_window(run)
    return None if m is None else m["evicted"] / m["ticks"]
