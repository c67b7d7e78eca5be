"""Rows the two joins touched per live pair, from their own counters
(``nexmark_model.swept_rows``: the delta rows' lookups, the pair slots
every trip of a probe's chain walk passes over, the appended rows) over
the pairs they emitted inside the window. Around 5 when a tick follows
its delta; it rises with ``probe_steps``, the segments of the hottest
late key. The arena sweep this deployment's sizes would otherwise pay is
2 x 2^27 rows a tick, ~ 7e4 a pair, and a join that swept would carry no
counters: the metric would be missing, not small."""

import nexmark_model


def read(run):
    m = nexmark_model.in_window(run)
    if m is None or m["pairs"] <= 0:
        return None
    return nexmark_model.swept_rows(run.cfg, run.traffic, m) / m["pairs"]
