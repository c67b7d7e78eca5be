"""Slots of its keyed tables the maximum wrote per tick inside the
window: by how much the node's third device counter (``blocks``: trips
of the loop that merges, writes back and emits a block of the tick's
touched auctions at a time) moved between the same two ``window_device``
spans the other counters are read from, times the slots of a block, over
the ticks. A block is an eighth of the reduce's delta capacity, and that
delta is the join's output, twice the tick's row bucket: the program's
rule (``lowerings._block_slots``), written down here from the traffic
file. ~300 distinct auctions a tick fit one block of 1 024; a program
that writes its tables once over the whole capacity would read 8 192
and has no such counter. The counter is int32 and wraps: the difference
is taken modulo 2^32. Counts only. None on a program whose maximum keeps
two counters."""

import nexmark_model
from common import bucket_capacity


def read(run):
    m = nexmark_model.in_window(run)
    if m is None:
        return None
    at = {t: c[nexmark_model.MAXIMUM] for t, _, c
          in nexmark_model._counted(run)}
    a, b = at[m["t0"]], at[m["t1"]]
    if len(a) < 3:
        return None
    cap = 2 * bucket_capacity(run.traffic["coalesce"]["max_rows"])
    slots = cap // 8 if cap >= 256 and cap % 8 == 0 else cap
    return float((b[2] - a[2]) % (1 << 32)) * slots / m["ticks"]
