"""How long a ticket waited to be *wired* to the durable watermark: the
ticket sub-span ``wire_wait`` (PR 24), dispatch returned -> the window's
block handed to ``wal.when_durable``. Until PR 25 that happened at the
window's retire, a whole pump cycle later (26.5 ms); since then the
block is wired when ``dispatch_staged`` returns and this reads ~0.05 ms.
It stays as the guard on that: a change that puts the retire, or
anything else, back between a dispatch and its durability point shows
here first. It lies inside the stage ``fsync``
(``durable_wait_ms.paced``); ``fsync - wire_wait`` is the exposed disk
wait. Median over the tickets of batches due inside the window."""

from measure import percentile


def read(run):
    ms = run.stage_ms("wire_wait")
    return percentile(ms, 50) if ms else None
