"""How long a ticket waited to be *wired* to the durable watermark: the
ticket sub-span ``wire_wait`` (PR 24), dispatch returned -> the window's
block handed to ``wal.when_durable`` (at depth 2 that happens at the
window's retire). It lies inside the stage ``fsync``
(``durable_wait_ms.paced``); ``fsync - wire_wait`` is the exposed disk
wait. Median over the tickets of batches due inside the window."""

from measure import percentile


def read(run):
    ms = run.stage_ms("wire_wait")
    return percentile(ms, 50) if ms else None
