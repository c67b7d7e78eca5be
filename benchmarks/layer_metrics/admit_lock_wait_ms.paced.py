"""How long a producer stood at the frontend's lock before its admission
could begin: the ticket sub-span ``admit_lock_wait`` (PR 24),
``submit()`` entered -> ``IngestFrontend._lock`` acquired. It lies
inside the stage ``admission``. Median over the tickets of batches due
inside the window."""

from measure import percentile


def read(run):
    ms = run.stage_ms("admit_lock_wait")
    return percentile(ms, 50) if ms else None
