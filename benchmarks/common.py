"""Small shared types of the benchmark's own files. No JAX."""

from __future__ import annotations

import threading
import time
from typing import Any, NamedTuple

import numpy as np

from reflow_tpu.delta import DeltaBatch

#: the one clock of every timestamp the benchmark takes, in both
#: processes: CLOCK_MONOTONIC. The program's spans use perf_counter,
#: which on Linux is the same clock (run.py checks that it is).
now = time.monotonic


class Heartbeat:
    """A thread that sleeps 5 ms at a time and keeps the longest it
    overslept, with when: whether a process (or the whole machine) stood
    still inside the window. Printed with every run, judged by nothing."""

    def __init__(self):
        self.worst_s, self.worst_at = 0.0, None
        self._stop = threading.Event()
        self._thread = threading.Thread(target=self._beat, daemon=True,
                                        name="bench-heartbeat")
        self._thread.start()

    def _beat(self) -> None:
        last = now()
        while not self._stop.wait(0.005):
            t = now()
            if t - last - 0.005 > self.worst_s:
                self.worst_s, self.worst_at = t - last - 0.005, last
            last = t

    def stop(self) -> dict:
        self._stop.set()
        self._thread.join(timeout=5)
        return {"worst_s": self.worst_s, "worst_at": self.worst_at}


class Minted(NamedTuple):
    """One batch of traffic as the generator mints it from the seed."""

    delta: DeltaBatch   # what is submitted (padded where the mix pads)
    rows: int           # real rows: not padding, not weight 0
    ref: Any            # what the reference needs to fold this batch in


class Check(NamedTuple):
    """One number compared and the limit it is held to."""

    name: str
    value: float
    limit: float
    ok: bool

    def line(self) -> str:
        return (f"check {self.name}: {self.value!r} "
                f"(limit {self.limit!r}) -> {'ok' if self.ok else 'FAIL'}")


def bucket_capacity(n: int, floor: int = 64) -> int:
    """Next power of two >= n, at least ``floor``: the capacity buckets
    the executor pads ingress batches to. Copied so that the shapes a
    traffic file lists are checked against a fixed rule."""
    return floor if n <= floor else 1 << (int(n) - 1).bit_length()


def pad_batch(batch: DeltaBatch, rows: int) -> DeltaBatch:
    """Pad a host batch to ``rows`` with weight-0 rows (semantic no-ops),
    so every batch of a mix lands in one capacity bucket. Copied from
    ``DeltaBatch.padded``."""
    n = len(batch)
    if n >= rows:
        return batch
    pad = rows - n
    vals = np.zeros((pad,) + batch.values.shape[1:], batch.values.dtype)
    return DeltaBatch.concat([batch, DeltaBatch(
        np.zeros(pad, np.int64), vals, np.zeros(pad, np.int64))])
