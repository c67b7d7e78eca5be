"""The one traffic generator's plan: which batches are minted, in which
order, and when each is due. Both processes run it from the same seed
(the generator to send the batches, the leader to know what was sent),
so the order here is part of the yardstick. No JAX.

A mix is a data file of parameters:

``arrivals``
    ``prefilled`` — the whole backlog is submitted while the frontend is
    paused; the window opens when it resumes (a catch-up).
    ``closed`` — every producer submits as fast as admission lets it
    (``policy="block"`` backpressure) until the window closes, and
    stands still while its link is down.
    ``poisson`` — open loop: each producer's arrivals are a seeded
    Poisson process of rate ``rate_per_s / producers``, conditioned on
    its count (``n`` uniform order statistics), so every seed offers the
    same amount of work.
``producers``        connections = lanes; a lane owns its own keys.
``batches``          batches minted in all (prefilled, closed).
``rate_per_s``       offered batches per second (poisson).
``ack_poll_s``       how often a producer polls for resolved tickets.
``coalesce``         the frontend's ``CoalesceWindow``.
``admission``        queue bound, byte budget, policy, pipeline depth.
``warm``             the program shapes the mix can produce, each
                     ``{"ticks": K, "rows": R}``: K feeds of up to R rows.
"""

from __future__ import annotations

from typing import List

import numpy as np

from common import Minted, bucket_capacity

ARRIVALS = ("prefilled", "closed", "poisson")


def per_lane(traffic: dict, seconds: float) -> int:
    """Batches each lane mints for the window."""
    p = traffic["producers"]
    if traffic["arrivals"] == "poisson":
        return max(1, round(traffic["rate_per_s"] * seconds / p))
    return max(1, traffic["batches"] // p)


def due_times(traffic: dict, seed: int, seconds: float) -> List[np.ndarray]:
    """Per lane, the offsets from the window's opening at which each
    batch is due. Zero for the mixes that do not pace."""
    p, n = traffic["producers"], per_lane(traffic, seconds)
    if traffic["arrivals"] != "poisson":
        return [np.zeros(n) for _ in range(p)]
    out = []
    for lane in range(p):
        rng = np.random.default_rng([seed, 2, lane])
        out.append(np.sort(rng.random(n)) * seconds)
    return out


def plan_warm(stream, traffic: dict) -> List[List[Minted]]:
    """Groups of batches that, submitted while the frontend is paused,
    coalesce into each listed shape: for ``{"ticks": K, "rows": R}``, K
    runs of batches whose rows total more than half of R's capacity
    bucket and at most R. Batches are taken round-robin from the lanes,
    in order; one that does not fit a run is kept for a later, larger
    one. What is left over forms a last group: every minted batch of a
    lane's prefix is applied, or later edits of the same keys would
    retract rows that were never inserted."""
    lanes = traffic["producers"]
    max_rows = traffic["coalesce"]["max_rows"]
    held: List[Minted] = []
    turn = [0]

    def pull() -> Minted:
        m = stream.next(turn[0] % lanes)
        turn[0] += 1
        return m

    groups = []
    for shape in traffic["warm"]:
        k, r = int(shape["ticks"]), int(shape["rows"])
        if r > max_rows or (k > 1 and r != max_rows):
            raise SystemExit(
                f"warm shape {shape}: rows may not exceed "
                f"coalesce.max_rows, and runs of several ticks need "
                f"rows == coalesce.max_rows, or they merge")
        floor = 0 if bucket_capacity(r) == bucket_capacity(1) \
            else bucket_capacity(r) // 2
        group: List[Minted] = []
        for _ in range(k):
            total = 0
            # held batches first, then fresh ones
            for m in list(held):
                if total + len(m.delta) <= r:
                    held.remove(m)
                    group.append(m)
                    total += len(m.delta)
            tries = 0
            while total <= floor:
                m = pull()
                tries += 1
                if total + len(m.delta) <= r:
                    group.append(m)
                    total += len(m.delta)
                else:
                    held.append(m)
                if tries > 100_000:
                    raise SystemExit(f"cannot fill warm shape {shape}")
        groups.append(group)
    if held:
        groups.append(held)
    return groups


def mint_traffic(stream, traffic: dict, seconds: float
                 ) -> List[List[Minted]]:
    """The window's batches, per lane; minted round-robin over the lanes
    so both processes intern keys in the same order."""
    lanes, n = traffic["producers"], per_lane(traffic, seconds)
    out: List[List[Minted]] = [[] for _ in range(lanes)]
    for _ in range(n):
        for lane in range(lanes):
            out[lane].append(stream.next(lane))
    return out


def batch_id(lane: int, seq: int) -> str:
    return f"L{lane}-{seq}"
