"""nexmark-q3q4: NEXmark's two unwindowed joins over the 100 M-event
suite's state. Data, reference and comparison; the graph is
``reflow_tpu.workloads.nexmark.build_graph``'s.

The generator is Apache Beam's (``sdks/java/testing/nexmark``:
``GeneratorConfig``, ``PersonGenerator``, ``AuctionGenerator``,
``BidGenerator``, ``PriceGenerator``), which ``nexmark-flink`` ports
and whose ``datagen`` table, one stream of tagged events with
``person`` / ``auction`` / ``bid`` as filtered views, is what this file
sends. Written down from memory (no network here), constant by constant;
``assumed`` in the ``.json`` beside this lists what I set myself:

- of every 50 consecutive event numbers the first is a person, the next
  3 are auctions, the other 46 bids; ids count up from 1 000, so event
  ``n`` makes person ``1000 + n // 50`` or auction ``1000 + 3 * (n //
  50) + (n % 50 - 1)``;
- a bid goes, with probability ``1 - 1/hotAuctionRatio`` (= 1/2), to the
  *hot* auction, the first of the current run of 100 auction ids
  (``HOT_AUCTION_RATIO`` = 100), else to one drawn evenly from the last
  100 auctions (``numInFlightAuctions``) and the 10 ids after them
  (``AUCTION_ID_LEAD``: a bid may name an auction that does not exist
  yet). A seller is the hot seller (first of the current run of 100
  person ids, probability 3/4) or drawn from the last 1 000 people
  (``numActivePeople``) and the 10 after; a bidder likewise, the hot
  one being the second of the run;
- ``price = round(10 ** (6 u) * 100)`` cents, ``u`` uniform: 100 to
  10^8, so past 2^24; an auction's ``reserve`` is its ``initialBid``
  plus another price;
- ``dateTime`` is the event number at the suite's 10 M events/s
  (``nexmark.workload.suite.100m.tps``), in whole milliseconds from the
  base time: ``n // 10 000``. ``expires = dateTime + 1 + U[0, max(2 h,
  1))`` with ``h`` the milliseconds until 100 more auctions will have
  been generated (1 666 events: 0 or 1 ms), the generator's rule;
- a person's name is one of 11 x 9 first and last names, the city one
  of 10, the state one of 6 (AZ, CA, ID, OR, WA, WY); they ride as
  codes;
- a record is as wide as the source's: 200 (person), 500 (auction),
  100 (bid) bytes (``avgPersonByteSize`` / ``avgAuctionByteSize`` /
  ``avgBidByteSize``, to which the generator itself fills ``extra``
  with random characters). The engine's rows are fixed-width, so a row
  is 100 bytes (``int32[25]``) and a record is 2, 5 or 1 consecutive
  rows (``records``): the first has the type, the numeric columns and
  17 opaque words, a further one the tag ``CONT`` and 24 opaque words,
  hashes of (seed, event, row, word) in the free text's place
  (``emailAddress``, ``creditCard``, ``itemName``, ``description``,
  ``extra``: neither query reads them). 63 rows = 6 300 bytes of every
  50 events, as at the source; nothing is padded and nothing left out.
  ``Minted.rows`` counts events, so ``rows_per_s`` is events a second.

Event ``n``'s draws are a hash of ``(seed, n, draw)``: any stretch of
the sequence is minted alone, which is what lets eight lanes and the
reference each make their own (the source's generator is splittable the
same way). Lane ``l``'s ``j``-th batch is block ``j * lanes + l`` of
``batch_events`` consecutive events after the history (3 250 = 65 x 50:
65 people, 195 auctions, 2 990 bids, 4 095 rows wherever the block
starts): the lanes move
through the sequence together, and since they are independent
connections a bid can be applied before its auction and an auction
before its seller.
"""

from __future__ import annotations

from types import SimpleNamespace

import numpy as np

from common import Check, Minted
from reflow_tpu.delta import DeltaBatch

PERSON, AUCTION, BID, CONT = 0, 1, 2, 3
COLS, FIELDS = 25, 8                  # a row's words; its numeric columns
RECORD_ROWS = np.array([2, 5, 1])     # rows a person / auction / bid is
FIRST_ID = 1000
FIRST_CATEGORY, CATEGORIES = 10, 5
HOT_RUN = 100            # HOT_AUCTION_RATIO, HOT_SELLER_RATIO, HOT_BIDDER_RATIO
ID_LEAD = 10             # AUCTION_ID_LEAD, PERSON_ID_LEAD
NAMES, CITIES, STATES = 11 * 9, 10, 6
Q3_STATES = (3, 2, 1)    # OR, ID, CA in (AZ, CA, ID, OR, WA, WY)

_M1 = np.uint64(0xBF58476D1CE4E5B9)
_M2 = np.uint64(0x94D049BB133111EB)
_GOLD = np.uint64(0x9E3779B97F4A7C15)


def _mix(x: np.ndarray) -> np.ndarray:
    """splitmix64's finalizer over uint64 (wrapping arithmetic)."""
    x = (x ^ (x >> np.uint64(30))) * _M1
    x = (x ^ (x >> np.uint64(27))) * _M2
    return x ^ (x >> np.uint64(31))


class _Draws:
    """Event ``n``'s ``k``-th random draw, a function of (seed, n, k)."""

    def __init__(self, seed: int, n: np.ndarray):
        with np.errstate(over="ignore"):
            self.base = _mix(np.uint64(seed) * _GOLD
                             + n.astype(np.uint64) * np.uint64(16))

    def bits(self, k: int) -> np.ndarray:
        with np.errstate(over="ignore"):
            return _mix(self.base + np.uint64(k) * _GOLD)

    def below(self, k: int, m) -> np.ndarray:
        """Uniform in ``[0, m)`` (``m`` far below 2^64: the modulo's
        bias is under 2^-40)."""
        return (self.bits(k) % np.asarray(m, np.uint64)).astype(np.int64)

    def unit(self, k: int) -> np.ndarray:
        return (self.bits(k) >> np.uint64(11)).astype(np.float64) * 2.0**-53


def _price(u: np.ndarray) -> np.ndarray:
    return np.rint(10.0 ** (u * 6.0) * 100.0).astype(np.int64)


_CHUNK = 1 << 16    # events minted per pass: the working set stays in cache


def events(cfg: dict, seed: int, first: int, n: int) -> np.ndarray:
    """The numeric columns of events ``first .. first + n`` of the
    sequence: ``int32[n, 8]`` (``reflow_tpu/workloads/nexmark.py``)."""
    if n > _CHUNK:
        return np.concatenate([
            events(cfg, seed, a, min(_CHUNK, first + n - a))
            for a in range(first, first + n, _CHUNK)])
    g = cfg["generator"]
    num = np.arange(first, first + n, dtype=np.int64)
    d = _Draws(seed, num)
    epoch, off = num // 50, num % 50
    ms = num // g["events_per_ms"]
    out = np.zeros((n, 8), np.int64)
    is_p, is_b = off < 1, off >= 4
    is_a = ~is_p & ~is_b
    out[:, 0] = np.where(is_p, PERSON, np.where(is_a, AUCTION, BID))
    last_person = epoch                       # lastBase0PersonId
    # lastBase0AuctionId: the newest auction at or before this event
    last_auction = np.where(is_p, epoch * 3 - 1,
                            np.where(is_a, epoch * 3 + off - 1,
                                     epoch * 3 + 2))

    def person_near(k):                       # nextBase0PersonId
        people = last_person + 1
        active = np.minimum(people, g["active_people"])
        return people - active + d.below(k, active + ID_LEAD)

    # person
    out[is_p, 1] = (FIRST_ID + epoch)[is_p]
    out[is_p, 2] = d.below(0, NAMES)[is_p]
    out[is_p, 3] = d.below(1, CITIES)[is_p]
    out[is_p, 4] = d.below(2, STATES)[is_p]
    out[is_p, 5] = ms[is_p]
    # auction
    hot_seller = d.below(0, g["hot_sellers_ratio"]) > 0
    seller = np.where(hot_seller, last_person // HOT_RUN * HOT_RUN,
                      person_near(1))
    initial = _price(d.unit(3))
    horizon = (num + g["in_flight_auctions"] * 50 // 3) \
        // g["events_per_ms"] - ms
    length = 1 + d.below(5, np.maximum(horizon * 2, 1))
    out[is_a, 1] = (FIRST_ID + epoch * 3 + off - 1)[is_a]
    out[is_a, 2] = (FIRST_ID + seller)[is_a]
    out[is_a, 3] = (FIRST_CATEGORY + d.below(2, CATEGORIES))[is_a]
    out[is_a, 4] = initial[is_a]
    out[is_a, 5] = (initial + _price(d.unit(4)))[is_a]
    out[is_a, 6] = ms[is_a]
    out[is_a, 7] = (ms + length)[is_a]
    # bid
    hot_auction = d.below(0, g["hot_auction_ratio"]) > 0
    lo = np.maximum(last_auction - g["in_flight_auctions"], 0)
    near = lo + d.below(1, last_auction - lo + 1 + ID_LEAD)
    hot_bidder = d.below(2, g["hot_bidders_ratio"]) > 0
    bidder = np.where(hot_bidder, last_person // HOT_RUN * HOT_RUN + 1,
                      person_near(3))
    out[is_b, 1] = (FIRST_ID + np.where(
        hot_auction, last_auction // HOT_RUN * HOT_RUN, near))[is_b]
    out[is_b, 2] = (FIRST_ID + bidder)[is_b]
    out[is_b, 3] = _price(d.unit(4))[is_b]
    out[is_b, 4] = ms[is_b]
    return out.astype(np.int32)


def records(cfg: dict, seed: int, first: int, n: int):
    """Events ``first .. first + n`` as they are sent: ``(rows int32[m,
    25], keys int64[m])``, every record at the source's width as 2, 5 or
    1 rows of 100 bytes, a row's key its event's number."""
    if n > _CHUNK:
        parts = [records(cfg, seed, a, min(_CHUNK, first + n - a))
                 for a in range(first, first + n, _CHUNK)]
        return (np.concatenate([p[0] for p in parts]),
                np.concatenate([p[1] for p in parts]))
    ev = events(cfg, seed, first, n)
    per = RECORD_ROWS[ev[:, 0]]
    at = np.cumsum(per) - per                 # an event's first row
    m = int(per.sum())
    of = np.repeat(np.arange(n), per)         # row -> its event
    part = np.arange(m) - at[of]              # row -> which of its record
    keys = first + of.astype(np.int64)
    # the opaque words, two to a hash of (seed, event, row of it, pair):
    # one multiply-and-fold each, since nothing reads them but the wire
    with np.errstate(over="ignore"):
        base = _mix(_Draws(seed, keys).base
                    + (part + 1).astype(np.uint64) * _M2)
        words = base[:, None] * (np.arange(1, 13, dtype=np.uint64) * _GOLD
                                 | np.uint64(1))
        words ^= words >> np.uint64(29)
    rows = np.empty((m, COLS), np.int32)
    rows[:, 0] = CONT
    rows[:, 1:] = words.view(np.int32)
    rows[at, :FIELDS] = ev
    return rows, keys


def _batch(cfg: dict, seed: int, first: int, n: int) -> DeltaBatch:
    rows, keys = records(cfg, seed, first, n)
    return DeltaBatch(keys, rows, np.ones(len(rows), np.int64))


class _History:
    """The load ticks, minted as they are asked for: the load generator
    also builds the stream and never looks at them, and 2^25 events are
    4.9 GB nobody needs to hold."""

    def __init__(self, cfg, seed):
        self.cfg, self.seed = cfg, seed
        self.n = -(-cfg["history_events"] // cfg["load_events_per_tick"])
        self._rows = []           # rows of each tick, once it was minted

    def __len__(self):
        return self.n

    def __iter__(self):
        per, end = (self.cfg["load_events_per_tick"],
                    self.cfg["history_events"])
        for i in range(self.n):
            name = f"load/history/{i}"
            if len(self._rows) == self.n:
                # a later pass (the leader counts the rows it loaded,
                # for its log) gets every tick's length and no rows
                yield [("events", range(self._rows[i]), name)]
                continue
            a = i * per
            batch = _batch(self.cfg, self.seed, a, min(a + per, end) - a)
            if len(self._rows) == i:
                self._rows.append(len(batch))
            yield [("events", batch, name)]


class Stream:
    """The event sequence, dealt in blocks. NumPy only."""

    source = "events"

    def __init__(self, cfg: dict, seed: int, lanes: int):
        self.cfg, self.seed, self.lanes = cfg, seed, lanes
        self._sent = [0] * lanes

    def load(self):
        return _History(self.cfg, self.seed)

    def next(self, lane: int) -> Minted:
        n = self.cfg["batch_events"]
        block = self._sent[lane] * self.lanes + lane
        self._sent[lane] += 1
        first = self.cfg["history_events"] + block * n
        if first + n > self.cfg["events_capacity"]:
            raise ValueError("the mix mints past events_capacity: state "
                             "is sized at build for no more")
        return Minted(_batch(self.cfg, self.seed, first, n), n, (first, n))


class Reference:
    """Q3 and Q4 over every event sent, history and traffic, in NumPy:
    the events are minted again from their numbers, so the order they
    were applied in cannot matter."""

    def __init__(self, stream: Stream):
        self.s = stream
        self.ranges = [(0, stream.cfg["history_events"])]

    def apply(self, ref) -> None:
        self.ranges.append(tuple(ref))

    def _tables(self):
        """Every event sent, folded into what the two queries read:
        people, auctions, the auctions of category 10 and the bids."""
        cfg = self.s.cfg
        who = np.full((cfg["persons"], 3), -1, np.int32)
        cat = np.full(cfg["auctions"], -1, np.int32)
        t0 = np.zeros(cfg["auctions"], np.int32)
        t1 = np.zeros(cfg["auctions"], np.int32)
        a10, bids = [], []
        step = 1 << 22
        for first, n in self.ranges:
            for at in range(first, first + n, step):
                ev = events(cfg, self.s.seed, at, min(step, first + n - at))
                p = ev[ev[:, 0] == PERSON]
                a = ev[ev[:, 0] == AUCTION]
                b = ev[ev[:, 0] == BID]
                who[p[:, 1]] = p[:, 2:5]
                cat[a[:, 1]], t0[a[:, 1]], t1[a[:, 1]] = (
                    a[:, 3], a[:, 6], a[:, 7])
                a10.append(a[a[:, 3] == 10][:, 1:3])
                bids.append(b[:, [1, 3, 4]])
        return who, cat, t0, t1, np.concatenate(a10), np.concatenate(bids)

    def expected(self, precision: str = "float32") -> dict:
        """``q3``: (auction, name, city, state) rows; ``final``:
        (auction, final price) rows; ``avg``: the mean final price of
        each of the 5 categories (NaN where none).
        ``precision="bfloat16"`` is the control: the mean's sum is
        accumulated, final price after final price, in bfloat16, the
        nearest precision below the float32 the graph's mean states."""
        cfg = self.s.cfg
        who, cat, t0, t1, a10, b = self._tables()
        # Q3: a dictionary join, person id -> (name, city, state)
        seller = who[a10[:, 1]]
        keep = (seller[:, 0] >= 0) & np.isin(seller[:, 2], Q3_STATES)
        q3 = np.concatenate([a10[keep, :1], seller[keep]],
                            axis=1).astype(np.int64)
        # Q4: bids against their auction, inside its time, maximum each
        at = b[:, 0]
        ok = (cat[at] >= 0) & (b[:, 2] >= t0[at]) & (b[:, 2] <= t1[at])
        final = np.zeros(cfg["auctions"], np.int64)
        np.maximum.at(final, at[ok], b[ok, 1])
        has = np.flatnonzero(final > 0)
        avg = np.full(CATEGORIES, np.nan)
        for c in range(CATEGORIES):
            x = final[has[cat[has] == FIRST_CATEGORY + c]]
            if not len(x):
                continue
            if precision == "bfloat16":
                import ml_dtypes
                bf = ml_dtypes.bfloat16
                avg[c] = float(np.cumsum(x.astype(np.float32).astype(bf),
                                         dtype=bf)[-1]) / len(x)
            elif precision == "float32":
                avg[c] = x.astype(np.float64).mean()
            else:
                raise ValueError(precision)
        load_ticks = -(-cfg["history_events"]
                       // cfg["load_events_per_tick"])
        return {"q3": q3, "final": np.stack([has, final[has]], axis=1),
                "avg": avg, "errors": 0, "maybe_pos": None, "has": None,
                "ticks": float(load_ticks + len(self.ranges) - 1)}


def build(cfg: dict):
    from reflow_tpu.workloads import nexmark

    dep = nexmark.build_graph(
        persons=cfg["persons"], auctions=cfg["auctions"],
        bid_arena=cfg["bid_arena"], q3_arena=cfg["q3_arena"],
        events=cfg["events_capacity"], candidates=cfg["candidates"],
        product_slack=cfg["product_slack"])
    return SimpleNamespace(graph=dep.graph, sources={"events": dep.events},
                           nodes=dep)


def read_state(cfg: dict, dep, sched) -> dict:
    """The three served tables as arrays, and what the operators say of
    themselves: every join's and the maximum's sticky ``error``, and the
    maximum's ``over_maybe_pos`` latch (its candidate buffer let a
    positive row of that auction go: a hot auction's 17th-best bid. An
    auction that has it and serves no maximum, where the reference has
    one, is an operator error)."""
    st = sched.executor.states
    n = dep.nodes
    q3 = st[n.q3.id]
    a3 = np.flatnonzero(np.asarray(q3["emitted_has"]))
    rows3 = np.asarray(q3["emitted"])[a3]
    mx = st[n.q4_max.id]
    a4 = np.flatnonzero(np.asarray(mx["emitted_has"]))
    q4 = st[n.q4.id]
    avg = np.where(np.asarray(q4["emitted_has"]),
                   np.asarray(q4["emitted"], np.float64), np.nan)
    errors = sum(int(bool(np.asarray(st[x.id]["error"])))
                 for x in (n.q3_join, n.q4_join, n.q4_max))
    return {"q3": np.concatenate([a3[:, None], rows3.astype(np.int64)],
                                 axis=1),
            "final": np.stack([a4, np.asarray(mx["emitted"])[a4, 0]
                               .astype(np.int64)], axis=1),
            "avg": avg[:CATEGORIES], "errors": errors,
            "maybe_pos": np.asarray(mx["over_maybe_pos"]),
            "has": np.asarray(mx["emitted_has"]), "ticks": None}


def avg_limit(ticks: float) -> float:
    """The limit on the relative error of a category's mean final price.

    The mean is ``wsum / wcnt`` with ``wsum`` a float32 that every tick
    adds one number to: the tick's own sum of (new maximum - old
    maximum) over the category's auctions whose maximum rose, itself
    taken from zero in float32. ``wcnt`` is an exact integer. Each add
    rounds ``wsum`` to the nearest float32: an error of at most half a
    unit in its last place, ``2^-24`` of ``wsum``, of either sign. Over
    ``n`` adds the errors walk: about ``sqrt(n / 3) * 2^-24`` if they
    were uniform and independent, ``n * 2^-24`` if every one fell the
    same way. The tick's own sum (hundreds of terms of at most 10^8
    beside a ``wsum`` of 10^13 and more) and the final division add a
    few ``2^-24`` and do not grow. The limit is ``16 * sqrt(n) * 2^-24``
    with ``n`` the ticks applied (every tick touches every category):
    28 times the walk's expectation, and at the cell's ~ 10^4 ticks
    9.5e-5. Readings (PERF.md): the chip's float32 lands some 30 times
    under it, the bfloat16 control (whose sum stops growing once a
    final price is under half a unit of its last place, 2^-9 of the
    sum) thousands of times over."""
    return 16.0 * float(np.sqrt(max(ticks, 1.0))) * 2.0 ** -24


def _rows_mismatch(got: np.ndarray, want: np.ndarray) -> int:
    """Rows of either table the other lacks (as multisets)."""
    if got.shape[1:] != want.shape[1:]:
        return len(got) + len(want)
    both = np.concatenate([got, want])
    sign = np.concatenate([np.ones(len(got), np.int64),
                           -np.ones(len(want), np.int64)])
    _, inv = np.unique(both, axis=0, return_inverse=True)
    return int(np.count_nonzero(np.bincount(inv.ravel(), weights=sign)))


def compare(cfg: dict, got: dict, expected: dict):
    bad3 = _rows_mismatch(got["q3"], expected["q3"])
    bad4 = _rows_mismatch(got["final"], expected["final"])
    g, w = got["avg"], expected["avg"]
    if (np.isnan(g) != np.isnan(w)).any():
        err = float("inf")
    else:
        m = ~np.isnan(w)
        err = float(np.max(np.abs(g[m] - w[m]) / np.abs(w[m]), initial=0.0))
    limit = avg_limit(expected["ticks"])
    ops = got["errors"]
    if got["maybe_pos"] is not None:
        decided = expected["final"][:, 0]
        ops += int(np.count_nonzero(got["maybe_pos"][decided]
                                    & ~got["has"][decided]))
    return [Check("q3_rows_mismatch", float(bad3), 0.0, bad3 == 0),
            Check("q4_final_mismatch", float(bad4), 0.0, bad4 == 0),
            Check("q4_avg_max_rel_err", err, limit, err <= limit),
            Check("operator_errors", float(ops), 0.0, ops == 0)]
