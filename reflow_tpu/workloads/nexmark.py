"""NEXmark Q3 and Q4: the benchmark's two unwindowed incremental joins.

NEXmark (Tucker, Tufte, Papadimos, Maier, 2008) models an auction site:
one stream of tagged events, ``person`` / ``auction`` / ``bid`` as
filtered views of it (as ``nexmark-flink``'s ``datagen`` table does).

- Q3, *who sells in a category*: ``SELECT P.name, P.city, P.state, A.id
  FROM auction A JOIN person P ON A.seller = P.id WHERE A.category = 10
  AND P.state IN ('OR','ID','CA')`` — filter, re-key, join.
- Q4, *average closing price by category*: ``SELECT Q.category,
  AVG(Q.final) FROM (SELECT MAX(B.price) AS final, A.category FROM
  auction A, bid B WHERE A.id = B.auction AND B.dateTime BETWEEN
  A.dateTime AND A.expires GROUP BY A.id, A.category) Q GROUP BY
  Q.category`` — a join on the stream that is 92 % of the events, a
  maximum per auction, and every rise of a maximum retracts and
  re-inserts a row of the mean: Reduce into Reduce.

Graph::

    events   source  int32[25] rows: 100 bytes, the columns below first
    person / auction / bid        Filter(type): continuation rows go here
    q3_join  Join(person by id, Filter(auction, category = 10) by seller)
    q3_rows  Filter(state in OR, ID, CA)     {seller: name, city, state, auction}
    q3       Reduce('sum') by auction        the served view of q3_rows
    q4_join  Join(auction by id, bid by auction)
    q4_live  Filter(bid.dateTime BETWEEN auction.dateTime AND expires)
    q4_max   Reduce('max') by auction over (price, category)
    q4       Reduce('mean') by category over price

Both joins have a unique left side (a person, an auction) and a right
side that is traffic, so on the device they keep an arena index
(``executors/arena.py``) and a tick costs what its delta matches. An
event may reach the leader before the one it refers to (lanes are
independent connections): the inner joins give the same view in any
arrival order.

**Records at the source's widths.** The generator's records are 200
(person), 500 (auction) and 100 (bid) bytes; the engine's rows are
fixed-width, so a row is 100 bytes (``int32[25]``) and a record rides as
2, 5 or 1 consecutive rows: the first holds the type, the numeric
columns and 17 opaque words, each further one the tag ``CONT`` and 24
opaque words (the free text neither query reads: ``emailAddress``,
``creditCard``, ``itemName``, ``description``, ``extra``). The three
``Filter``s keep first rows only, and the first ``GroupBy`` behind each
projects the opaque words away: they cross the wire, the WAL and the
ingress queue and stop there, as in any engine that reads two columns
of a wide record. Names, cities and states ride as codes into the
generator's fixed lists; prices are int32 cents (up to 10^8, past
float32's 2^24), so the maximum's candidates are held as integers.
"""

from __future__ import annotations

import dataclasses
import numpy as np

from reflow_tpu.delta import DeltaBatch, Spec
from reflow_tpu.graph import FlowGraph, Node

#: a row: ``[type, id, c2 .. c7, 17 opaque words]``, int32: 100 bytes
COLS = 25
#: the numeric columns every record's first row starts with
FIELDS = 8
PERSON, AUCTION, BID, CONT = 0, 1, 2, 3
#: rows a record rides as: the source's 200 / 500 / 100 bytes
RECORD_ROWS = {PERSON: 2, AUCTION: 5, BID: 1}
#: person: id, name, city, state, dateTime
P_NAME, P_CITY, P_STATE, P_TIME = 2, 3, 4, 5
#: auction: id, seller, category, initialBid, reserve, dateTime, expires
A_SELLER, A_CATEGORY, A_INITIAL, A_RESERVE, A_TIME, A_EXPIRES = 2, 3, 4, 5, 6, 7
#: bid: auction (in the id column), bidder, price, dateTime
B_BIDDER, B_PRICE, B_TIME = 2, 3, 4

FIRST_CATEGORY = 10
CATEGORIES = 5
US_STATES = ("AZ", "CA", "ID", "OR", "WA", "WY")
Q3_STATES = tuple(US_STATES.index(s) for s in ("OR", "ID", "CA"))
Q3_CATEGORY = 10


@dataclasses.dataclass
class NexmarkGraph:
    graph: FlowGraph
    events: Node     # source
    q3_join: Node
    q3: Node         # Reduce: {auction: (name, city, state)}
    q4_join: Node
    q4_max: Node     # Reduce: {auction: (final price, category)}
    q4: Node         # Reduce: {category - 10: mean final price}


def _q3_merge(k, person, auction):
    """((name, city, state), auction id) -> (name, city, state, auction);
    per row on the CPU oracle, batched on the device (``Join``)."""
    if getattr(person, "ndim", 1) <= 1:
        return np.asarray([person[0], person[1], person[2], auction])
    import jax.numpy as jnp

    return jnp.concatenate([person, auction[:, None]], axis=-1)


def _q4_merge(k, auction, bid):
    """((category, dateTime, expires), (price, dateTime)) ->
    (price, category, bid time, auction time, expires)."""
    if getattr(auction, "ndim", 1) <= 1:
        return np.asarray([bid[0], auction[0], bid[1], auction[1],
                           auction[2]])
    import jax.numpy as jnp

    return jnp.stack([bid[:, 0], auction[:, 0], bid[:, 1], auction[:, 1],
                      auction[:, 2]], axis=-1)


def _in_q3_states(v):
    keep = v[:, 2] == Q3_STATES[0]
    for s in Q3_STATES[1:]:
        keep = keep | (v[:, 2] == s)
    return keep


def build_graph(*, persons: int, auctions: int, bid_arena: int,
                q3_arena: int, events: int = 1 << 27,
                candidates: int = 16,
                product_slack: int = 2) -> NexmarkGraph:
    """``persons`` / ``auctions``: key spaces (ids are used as keys, so
    past the largest id); ``bid_arena`` / ``q3_arena``: rows the two
    joins' right sides may ever hold (every bid; every auction of
    category 10); ``events``: the source's key space (a row's key is its
    event number). State is sized here, once: nothing regrows inside a
    served window. The graph is sink-free, which the served window path
    needs: the views are the three Reduce tables (``read_table``)."""
    i32, f32 = np.int32, np.float32
    g = FlowGraph("nexmark")
    ev = g.source("events", Spec((COLS,), i32, key_space=events))
    person = g.filter(ev, lambda v: v[:, 0] == PERSON, vectorized=True,
                      name="person")
    auction = g.filter(ev, lambda v: v[:, 0] == AUCTION, vectorized=True,
                       name="auction")
    bid = g.filter(ev, lambda v: v[:, 0] == BID, vectorized=True,
                   name="bid")

    # -- Q3 ---------------------------------------------------------------
    person_by_id = g.group_by(
        person, key_fn=lambda k, v: v[:, 1],
        value_fn=lambda k, v: v[:, [P_NAME, P_CITY, P_STATE]],
        vectorized=True, name="person_by_id",
        spec=Spec((3,), i32, key_space=persons, unique=True))
    in_category = g.filter(auction,
                           lambda v: v[:, A_CATEGORY] == Q3_CATEGORY,
                           vectorized=True, name="auction_in_category")
    by_seller = g.group_by(
        in_category, key_fn=lambda k, v: v[:, A_SELLER],
        value_fn=lambda k, v: v[:, 1], vectorized=True, name="by_seller",
        spec=Spec((), i32, key_space=persons))
    q3_join = g.join(person_by_id, by_seller, merge=_q3_merge,
                     spec=Spec((4,), i32, key_space=persons),
                     arena_capacity=q3_arena, product_slack=product_slack,
                     name="q3_join")
    q3_rows = g.filter(q3_join, _in_q3_states, vectorized=True,
                       name="q3_rows")
    q3_by_auction = g.group_by(
        q3_rows, key_fn=lambda k, v: v[:, 3],
        value_fn=lambda k, v: v[:, :3].astype(f32), vectorized=True,
        name="q3_by_auction", spec=Spec((3,), f32, key_space=auctions))
    q3 = g.reduce(q3_by_auction, "sum", name="q3")

    # -- Q4 ---------------------------------------------------------------
    auction_by_id = g.group_by(
        auction, key_fn=lambda k, v: v[:, 1],
        value_fn=lambda k, v: v[:, [A_CATEGORY, A_TIME, A_EXPIRES]],
        vectorized=True, name="auction_by_id",
        spec=Spec((3,), i32, key_space=auctions, unique=True))
    bid_by_auction = g.group_by(
        bid, key_fn=lambda k, v: v[:, 1],
        value_fn=lambda k, v: v[:, [B_PRICE, B_TIME]], vectorized=True,
        name="bid_by_auction", spec=Spec((2,), i32, key_space=auctions))
    q4_join = g.join(auction_by_id, bid_by_auction, merge=_q4_merge,
                     spec=Spec((5,), i32, key_space=auctions),
                     arena_capacity=bid_arena, product_slack=product_slack,
                     name="q4_join")
    q4_live = g.filter(q4_join,
                       lambda v: (v[:, 2] >= v[:, 3]) & (v[:, 2] <= v[:, 4]),
                       vectorized=True, name="q4_live")
    priced = g.map(q4_live, lambda v: v[:, :2], vectorized=True,
                   name="q4_priced",
                   spec=Spec((2,), i32, key_space=auctions))
    q4_max = g.reduce(priced, "max", candidates=candidates, name="q4_max")
    by_category = g.group_by(
        q4_max, key_fn=lambda k, v: v[:, 1] - FIRST_CATEGORY,
        value_fn=lambda k, v: v[:, 0].astype(f32), vectorized=True,
        name="by_category", spec=Spec((), f32, key_space=8))
    q4 = g.reduce(by_category, "mean", name="q4")

    return NexmarkGraph(g, ev, q3_join, q3, q4_join, q4_max, q4)


def events_batch(fields: np.ndarray, first: int = 0) -> DeltaBatch:
    """Events given by their numeric columns ``int32[n, 8]`` as a batch
    for the ``events`` source, one first row each with no opaque words
    behind it (a producer that has the free text sends it as the rest of
    the row and as ``CONT`` rows): a row's key is its event number, its
    weight 1."""
    n = len(fields)
    rows = np.zeros((n, COLS), np.int32)
    rows[:, :FIELDS] = fields
    return DeltaBatch(np.arange(first, first + n, dtype=np.int64), rows,
                      np.ones(n, np.int64))
