"""TPC-H Q3, *Shipping Priority*, as a standing view under the
specification's refresh functions.

TPC-H (TPC Benchmark H, tpc.org) models a wholesale supplier; Q3 lists
the unshipped orders of one market segment by the revenue still to
ship::

    SELECT l_orderkey, SUM(l_extendedprice * (1 - l_discount)) AS revenue,
           o_orderdate, o_shippriority
    FROM customer, orders, lineitem
    WHERE c_mktsegment = 'BUILDING' AND c_custkey = o_custkey
      AND l_orderkey = o_orderkey
      AND o_orderdate < DATE '1995-03-15' AND l_shipdate > DATE '1995-03-15'
    GROUP BY l_orderkey, o_orderdate, o_shippriority
    ORDER BY revenue DESC, o_orderdate LIMIT 10

with the validation run's substitution parameters. The view is kept
while the refresh functions run against the tables: RF1 inserts new
orders with their lineitems, RF2 deletes old orders with theirs. A
delete reaches a delta engine as the deleted row with weight -1 (a
change feed's before-image), so every operator below retracts.

Graph::

    changes  source  int32[28] rows: 112 bytes, the columns below first
    customer / orders / lineitem   Filter(type): continuation rows go here
    customer_by_key  Filter(c_mktsegment = BUILDING), by c_custkey, unique
    orders_by_cust   Filter(o_orderdate < D), by o_custkey
    q3_orders  Join(customer_by_key, orders_by_cust)
                     {custkey: orderkey, orderdate, shippriority}
    order_by_key     q3_orders re-keyed by o_orderkey, unique
    lineitem_by_order  Filter(l_shipdate > D), by l_orderkey
    q3_join    Join(order_by_key, lineitem_by_order)
                     {orderkey: extendedprice, discount}
    q3_revenue Map   extendedprice * (1 - discount), float32 dollars
    q3         Reduce('sum', tol) by orderkey: the served view

The filters sit below the joins, as any planner puts them. Both joins
have a unique left side (a customer, an order) and a right side that is
traffic, so on the device each keeps an arena index
(``executors/arena.py``); the second join's left side is the first
join's output, so an order's arrival or deletion reaches it as a left
delta whose capacity is the first join's pair budget. A deleted order
retracts through the probe every lineitem pair the arena holds for it,
whichever of the two arrived first, in one tick or ticks apart.

``o_orderdate`` and ``o_shippriority`` depend on the key, so the sum
carries revenue alone and the two are read from the order table, the
second join's left table (:func:`order_columns`): one float32 column of
state at the order-key space and not three.

**Records at the source's widths.** The specification's typical row
lengths are 112 (lineitem), 104 (orders) and 179 (customer) bytes. The
engine's rows are fixed-width, so a row is lineitem's 112 bytes
(``int32[28]``): a lineitem is one row, an order one row whose last two
words are zero, a customer two rows, the second tagged ``CONT``. A
record's first row holds its type, its numeric columns and opaque words
in the place of the text the query does not read (names, addresses,
comments, clerk, ship mode and instructions); the three ``Filter``s keep
first rows and the first re-key behind each projects the opaque words
away: they cross the wire, the WAL and the ingress queue and stop
there. Money rides as int32 cents, dates as int32 days since 1992-01-01,
discount as whole percent.
"""

from __future__ import annotations

import dataclasses
import numpy as np

from reflow_tpu.delta import DeltaBatch, Spec
from reflow_tpu.graph import FlowGraph, Node

#: a row: ``[type, key, numeric columns, opaque words]``, int32: 112 bytes
COLS = 28
CUSTOMER, ORDERS, LINEITEM, CONT = 0, 1, 2, 3
#: rows a record rides as: the source's 179 / 104 / 112 bytes
RECORD_ROWS = {CUSTOMER: 2, ORDERS: 1, LINEITEM: 1}
#: customer: c_custkey, c_nationkey, c_mktsegment, c_acctbal
C_NATION, C_SEGMENT, C_ACCTBAL = 2, 3, 4
#: orders: o_orderkey, o_custkey, o_orderdate, o_shippriority
O_CUST, O_DATE, O_SHIPPRIORITY = 2, 3, 4
#: lineitem: l_orderkey, l_linenumber, l_shipdate, l_extendedprice,
#: l_discount, l_quantity, l_tax, l_partkey, l_suppkey, l_commitdate,
#: l_receiptdate
(L_LINE, L_SHIPDATE, L_PRICE, L_DISCOUNT, L_QUANTITY, L_TAX, L_PART,
 L_SUPP, L_COMMIT, L_RECEIPT) = 2, 3, 4, 5, 6, 7, 8, 9, 10, 11

SEGMENTS = ("AUTOMOBILE", "BUILDING", "FURNITURE", "HOUSEHOLD", "MACHINERY")
Q3_SEGMENT = SEGMENTS.index("BUILDING")
#: DATE '1995-03-15' in days since 1992-01-01
Q3_DATE = 1169
#: the sum's ``tol``, dollars. An order's lineitems are added to its
#: float32 ``wsum`` term by term and taken out term by term, so what a
#: deleted order leaves behind is not 0 but the roundings of up to 14
#: adds below 2^20, each at most 2^-5: under 0.44. A group whose weights
#: sum to 0 and whose sum is within ``tol`` of 0 is gone (the Reduce's
#: rule for float sums); any lineitem's revenue is 810.00 or more, so
#: no change of a live group is as small.
REVENUE_TOL = 0.5


@dataclasses.dataclass
class TpchGraph:
    graph: FlowGraph
    changes: Node    # source
    q3_orders: Node  # Join: {custkey: (orderkey, orderdate, shippriority)}
    q3_join: Node    # Join: {orderkey: (extendedprice, discount)}
    q3: Node         # Reduce: {orderkey: revenue}


def _orders_merge(k, customer, order):
    """(segment, (orderkey, orderdate, shippriority)) -> the order's
    three columns; per row on the CPU oracle, batched on the device."""
    return order


def _lineitem_merge(k, order, lineitem):
    """((orderdate, shippriority), (extendedprice, discount)) -> the
    lineitem's two columns."""
    return lineitem


def _revenue(v):
    """``l_extendedprice * (1 - l_discount)`` in float32 dollars from
    cents and whole percent: the int32 product (at most 1.05e9) is
    exact, so a term carries one rounding to float32 and one of the
    division."""
    cents_pct = v[:, 0] * (100 - v[:, 1])
    return cents_pct.astype(np.float32) / np.float32(10000.0)


def build_graph(*, customers: int, order_keys: int, orders_arena: int,
                lineitem_arena: int, changes: int = 1 << 30,
                product_slack: int = 1) -> TpchGraph:
    """``customers`` / ``order_keys``: key spaces (keys are the tables'
    own, so past the largest); ``orders_arena`` / ``lineitem_arena``:
    rows the two joins' right sides may hold between two compactions
    (the orders before the date, the lineitems shipped after it, live
    and retracted); ``changes``: the source's key space (a row's key
    names its record). State is sized here, once: nothing regrows inside
    a served window. The graph is sink-free, which the served window
    path needs: the view is the Reduce's table (``read_table``)."""
    i32, f32 = np.int32, np.float32
    g = FlowGraph("tpch")
    ch = g.source("changes", Spec((COLS,), i32, key_space=changes))
    customer = g.filter(ch, lambda v: v[:, 0] == CUSTOMER, vectorized=True,
                        name="customer")
    orders = g.filter(ch, lambda v: v[:, 0] == ORDERS, vectorized=True,
                      name="orders")
    lineitem = g.filter(ch, lambda v: v[:, 0] == LINEITEM, vectorized=True,
                        name="lineitem")

    building = g.filter(customer, lambda v: v[:, C_SEGMENT] == Q3_SEGMENT,
                        vectorized=True, name="customer_building")
    customer_by_key = g.group_by(
        building, key_fn=lambda k, v: v[:, 1],
        value_fn=lambda k, v: v[:, C_SEGMENT], vectorized=True,
        name="customer_by_key",
        spec=Spec((), i32, key_space=customers, unique=True))
    before = g.filter(orders, lambda v: v[:, O_DATE] < Q3_DATE,
                      vectorized=True, name="orders_before_date")
    orders_by_cust = g.group_by(
        before, key_fn=lambda k, v: v[:, O_CUST],
        value_fn=lambda k, v: v[:, [1, O_DATE, O_SHIPPRIORITY]],
        vectorized=True, name="orders_by_cust",
        spec=Spec((3,), i32, key_space=customers))
    q3_orders = g.join(customer_by_key, orders_by_cust, merge=_orders_merge,
                       spec=Spec((3,), i32, key_space=customers),
                       arena_capacity=orders_arena,
                       product_slack=product_slack, name="q3_orders")

    order_by_key = g.group_by(
        q3_orders, key_fn=lambda k, v: v[:, 0],
        value_fn=lambda k, v: v[:, 1:3], vectorized=True,
        name="order_by_key",
        spec=Spec((2,), i32, key_space=order_keys, unique=True))
    shipped_after = g.filter(lineitem, lambda v: v[:, L_SHIPDATE] > Q3_DATE,
                             vectorized=True, name="lineitem_after_date")
    lineitem_by_order = g.group_by(
        shipped_after, key_fn=lambda k, v: v[:, 1],
        value_fn=lambda k, v: v[:, [L_PRICE, L_DISCOUNT]], vectorized=True,
        name="lineitem_by_order",
        spec=Spec((2,), i32, key_space=order_keys))
    q3_join = g.join(order_by_key, lineitem_by_order, merge=_lineitem_merge,
                     spec=Spec((2,), i32, key_space=order_keys),
                     arena_capacity=lineitem_arena,
                     product_slack=product_slack, name="q3_join")
    revenue = g.map(q3_join, _revenue, vectorized=True, name="q3_revenue",
                    spec=Spec((), f32, key_space=order_keys))
    q3 = g.reduce(revenue, "sum", tol=REVENUE_TOL, name="q3")
    return TpchGraph(g, ch, q3_orders, q3_join, q3)


def order_columns(executor, dep: TpchGraph, keys: np.ndarray) -> np.ndarray:
    """``(o_orderdate, o_shippriority)`` of the orders ``keys`` of the
    view, ``int32[n, 2]``, read from the order table: the second join's
    left table on the device executor (a device read: outside a served
    window)."""
    lval = executor.states[dep.q3_join.id]["lval"]
    return np.asarray(lval[np.asarray(keys, np.int32)]).reshape(-1, 2)


def changes_batch(records, keys, weights=None) -> DeltaBatch:
    """Records given by their leading numeric columns (a list of rows of
    up to 12 numbers, each as long as its table has columns here) as a
    batch for the ``changes`` source, one first row each with no opaque
    words behind it (a producer that has the text sends it as the rest
    of the row and as ``CONT`` rows); ``weights`` default to +1, a
    delete carries -1 and the row as it was inserted."""
    rows = np.zeros((len(records), COLS), np.int32)
    for row, record in zip(rows, records):
        row[:len(record)] = record
    w = np.ones(len(rows), np.int64) if weights is None \
        else np.asarray(weights, np.int64)
    return DeltaBatch(np.asarray(keys, np.int64), rows, w)
