"""tpch-q3: TPC-H's Q3 kept as a standing view while the specification's
refresh functions run against the tables. Data, reference and
comparison; the graph is ``reflow_tpu.workloads.tpch.build_graph``'s.

The population is ``dbgen``'s at scale factor 10 as I recall it (no
network here; ``assumed`` in the ``.json`` beside this lists what I set
myself), each record a function of ``(seed, its number)`` so that any
stretch is minted alone:

- customers ``1 .. 1 500 000``; ``c_mktsegment`` one of five, evenly;
- order number ``i`` (from 0, ``dbgen``'s order) has the sparse key
  ``(i >> 3 << 5 | i & 7) + 1``: 8 of every 32 keys, 15 M orders in a
  key space of 60 M; ``o_custkey`` uniform over the customers whose key
  is not divisible by 3; ``o_orderdate`` uniform on ``[1992-01-01,
  1998-12-31 - 151 d]``; ``o_shippriority`` 0; 1 to 7 lineitems, evenly;
- a lineitem: ``l_partkey`` uniform on ``1 .. 2 000 000``, the part's
  retail price ``90000 + (partkey // 10) % 20001 + 100 * (partkey %
  1000)`` cents (900.00 to 2 099.00), ``l_quantity`` 1 to 50,
  ``l_extendedprice`` their product (at most 104 950.00),
  ``l_discount`` 0 to 10 %, ``l_tax`` 0 to 8 %, ``l_shipdate`` the
  order's date + 1 .. 121 d, ``l_commitdate`` + 30 .. 90 d,
  ``l_receiptdate`` the ship date + 1 .. 30 d.

Dates are int32 days since 1992-01-01, money int32 cents, discount and
tax whole percent. A record is as wide as the specification's typical
row (lineitem 112, orders 104, customer 179 bytes): a row is 112 bytes
(``int32[28]``); a lineitem is one row, an order one row whose last two
words are zero, a customer two rows (224 bytes: the one record that
rides wider than its source, and only set-up sends it), the second
tagged ``CONT``. Behind a first row's numeric columns come opaque
words, hashes in the place of the columns Q3 does not read.

**Set-up** loads every customer and the first ``orders`` orders of the
scale factor's 15 M, in ``dbgen``'s order, with their lineitems.

**Traffic**: a batch is one refresh pair, RF1 then RF2: ``n`` new
orders with their lineitems, then the ``n`` oldest live orders of the
lane deleted with theirs, each deleted row as it was inserted with
weight -1 (the before-image of a change feed). ``n`` is
``batch_orders`` (400), less where the pair's rows would pass
``batch_rows`` (4 095). A lane owns the orders ``[lane, lane + 1) x
orders / lanes`` of the history and their 32-key blocks: its new orders
take the unused keys of those blocks (the second octet of every block
first, then the third and the fourth), its deletes run through its
history in key order and then through what it inserted, oldest first,
and it never deletes what the same batch inserts. ``Minted.rows``
counts table rows inserted or deleted, so ``rows_per_s`` is row changes
a second.
"""

from __future__ import annotations

from types import SimpleNamespace

import numpy as np

from common import Check, Minted
from reflow_tpu.delta import DeltaBatch

CUSTOMER, ORDERS, LINEITEM, CONT = 0, 1, 2, 3
COLS = 28                             # a row's words: 112 bytes
ORDER_WORDS = 26                      # an order is 104 bytes of its row
SEGMENTS = 5
Q3_SEGMENT = 1                        # BUILDING, second of the five by name
Q3_DATE = 1169                        # 1995-03-15 in days since 1992-01-01
ORDER_DATES = 2406                    # 1992-01-01 .. 1998-12-31 - 151 d
MAX_LINES = 7
PARTS_PER_SF = 200_000
#: the largest l_extendedprice * (1 - l_discount), dollars: 50 x 2 099.00
MAX_TERM = 104_950.0

_M1 = np.uint64(0xBF58476D1CE4E5B9)
_M2 = np.uint64(0x94D049BB133111EB)
_GOLD = np.uint64(0x9E3779B97F4A7C15)
_CUST, _ORD, _LINE, _ROW = 1, 2, 3, 4     # what a number is the number of


def _mix(x: np.ndarray) -> np.ndarray:
    """splitmix64's finalizer over uint64 (wrapping arithmetic)."""
    x = (x ^ (x >> np.uint64(30))) * _M1
    x = (x ^ (x >> np.uint64(27))) * _M2
    return x ^ (x >> np.uint64(31))


class _Draws:
    """Record ``n``'s ``k``-th random draw, a function of (seed, what
    kind of record, n, k)."""

    def __init__(self, seed: int, kind: int, n: np.ndarray):
        with np.errstate(over="ignore"):
            salt = _mix(np.uint64(seed) * _GOLD + np.uint64(kind))
            self.base = _mix(salt + np.asarray(n).astype(np.uint64)
                             * np.uint64(16))

    def below(self, k: int, m) -> np.ndarray:
        """Uniform in ``[0, m)`` (``m`` far below 2^64: the modulo's
        bias is under 2^-40)."""
        with np.errstate(over="ignore"):
            bits = _mix(self.base + np.uint64(k) * _GOLD)
        return (bits % np.asarray(m, np.uint64)).astype(np.int64)


# -- the tables, as functions of a record's number -------------------------

def segments(cfg: dict, seed: int, cust: np.ndarray) -> np.ndarray:
    return _Draws(seed, _CUST, cust).below(1, SEGMENTS)


def customer_fields(cfg: dict, seed: int, cust: np.ndarray) -> np.ndarray:
    """``[type, c_custkey, c_nationkey, c_mktsegment, c_acctbal]``."""
    d = _Draws(seed, _CUST, cust)
    return np.stack([np.full(len(cust), CUSTOMER), cust, d.below(0, 25),
                     segments(cfg, seed, cust),
                     d.below(2, 1_100_000) - 99_999], axis=1)


def order_key(cfg: dict, lanes: int, oid: np.ndarray) -> np.ndarray:
    """The key of order ``oid``: the history's orders ``[0, orders)`` in
    ``dbgen``'s sparse keys, then each lane's new orders in the unused
    keys of its own blocks."""
    oid = np.asarray(oid, np.int64)
    H = cfg["orders"]
    hl = H // lanes
    j = np.maximum(oid - H, 0)
    lane, j = j // (3 * hl), j % (3 * hl)
    block = lane * (hl // 8) + (j % hl) // 8
    new = block * 32 + (1 + j // hl) * 8 + j % 8 + 1
    return np.where(oid < H, (oid >> 3 << 5 | oid & 7) + 1, new)


def order_fields(cfg: dict, seed: int, lanes: int, oid: np.ndarray):
    """``([type, o_orderkey, o_custkey, o_orderdate, o_shippriority],
    lineitems of each)``."""
    d = _Draws(seed, _ORD, oid)
    u = d.below(0, cfg["customers"] - cfg["customers"] // 3)
    cust = u + u // 2 + 1                     # never divisible by 3
    f = np.stack([np.full(len(oid), ORDERS), order_key(cfg, lanes, oid),
                  cust, d.below(1, ORDER_DATES), np.zeros(len(oid), np.int64)],
                 axis=1)
    return f, 1 + d.below(2, MAX_LINES)


def lineitem_fields(cfg: dict, seed: int, oid: np.ndarray, line: np.ndarray,
                    key: np.ndarray, date: np.ndarray) -> np.ndarray:
    """Lineitem ``line`` (1 ..) of the orders ``oid`` with keys ``key``
    and dates ``date``: the twelve columns of
    ``reflow_tpu/workloads/tpch.py``."""
    d = _Draws(seed, _LINE, oid * 8 + line)
    part = 1 + d.below(0, PARTS_PER_SF * cfg["scale_factor"])
    retail = 90_000 + (part // 10) % 20_001 + 100 * (part % 1000)
    qty = 1 + d.below(2, 50)
    ship = date + 1 + d.below(5, 121)
    return np.stack([
        np.full(len(oid), LINEITEM), key, line, ship, qty * retail,
        d.below(3, 11), qty, d.below(4, 9), part,
        1 + d.below(1, 10_000 * cfg["scale_factor"]),
        date + 30 + d.below(6, 61), ship + 1 + d.below(7, 30)], axis=1)


def _opaque(seed: int, keys: np.ndarray) -> np.ndarray:
    """28 words a row, hashes of (seed, the row's key, word): they stand
    for the text and stop at the first re-key."""
    with np.errstate(over="ignore"):
        base = _Draws(seed, _ROW, keys).base
        words = base[:, None] * (np.arange(1, COLS // 2 + 1,
                                           dtype=np.uint64) * _GOLD
                                 | np.uint64(1))
        words ^= words >> np.uint64(29)
    return words.view(np.int32).reshape(len(keys), COLS)


def order_rows(cfg: dict, seed: int, lanes: int, oid: np.ndarray):
    """The orders ``oid`` as they are sent, each followed by its
    lineitems: ``(rows int32[m, 28], keys int64[m], rows of each
    order)``. A row's key is ``8 x oid + line`` (0 for the order)."""
    oid = np.asarray(oid, np.int64)
    f, nl = order_fields(cfg, seed, lanes, oid)
    per = 1 + nl
    of = np.repeat(np.arange(len(oid)), per)
    line = np.arange(int(per.sum())) - (np.cumsum(per) - per)[of]
    keys = oid[of] * 8 + line
    rows = _opaque(seed, keys)
    first = line == 0
    rows[first, :f.shape[1]] = f
    rows[first, ORDER_WORDS:] = 0
    li = ~first
    lf = lineitem_fields(cfg, seed, oid[of][li], line[li], f[of, 1][li],
                         f[of, 3][li])
    rows[li, :lf.shape[1]] = lf
    return rows, keys, per


def customer_rows(cfg: dict, seed: int, cust: np.ndarray):
    """Customers as they are sent, two rows each; keys from the top of
    the source's key space down, clear of the orders'."""
    f = customer_fields(cfg, seed, cust)
    keys = (cfg["changes_capacity"] - 2 * (cfg["customers"] + 1)
            + np.repeat(cust * 2, 2) + np.tile([0, 1], len(cust)))
    rows = _opaque(seed, keys)
    rows[1::2, 0] = CONT
    rows[0::2, :f.shape[1]] = f
    return rows, keys


# -- the lanes' refresh pairs ------------------------------------------------

def delete_queue(cfg: dict, lanes: int, lane: int, at: np.ndarray):
    """The ``at``-th order a lane deletes: its history in key order,
    then what it inserted, oldest first."""
    H = cfg["orders"]
    hl = H // lanes
    return np.where(at < hl, lane * hl + at, H + lane * 3 * hl + (at - hl))


def new_orders(cfg: dict, lanes: int, lane: int, at: np.ndarray):
    H = cfg["orders"]
    return H + lane * 3 * (H // lanes) + at


class _History:
    """The load ticks, minted as they are asked for: customers, then the
    orders in ``dbgen``'s order with their lineitems."""

    def __init__(self, cfg, seed, lanes):
        self.cfg, self.seed, self.lanes = cfg, seed, lanes
        self.cust_ticks = -(-cfg["customers"] // cfg["load_customers_per_tick"])
        self.n = self.cust_ticks + -(-cfg["orders"]
                                     // cfg["load_orders_per_tick"])
        self._rows = []           # rows of each tick, once it was minted

    def __len__(self):
        return self.n

    def _tick(self, i):
        cfg = self.cfg
        if i < self.cust_ticks:
            per = cfg["load_customers_per_tick"]
            cust = np.arange(1 + i * per,
                             1 + min((i + 1) * per, cfg["customers"]))
            rows, keys = customer_rows(cfg, self.seed, cust)
        else:
            per = cfg["load_orders_per_tick"]
            a = (i - self.cust_ticks) * per
            rows, keys, _ = order_rows(
                cfg, self.seed, self.lanes,
                np.arange(a, min(a + per, cfg["orders"])))
        return DeltaBatch(keys, rows, np.ones(len(rows), np.int64))

    def __iter__(self):
        for i in range(self.n):
            name = f"load/history/{i}"
            if len(self._rows) == self.n:
                # a later pass (the leader counts the rows it loaded,
                # for its log) gets every tick's length and no rows
                yield [("changes", range(self._rows[i]), name)]
                continue
            batch = self._tick(i)
            if len(self._rows) == i:
                self._rows.append(len(batch))
            yield [("changes", batch, name)]


class Stream:
    """The refresh pairs, lane by lane. NumPy only."""

    source = "changes"

    def __init__(self, cfg: dict, seed: int, lanes: int):
        if cfg["orders"] % (8 * lanes):
            raise ValueError("orders must divide into whole 32-key blocks "
                             "a lane")
        self.cfg, self.seed, self.lanes = cfg, seed, lanes
        self._deleted = [0] * lanes
        self._inserted = [0] * lanes

    def load(self):
        return _History(self.cfg, self.seed, self.lanes)

    def pair(self, lane: int, d: int, j: int, n: int):
        """The orders a refresh pair inserts and deletes."""
        at = np.arange(n)
        return (new_orders(self.cfg, self.lanes, lane, j + at),
                delete_queue(self.cfg, self.lanes, lane, d + at))

    def next(self, lane: int) -> Minted:
        cfg = self.cfg
        d, j = self._deleted[lane], self._inserted[lane]
        ins, dele = self.pair(lane, d, j, cfg["batch_orders"])
        irows, ikeys, iper = order_rows(cfg, self.seed, self.lanes, ins)
        drows, dkeys, dper = order_rows(cfg, self.seed, self.lanes, dele)
        # the most whole pairs whose rows fit one tick
        n = int(np.searchsorted(np.cumsum(iper + dper), cfg["batch_rows"],
                                side="right"))
        hl = cfg["orders"] // self.lanes
        if j + n > 3 * hl:
            raise ValueError("the mix mints past a lane's unused keys")
        if d + n > hl + j:
            raise ValueError("a lane would delete what its batch inserts")
        self._deleted[lane], self._inserted[lane] = d + n, j + n
        ni, nd = int(iper[:n].sum()), int(dper[:n].sum())
        delta = DeltaBatch(
            np.concatenate([ikeys[:ni], dkeys[:nd]]),
            np.concatenate([irows[:ni], drows[:nd]]),
            np.concatenate([np.ones(ni, np.int64), -np.ones(nd, np.int64)]))
        return Minted(delta, ni + nd, (lane, d, j, n))


class Reference:
    """Q3 over every row sent, set-up and traffic, inserts less deletes,
    in NumPy: a weight an order (its lineitems travel with it), then the
    live orders minted again from their numbers, three array filters,
    two look-ups and an exact integer sum a key."""

    def __init__(self, stream: Stream):
        self.s = stream
        H = stream.cfg["orders"]
        self.weight = np.zeros(4 * H, np.int8)
        self.weight[:H] = 1

    def apply(self, ref) -> None:
        lane, d, j, n = ref
        ins, dele = self.s.pair(lane, d, j, n)
        np.add.at(self.weight, ins, 1)
        np.add.at(self.weight, dele, -1)

    def expected(self, precision: str = "float32") -> dict:
        """The view ``{orderkey: revenue, orderdate, shippriority}`` in
        key order, Q3's ``ORDER BY revenue DESC, o_orderdate LIMIT 10``
        over it, and the rows each join's arena holds (the orders before
        the date, the lineitems shipped after it, by weight).
        ``precision="bfloat16"`` is the control: every revenue term
        rounded to bfloat16, the nearest precision below the float32 the
        graph states, and summed exactly."""
        if precision not in ("float32", "bfloat16"):
            raise ValueError(precision)
        cfg, seed, lanes = self.s.cfg, self.s.seed, self.s.lanes
        if np.any((self.weight < 0) | (self.weight > 1)):
            raise ValueError("an order deleted twice or before it was sent")
        live = np.flatnonzero(self.weight)
        seg = segments(cfg, seed, np.arange(cfg["customers"] + 1))
        n_orders = n_lines = 0
        keys, revenue, dates = [], [], []
        for a in range(0, len(live), 1 << 18):
            oid = live[a:a + (1 << 18)]
            f, nl = order_fields(cfg, seed, lanes, oid)
            before = f[:, 3] < Q3_DATE
            n_orders += int(before.sum())
            of = np.repeat(np.arange(len(oid)), nl)
            line = 1 + np.arange(int(nl.sum())) - (np.cumsum(nl) - nl)[of]
            lf = lineitem_fields(cfg, seed, oid[of], line, f[of, 1],
                                 f[of, 3])
            after = lf[:, 3] > Q3_DATE
            n_lines += int(after.sum())
            hit = after & (before & (seg[f[:, 2]] == Q3_SEGMENT))[of]
            # cents x percent: exact in int64
            term = lf[hit, 4] * (100 - lf[hit, 5])
            if precision == "bfloat16":
                import ml_dtypes
                term = (term / 1e4).astype(np.float32).astype(
                    ml_dtypes.bfloat16).astype(np.float64)
            total = np.bincount(of[hit], weights=term, minlength=len(oid))
            has = np.bincount(of[hit], minlength=len(oid)) > 0
            keys.append(f[has, 1])
            dates.append(f[has, 3])
            revenue.append(total[has] if precision == "bfloat16"
                           else total[has] / 1e4)
        keys, revenue, dates = (np.concatenate(x) for x in
                                (keys, revenue, dates))
        order = np.argsort(keys)
        out = {"keys": keys[order], "revenue": revenue[order],
               "orderdate": dates[order],
               "shippriority": np.zeros(len(keys), np.int64),
               "orders_live": n_orders, "lineitems_live": n_lines,
               "errors": 0}
        out["top10"] = top10(out)
        return out


def top10(view: dict) -> np.ndarray:
    """``ORDER BY revenue DESC, o_orderdate LIMIT 10``: the keys."""
    order = np.lexsort((view["keys"], view["orderdate"], -view["revenue"]))
    return view["keys"][order[:10]]


def build(cfg: dict):
    from reflow_tpu.workloads import tpch

    dep = tpch.build_graph(
        customers=cfg["customers"] + 1, order_keys=cfg["order_keys"],
        orders_arena=cfg["orders_arena"],
        lineitem_arena=cfg["lineitem_arena"],
        changes=cfg["changes_capacity"],
        product_slack=cfg["product_slack"])
    return SimpleNamespace(graph=dep.graph, sources={"changes": dep.changes},
                           nodes=dep)


def _arena_weight(st) -> int:
    n = int(st["rcount"])
    return int(np.asarray(st["rw"][:n], np.int64).sum())


def read_state(cfg: dict, dep, sched) -> dict:
    """The served table as arrays, the two order columns from the order
    table (the second join's left table), what each arena holds by
    weight, and both joins' sticky ``error``."""
    from reflow_tpu.workloads import tpch

    st = sched.executor.states
    n = dep.nodes
    q3 = st[n.q3.id]
    keys = np.flatnonzero(np.asarray(q3["emitted_has"]))
    cols = tpch.order_columns(sched.executor, n, keys).astype(np.int64)
    out = {"keys": keys,
           "revenue": np.asarray(q3["emitted"])[keys].astype(np.float64),
           "orderdate": cols[:, 0], "shippriority": cols[:, 1],
           "orders_live": _arena_weight(st[n.q3_orders.id]),
           "lineitems_live": _arena_weight(st[n.q3_join.id]),
           "errors": sum(int(bool(np.asarray(st[x.id]["error"])))
                         for x in (n.q3_orders, n.q3_join))}
    out["top10"] = top10(out)
    return out


def revenue_limit() -> float:
    """The limit on a group's revenue, dollars.

    A group is the sum of at most 7 terms ``l_extendedprice * (1 -
    l_discount)``, each at most 104 950.00. A term is the exact int32
    product ``cents x (100 - percent)`` converted to float32 and divided
    by 10 000: two roundings, a third allowed for a divide that is not
    correctly rounded, each at most ``2^-24`` of the term. The sum stays
    under ``2^20`` (734 650), where a float32's last place is ``2^-4``:
    every one of the at most 7 adds into ``wsum`` rounds by at most half
    of that. An order's lineitems arrive in one tick and leave in one,
    and its key is not used again, so nothing else touches the slot.
    ``7 x (3 x 2^-24 x 104 950 + 2^-5)`` = 0.35: four hundredths of a
    percent of the specification's own tolerance for a ``SUM``, $100
    (clause 2.1.3.5 as I recall it), which stays the outer limit.
    Readings (PERF.md): float32 on the chip lands several times under
    it, the bfloat16 control (a term off by up to ``2^-9`` of itself,
    $200) hundreds of times over."""
    return MAX_LINES * (3 * 2.0 ** -24 * MAX_TERM + 2.0 ** -5)


def compare(cfg: dict, got: dict, expected: dict):
    limit = revenue_limit()
    gk, wk = got["keys"], expected["keys"]
    groups = len(np.setxor1d(gk, wk))
    both, gi, wi = np.intersect1d(gk, wk, return_indices=True)
    err = float(np.max(np.abs(got["revenue"][gi] - expected["revenue"][wi]),
                       initial=0.0))
    cols = int(np.count_nonzero(
        (got["orderdate"][gi] != expected["orderdate"][wi])
        | (got["shippriority"][gi] != expected["shippriority"][wi])))
    # a served rank may differ from the reference's only where the
    # reference's revenues at that rank lie within the limit of each
    # other's: a tie the arithmetic may break either way
    rev = dict(zip(wk.tolist(), expected["revenue"].tolist()))
    g10, w10 = got["top10"], expected["top10"]
    top = abs(len(g10) - len(w10)) + sum(
        1 for a, b in zip(g10.tolist(), w10.tolist())
        if a != b and not (a in rev and abs(rev[a] - rev[b]) <= 2 * limit))
    orders = abs(got["orders_live"] - expected["orders_live"])
    lines = abs(got["lineitems_live"] - expected["lineitems_live"])
    return [Check("q3_groups_mismatch", float(groups), 0.0, groups == 0),
            Check("q3_order_columns_mismatch", float(cols), 0.0, cols == 0),
            Check("q3_revenue_max_err", err, limit, err <= limit),
            Check("q3_top10_mismatch", float(top), 0.0, top == 0),
            Check("q3_orders_live_mismatch", float(orders), 0.0,
                  orders == 0),
            Check("q3_lineitems_live_mismatch", float(lines), 0.0,
                  lines == 0),
            Check("operator_errors", float(got["errors"]), 0.0,
                  got["errors"] == 0)]
