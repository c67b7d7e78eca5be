"""TPC-H Q3 under the refresh functions (``workloads/tpch.py``) on the
device executor and on the CPU oracle against the plain NumPy reference
of the benchmark's configuration (``benchmarks/configs/tpch-q3.py``: its
generator, its reference, its comparison) under a seeded RF1 / RF2
stream; an order deleted before, with and after its lineitems; a key
that vanishes and returns; the bfloat16 control; the generator against
the one number the specification's answer set gives for the query.
Small seeded sizes, CPU."""

import importlib.util
import json
import os
import sys

import numpy as np
import pytest

from reflow_tpu import DirtyScheduler
from reflow_tpu.executors import get_executor
from reflow_tpu.workloads import tpch

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
BENCH = os.path.join(ROOT, "benchmarks")


def _config():
    """The benchmark configuration's module and its ``tiny`` sizes."""
    if BENCH not in sys.path:
        sys.path.insert(0, BENCH)
    path = os.path.join(BENCH, "configs", "tpch-q3")
    spec = importlib.util.spec_from_file_location("tpch_q3", path + ".py")
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    with open(path + ".json") as f:
        cfg = json.load(f)
    cfg.update(cfg.pop("tiny"))
    return mod, cfg


MOD, CFG = _config()
#: a history of 512 orders, so that a test's few dozen ticks turn a
#: visible share of it over
SMALL = dict(CFG, customers=600, orders=512, order_keys=2048,
             orders_arena=400, lineitem_arena=1600, load_orders_per_tick=16,
             load_customers_per_tick=64)
LANES = 2


def _loaded(cfg, seed, executor):
    stream = MOD.Stream(cfg, seed, LANES)
    dep = MOD.build(cfg)
    sched = DirtyScheduler(dep.graph, get_executor(executor))
    for batches in stream.load():
        for source, batch, _bid in batches:
            sched.push(dep.sources[source], batch)
        assert sched.tick().quiesced
    return stream, MOD.Reference(stream), dep, sched


def _view(dep, sched):
    """``dep``: the workload's graph or the configuration's build."""
    q3 = getattr(dep, "nodes", dep).q3
    return {int(k): float(v) for k, v in sched.read_table(q3).items()}


# -- the views against the reference -------------------------------------


@pytest.mark.parametrize("seed", [2**31 + 11, 5])
@pytest.mark.parametrize("executor", ["cpu", "tpu"])
def test_view_equals_the_reference_under_refresh_pairs(executor, seed):
    """Sixty refresh pairs over a 512-order history, both lanes: the
    served view holds the reference's groups and revenues; on the device
    also its two order columns, Q3's top ten, each arena's rows by
    weight and no sticky error, with the arenas compacted on the way."""
    stream, ref, dep, sched = _loaded(SMALL, seed, executor)
    for t in range(60):
        m = stream.next(t % LANES)
        assert m.rows == len(m.delta) <= SMALL["batch_rows"]
        ref.apply(m.ref)
        sched.push(dep.sources["changes"], m.delta)
        assert sched.tick().quiesced
    want = ref.expected()
    got = _view(dep, sched)
    assert sorted(got) == want["keys"].tolist() and len(got) > 0
    np.testing.assert_allclose([got[k] for k in want["keys"].tolist()],
                               want["revenue"], atol=MOD.revenue_limit())
    if executor == "tpu":
        sched.executor.check_errors()
        checks = MOD.compare(SMALL, MOD.read_state(SMALL, dep, sched), want)
        assert all(c.ok for c in checks), checks
        c = sched.executor.op_counters()
        assert c["q3_join"]["index_rebuilds"] >= 1
        assert c["q3_orders"]["index_rebuilds"] >= 1
        for j in c.values():
            # indexed, never swept
            assert j["sweeps"] == j["swept_rows"] == 0
            assert 0 < j["retracted"] < j["pairs"] + j["retracted"]


def test_the_generator_returns_q3s_share_of_the_orders():
    """Q3 at scale factor 1 returns 11 620 groups of 1 500 000 orders
    (the specification's answer set): 0.775 %. The generator's laws give
    the same share, here at 40 000 orders within sampling noise."""
    cfg = dict(CFG, customers=4000, orders=40000, order_keys=160000)
    ref = MOD.Reference(MOD.Stream(cfg, 7, 8))
    want = ref.expected()
    share = len(want["keys"]) / cfg["orders"]
    assert abs(share - 11620 / 1.5e6) < 4 * (11620 / 1.5e6 / 40000) ** 0.5
    assert abs(want["orders_live"] / cfg["orders"] - 1169 / 2406) < 0.01
    assert abs(want["lineitems_live"] / cfg["orders"]
               - 4 * 1297 / 2406) < 0.05
    assert want["revenue"].max() < 2**20


def test_lanes_are_disjoint_and_seeded():
    """A lane inserts and deletes in its own key range, deletes nothing
    its own batch inserts, and a seed gives the same rows again."""
    a, b = MOD.Stream(SMALL, 9, LANES), MOD.Stream(SMALL, 9, LANES)
    other = MOD.Stream(SMALL, 10, LANES)
    per_lane = SMALL["order_keys"] // LANES
    seen = set()
    for t in range(12):
        lane = t % LANES
        m, m2, m3 = a.next(lane), b.next(lane), other.next(lane)
        np.testing.assert_array_equal(m.delta.values, m2.delta.values)
        np.testing.assert_array_equal(m.delta.keys, m2.delta.keys)
        assert not np.array_equal(m.delta.values[:8], m3.delta.values[:8])
        v, w = m.delta.values, m.delta.weights
        first = v[:, 0] != MOD.CONT
        assert ((v[first, 1] - 1) // per_lane == lane).all()
        ins = set(v[(w > 0) & (v[:, 0] == MOD.ORDERS), 1].tolist())
        dele = set(v[(w < 0) & (v[:, 0] == MOD.ORDERS), 1].tolist())
        assert len(ins) == len(dele) > 0 and not ins & dele
        assert not ins & seen           # an unused key, every time
        seen |= ins


# -- an order and its lineitems in every order of arrival ------------------

_ORDER = [tpch.ORDERS, 40, 1, 1000, 0]
_LINES = [[tpch.LINEITEM, 40, 1, 1200, 5000000, 4],
          [tpch.LINEITEM, 40, 2, 1300, 1234567, 10],
          [tpch.LINEITEM, 40, 3, 1100, 9999999, 0]]      # shipped before D
_OTHER = [[tpch.ORDERS, 41, 1, 900, 0],
          [tpch.LINEITEM, 41, 1, 1500, 777700, 7]]
_REVENUE_40 = 50000.00 * 0.96 + 12345.67 * 0.90
_REVENUE_41 = 7777.00 * 0.93


def _push(sched, dep, rows, weight=1):
    keys = [r[1] * 8 + (r[2] if r[0] == tpch.LINEITEM else 0) for r in rows]
    sched.push(dep.changes, tpch.changes_batch(
        rows, keys, [weight] * len(rows)))


def _fresh(executor):
    dep = tpch.build_graph(customers=16, order_keys=64, orders_arena=64,
                           lineitem_arena=64, changes=1 << 12,
                           product_slack=4)
    sched = DirtyScheduler(dep.graph, get_executor(executor))
    cust = [[tpch.CUSTOMER, c, 0, c % 5, 0] for c in range(1, 12)]
    sched.push(dep.changes, tpch.changes_batch(
        cust, [2048 + c for c in range(1, 12)]))
    sched.tick()
    return dep, sched


@pytest.mark.parametrize("delete", ["order_first", "together",
                                    "lineitems_first"])
@pytest.mark.parametrize("insert", ["order_first", "together",
                                    "lineitems_first"])
@pytest.mark.parametrize("executor", ["cpu", "tpu"])
def test_an_order_deleted_before_with_and_after_its_lineitems(
        executor, insert, delete):
    """Whichever of an order and its lineitems arrives first, and
    whichever leaves first, in one tick or two: the view holds the order
    while both are in and nothing of it afterwards, and a second order
    of the same customer is untouched throughout."""
    dep, sched = _fresh(executor)

    def apply(order, weight):
        first, second = (([_ORDER], _LINES) if order == "order_first"
                         else (_LINES, [_ORDER]))
        if order == "together":
            _push(sched, dep, [_ORDER] + _LINES, weight)
        else:
            _push(sched, dep, first, weight)
            sched.tick()
            _push(sched, dep, second, weight)
        sched.tick()

    _push(sched, dep, _OTHER)
    apply(insert, 1)
    got = _view(dep, sched)
    assert sorted(got) == [40, 41]
    assert got[40] == pytest.approx(_REVENUE_40, abs=0.02)
    assert got[41] == pytest.approx(_REVENUE_41, abs=0.01)
    apply(delete, -1)
    got = _view(dep, sched)
    assert sorted(got) == [41]
    assert got[41] == pytest.approx(_REVENUE_41, abs=0.01)
    if executor == "tpu":
        sched.executor.check_errors()
        np.testing.assert_array_equal(
            tpch.order_columns(sched.executor, dep, [41]), [[900, 0]])


@pytest.mark.parametrize("executor", ["cpu", "tpu"])
def test_a_key_that_vanishes_and_returns(executor):
    """An order deleted and placed again under its key, three times
    over, with other lineitems: the group leaves the view each time its
    weights sum to 0 (the float32 sum's residue stays under the
    Reduce's ``tol``) and comes back with the new revenue."""
    dep, sched = _fresh(executor)
    for turn in range(3):
        lines = [[tpch.LINEITEM, 40, n, 1200 + n, 10494950 - 97 * turn, n]
                 for n in range(1, 8)]
        _push(sched, dep, [_ORDER] + lines)
        sched.tick()
        want = sum(r[4] * (100 - r[5]) for r in lines) / 1e4
        got = _view(dep, sched)
        assert sorted(got) == [40]
        assert got[40] == pytest.approx(want, abs=MOD.revenue_limit()
                                        + tpch.REVENUE_TOL)
        _push(sched, dep, lines, -1)
        sched.tick()
        _push(sched, dep, [_ORDER], -1)
        sched.tick()
        assert _view(dep, sched) == {}
    if executor == "tpu":
        sched.executor.check_errors()


# -- the control ------------------------------------------------------------


@pytest.mark.parametrize("seed", [2**31 + 11, 5])
def test_revenue_terms_in_bfloat16_are_not_correct(seed):
    """The reference with every revenue term rounded to bfloat16, in the
    program's place: the comparison fails it, by the revenue limit and
    by nothing that is exact."""
    stream = MOD.Stream(CFG, seed, LANES)
    ref = MOD.Reference(stream)
    for t in range(20):
        ref.apply(stream.next(t % LANES).ref)
    want = ref.expected()
    control = ref.expected(precision="bfloat16")
    checks = {c.name: c for c in MOD.compare(CFG, control, want)}
    assert not checks["q3_revenue_max_err"].ok
    assert checks["q3_revenue_max_err"].value > 20 * MOD.revenue_limit()
    assert all(c.ok for name, c in checks.items()
               if name not in ("q3_revenue_max_err", "q3_top10_mismatch"))
    assert all(c.ok for c in MOD.compare(CFG, want, want))
