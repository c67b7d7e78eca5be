"""The device Join's layout is one decision (``executors/join.py``):
``join_layout`` makes it at bind, ``join_state`` builds it, ``layout_of``
reads it back off a state tree, and both counting layouts keep every
name of ``OP_COUNTERS["join"]`` at the positions readers know."""

import numpy as np
import pytest

import jax.numpy as jnp

from reflow_tpu.delta import Spec
from reflow_tpu.executors import get_executor
from reflow_tpu.executors import join as jn
from reflow_tpu.executors.lowerings import OP_COUNTERS
from reflow_tpu.graph import FlowGraph

K, R = 32, 128
NAMES = ("pairs", "late_pairs", "arena_rows", "index_rebuilds",
         "compactions", "probe_steps", "sweeps", "swept_rows", "left_rows",
         "retracted", "probes")


def _join(layout, k=K):
    """A graph whose one join ``TpuExecutor.bind`` gives ``layout``:
    -> (graph, join node)."""
    g = FlowGraph(layout)
    unique = layout != "multiset"
    edges = g.source("edges", Spec((), np.float32, key_space=k))
    if layout == "viewed":
        left = g.loop("x", Spec((), np.float32, key_space=k, unique=True))
    else:
        left = g.source("left", Spec((), np.float32, key_space=k,
                                     unique=unique))
    j = g.join(left, edges, merge=lambda key, a, b: a + b,
               spec=Spec((), np.float32, key_space=k), arena_capacity=R,
               linear_left=layout == "swept", name="j")
    if layout == "viewed":
        g.close_loop(left, g.reduce(j, "min", name="best", spec=left.spec))
    else:
        g.sink(j, "out")
    return g, j


@pytest.mark.parametrize("layout", jn.LAYOUTS)
def test_a_state_says_its_layout(layout):
    g, j = _join(layout)
    st = jn.join_state(j.op, j.inputs[0].spec, j.inputs[1].spec, layout)
    assert jn.layout_of(st) == layout
    counting = layout in ("indexed", "viewed")
    assert ("counters" in st) == counting
    if counting:
        assert st["counters"].shape == (len(OP_COUNTERS["join"]),)


def test_an_unknown_layout_is_refused():
    g, j = _join("swept")
    with pytest.raises(ValueError, match="join layout"):
        jn.join_state(j.op, j.inputs[0].spec, j.inputs[1].spec, "dense")


@pytest.mark.parametrize("layout", jn.LAYOUTS)
def test_bind_reads_the_layout_off_the_graph(layout):
    """Loop-free and unique-left: indexed; the same join under a loop:
    viewed; a declared-linear left: swept; a non-unique left: multiset.
    A counting layout reports every name."""
    g, j = _join(layout)
    ex = get_executor("tpu")
    ex.bind(g)
    assert jn.layout_of(ex.states[j.id]) == layout
    assert set(ex._room.joins) == ({j.id} if layout == "indexed" else set())
    if layout in ("indexed", "viewed"):
        assert ex.counter_names()["j"] == OP_COUNTERS["join"]
        assert set(ex.op_counters()["j"]) == set(NAMES)
    else:
        assert "j" not in ex.counter_names()


@pytest.mark.parametrize("layout", jn.LAYOUTS)
def test_the_sharded_executor_never_keeps_an_index(layout):
    from reflow_tpu.parallel.mesh import make_mesh
    from reflow_tpu.parallel.shard import ShardedTpuExecutor

    g, j = _join(layout)
    ex = ShardedTpuExecutor(make_mesh(8))
    ex.bind(g)
    assert jn.layout_of(ex.states[j.id]) == (
        "multiset" if layout == "multiset" else "swept")
    assert not ex._room.joins and "j" not in ex.counter_names()


def test_join_layout_is_the_rule():
    g, j = _join("indexed")
    unique, plain = j.inputs[0].spec, j.inputs[1].spec
    linear = _join("swept")[1].op
    assert jn.join_layout(j.op, unique, looped=False) == "indexed"
    assert jn.join_layout(j.op, unique, looped=True) == "viewed"
    assert jn.join_layout(linear, unique, looped=True) == "swept"
    assert jn.join_layout(j.op, unique, looped=False, index=False) == "swept"
    assert jn.join_layout(j.op, unique, looped=True, index=False) == "swept"
    for looped in (False, True):
        for index in (False, True):
            assert jn.join_layout(j.op, plain, looped=looped,
                                  index=index) == "multiset"


def test_counts_go_by_name_at_the_positions_readers_know():
    """The eleven names are where the benchmark's readers look for them
    (positions 0 - 10), and the named helper lays counts out in that
    order, 0 where a name is not given."""
    assert OP_COUNTERS["join"] == NAMES
    assert OP_COUNTERS["join"] is jn.JOIN_COUNTERS
    got = np.asarray(jn.counts(**{n: i + 1 for i, n in enumerate(NAMES)}))
    assert got.dtype == np.int32 and got.tolist() == list(range(1, 12))
    some = np.asarray(jn.counts(late_pairs=jnp.int32(5), retracted=2))
    assert some.tolist() == [0, 5, 0, 0, 0, 0, 0, 0, 0, 2, 0]
    with pytest.raises(KeyError, match="no join counter"):
        jn.counts(pears=1)


def test_an_indexed_join_counts_by_name_through_a_reindex():
    """An indexed join's leaf is as wide as a viewed one's: ``probes``
    and the sweep's names stay 0, ``arena_rows`` is the level the last
    tick left, and a ``join_reindex`` bumps ``index_rebuilds`` and
    ``compactions``."""
    from reflow_tpu.executors.device_delta import DeviceDelta

    g, j = _join("indexed")
    st = jn.join_state(j.op, j.inputs[0].spec, j.inputs[1].spec, "indexed")
    keys = jnp.asarray([3, 3, 5, 0], jnp.int32)
    vals = jnp.asarray([1.0, 2.0, 3.0, 0.0], jnp.float32)
    db = DeviceDelta(keys, vals, jnp.asarray([1, 1, 1, 0], jnp.int32))
    _, st = jn.join_core(j.op, K, R, np.float32, st, None, db)
    gone = DeviceDelta(keys, vals, jnp.asarray([-1, 0, 0, 0], jnp.int32))
    _, st = jn.join_core(j.op, K, R, np.float32, st, None, gone)
    da = DeviceDelta(jnp.asarray([3, 5], jnp.int32),
                     jnp.asarray([10.0, 20.0], jnp.float32),
                     jnp.asarray([1, 1], jnp.int32))
    out, st = jn.join_core(j.op, K, R, np.float32, st, da, None)
    st = jn.join_reindex(st)
    c = dict(zip(OP_COUNTERS["join"], np.asarray(st["counters"]).tolist()))
    assert c == dict.fromkeys(NAMES, 0) | {
        "pairs": 4, "late_pairs": 4, "arena_rows": 4, "index_rebuilds": 1,
        "compactions": 1, "probe_steps": c["probe_steps"], "retracted": 1}
    assert c["probe_steps"] >= 1 and int(st["rcount"]) == 2


def test_a_checkpoint_with_a_narrower_counters_leaf_is_refused(tmp_path):
    """A state leaf's width is part of a checkpoint: one saved while an
    indexed join's ``counters`` were ten wide (before PR 46) does not
    restore into eleven, and says so; it is never padded, cut or fed to
    a tick as it is (docs/guide.md, Durability; ROADMAP D8)."""
    from reflow_tpu.scheduler import DirtyScheduler
    from reflow_tpu.utils.checkpoint import load_checkpoint, save_checkpoint

    g, j = _join("indexed")
    old = DirtyScheduler(g, get_executor("tpu"))
    st = dict(old.executor.states[j.id])
    st["counters"] = st["counters"][:10]
    old.executor.states[j.id] = st
    save_checkpoint(old, str(tmp_path / "ck"))

    g, j = _join("indexed")
    new = DirtyScheduler(g, get_executor("tpu"))
    with pytest.raises(ValueError, match="not compatible with the stored"):
        load_checkpoint(new, str(tmp_path / "ck"))
