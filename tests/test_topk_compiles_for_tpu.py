"""The top-k kernels, a reduce's block writes into its keyed tables and
a loop join's probe of its key-sorted view, compiled for the chip,
without the chip: the TPU's
compiler is installed here and compiles for a v5e that is described and
not attached, so what Mosaic would refuse on the chip (a misaligned
slice, too much VMEM, an operand it cannot place) fails here, at the
cell's real widths. Nothing runs: no result, no time. The one file
whose fixture loads the TPU's library (never at import)."""

import importlib
import re

import pytest

Q, K, CHUNK, D, DIM = 256, 16, 8192, 1 << 20, 768


@pytest.fixture(scope="module")
def one_chip():
    import os

    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    from jax.sharding import SingleDeviceSharding
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture()
def kernels(monkeypatch):
    """``kernels.topk`` taking its TPU branch (the compiled kernel) while
    the backend here is the CPU."""
    mod = importlib.import_module("reflow_tpu.kernels.topk")
    monkeypatch.setattr(mod, "_which", lambda use_pallas: (True, False))
    return mod


def _shape(one_chip, shape, dtype):
    import jax

    return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)


@pytest.mark.parametrize("q,c,k", [(Q, CHUNK, K), (16, 300, 4), (12, 3, 8)],
                         ids=["cell", "ragged", "narrower_than_k"])
def test_fold_kernel_compiles(one_chip, kernels, q, c, k):
    import jax
    import jax.numpy as jnp

    text = jax.jit(
        lambda v, i, n, s, lo: kernels.fold_topk(v, i, n, s, lo, k)
    ).lower(_shape(one_chip, (q, k), jnp.float32),
            _shape(one_chip, (q, k), jnp.int32),
            _shape(one_chip, (kernels.sweep_blocks(q),), jnp.int32),
            _shape(one_chip, (q, c), jnp.float32),
            _shape(one_chip, (), jnp.int32)).compile().as_text()
    assert text.count("tpu_custom_call") == 1


@pytest.mark.parametrize("n", [8208, 65552, 2 * K],
                         ids=["one_chunk", "load_tick_merge", "ring_merge"])
def test_generic_kernel_compiles(one_chip, kernels, n):
    import jax
    import jax.numpy as jnp

    text = jax.jit(lambda x: kernels.topk(x, K)).lower(
        _shape(one_chip, (Q, n), jnp.float32)).compile().as_text()
    assert text.count("tpu_custom_call") == 1


def test_rescan_program_on_the_chip_has_no_id_block(one_chip, kernels):
    """The cell's whole rescan as the v5e compiler leaves it: the loop
    body is the matmul fusion and the kernel; no ``s32[256, 8208]``, no
    padded ``[256, 8320]`` score block, no gather."""
    import jax
    import jax.numpy as jnp

    text = jax.jit(
        lambda q, d, live: kernels.chunked_corpus_topk(q, d, live, K, CHUNK)
    ).lower(_shape(one_chip, (Q, DIM), jnp.bfloat16),
            _shape(one_chip, (D, DIM), jnp.int8),
            _shape(one_chip, (D,), jnp.bool_)).compile().as_text()
    assert text.count("tpu_custom_call") == 1
    assert f"f32[{Q},{CHUNK}]" in text
    for gone in (f"s32[{Q},{K + CHUNK}]", f"f32[{Q},{K + CHUNK}]",
                 f"[{Q},8320]"):
        assert gone not in text
    assert not re.search(r"\bgather\(", text)


@pytest.mark.parametrize("how,vshape,dtype,kw", [
    ("max", (2,), "int32", {"candidates": 16}), ("sum", (3,), "float32", {})])
def test_reduce_tables_ride_the_block_loop_in_place(one_chip, how, vshape,
                                                    dtype, kw):
    """A reduce over the NEXmark cell's 2^23 keys (a 1 024-slot delta
    here: the maximum's sort takes a minute to compile at the cell's
    8 192) as the v5e compiler leaves it: every table is donated and
    aliased, none is copied on its way through the ``while`` loop
    (``copy-start`` is the compiler's own prefetch into near memory),
    and every scatter into one takes an eighth of the delta's slots."""
    import jax
    import jax.numpy as jnp

    from reflow_tpu.delta import Spec
    from reflow_tpu.executors import lowerings as lw
    from reflow_tpu.executors.device_delta import DeviceDelta
    from reflow_tpu.graph import FlowGraph

    keys, cap = 1 << 23, 1024
    g = FlowGraph("blocks")
    node = g.reduce(g.source("s", Spec(vshape, dtype, key_space=keys)),
                    how, name="r", **kw)
    state = jax.tree.map(
        lambda x: _shape(one_chip, x.shape, x.dtype),
        jax.eval_shape(lambda: lw.reduce_state(
            node.op, node.inputs[0].spec, node.spec)))
    delta = DeviceDelta(_shape(one_chip, (cap,), jnp.int32),
                        _shape(one_chip, (cap,) + vshape, dtype),
                        _shape(one_chip, (cap,), jnp.int32))
    comp = jax.jit(lambda s, d: lw._lower_reduce(node.op, node, s, [d]),
                   donate_argnums=0).lower(state, delta).compile()
    mem = comp.memory_analysis()
    tables = sum(x.size * x.dtype.itemsize for x in jax.tree.leaves(state))
    assert mem.alias_size_in_bytes >= 0.999 * tables
    assert mem.temp_size_in_bytes < 16 << 20
    text = comp.as_text()
    assert re.search(r" while\(", text)
    assert not re.search(r"= \S+\[%d[,\]]\S* copy\(" % keys, text)
    updates = set()
    for args in re.findall(r"= \S+\[%d[,\]]\S* scatter\(([^)]*)\)" % keys,
                           text):
        upd = re.findall(r"%[\w.\-]+", args)[-1]
        shape = re.search(r"^\s*(?:ROOT )?%s = \w+\[(\d+)" % re.escape(upd),
                          text, re.M)
        updates.add(int(shape.group(1)))
    assert updates == {lw._block_slots(cap)}


def test_a_loop_joins_pass_probes_its_view_through_near_memory(one_chip):
    """A pass of the ``sssp-graph500`` cell's join with a left delta
    (2^16 keys, a 2^21-row arena, the minimum's 2^17-slot delta) as the
    v5e compiler leaves it: one ``conditional`` between the probe and
    the sweep, the arena and the view donated and aliased through it,
    and the probe's table of slot marks (``view_probe``: one scatter of
    ``K`` slots) in near memory, ``S(1)``, like the four dense tables
    the left delta is scattered into."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from reflow_tpu.executors import join as jn
    from reflow_tpu.executors.device_delta import DeviceDelta
    from reflow_tpu.workloads import sssp

    keys, rows, cap = 1 << 16, 1 << 21, 1 << 17
    sg = sssp.build_graph(keys, arena_capacity=rows)
    relax = next(n for n in sg.graph.nodes if n.name == "relax")
    state = jax.tree.map(
        lambda x: _shape(one_chip, x.shape, x.dtype),
        jax.eval_shape(lambda: jn.join_state(
            relax.op, relax.inputs[0].spec, relax.inputs[1].spec,
            "viewed")))
    delta = DeviceDelta(_shape(one_chip, (cap,), jnp.int32),
                        _shape(one_chip, (cap,), jnp.float32),
                        _shape(one_chip, (cap,), jnp.int32))
    comp = jax.jit(
        lambda s, d: jn.join_core(relax.op, keys, rows, np.float32, s, d,
                                  None, oshape=(2,)),
        donate_argnums=0).lower(state, delta).compile()
    held = sum(x.size * x.dtype.itemsize for x in jax.tree.leaves(state))
    assert comp.memory_analysis().alias_size_in_bytes >= 0.999 * held
    text = comp.as_text()
    assert len(re.findall(r" conditional\(", text)) == 1
    marks = re.findall(r"= s32\[%d\]\{([^}]*)\} scatter\([^\n]*"
                       r"view_probe/scatter-max" % keys, text)
    assert marks and all("S(1)" in m for m in marks)
