"""ShardedTpuExecutor: the tick pass as an explicit SPMD program.

SURVEY.md §7.8 / north star: delta buffers row-sharded over the mesh, keyed
state tables key-range-sharded, cross-shard combines as explicit
collectives (``psum_scatter``/``all_to_all`` row routing in Reduce and
Join, ``pmax`` extrema combine in min/max, ``all_gather`` candidate merge
in k-NN) under ``jax.shard_map``. Composes with the on-device fixpoint
unchanged: ``build_pass_fn`` keeps the global ``(states, ingress) ->
(states', egress)`` signature, so ``FixpointProgram`` wraps the shard_map'd
pass in its ``lax.while_loop`` exactly like the single-device one — and
the fused linear fixpoint runs its whole loop inside one shard_map region
(linear_fixpoint.py).

Divisibility contract (validated at bind): the mesh size must be a power
of two no larger than the minimum delta capacity (so every bucketed delta
capacity is a multiple of it), and every keyed op's ``key_space`` and
every Join's ``arena_capacity`` must be multiples of the mesh size.
"""

from __future__ import annotations

from typing import Dict, List, Optional

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, PartitionSpec as P

from reflow_tpu.executors.device_delta import MIN_CAPACITY, DeviceDelta
from reflow_tpu.executors.join import layout_of
from reflow_tpu.executors.tpu import TpuExecutor
from reflow_tpu.graph import FlowGraph, GraphError, Node
from reflow_tpu.parallel.mesh import make_mesh, replicate
from reflow_tpu.parallel.shard_lowerings import lower_node_sharded

__all__ = ["ShardedTpuExecutor"]


class ShardedTpuExecutor(TpuExecutor):
    name = "sharded"

    def __init__(self, mesh: Optional[Mesh] = None, *, fixpoint: bool = True,
                 model_axis: Optional[str] = None):
        super().__init__(fixpoint=fixpoint)
        self.mesh = mesh if mesh is not None else make_mesh()
        #: tensor-parallel axis (VERDICT r4 #8): delta rows and keyed
        #: state shard over the remaining (data) axes and REPLICATE over
        #: this one; Map params with ``param_specs`` shard over it, and
        #: the map fn runs its own model-axis collectives
        #: (models.vit.vit_forward_tp). None = every mesh axis is data.
        self.model_axis = model_axis
        names = self.mesh.axis_names
        if model_axis is not None:
            if model_axis not in names:
                raise GraphError(
                    f"model_axis {model_axis!r} not in mesh axes {names}")
            names = tuple(a for a in names if a != model_axis)
            if not names:
                raise GraphError("a pure-model mesh has no data axis; "
                                 "add a delta axis")
        #: a 2-axis (dcn, ici) data mesh shards over the flattened
        #: PRODUCT axis (dcn-major — jax.lax.axis_index's flat order):
        #: key ranges span all chips, intra-slice legs of the
        #: collectives ride ICI, only the cross-slice legs cross DCN.
        #: Every collective this executor emits accepts the tuple form.
        self.axis = names[0] if len(names) == 1 else tuple(names)
        import numpy as _np
        self.n = int(_np.prod([self.mesh.shape[a] for a in names]))
        #: per-axis extents for 2-axis data meshes (the hierarchical
        #: router needs static (n_dcn, n_ici)); None on 1-axis meshes
        self._axis_sizes = (tuple(self.mesh.shape[a] for a in names)
                            if len(names) > 1 else None)
        if self.n & (self.n - 1) or self.n > MIN_CAPACITY:
            raise GraphError(
                f"mesh size {self.n} must be a power of two <= "
                f"{MIN_CAPACITY} so bucketed delta capacities shard evenly")
        self._arena_divisor = self.n

    #: sharded pass programs close over this executor's mesh/axis (via
    #: ``_lower`` and ``_state_tree_specs``), so the process-wide
    #: window-program share would cross-wire meshes — per-executor only
    _share_window_programs = False

    def place(self, device) -> None:
        """A sharded executor spans the whole mesh — it cannot be pinned
        to one device. Use a plain TpuExecutor for tenant placement, or
        the sharded path for one hot tenant across the mesh."""
        raise GraphError(
            "ShardedTpuExecutor spans the device mesh and cannot be "
            "placed on a single device; use TpuExecutor with "
            "GraphConfig(device=...) / placement='spread' instead")

    @property
    def device_label(self) -> str:
        return f"mesh[{self.n}]"

    def _ingress_placement(self):
        # queue buffers / stacked feeds shard their capacity axis over
        # the mesh so slot writes and padding land shard-local and the
        # window program dispatches SPMD
        return (self.mesh, self.axis)

    # -- bind: divisibility validation + sharded state placement -----------

    def _indexes_joins(self) -> bool:
        return False

    def bind(self, graph: FlowGraph) -> None:
        super().bind(graph)
        n = self.n
        for st in self.states.values():
            # a replicated counter every shard adds its own share to
            # would read whatever shard is asked
            if isinstance(st, dict) and "error" in st:
                st.pop("counters", None)
        #: node ids whose state is mesh-REPLICATED (Map params: every
        #: shard runs the full model on its delta slice — data parallel),
        #: vs the default key/row sharding of table/arena states
        self._replicated_ids = {
            node.id for node in graph.nodes
            if node.kind == "op" and node.op.kind == "map"
            and node.op.params is not None}
        self._knn_ids = set()
        for node in graph.nodes:
            if node.kind == "op" and node.op.kind == "knn":
                if isinstance(self.axis, tuple):
                    raise GraphError(
                        f"{node}: sharded k-NN's ring merge (ppermute) "
                        f"needs a 1-axis mesh; run knn graphs on the ICI "
                        f"mesh (make_mesh() without dcn=)")
                Q = node.inputs[0].spec.key_space
                D = node.inputs[1].spec.key_space
                if Q % n or D % n:
                    raise GraphError(
                        f"{node}: query space {Q} and corpus space {D} "
                        f"must be multiples of the mesh size {n}")
                if (D // n) % min(node.op.scan_chunk, D // n):
                    raise GraphError(
                        f"{node}: per-shard corpus {D // n} must be a "
                        f"multiple of scan_chunk {node.op.scan_chunk}")
                self._knn_ids.add(node.id)
                continue
            if node.kind != "op" or node.op.kind not in ("reduce", "join"):
                continue
            K = node.inputs[0].spec.key_space
            if K % n:
                raise GraphError(
                    f"{node}: key_space {K} must be a multiple of the mesh "
                    f"size {n} (round it up)")
            if node.op.kind == "reduce":
                from reflow_tpu.executors.lowerings import \
                    LINEAR_DEVICE_REDUCERS

                if node.op.how in LINEAR_DEVICE_REDUCERS:
                    # sparse-route overflow is surfaced through the same
                    # sticky per-node error scalar min/max use (ADVICE r2
                    # high: without this key the route_rows overflow flag
                    # would be dropped)
                    self.states[node.id]["error"] = jnp.zeros((), jnp.bool_)
                # min/max states (agg/wcnt/emitted tables) key-shard like
                # the linear ones; their error scalar ships in reduce_state
            if node.op.kind == "join":
                if node.op.arena_capacity % n:
                    raise GraphError(
                        f"{node}: arena_capacity {node.op.arena_capacity} "
                        f"must be a multiple of the mesh size {n}")
                # per-shard append counters and arena generations (one
                # scalar per mesh slot) + the sticky route-overflow flag
                # (large meshes route both delta sides to key owners via
                # all_to_all)
                self.states[node.id]["rcount"] = jnp.zeros((n,), jnp.int32)
                self.states[node.id]["gen"] = jnp.zeros((n,), jnp.int32)
                self.states[node.id]["error"] = jnp.zeros((), jnp.bool_)
                if layout_of(self.states[node.id]) == "multiset":
                    La = node.op.left_arena_capacity or node.op.arena_capacity
                    if La % n:
                        raise GraphError(
                            f"{node}: left_arena_capacity {La} must be a "
                            f"multiple of the mesh size {n}")
                    self.states[node.id]["lcount"] = jnp.zeros((n,),
                                                               jnp.int32)
                    self.states[node.id]["lgen"] = jnp.zeros((n,),
                                                             jnp.int32)
        # placement derives from the SAME per-leaf specs shard_map uses
        # (one source of truth: _state_tree_specs), so the bound layout
        # can never disagree with the pass programs' in_specs
        from jax.sharding import NamedSharding

        specs = self._state_tree_specs(self.states)
        self.states = {
            nid: jax.tree.map(
                lambda x, s: jax.device_put(x, NamedSharding(self.mesh, s)),
                st, specs[nid])
            for nid, st in self.states.items()}

    def _state_spec(self, x) -> P:
        if getattr(x, "ndim", 0) >= 1 and x.shape[0] % self.n == 0:
            return P(self.axis)
        return P()

    def _state_tree_specs(self, states):
        """Per-node shard_map partition specs: replicated nodes (Map
        params) get P() on every leaf regardless of divisibility — a
        weight matrix whose dim 0 happens to divide the mesh must NOT be
        row-sharded — and knn states use their per-leaf layout."""
        from reflow_tpu.parallel.shard_lowerings import knn_state_specs

        repl = getattr(self, "_replicated_ids", frozenset())
        knn_ids = getattr(self, "_knn_ids", frozenset())
        knn_axes = knn_state_specs(self.axis)

        pspec_ids = {
            node.id: node.op.param_specs for node in self.graph.nodes
            if node.kind == "op" and node.op.kind == "map"
            and node.op.param_specs is not None
        } if getattr(self, "graph", None) is not None else {}

        def specs(nid, st):
            if nid in pspec_ids:
                # tensor-parallel Map: params shard per the op's declared
                # specs (typically over the model axis)
                return {"params": pspec_ids[nid]}
            if nid in repl:
                return jax.tree.map(lambda _: P(), st)
            if nid in knn_ids:
                return {k: P(knn_axes[k]) if knn_axes[k] else P()
                        for k in st}
            return jax.tree.map(self._state_spec, st)

        return {nid: specs(nid, st) for nid, st in states.items()}

    def update_params(self, node: Node, params) -> None:
        super().update_params(node, params)
        if node.op.param_specs is not None:
            from jax.sharding import NamedSharding

            specs = self._state_tree_specs(
                {node.id: self.states[node.id]})[node.id]
            self.states[node.id] = jax.tree.map(
                lambda x, s: jax.device_put(x, NamedSharding(self.mesh, s)),
                self.states[node.id], specs)
        else:
            self.states[node.id] = replicate(self.states[node.id], self.mesh)

    def refresh_minmax(self, node: Node, batch) -> None:
        """Sharded latch refresh: replay rows reach their key's owner
        (the min/max comm policy), then the shared refresh kernel runs
        per shard on the owned key slice."""
        from reflow_tpu.executors.device_delta import to_device
        from reflow_tpu.executors.lowerings import minmax_refresh_core
        from reflow_tpu.parallel.shard_lowerings import deliver_to_owner

        d = to_device(batch, node.inputs[0].spec)
        K = node.inputs[0].spec.key_space
        n, axis, mesh = self.n, self.axis, self.mesh
        sig = ("mmrefresh", node.id, d.capacity)
        fn = self._cache.get(sig)
        if fn is None:
            op = node.op
            oshape, odt = tuple(node.spec.value_shape), node.spec.value_dtype
            Kl = K // n

            sizes = self._axis_sizes

            def body(st, dd):
                import jax.numpy as jnp
                base = (jax.lax.axis_index(axis) * Kl).astype(jnp.int32)
                dl, route_err = deliver_to_owner(dd, axis, n, Kl,
                                                 sizes=sizes)
                err = st["error"] | route_err
                st2 = minmax_refresh_core(op, Kl, oshape, odt,
                                          {**st, "error": err}, dl,
                                          key_offset=base)
                st2["error"] = (jax.lax.pmax(
                    st2["error"].astype(jnp.int32), axis) > 0)
                return st2

            from jax.sharding import PartitionSpec as P2

            sspec = self._state_tree_specs(
                {node.id: self.states[node.id]})[node.id]
            dspec = DeviceDelta(P2(axis), P2(axis), P2(axis))
            fn = self._cache[sig] = jax.jit(jax.shard_map(
                body, mesh=mesh, in_specs=(sspec, dspec),
                out_specs=sspec, check_vma=False), donate_argnums=0)
        self.states[node.id] = fn(self.states[node.id], d)

    # -- the SPMD pass program ---------------------------------------------

    def _lower(self, node: Node, state, ins):
        return lower_node_sharded(node, state, ins, self.axis, self.n,
                                  sizes=self._axis_sizes)

    def build_pass_fn(self, plan: List[Node], extra_egress=()):
        graph = self.graph
        mesh, axis = self.mesh, self.axis
        # the shared traversal from TpuExecutor (with this class's _lower
        # hook) becomes the per-shard body under shard_map
        local_pass = super().build_pass_fn(plan, extra_egress)
        sink_inputs = [(s.inputs[0].id, s.id) for s in graph.sinks]
        back_edges = [(l.back_input.id, l.id) for l in graph.loops
                      if l.back_input is not None]
        extra = tuple(extra_egress)
        dspec = DeviceDelta(P(axis), P(axis), P(axis))

        def _egress_ids(ingress_ids):
            # mirror of the traversal's reachability, capacities aside
            outs = set(ingress_ids)
            for node in plan:
                if (node.id in outs or
                        node.kind in ("source", "loop", "sink")):
                    continue
                if any(i.id in outs for i in node.inputs):
                    outs.add(node.id)
            eg = [sid for src, sid in sink_inputs if src in outs]
            eg += [lid for bid, lid in back_edges if bid in outs]
            eg += [nid for nid in extra if nid in outs]
            return eg

        def pass_fn(states, ingress):
            # ingress structure is static at trace time: derive the
            # shard_map partitioning specs for exactly this signature
            state_specs = self._state_tree_specs(states)
            in_specs = (state_specs, {nid: dspec for nid in ingress})
            out_specs = (state_specs, {eid: dspec
                                       for eid in _egress_ids(ingress)})
            fn = jax.shard_map(local_pass, mesh=mesh, in_specs=in_specs,
                           out_specs=out_specs, check_vma=False)
            return fn(states, ingress)

        return pass_fn
