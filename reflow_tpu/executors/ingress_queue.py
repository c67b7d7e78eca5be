"""Device-resident ingress queue for compiled mega-ticks.

One K-tick commit window is one device execution
(``TpuExecutor.run_window``): the scan body consumes one queue *slot*
— a ``(tick, source)`` cell of a preallocated, statically-shaped delta
buffer — per tick per source. The queue replaces the host-side
``_stack_feeds`` restack (allocate + copy + upload [K, C] arrays every
window) with index-updates into persistent device buffers:

- buffers are allocated ONCE per (plan, capacity, K) signature and
  reused window after window (they live in the executor's program
  cache, invalidated with it on rebind);
- each host micro-batch is padded to its source's capacity bucket and
  written into its slot with a jitted ``.at[t].set`` (the slot index is
  a traced scalar, so writes never recompile);
- an empty slot (window padding — a tick where this source had no
  deltas) is overwritten from a cached device-resident zero image: no
  host transfer at all, and no stale rows from the previous window can
  leak (every slot is written every window);
- capacity is negotiated with the arena up front: the caller validates
  the per-source caps through the same static propagation the per-tick
  path uses (``arena.propagate_plan_caps``) BEFORE any device memory is
  reserved.

The buffers ARE donated to the window program (alongside the state
pytree): the program hands back a fresh zeroed stack in (potentially)
the same device memory, and the caller hands it back via the retire
step (:meth:`DeviceIngressQueue.retire`), so the window no longer
holds an extra live copy of every source buffer across the dispatch.

**Generation rotation (pipelined windows).** The buffers come in
*generations* — independent full buffer sets. ``write`` targets the
current *staging* generation; :meth:`seal` hands that generation to a
dispatch (its buffers now belong to the in-flight window program via
donation) and the next ``write`` rotates onto a free generation, so
window N+1's slot writes NEVER touch a buffer set an in-flight program
owns. :meth:`retire` re-adopts the program's returned zeroed stack
into the sealed generation and frees it for reuse. Generations are
allocated lazily: a depth-1 caller (seal → dispatch → retire → seal)
ping-pongs on generation 0 forever and pays for exactly one buffer
set, same as before pipelining; a depth-D pump allocates at most D
sets. The pump bounds the in-flight depth — the queue just rotates.

``placement`` pins the buffers: a ``jax.Device`` commits them (and the
zero images, and therefore every slot write and the window program
itself) to that device — the serve tier's tenant-placement path — and a
``(mesh, axis)`` pair gives them a ``NamedSharding`` along the delta
(capacity) axis, so slot writes and padding land shard-local and the
window program runs SPMD over the mesh (the sharded hot-tenant path).
Bucketed capacities are powers of two >= MIN_CAPACITY >= the mesh size,
so the capacity axis always divides.

``slot_nbytes`` is the admission-side view of the same reservation: the
device bytes one host batch will occupy in its queue slot, used by the
serve frontend to key the ``AdmissionBudget`` on device memory pressure
instead of host payload bytes.
"""

from __future__ import annotations

from typing import Dict, List, Optional

import numpy as np

import jax

from reflow_tpu.executors.device_delta import (DeviceDelta, bucket_capacity,
                                               check_weight_mass)
from reflow_tpu.utils.faults import DeliveryError

__all__ = ["DeviceIngressQueue", "slot_nbytes"]

_I32 = np.iinfo(np.int32)


def slot_nbytes(spec, rows: int) -> int:
    """Device bytes a host batch of ``rows`` reserves in its queue slot:
    the capacity bucket times the per-row footprint (int32 key + int32
    weight + the value payload). This is what admission should charge
    when backpressure tracks device memory, not host payload size."""
    cap = bucket_capacity(int(rows))
    per_val = int(np.prod(spec.value_shape)) if spec.value_shape else 1
    return cap * (4 + 4 + per_val * np.dtype(spec.value_dtype).itemsize)


def _write_slot(bufs: DeviceDelta, t, keys, values, weights) -> DeviceDelta:
    # t is traced (dynamic_update_slice), so one compilation covers every
    # slot of a buffer shape; donated bufs make the update in place
    return DeviceDelta(bufs.keys.at[t].set(keys),
                       bufs.values.at[t].set(values),
                       bufs.weights.at[t].set(weights))


# one writer for every queue: jax caches the compiled update per
# (shape, dtype, sharding), so same-shaped queues across graphs (and
# devices) share the compilation instead of re-jitting per queue
_WRITER = jax.jit(_write_slot, donate_argnums=0)

class DeviceIngressQueue:
    """Per-source [K, cap] delta buffers plus their jitted slot writer.

    ``specs``/``caps`` map source node ids to their Spec and padded
    per-tick row capacity; ``k`` is the window length in ticks.
    ``placement`` is None (default device), a ``jax.Device`` (commit the
    buffers — and every dispatch over them — to that device), or a
    ``(mesh, axis)`` pair (NamedSharding the capacity axis over the
    mesh's ``axis``).
    """

    def __init__(self, specs: Dict[int, object], caps: Dict[int, int],
                 k: int, placement=None):
        import jax.numpy as jnp

        self.k = int(k)
        self.caps = dict(caps)
        self._specs = dict(specs)
        self.placement = placement
        self.writes = 0
        self.zero_writes = 0
        self.generations = 0
        self.nbytes = 0
        self.gen_nbytes = sum(k * slot_nbytes(specs[nid], cap)
                              for nid, cap in caps.items())
        self._zero: Dict[int, tuple] = {}
        for nid, cap in sorted(caps.items()):
            spec = specs[nid]
            vshape = tuple(spec.value_shape)
            # the padding image: device-resident so an empty slot's write
            # is a pure on-device index-update (zero host bytes moved);
            # shared read-only across generations
            self._zero[nid] = (
                self._put(jnp.zeros((cap,), jnp.int32), stacked=False),
                self._put(jnp.zeros((cap,) + vshape, spec.value_dtype),
                          stacked=False),
                self._put(jnp.zeros((cap,), jnp.int32), stacked=False))
        #: generation -> {nid: DeviceDelta}; _staging is the generation
        #: writes land in, _inflight the sealed (donated, program-owned)
        #: ones in dispatch order, _free the reusable ones (LIFO so the
        #: depth-1 flow ping-pongs on generation 0)
        self._gens: List[Dict[int, DeviceDelta]] = []
        self._free: List[int] = []
        self._inflight: List[int] = []
        self._staging: Optional[int] = None
        self._alloc_gen()  # generation 0, eagerly — same memory as before
        self._writer = _WRITER

    def _alloc_gen(self) -> int:
        import jax.numpy as jnp

        bufs: Dict[int, DeviceDelta] = {}
        for nid, cap in sorted(self.caps.items()):
            spec = self._specs[nid]
            vshape = tuple(spec.value_shape)
            bufs[nid] = DeviceDelta(
                self._put(jnp.zeros((self.k, cap), jnp.int32), stacked=True),
                self._put(jnp.zeros((self.k, cap) + vshape, spec.value_dtype),
                          stacked=True),
                self._put(jnp.zeros((self.k, cap), jnp.int32), stacked=True))
        gen = len(self._gens)
        self._gens.append(bufs)
        self._free.append(gen)
        self.generations += 1
        self.nbytes += self.gen_nbytes
        return gen

    def _put(self, x, *, stacked: bool):
        """Apply the queue's placement to one freshly-allocated buffer:
        commit to the pinned device, or shard the capacity axis (dim 1 of
        a [K, cap, ...] stack, dim 0 of a [cap, ...] zero image) over the
        mesh. None = wherever jax's default device is."""
        if self.placement is None:
            return x
        if isinstance(self.placement, tuple):
            from jax.sharding import NamedSharding, PartitionSpec

            mesh, axis = self.placement
            dims = ((None, axis) if stacked else (axis,))
            dims = dims + (None,) * (x.ndim - len(dims))
            return jax.device_put(x, NamedSharding(mesh,
                                                   PartitionSpec(*dims)))
        return jax.device_put(x, self.placement)

    # -- generation rotation -----------------------------------------------

    @property
    def in_flight(self) -> int:
        """Sealed generations currently owned by dispatched programs."""
        return len(self._inflight)

    def _ensure_staging(self) -> int:
        if self._staging is None:
            if not self._free:
                self._alloc_gen()
            self._staging = self._free.pop()
        return self._staging

    def seal(self) -> int:
        """Hand the staging generation to a dispatch: its buffers now
        belong to the window program (donation) and the next ``write``
        rotates onto a free generation. Returns the generation id the
        caller must :meth:`retire` (or :meth:`cancel`) later."""
        gen = self._ensure_staging()
        self._staging = None
        self._inflight.append(gen)
        return gen

    def retire(self, gen: int, stacked: Dict[int, DeviceDelta]) -> None:
        """Adopt the window program's returned (zeroed, donated-memory)
        stack back into generation ``gen`` and free it for restaging.
        The stack the program consumed was DONATED — the old buffer
        handles are dead — so the caller must hand the pass-through
        output back here before the generation is written again."""
        if gen not in self._inflight:
            raise ValueError(f"generation {gen} is not in flight")
        if sorted(stacked) != sorted(self.caps):
            raise ValueError(
                f"retire stack keys {sorted(stacked)} != queue sources "
                f"{sorted(self.caps)}")
        # re-assert the queue's placement on the adopted buffers: the
        # compiler picks the window program's output sharding freely, so
        # a sharded stack can come back replicated — a no-op when the
        # sharding already matches, a one-time reshard when it doesn't
        # (without it, every later slot write loses shard-locality).
        if self.placement is not None:
            stacked = {nid: jax.tree.map(
                lambda x: self._put(x, stacked=True), dd)
                for nid, dd in stacked.items()}
        self._gens[gen] = dict(stacked)
        self._inflight.remove(gen)
        self._free.append(gen)

    def cancel(self, gen: int) -> None:
        """Un-seal a generation whose dispatch never happened. Its
        buffers are still live (nothing was donated), so it goes
        straight back to the free list — every slot is rewritten every
        window, so stale rows can't leak."""
        if gen in self._inflight:
            self._inflight.remove(gen)
            self._free.append(gen)

    def rebind(self, stacked: Dict[int, DeviceDelta]) -> None:
        """Legacy single-generation surface: retire the OLDEST in-flight
        generation (the depth-1 flow seals exactly one at a time)."""
        if not self._inflight:
            raise ValueError("rebind with no sealed generation in flight")
        self.retire(self._inflight[0], stacked)

    # -- slot writes --------------------------------------------------------

    def write(self, t: int, nid: int, batch) -> None:
        """Fill slot ``(t, nid)`` of the staging generation from a host
        batch (zero-row batches write the cached zero image). Every slot
        must be written every window — the buffers persist, so a skipped
        slot would replay a previous window's rows."""
        cap = self.caps[nid]
        n = len(batch)
        if n > cap:
            raise ValueError(
                f"batch of {n} rows exceeds queue slot capacity {cap} "
                f"for node {nid}")
        gen = self._ensure_staging()
        bufs = self._gens[gen]
        if n == 0:
            keys, values, weights = self._zero[nid]
            self.zero_writes += 1
        else:
            check_weight_mass(batch)   # same host-boundary guard as upload
            bkeys = np.asarray(batch.keys)
            if bkeys.size and (int(bkeys.max()) > _I32.max
                               or int(bkeys.min()) < _I32.min):
                # the slot buffers are int32: assigning int64 keys would
                # silently wrap anything >= 2^31 — refuse at the host
                # boundary instead of folding a corrupted key
                raise DeliveryError(
                    f"node {nid}: batch keys exceed the int32 ingress "
                    f"key range [{_I32.min}, {_I32.max}] "
                    f"(max {int(bkeys.max())}, min {int(bkeys.min())})")
            keys, values, weights = self._pad_host(nid, n, cap, bkeys, batch)
        bufs[nid] = self._writer(bufs[nid], t, keys, values, weights)
        self.writes += 1

    def _pad_host(self, nid: int, n: int, cap: int, bkeys, batch):
        """Capacity-padded host images of one batch's columns — FRESH
        arrays every call, never a reused scratch set. The slot writer's
        dispatch is asynchronous and the runtime may read a host argument
        after the call returns (the CPU client aliases a suitably aligned
        buffer outright; an accelerator copies it when its transfer
        runs), so an array handed to it must never be written again:
        refilling a shared scratch for slot t+1 rewrote slot t's rows
        under the in-flight write."""
        spec = self._specs[nid]
        vshape = tuple(spec.value_shape)
        keys = np.zeros(cap, np.int32)
        values = np.zeros((cap,) + vshape, spec.value_dtype)
        weights = np.zeros(cap, np.int32)
        keys[:n] = bkeys
        weights[:n] = batch.weights
        values[:n] = np.asarray(batch.values).reshape((n,) + vshape)
        return keys, values, weights

    def stacked(self) -> Dict[int, DeviceDelta]:
        """The staging generation's contents as the [K, cap] ingress
        stack the window program scans — same pytree shape
        ``_stack_feeds`` produces, so the compiled programs are shared
        between paths."""
        return dict(self._gens[self._ensure_staging()])
