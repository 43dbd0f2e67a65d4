"""Device mesh + shard placement.

Reference mapping:
- shard -> node placement: fnv64a(index,shard) mod 256 partitions ->
  jump-hash -> node (cluster.go:828-913). Here placement is *static block
  assignment onto a mesh axis*: once the shard list is padded to a
  multiple of the mesh size, device d owns the d-th contiguous block of
  it (shard_position // (n_padded // n_devices) == d) — what
  `bank_sharding`'s P(None, "shards", None) places. Elastic resize
  (cluster.go:1150's resize jobs streaming fragments node-to-node)
  becomes: change the mesh, re-put the
  banks — the durable store is the source of truth, so "resize" is a
  re-shard + recompile, not a data-migration protocol.
- mapReduce scatter-gather + reduce over HTTP (executor.go:2277-2415):
  the executor's single compiled program runs SPMD over the mesh; the
  shard-axis reduction (Count, TopN counts, BSI sums) lowers to psum/
  all-reduce on ICI within a slice and DCN across slices.
- replication (ReplicaN successor nodes, cluster.go:857): an optional
  leading `replica` mesh axis over which banks are *replicated*
  (PartitionSpec None on the shard axes), giving query failover the same
  way replicas served reads in the reference.

Multi-host: under `jax.distributed` initialization the same code spans
hosts — the mesh covers all global devices and XLA routes inter-host
collectives over DCN. No gossip/coordinator consensus is needed: the
single controller owns schema and placement (survey §7.6).
"""

from __future__ import annotations

from typing import List, Optional, Sequence

import numpy as np


class ShardPlacement:
    """Static block placement of a shard list onto n devices."""

    def __init__(self, n_devices: int):
        self.n = n_devices

    def pad(self, shards: Sequence[int], floor: int = 0) -> List[int]:
        """Pad the shard list to a multiple of n with provably-absent shard
        ids (>= max(floor, max(shards)+1)); absent shards materialize as
        all-zero bank columns and contribute nothing to any reduction.
        `floor` must exceed every *existing* shard of the index, not just
        the requested subset — otherwise padding could alias real shards
        the caller excluded."""
        shards = list(shards)
        if not shards:
            shards = [0]
        rem = (-len(shards)) % self.n
        if rem:
            pad_base = max(floor, max(shards) + 1)
            shards = shards + [pad_base + i for i in range(rem)]
        return shards

    def device_of(self, shards: Sequence[int], shard: int) -> int:
        """Which shard device owns a shard: the index of its block."""
        padded = self.pad(shards)
        return padded.index(shard) // (len(padded) // self.n)

    def blocks(self, n_shards: int) -> List[slice]:
        """Device d's positions in a shard list of `n_shards` (a multiple
        of n, as pad() leaves it): n contiguous slices, in device order."""
        if n_shards % self.n:
            raise ValueError(f"{n_shards} shards do not split over "
                             f"{self.n} devices; pad the list first")
        per = n_shards // self.n
        return [slice(d * per, (d + 1) * per) for d in range(self.n)]


class MeshContext:
    """Wraps a 1-or-2-axis mesh: optional 'replica' axis x 'shards' axis."""

    SHARD_AXIS = "shards"
    REPLICA_AXIS = "replica"

    def __init__(self, devices: Optional[Sequence] = None,
                 replicas: int = 1):
        import jax
        from jax.sharding import Mesh

        devices = list(devices if devices is not None else jax.devices())
        if replicas > 1:
            if len(devices) % replicas:
                raise ValueError(
                    f"{len(devices)} devices not divisible by "
                    f"{replicas} replicas")
            arr = np.array(devices).reshape(replicas, -1)
            self.mesh = Mesh(arr, (self.REPLICA_AXIS, self.SHARD_AXIS))
            self.n_shard_devices = arr.shape[1]
        else:
            self.mesh = Mesh(np.array(devices), (self.SHARD_AXIS,))
            self.n_shard_devices = len(devices)
        self.replicas = replicas
        self.placement = ShardPlacement(self.n_shard_devices)

    # -- shardings ----------------------------------------------------------

    def bank_sharding(self):
        """[rows, shards, words]: shard axis split across devices, rows and
        words replicated within a shard device."""
        from jax.sharding import NamedSharding, PartitionSpec as P
        return NamedSharding(self.mesh, P(None, self.SHARD_AXIS, None))

    def row_sharding(self):
        """[shards, words] query-result rows."""
        from jax.sharding import NamedSharding, PartitionSpec as P
        return NamedSharding(self.mesh, P(self.SHARD_AXIS, None))

    def replicated(self):
        from jax.sharding import NamedSharding, PartitionSpec as P
        return NamedSharding(self.mesh, P())

    def cache_key(self) -> str:
        dev_ids = tuple(d.id for d in self.mesh.devices.flat)
        return f"mesh{self.replicas}x{self.n_shard_devices}:{hash(dev_ids)}"

    def put_bank(self, host):
        import jax
        return jax.device_put(host, self.bank_sharding())

    def put_bank_blocks(self, shape, build_block):
        """A [rows, shards, words] bank placed as `bank_sharding` places
        it, built one shard device's block at a time:
        `build_block(positions)` returns the host block
        [rows, len(positions), words] of that slice of the shard list.
        A block's upload (to each replica's device that holds it) is
        started and the next block is built meanwhile; the host never
        holds more than the block being built and the one in flight.
        Under `jax.distributed` a process builds and places only the
        blocks of its own devices."""
        import jax
        sharding = self.bank_sharding()
        me = jax.process_index()
        # mesh.devices is [shard] or [replica, shard].
        by_block = np.asarray(self.mesh.devices).reshape(
            -1, self.n_shard_devices).T
        parts, in_flight = [], []
        for positions, devices in zip(self.placement.blocks(shape[1]),
                                      by_block):
            devices = [d for d in devices if d.process_index == me]
            if not devices:
                continue
            block = build_block(positions)
            jax.block_until_ready(in_flight)
            in_flight = [jax.device_put(block, d) for d in devices]
            parts.extend(in_flight)
        return jax.make_array_from_single_device_arrays(
            tuple(shape), sharding, parts)

    def put_row(self, arr):
        """Commit a [shards, words] (or [k, shards, words]) array to the
        mesh with the shard axis split."""
        import jax
        from jax.sharding import NamedSharding, PartitionSpec as P
        spec = (P(self.SHARD_AXIS, None) if arr.ndim == 2
                else P(None, self.SHARD_AXIS, None))
        return jax.device_put(arr, NamedSharding(self.mesh, spec))

    def pad_shards(self, shards: Sequence[int], floor: int = 0) -> List[int]:
        return self.placement.pad(shards, floor)
