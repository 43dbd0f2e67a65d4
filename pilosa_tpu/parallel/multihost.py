"""Multi-host SPMD dryrun: two `jax.distributed` processes, one global
mesh, cross-host reductions.

Reference analog: the scatter-gather HTTP fan-out between nodes
(/root/reference/executor.go:2277-2415) and its NCCL-free HTTP data
plane. The TPU-native story (SURVEY §7 step 6): `jax.distributed`
initializes a process group, the mesh spans every host's devices, and
XLA lowers the shard-axis reductions to collectives that ride ICI
within a host/slice and DCN across hosts — no NCCL/MPI code here, just
shardings.

`python -m pilosa_tpu.parallel.multihost` runs the coordinator-side
parent: it spawns two child processes on localhost (each with 4 virtual
CPU devices), initializes jax.distributed in both, builds one
(2 hosts x 4 devices) shard-axis mesh, and runs the framework's fused
Count(Intersect) kernel over a globally-sharded bank assembled with
`jax.make_array_from_callback` — each process contributes only the
shards its addressable devices own, exactly how per-host fragment data
feeds a pod-wide query. The result is verified against a host numpy
model in every process.
"""

from __future__ import annotations

import os
import subprocess
import sys

import numpy as np

N_PROCESSES = 2
DEVICES_PER_PROCESS = 4
ROWS = 8
SHARDS = N_PROCESSES * DEVICES_PER_PROCESS
WORDS = 512  # small: the point is the cross-process lowering


def cpu_multiprocess_supported() -> bool:
    """Whether this jax/jaxlib can run cross-process computations on
    the CPU backend: XLA:CPU only implements multi-process collectives
    through a CpuCollectives plugin (gloo over TCP), so both the
    jaxlib hooks and the jax config knob that selects them must exist.
    The dryrun (and its tier-1 test) runs where this holds and skips
    precisely where it cannot — older wheels raise
    "Multiprocess computations aren't implemented on the CPU backend"
    at dispatch time."""
    try:
        import jax
        from jaxlib import xla_client
    except Exception:
        return False
    return (hasattr(xla_client._xla, "make_gloo_tcp_collectives")
            and "jax_cpu_collectives_implementation"
            in getattr(jax.config, "values", {}))


def child(process_id: int, coordinator: str) -> None:
    import jax

    jax.config.update("jax_platforms", "cpu")
    # XLA:CPU needs an explicit collectives plugin for cross-process
    # computations (TPU/GPU backends bring their own); gloo-over-TCP is
    # the portable one. Without this, dispatch fails with
    # "Multiprocess computations aren't implemented on the CPU
    # backend" on every jaxlib that doesn't default it.
    jax.config.update("jax_cpu_collectives_implementation", "gloo")
    jax.distributed.initialize(coordinator_address=coordinator,
                               num_processes=N_PROCESSES,
                               process_id=process_id)
    import jax.numpy as jnp
    from jax.sharding import NamedSharding, PartitionSpec as P

    from pilosa_tpu.ops.bitset import popcount
    from pilosa_tpu.parallel import MeshContext

    assert len(jax.devices()) == SHARDS, jax.devices()
    assert len(jax.local_devices()) == DEVICES_PER_PROCESS
    # What cmd_server and GET /info call: it must answer for this
    # process's devices only — memory_stats() raises on the other
    # host's.
    from pilosa_tpu.utils.jaxenv import describe_devices
    assert [d["id"] for d in describe_devices()] == \
        [d.id for d in jax.local_devices()]
    mesh = MeshContext()  # all global devices, shard axis
    sharding = NamedSharding(mesh.mesh, P(None, MeshContext.SHARD_AXIS,
                                          None))

    # Every process derives the same global model data from the seed;
    # make_array_from_callback asks each process only for the blocks its
    # own devices hold (per-host fragment data in production).
    rng = np.random.default_rng(123)
    a = rng.integers(0, 2**32, (ROWS, SHARDS, WORDS), dtype=np.uint32)
    b = rng.integers(0, 2**32, (ROWS, SHARDS, WORDS), dtype=np.uint32)
    ga = jax.make_array_from_callback(a.shape, sharding,
                                      lambda idx: a[idx])
    gb = jax.make_array_from_callback(b.shape, sharding,
                                      lambda idx: b[idx])

    # graftlint: disable=GL006 — multihost dry-run probe kernel,
    # compiled once per child process; no serving executor exists here.
    @jax.jit
    def count_intersect(x, y):
        # The executor's fused hot kernel: AND + popcount reduced over
        # the sharded axis — lowers to a cross-process all-reduce.
        return popcount(jnp.bitwise_and(x, y), axis=(-2, -1))

    got = np.asarray(count_intersect(ga, gb))
    want = np.bitwise_count(a & b).sum(axis=(1, 2)) if \
        hasattr(np, "bitwise_count") else None
    if want is not None:
        assert np.array_equal(got, want), (got, want)
    print(f"multihost child {process_id}: OK counts={got[:3].tolist()}...",
          flush=True)
    jax.distributed.shutdown()


def main() -> int:
    if "--child" in sys.argv:
        i = sys.argv.index("--child")
        child(int(sys.argv[i + 1]), sys.argv[i + 2])
        return 0
    import socket

    with socket.socket() as s:
        s.bind(("127.0.0.1", 0))
        port = s.getsockname()[1]
    coordinator = f"127.0.0.1:{port}"
    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"
    env["XLA_FLAGS"] = (env.get("XLA_FLAGS", "") +
                        " --xla_force_host_platform_device_count="
                        f"{DEVICES_PER_PROCESS}").strip()
    procs = [subprocess.Popen(
        [sys.executable, "-m", "pilosa_tpu.parallel.multihost",
         "--child", str(i), coordinator], env=env)
        for i in range(N_PROCESSES)]
    rc = 0
    for p in procs:
        try:
            rc |= p.wait(timeout=300)
        except subprocess.TimeoutExpired:
            p.kill()
            rc |= 1
    print(f"multihost dryrun: {'OK' if rc == 0 else 'FAILED'} "
          f"({N_PROCESSES} processes x {DEVICES_PER_PROCESS} devices)")
    return rc


if __name__ == "__main__":
    sys.exit(main())
