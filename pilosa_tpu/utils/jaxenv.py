"""Process-level JAX set-up shared by every entry point that compiles
(cmd_server, bench.py, benches/*): where the persistent compile cache
lives, and the description of the devices the process ended up on."""

from __future__ import annotations

import os
from typing import Any, Dict, List

CHECKOUT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))


def enable_compile_cache() -> str:
    """Arm JAX's persistent compilation cache before the first compile
    and return its directory.

    JAX_COMPILATION_CACHE_DIR, when set, is the only placement: JAX
    reads it itself and this function names no other directory.
    Otherwise the cache sits at <checkout>/.jax_cache — a fixed path,
    because the directory is part of what a later process must find
    again; a temp name, pid or timestamp would never hit. The server
    compiles many sub-second programs (one per query signature), so
    the keep-thresholds drop to zero: a cold start that recompiled only
    the "cheap" ones would still pay for hundreds of them."""
    import jax

    if not os.environ.get("JAX_COMPILATION_CACHE_DIR"):
        jax.config.update("jax_compilation_cache_dir",
                          os.path.join(CHECKOUT, ".jax_cache"))
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    return jax.config.jax_compilation_cache_dir


def describe_devices() -> List[Dict[str, Any]]:
    """One entry per device THIS process addresses: id, platform, kind
    and the allocator's byte counters (None where the backend keeps
    none — the CPU backend's memory_stats() is None). Under
    jax.distributed, jax.devices() also lists the other hosts' devices,
    whose memory_stats() raises; each host's server answers for its own
    (jax.device_count() is the global figure). Initialises the backend;
    a platform that cannot start raises here."""
    import jax

    out = []
    for d in jax.local_devices():
        ms = d.memory_stats() or {}
        out.append({
            "id": int(d.id),
            "platform": d.platform,
            "kind": d.device_kind,
            "bytesInUse": ms.get("bytes_in_use"),
            "peakBytesInUse": ms.get("peak_bytes_in_use"),
            "bytesLimit": ms.get("bytes_limit"),
        })
    return out
