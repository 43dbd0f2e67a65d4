"""Process-level JAX set-up (pilosa_tpu/utils/jaxenv.py): where the
persistent compile cache goes, what GET /info says about the devices,
and which imports stay clear of jax."""

import json
import os
import subprocess
import sys

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

# Each case runs in a fresh interpreter: the cache directory latches at
# the first compile, and arming it in the pytest process would redirect
# every later test's compiles.
_COMPILE = """
import json, os, sys
sys.path.insert(0, %(repo)r)
import jax
updated = []
orig = jax.config.update
def spy(name, value):
    updated.append(name)
    return orig(name, value)
jax.config.update = spy
from pilosa_tpu.utils.jaxenv import enable_compile_cache
d = enable_compile_cache()
jax.config.update = orig
import jax.numpy as jnp
jax.jit(lambda x: x * 3 + 1)(jnp.arange(16)).block_until_ready()
print(json.dumps({
    "dir": d, "updated": updated,
    "files": sorted(os.listdir(d)) if os.path.isdir(d) else [],
    "min_secs": jax.config.jax_persistent_cache_min_compile_time_secs,
    "min_bytes": jax.config.jax_persistent_cache_min_entry_size_bytes}))
""" % {"repo": REPO}


def _run(src, **env):
    full = {k: v for k, v in os.environ.items()
            if k != "JAX_COMPILATION_CACHE_DIR"}
    full.update(JAX_PLATFORMS="cpu", **env)
    r = subprocess.run([sys.executable, "-c", src], env=full,
                       capture_output=True, text=True, timeout=120)
    assert r.returncode == 0, r.stderr[-2000:]
    return json.loads(r.stdout.strip().splitlines()[-1])


def test_compile_cache_goes_where_the_environment_says(tmp_path):
    """JAX_COMPILATION_CACHE_DIR set: the compiled program lands there
    and the code sets no directory of its own."""
    d = str(tmp_path / "cache")
    out = _run(_COMPILE, JAX_COMPILATION_CACHE_DIR=d)
    assert out["dir"] == d
    assert "jax_compilation_cache_dir" not in out["updated"]
    assert any(f.endswith("-cache") for f in out["files"]), out


def test_compile_cache_defaults_to_the_checkout():
    """Unset: <checkout>/.jax_cache — a fixed path, never a temp name."""
    out = _run(_COMPILE)
    assert out["dir"] == os.path.join(REPO, ".jax_cache")
    assert "jax_compilation_cache_dir" in out["updated"]
    assert any(f.endswith("-cache") for f in out["files"])


def test_compile_cache_keeps_subsecond_programs(tmp_path):
    """The server's programs compile in well under JAX's default 1 s
    keep-threshold; with the thresholds at zero even this test's
    trivial jit is kept."""
    out = _run(_COMPILE, JAX_COMPILATION_CACHE_DIR=str(tmp_path))
    assert out["min_secs"] == 0.0 and out["min_bytes"] == 0
    assert out["files"]


def test_describe_devices_names_every_device():
    from pilosa_tpu.utils.jaxenv import describe_devices
    devs = describe_devices()
    assert len(devs) == 8 and [d["id"] for d in devs] == list(range(8))
    for d in devs:
        assert d["platform"] == "cpu" and d["kind"]
        # The CPU backend keeps no allocator counters: None, not 0.
        assert d["bytesInUse"] is None and d["bytesLimit"] is None


def test_info_carries_the_devices_stanza(live_server):
    import urllib.request
    base, api, _ = live_server
    with urllib.request.urlopen(base + "/info") as r:
        info = json.loads(r.read())
    assert [d["platform"] for d in info["devices"]] == ["cpu"] * 8
    assert set(info["devices"][0]) == {
        "id", "platform", "kind", "bytesInUse", "peakBytesInUse",
        "bytesLimit"}
    assert info["meshDevices"] == 1
    assert info["native"] == {"loaded": True, "error": ""}
    assert "compileCacheDir" in info


@pytest.mark.parametrize("module", ["pilosa_tpu.storage",
                                    "pilosa_tpu.native"])
def test_storage_codec_imports_without_jax(module):
    """chip_smoke.py's parent builds roaring payloads with the storage
    codec and must never load jax (a parent that has touched JAX holds
    the chip)."""
    src = (f"import sys; sys.path.insert(0, {REPO!r}); import {module}; "
           "print('jax' in sys.modules)")
    r = subprocess.run([sys.executable, "-c", src], capture_output=True,
                       text=True, timeout=60)
    assert r.returncode == 0, r.stderr
    assert r.stdout.strip() == "False"


def test_package_constants_still_resolve():
    import pilosa_tpu
    from pilosa_tpu.ops import bitset
    assert pilosa_tpu.SHARD_WIDTH == bitset.SHARD_WIDTH == 1 << 20
    assert pilosa_tpu.WORDS_PER_SHARD == bitset.WORDS_PER_SHARD
    with pytest.raises(AttributeError):
        pilosa_tpu.no_such_name
