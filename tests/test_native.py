"""Native C++ host-runtime library (native/pilosa_native.cpp) tests.

Cross-checks the native roaring codec against the pure-Python reference
semantics in storage/roaring.py: identical parse results, byte-identical
serialization, identical error behavior on corrupt input."""

import struct

import numpy as np
import pytest

from pilosa_tpu import native
from pilosa_tpu.storage.roaring import (
    Bitmap, encode_op, OP_ADD, OP_ADD_BATCH, OP_REMOVE, OP_REMOVE_BATCH,
)

pytestmark = pytest.mark.skipif(
    not native.available(), reason="native library unavailable")


def _python_bitmap(data: bytes, tolerate_torn_tail: bool = False) -> Bitmap:
    """Force the pure-Python reader regardless of native availability."""
    b = Bitmap.__new__(Bitmap)
    b.__init__()
    with native.force_python():
        b.read_bytes(data, tolerate_torn_tail=tolerate_torn_tail)
    return b


def _mixed_bitmap() -> Bitmap:
    rng = np.random.default_rng(7)
    b = Bitmap()
    # array container
    b.add_batch(rng.choice(1 << 16, 300, replace=False).astype(np.uint64))
    # bitmap container
    b.add_batch((1 << 16) + rng.choice(1 << 16, 50000,
                                       replace=False).astype(np.uint64))
    # run container
    b.add_batch(np.arange(5 << 16, (5 << 16) + 20000, dtype=np.uint64))
    # full container (cardinality 65536 → card-1 wraps to uint16 max)
    b.add_batch(np.arange(9 << 16, 10 << 16, dtype=np.uint64))
    return b


def test_native_parse_matches_python():
    data = _mixed_bitmap().write_bytes()
    keys, words, op_n, _ = native.roaring_load(data)
    pb = _python_bitmap(data)
    assert keys == sorted(pb.containers)
    assert op_n == 0
    from pilosa_tpu.storage.roaring import _as_dense
    for i, k in enumerate(keys):
        assert np.array_equal(words[i], _as_dense(pb.containers[k]))


def test_native_serialize_byte_identical():
    b = _mixed_bitmap()
    keys = sorted(b.containers)
    nk = np.array(keys, dtype=np.uint64)
    nw = np.stack([b.containers[k] for k in keys])
    with native.force_python():
        python_bytes = b.write_bytes()
    assert native.roaring_serialize(nk, nw) == python_bytes


def test_native_ops_replay():
    b = _mixed_bitmap()
    data = b.write_bytes()
    data += encode_op(OP_ADD, (20 << 16) + 5)
    data += encode_op(OP_ADD_BATCH,
                      values=np.array([1, 2, (21 << 16) + 3], dtype=np.uint64))
    data += encode_op(OP_REMOVE, (20 << 16) + 5)
    data += encode_op(OP_REMOVE_BATCH, values=np.array([2], dtype=np.uint64))
    keys, words, op_n, _ = native.roaring_load(data)
    pb = _python_bitmap(data)
    assert op_n == 6  # 1 add + 3 batch-adds + 1 remove + 1 batch-remove
    assert keys == sorted(pb.containers)
    from pilosa_tpu.storage.roaring import _as_dense
    for i, k in enumerate(keys):
        assert np.array_equal(words[i], _as_dense(pb.containers[k]))
    # container 20<<16 emptied by the remove op must not be materialized
    assert (20 << 16) >> 16 not in keys


def test_native_rejects_bad_magic():
    with pytest.raises(ValueError, match="magic"):
        native.roaring_load(struct.pack("<HHI", 999, 0, 0))


def test_native_rejects_corrupt_op_checksum():
    data = Bitmap([1, 2, 3]).write_bytes()
    op = bytearray(encode_op(OP_ADD, 42))
    op[9] ^= 0xFF  # flip a checksum byte
    with pytest.raises(ValueError, match="checksum"):
        native.roaring_load(data + bytes(op))


def test_native_empty_bitmap_roundtrip():
    data = Bitmap().write_bytes()
    keys, words, op_n, _ = native.roaring_load(data)
    assert keys == [] and words.shape == (0, 1024) and op_n == 0


def test_native_fnv1a32_matches_python():
    from pilosa_tpu.storage.roaring import _FNV_OFFSET, _FNV_PRIME

    def py_fnv(*chunks):
        h = _FNV_OFFSET
        for chunk in chunks:
            for byte in chunk:
                h = ((h ^ byte) * _FNV_PRIME) & 0xFFFFFFFF
        return h

    cases = [(b"",), (b"\x00",), (b"hello",), (b"abc", b"defgh"),
             (bytes(range(256)),), (np.arange(1000, dtype="<u8")
                                    .tobytes(),)]
    for chunks in cases:
        assert native.fnv1a32(chunks) == py_fnv(*chunks)


def test_popcount_kernels_match_numpy():
    rng = np.random.default_rng(3)
    a = rng.integers(0, 2**63, 2048, dtype=np.uint64)
    b = rng.integers(0, 2**63, 2048, dtype=np.uint64)
    assert native.popcount(a) == int(np.bitwise_count(a).sum())
    assert native.intersection_count(a, b) == \
        int(np.bitwise_count(a & b).sum())
    rows = a.reshape(8, -1)
    assert np.array_equal(native.row_popcounts(rows),
                          np.bitwise_count(rows).sum(axis=1))


def test_bitmap_roundtrip_through_native_paths():
    """Full loop: Python-built bitmap → native serialize → native parse."""
    b = _mixed_bitmap()
    b2 = Bitmap.from_bytes(b.write_bytes())
    assert sorted(b.containers) == sorted(b2.containers)
    assert b.count() == b2.count()
    assert np.array_equal(b.slice(), b2.slice())


def test_build_masks_matches_python_scatter():
    """direct_add_n produces identical storage with and without the
    native mask builder."""
    rng = np.random.default_rng(5)
    positions = np.unique(rng.integers(0, 40 << 16, 20000, dtype=np.uint64))
    a = Bitmap()
    a.direct_add_n(positions)  # native path (len >= 4096)
    b = Bitmap()
    orig = native.build_masks
    native.build_masks = lambda *args: None
    try:
        b.direct_add_n(positions)
    finally:
        native.build_masks = orig
    assert sorted(a.containers) == sorted(b.containers)
    for k in a.containers:
        assert np.array_equal(a.containers[k], b.containers[k])
    assert a.count() == b.count() == len(positions)
    # incremental merge into existing containers, both paths
    more = np.unique(rng.integers(0, 40 << 16, 20000, dtype=np.uint64))
    a.direct_add_n(more)
    native.build_masks = lambda *args: None
    try:
        b.direct_add_n(more)
    finally:
        native.build_masks = orig
    assert a.count() == b.count() == len(np.union1d(positions, more))
    for k in a.containers:
        assert np.array_equal(a.containers[k], b.containers[k])


def test_scatter_rows_bound_filtering():
    out = np.zeros((3, 8), np.uint64)
    ok = native.scatter_rows(
        np.array([0, 511, 512, 63], np.uint16),   # 512 = first out-of-range
        np.array([3, 1], np.uint64),
        np.array([2, 0], np.uint64), 8, out)
    if not ok:
        return  # native unavailable: nothing to check
    assert out[2][0] & 1 and out[2][7] >> 63
    assert not (out[2][0] >> 1) & 1  # 512 filtered (>= 8*64)
    assert out[0][0] == np.uint64(1) << 63


def test_torn_tail_tolerated_both_codecs():
    """A record torn at EOF (crash mid-append) is dropped, not fatal;
    everything before it replays (divergence from the reference, which
    refuses to open — op.UnmarshalBinary roaring.go:3659)."""
    b = Bitmap([1, 2, 3])
    data = b.write_bytes()
    data += encode_op(OP_ADD, 42)
    good_len = len(data)
    data += encode_op(OP_ADD_BATCH,
                      values=np.arange(10, dtype=np.uint64))[:-5]
    # native
    keys, words, op_n, dropped = native.roaring_load(data)
    assert op_n == 1 and dropped == len(data) - good_len
    # python fallback (opt-in tolerance)
    pb = _python_bitmap(data, tolerate_torn_tail=True)
    assert pb.op_n == 1 and pb.tail_dropped == len(data) - good_len
    assert pb.contains(42)
    # short torn head (< 13 bytes) also tolerated
    data2 = b.write_bytes() + encode_op(OP_ADD, 7)[:6]
    _, _, op_n, dropped = native.roaring_load(data2)
    assert op_n == 0 and dropped == 6
    pb2 = _python_bitmap(data2, tolerate_torn_tail=True)
    assert pb2.op_n == 0 and pb2.tail_dropped == 6


def test_torn_tail_fail_hard_by_default():
    """Wire-received bytes (imports, Bitmap.from_bytes) keep fail-hard
    semantics: a truncated payload errors instead of half-applying."""
    data = Bitmap([1, 2, 3]).write_bytes() + encode_op(OP_ADD, 42)[:-5]
    with pytest.raises(ValueError, match="truncated|out of bounds"):
        Bitmap.from_bytes(data)          # native path
    with pytest.raises(ValueError, match="truncated|out of bounds"):
        _python_bitmap(data)             # python path


def test_torn_tail_mid_log_corruption_still_fatal():
    """A checksum mismatch on a COMPLETE record is corruption, not a torn
    write — both codecs must still refuse it."""
    data = Bitmap([1]).write_bytes()
    op = bytearray(encode_op(OP_ADD, 42))
    op[9] ^= 0xFF
    data = data + bytes(op) + encode_op(OP_ADD, 43)
    with pytest.raises(ValueError, match="checksum"):
        native.roaring_load(data)
    with pytest.raises(ValueError, match="checksum"):
        _python_bitmap(data)


def test_fragment_truncates_torn_tail_on_open(tmp_path):
    """Fragment.open drops the torn bytes from the file so later appends
    start at a clean boundary, and the fragment keeps working."""
    import os
    from pilosa_tpu.core.fragment import Fragment

    p = str(tmp_path / "f")
    f = Fragment(p, "i", "f", "standard", 0)
    f.open()
    for c in range(50):
        f.set_bit(1, c)
    f.close()
    size = os.path.getsize(p)
    with open(p, "r+b") as fh:
        fh.truncate(size - 3)

    f2 = Fragment(p, "i", "f", "standard", 0)
    f2.open()
    assert f2.row_count(1) == 49        # last torn Set dropped
    assert os.path.getsize(p) == size - 3 - 10  # torn record removed
    assert os.path.getsize(p + ".torn") == 10   # bytes preserved, not lost
    f2.set_bit(1, 49)                   # appends work after truncation
    f2.close()
    f3 = Fragment(p, "i", "f", "standard", 0)
    f3.open()
    assert f3.row_count(1) == 50
    f3.close()


def test_parallel_import_build_matches_serial():
    """pn_import_build and pn_serialize_groups parallelize over threads
    (VERDICT r3 next #5; reference: errgroup-parallel import,
    api.go:878-888). Output must be byte-identical at any thread count
    — the stripe order is deterministic. Runs each count in a fresh
    subprocess because the thread count is latched on first native
    call."""
    import os
    import subprocess
    import sys

    code = r"""
import hashlib, sys
import numpy as np
sys.path.insert(0, %(repo)r)
from pilosa_tpu import native
assert native.available()
rng = np.random.default_rng(7)
# Dense-scatter shape, big enough for the parallel scatter gate
# (>= 2^20 pairs) and multi-stripe count/payload passes.
n = 1_600_000
rows = rng.integers(0, 2, n, dtype=np.uint64)
cols = rng.integers(0, 1 << 20, n, dtype=np.uint64)
keys, words, counts, payload, nbits = native.import_build(rows, cols, 20)
# Grouped-serialize shape: >4096 groups so its stripe fill splits.
gkeys = np.arange(6000, dtype=np.uint64)
glows = np.tile(np.arange(3, dtype=np.uint16), 6000)
gbounds = np.arange(0, 3 * 6000 + 1, 3, dtype=np.uint64)
gp = native.serialize_groups(gkeys, glows, gbounds)
print(hashlib.sha256(payload).hexdigest(), int(nbits), len(keys),
      hashlib.sha256(gp).hexdigest())
""" % {"repo": os.path.dirname(os.path.dirname(os.path.abspath(__file__)))}
    outs = {}
    for threads in ("1", "4"):
        env = {**os.environ, "PILOSA_NATIVE_THREADS": threads}
        p = subprocess.run([sys.executable, "-c", code], env=env,
                           capture_output=True, text=True, timeout=120)
        assert p.returncode == 0, p.stderr
        outs[threads] = p.stdout.strip()
        assert outs[threads]
    assert outs["1"] == outs["4"]


def test_crash_point_fuzz_reopen_prefix_semantics(tmp_path):
    """Randomized crash-point fuzz: build a fragment through mixed
    single-bit ops and bulk imports, then truncate the file at MANY
    random byte offsets within the op-log region and reopen each
    prefix. Every reopen must either succeed with a bit-state equal to
    some PREFIX of the applied operations (torn tail dropped), and
    appends must work afterwards — no offset may corrupt silently or
    crash (reference: ops-log replay, roaring.go:1100-1126; our
    torn-tail sidecar recovery)."""
    import os

    import numpy as np

    from pilosa_tpu.core.fragment import Fragment

    rng = np.random.default_rng(77)
    p = str(tmp_path / "f")
    f = Fragment(p, "i", "f", "standard", 0)
    f.open()
    # Operation log we replay host-side: (kind, payload)
    states = []  # cumulative set(positions) AFTER each op
    cur: set = set()

    def snap():
        states.append(set(cur))

    snap()  # state after zero ops
    for step in range(12):
        if rng.random() < 0.5:
            r, c = int(rng.integers(0, 4)), int(rng.integers(0, 3000))
            f.set_bit(r, c)
            cur.add((r, c))
        else:
            rows = rng.integers(0, 4, 25)
            cols = rng.integers(0, 3000, 25)
            f.bulk_import(rows.astype(np.uint64), cols.astype(np.uint64))
            cur.update(zip(rows.tolist(), cols.tolist()))
        snap()
    f.close()
    size = os.path.getsize(p)
    full = open(p, "rb").read()

    prefix_counts = sorted({len(s) for s in states})
    for trial in range(40):
        cut = int(rng.integers(1, size + 1))
        fp = str(tmp_path / f"cut{trial}")
        with open(fp, "wb") as fh:
            fh.write(full[:cut])
        g = Fragment(fp, "i", "f", "standard", 0)
        try:
            g.open()
        except ValueError:
            # Acceptable only for cuts INSIDE the snapshot section
            # (mid-file corruption is fail-hard by design); op-log cuts
            # must recover.
            assert cut <= g.storage.snapshot_bytes or \
                g.storage.snapshot_bytes == 0, \
                (cut, size, g.storage.snapshot_bytes)
            continue
        # Count-based prefix check (order-insensitive): the recovered
        # bit-set must be exactly one of the cumulative states.
        total = sum(g.row_count(r) for r in range(4))
        assert total in prefix_counts, (cut, total, prefix_counts)
        # The recovered fragment accepts new appends.
        g.set_bit(3, 2999)
        assert g.bit(3, 2999)
        g.close()


# ---------------------------------------------------- sanitizer variants


def test_unknown_san_variant_yields_none(monkeypatch):
    """An unrecognized PILOSA_TPU_NATIVE_SAN must NOT fall back to the
    uninstrumented library — that would fake a green sanitized run."""
    monkeypatch.setenv("PILOSA_TPU_NATIVE_SAN", "bogus")
    assert native.load() is None
    assert not native.available()


def test_build_failure_is_logged_once_and_reported(monkeypatch, caplog,
                                                   tmp_path):
    """make fails and no library exists: load() returns None as
    before, but says why — one WARNING carrying make's stderr, and
    status() (the start line, GET /info, chip_smoke.py) reports it."""
    import logging
    monkeypatch.setattr(native, "_build",
                        lambda san: "make: g++: No such file")
    monkeypatch.setattr(native, "_so_path",
                        lambda san: str(tmp_path / "absent.so"))
    monkeypatch.setattr(native, "_libs", {})
    monkeypatch.setattr(native, "_load_errors", {})
    monkeypatch.delenv("PILOSA_TPU_NATIVE_SAN", raising=False)
    with caplog.at_level(logging.WARNING, logger="pilosa_tpu.native"):
        assert native.load() is None
        assert native.load() is None      # cached: not built again
        assert native.status() == (False, "make: g++: No such file")
    warnings = [r for r in caplog.records if r.levelno == logging.WARNING]
    assert len(warnings) == 1
    assert "make: g++: No such file" in warnings[0].getMessage()


def test_status_when_loaded_and_when_disabled(monkeypatch):
    assert native.status() == (True, "")
    monkeypatch.setenv("PILOSA_TPU_NO_NATIVE", "1")
    assert native.status() == (False,
                               "disabled by PILOSA_TPU_NO_NATIVE")


def test_load_cache_is_keyed_on_san_variant(monkeypatch):
    """A variant requested AFTER another was first loaded must not be
    served that cached library (regression: a single _tried/_lib pair
    pinned whatever variant touched load() first for process life)."""
    base_lib = native.load()
    base = native.active_san()
    # The counterpart variant must be loadable WITHOUT a runtime
    # preload, whatever leg this test runs under: plain and ubsan both
    # qualify (dlopen'ing the asan .so into a process that did not
    # preload libasan hard-aborts — "runtime does not come first").
    other = "ubsan" if base != "ubsan" else ""
    monkeypatch.setenv("PILOSA_TPU_NATIVE_SAN", other)
    got = native.load()
    assert got is not base_lib or base_lib is None
    monkeypatch.setenv("PILOSA_TPU_NATIVE_SAN", base)
    assert native.load() is base_lib


def test_staged_bytes_uses_exact_malloc_block_under_san(monkeypatch):
    """Under a sanitizer the input staging path must round-trip through
    the exact-size libc malloc block (where ASan redzones sit)."""
    monkeypatch.setenv("PILOSA_TPU_NATIVE_SAN", "ubsan")
    data = bytes(range(256)) * 3
    staged = native._StagedBytes(data)
    with staged as ptr:
        assert staged._raw is not None  # malloc path, not ctypes copy
        assert bytes(ptr[i] for i in range(len(data))) == data
    assert staged._raw is None  # freed on exit
