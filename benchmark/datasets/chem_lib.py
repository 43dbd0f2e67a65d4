"""The chemical-similarity index at library scale: one chip's
id-partition of a PubChem-sized compound library.

The schema, the generator, the query and the plain reference are
`datasets/chem.py`'s, imported and not copied: index `mole`, field
`fingerprint`, a ROW per molecule, a COLUMN per on-bit of its 4,096-bit
Morgan fingerprint, `TopN(fingerprint, Row(fingerprint=<m>)[, n=<n>],
tanimotoThreshold=<T>)`, upstream's own threshold rule, pruning and
order. Only the molecule count differs — 8,388,607 where `chem-chip`
holds 2,097,151 — and with it the mechanism that serves the field: its
dense bank would be 4 GiB, twice the resident sweep's limit, so
the server keeps the rows' u16 bit positions on the device (`core/view.
py: PositionsBank`) and answers every TopN from them, a segment program
at a time.

This module's own:

- the loader (`load`), which refuses before the first byte a server
  that keeps a similarity of exactly T (`chem.refuse_another_rule`) or
  whose `/debug/vars` publishes no `executor.pbank_launches` (a program
  that cannot say what the positions path does; the parent of PR 40
  also answers the source's own query, which has no `n`, by streaming
  8,192 chunk banks through the device), and after the load one whose
  answer to that query did not come from the positions bank;
- the family queries of every warm-up: the source's query (no `n`:
  every molecule past the threshold) and one `n` = 50 query a
  threshold of the traffic;
- a request's reference as an object that keeps its family and
  constants (`readers/answer_roofline.py` reads `r.ref.constants`);
- `least_bytes`: what one answer cannot be computed without reading.
"""

from __future__ import annotations

import threading
import time

import numpy as np

from datasets import chem
from datasets.chem import (BITS, BLOCK, FIELD, INDEX, SHARD_WIDTH,  # noqa: F401
                           SOURCE_QUERY, Draws, Library, pql, similar)
from harness.server import BenchFailure

SCHEMA = 1              # a kept data directory of another schema is reloaded
THRESHOLDS = (90, 80, 70, 50)   # the traffic's, one family query each
PAGE = 50               # neighbours a page: the traffic's `n`
BLOCK_ROWS = 1 << 21    # molecules a block of the reference
LAUNCH_COUNTER = "executor.pbank_launches"
PATH_COUNTER = "executor.topn_sweeps{path:%s}"

# What `make` leaves for `least_bytes`: the molecules' on-bit counts
# and their cumulative histogram — by count c, how many molecules of
# the library have at most c on-bits, and how many positions those
# molecules hold.
POPCOUNT = ROWS_UP_TO = POSITIONS_UP_TO = None


class Block:
    """Molecules [r0, r1) of a library in the shape `chem.similar`
    reads (`n`, `popcount`, `post`, `post_offsets`, `fingerprint`), so
    that one answer's temporaries are a block's and not the library's:
    the reference indexes eight arrays by its candidates — every
    molecule that shares a bit with the query, which is nearly all of
    them — and at 16.7 M molecules those are 134 MB each, past the
    32 MiB under which glibc hands a freed array's memory out again;
    a dozen comparison threads freeing them met the chip machine's
    40 GiB limit (its sandbox counts freed memory late: PERF.md §6,
    PRs 26 and 40). A block is `chem-chip`'s whole library's size.
    Nothing is copied: a bit's list of the block is a slice of the
    library's (they ascend by molecule), shifted to the block's ids;
    the query molecule's fingerprint is the library's."""

    def __init__(self, lib, r0: int, r1: int):
        self.lib, self.r0, self.n = lib, r0, r1 - r0
        self.popcount = lib.popcount[r0:r1]
        lists = [lib.post[a:b] for a, b in zip(lib.post_offsets[:-1],
                                                lib.post_offsets[1:])]
        self._lo = np.array([a + np.searchsorted(li, r0) for a, li in
                             zip(lib.post_offsets[:-1], lists)])
        self._hi = np.array([a + np.searchsorted(li, r1) for a, li in
                             zip(lib.post_offsets[:-1], lists)])
        # `similar` reads `post[post_offsets[b]:post_offsets[b + 1]]`:
        # with the identity for offsets it asks `post[b:b + 1]`, and
        # `post` is this object (see __getitem__).
        self.post_offsets = np.arange(chem.BITS + 1)
        self.post = self

    def fingerprint(self, m: int) -> np.ndarray:
        return self.lib.fingerprint(m)

    def __getitem__(self, span: slice) -> np.ndarray:
        """`post[b:b + 1]`: the block's molecules that have bit b, by
        the block's own ids."""
        b = span.start
        return self.lib.post[self._lo[b]:self._hi[b]] - np.uint32(self.r0)


class LibraryInTheMaking:
    """`chem.Library` at this size takes minutes to generate, and the
    harness calls `make` before it has asked the server anything: so
    the generation runs on a thread of its own from that call on —
    beside the server's start, as before — and whoever first reads the
    fingerprints waits for it. What needs no fingerprint does not wait:
    the sizes the load generator reads, and `load`'s two refusals, so a
    server that cannot run the deployment is refused in seconds."""

    def __init__(self, config: dict, shard_width: int):
        self.n = self.grid_rows = config["grid_rows"]   # loadgen's names
        self.n_days = 0
        self._made = self._error = None
        self._thread = threading.Thread(
            target=self._make, args=(config, shard_width), daemon=True,
            name="chem-lib-make")
        self._thread.start()

    def _make(self, config: dict, shard_width: int) -> None:
        global POPCOUNT, ROWS_UP_TO, POSITIONS_UP_TO
        try:
            lib = chem.make(config, shard_width)
            lib.blocks = [Block(lib, r0, min(r0 + BLOCK_ROWS, lib.n))
                          for r0 in range(0, lib.n, BLOCK_ROWS)]
            rows = np.bincount(lib.popcount, minlength=chem.MAX_BITS + 1)
            POPCOUNT = lib.popcount
            ROWS_UP_TO = np.cumsum(rows)
            POSITIONS_UP_TO = np.cumsum(rows * np.arange(len(rows)))
            self._made = lib
        except BaseException as e:      # raised where the library is read
            self._error = e

    def __getattr__(self, name: str):
        # Only what `__init__` did not set comes here: the library's own
        # arrays and methods (`bits`, `popcount`, `post`, `fingerprint`…).
        if name.startswith("_"):
            raise AttributeError(name)
        self._thread.join()
        if self._error is not None:
            raise self._error
        return getattr(self._made, name)


def make(config: dict, shard_width: int) -> LibraryInTheMaking:
    return LibraryInTheMaking(config, shard_width)


def fingerprint(config: dict, shard_width: int) -> dict:
    """What a kept data directory must have been loaded with."""
    return {"dataset": "chem_lib", "schema": SCHEMA,
            "data_seed": config["data_seed"],
            "molecules": config["grid_rows"], "bits": BITS,
            "shard_width": shard_width}


def bank_bytes(config: dict) -> int:
    """Bytes of the library as positions, pad-free: 2 a position and
    one 4-byte row start a molecule (and one more). Needs `make`'s
    library read once (its histogram is then there)."""
    return int(POSITIONS_UP_TO[-1]) * 2 + (int(ROWS_UP_TO[-1]) + 1) * 4


# ------------------------------------------------------------------ loading


def _counters(srv) -> dict:
    return srv.get("/debug/vars").get("counters", {})


def refuse_no_launch_counter(srv) -> None:
    """A server whose `/debug/vars` has no `executor.pbank_launches`
    cannot say what the positions path did for an answer, and the
    program that lacks it (the parent of PR 40) sends the source's own
    query — no `n` — down the streamed path: 8,192 chunk banks
    uploaded and swept one after another, a query. It is refused here,
    in seconds and before a byte is loaded. The check is on the
    benchmark's side: no server setting exists for it."""
    if LAUNCH_COUNTER not in _counters(srv):
        raise BenchFailure(
            f"the server's /debug/vars publishes no {LAUNCH_COUNTER}: "
            "this program cannot report what its positions bank does, "
            "and chem-lib-chip is not measured on it")


def body_bytes(lib, m0: int, m1: int) -> bytes:
    """The on-bits of molecules [m0, m1) as one import-roaring body, in
    upstream's file format (magic 12348, version 0): a header of one
    (key u64, type u16, cardinality - 1 u16) a container and one u32
    offset a container, then the containers — here one ARRAY container
    (type 1: ascending u16 values) a molecule, under the key of its
    row's first container, row x (shard width / 2^16). Written from
    the library's own arrays in bulk: `datasets/taxi.py: roaring_bytes`
    builds the same body through the program's mutable bitmap, an 8 KiB
    container a molecule, at 1.6 s a body of 65,536 — 3.4 minutes over
    this library's 128 bodies (my CPU run, PR 40, the sandbox: a count
    of seconds, not a device number)."""
    n = m1 - m0
    head = np.zeros(n, dtype=[("key", "<u8"), ("typ", "<u2"),
                              ("card", "<u2")])
    head["key"] = np.arange(m0, m1, dtype=np.uint64) \
        * np.uint64(SHARD_WIDTH >> 16)
    head["typ"] = 1
    head["card"] = lib.popcount[m0:m1] - 1
    o0 = int(lib.offsets[m0])
    at = 8 + 16 * n + 2 * (lib.offsets[m0:m1] - o0)
    return b"".join([
        np.asarray([12348, n], dtype="<u4").tobytes(), head.tobytes(),
        at.astype("<u4").tobytes(),
        lib.bits[o0:int(lib.offsets[m1])].astype("<u2").tobytes()])


def load(srv, lib, log=lambda m: None) -> None:
    """Schema + data through the public routes, as `chem.load` does (a
    block of rows an import-roaring body), then the source's query
    once: it has no `n`, and a server that does not answer it from the
    positions bank, equal to the reference, cannot run the deployment."""
    chem.refuse_another_rule(srv)
    refuse_no_launch_counter(srv)
    srv.post_json(f"/index/{INDEX}", {})
    srv.post_json(f"/index/{INDEX}/field/{FIELD}",
                  {"options": {"maxColumns": BITS}})
    t_body = time.monotonic()
    for m0 in range(0, lib.n, BLOCK):
        m1 = min(m0 + BLOCK, lib.n)
        srv.request("POST", f"/index/{INDEX}/field/{FIELD}/import-roaring/0",
                    body_bytes(lib, m0, m1), "application/octet-stream")
        now = time.monotonic()
        log(f"loaded molecules {m1}/{lib.n} ({now - t_body:.2f} s a body)")
        t_body = now
    before = _counters(srv)
    text, want = family_queries(lib)[0]
    got = srv.query(INDEX, text)
    after = _counters(srv)

    def moved(path: str) -> int:
        name = PATH_COUNTER % path
        return after.get(name, 0) - before.get(name, 0)

    if moved("positions") != 1 or moved("streamed"):
        raise BenchFailure(
            f"{text} after the load: executor.topn_sweeps moved "
            f"positions by {moved('positions')}, streamed by "
            f"{moved('streamed')}: this server does not answer the "
            "source's query from the positions bank")
    if not equal(got, want):
        raise BenchFailure(f"{text} after the load: server "
                           f"{str(got)[:200]} reference {str(want)[:200]}")


# ------------------------------------------------------------------ queries


class Reference:
    """One reference answer, computed when the comparison asks for it;
    a request's keeps its family and constants for `least_bytes`."""

    def __init__(self, lib, family, m, n, threshold):
        self.lib, self.family, self.n = lib, family, n
        self.constants = (m, threshold)

    def __call__(self):
        """`chem.similar` a block at a time (no `n`: every pair past
        the threshold), the blocks' pairs merged in the contract's
        order — count, largest first, then the smaller id — and cut to
        `n`: what `similar` gives for the whole library."""
        m, threshold = self.constants
        pairs = [(-p["count"], p["id"] + blk.r0)
                 for blk in self.lib.blocks
                 for p in similar(blk, m, 0, threshold)]
        pairs.sort()
        return [{"id": i, "count": -c}
                for c, i in (pairs[:self.n] if self.n else pairs)]

    def __repr__(self):
        return repr(self())


def family_queries(lib) -> list:
    """(pql, expected) of every warm-up: the source's own query — its
    molecule, its threshold, no `n` — and a page of that molecule's
    neighbours at each threshold of the traffic. The expected answers
    are computed when `equal` compares them: the first query is posted
    while the library is still in the making, and the server builds
    its positions bank meanwhile."""
    m, n, t = SOURCE_QUERY
    m = min(m, lib.n - 1)
    asked = [(m, n, t)] + [(m, PAGE, t) for t in THRESHOLDS]
    return [(pql(*q), Reference(lib, "tanimoto", *q)) for q in asked]


def equal(got, want) -> bool:
    """`chem.equal` (exact equality, in order; limit 0), of an answer
    with the reference's — computed here where it is still to come."""
    return chem.equal(got, want() if isinstance(want, Reference) else want)


def query(lib, family: str, draws: Draws, threshold=70, n=PAGE,
          **_) -> tuple:
    if family != "tanimoto":
        raise KeyError(family)
    m = draws.molecule()
    return pql(m, n, threshold), Reference(lib, family, m, n, threshold)


def least_bytes(family: str, constants: tuple, config: dict) -> int:
    """The bytes an answer cannot be computed without reading, whatever
    computes it: upstream's own rule intersects a row only when its
    on-bit count lies inside the open interval (src*T/100, src*100/T)
    (`chem.py`'s text, fragment.go:1087-1093), so the answer needs the
    positions (2 B each) and one row start (4 B) of exactly those rows,
    and the query row's own 512 B. A kernel that reads the whole bank
    for T = 90 reads low by construction: that is the headroom the
    number is for. Needs `make`."""
    m, threshold = constants
    src = int(POPCOUNT[m])
    counts = np.arange(len(ROWS_UP_TO))
    inside = np.flatnonzero((counts * 100 > src * threshold)
                            & (counts * threshold < src * 100))
    if not len(inside):
        return BITS // 8
    lo, hi = int(inside[0]), int(inside[-1])
    rows = int(ROWS_UP_TO[hi] - (ROWS_UP_TO[lo - 1] if lo else 0))
    positions = int(POSITIONS_UP_TO[hi]
                    - (POSITIONS_UP_TO[lo - 1] if lo else 0))
    return positions * 2 + rows * 4 + BITS // 8

