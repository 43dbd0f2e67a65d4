"""The chemical-similarity deployment: a compound library's fingerprints,
its loader, its one query and the plain reference.

The schema is the source's (upstream docs/examples.md, "Chemical
similarity search"): index `mole`, field `fingerprint`, a ROW per
molecule, a COLUMN per on-bit of its 4,096-bit Morgan fingerprint, and
the query `TopN(fingerprint, Row(fingerprint=<molecule>), n=<n>,
tanimotoThreshold=<T>)`. Columns stop at 4,096, so the whole library is
one shard.

The library is made from `data_seed`, in bulk: molecules come in analog
series (a scaffold fingerprint; each member drops a share of the
scaffold's on-bits and adds a few of its own) whose sizes are Zipf
distributed, over bits of Zipf-skewed popularity, so that every
threshold of the traffic meets neighbours and not only the query
molecule itself. The configuration's `assumed` gives each constant.

**The reference** is numpy only and shares nothing with the program: an
inverted index bit -> molecules built once from the fingerprints; one
answer adds the lists of the query's on-bits into a one-byte counter a
molecule (|A and B|), takes |A| and |B| from the stored popcounts, and
then applies upstream's own rule (`fragment.go`, `fragment.top`):

- a row whose own count `cnt` lies outside the open interval
  (`src*T/100`, `src*100/T`) is skipped before it is intersected
  (`:1087-1093`, the candidate pruning: `float64(cnt) <= minTanimoto ||
  float64(cnt) >= maxTanimoto`);
- a row with an empty intersection is skipped;
- `tanimoto := math.Ceil(float64(count*100) / float64(cnt+srcCount-count))`
  and the row is skipped when `tanimoto <= T` (`:1146-1150`): a row
  stays only when 100*|A and B| > T*|A or B| — a ratio of exactly T (7
  of 10 bits at T = 70) is out;
- the pairs are (row, |A and B|), ordered by that count, largest first,
  cut to `n` (0 = all). Upstream's `sort.Sort(Pairs)` leaves the order
  of equal counts open; the deployment's contract closes it as every
  answer of this program does, the smaller row id first.

No temporary is larger than one byte a molecule: `harness/cell.py`
compares on one thread per CPU.

Nothing here imports the program except `datasets/taxi.py`'s
`roaring_bytes`, the client-side serialiser of an import payload
(`pilosa_tpu.storage` is jax-free).
"""

from __future__ import annotations

import numpy as np

from datasets.taxi import roaring_bytes
from harness.server import BenchFailure

INDEX = "mole"
FIELD = "fingerprint"
BITS = 4096             # the fingerprint's width: the field's maxColumns
SCHEMA = 1              # a kept data directory of another schema is reloaded
SHARD_WIDTH = 1 << 20

# The source's own query, verbatim: its molecule, no `n` (every
# molecule past the threshold), its threshold. A family query of every
# warm-up.
SOURCE_QUERY = (6, 0, 90)

# The generator's constants (the configuration's `assumed`).
BIT_SKEW = 0.8          # Zipf exponent of a bit's popularity
SERIES_SKEW = 1.6       # Zipf exponent of a series' size
SERIES_MAX = 4096       # and its cap
SCAFFOLD_BITS = (59.0, 14.0, 10, 160)    # draws: mean, sd, min, max
DROP_SHAPE = (1.2, 6.8)  # Beta: a member's drop share, mean 0.15
ADDS_PER_DROP = 20.0    # a member adds Poisson(20 x its drop share) bits
MIN_BITS, MAX_BITS = 8, 128
BLOCK = 1 << 16         # molecules generated, and imported, at a time


class Library:
    """The fingerprints as the arrays every reference answer is computed
    from: `bits[offsets[m]:offsets[m+1]]` are molecule m's on-bits,
    ascending; `popcount[m]` their number; `post[post_offsets[b]:
    post_offsets[b+1]]` the molecules that have bit b, ascending."""

    def __init__(self, seed: int, n_molecules: int):
        self.n = self.grid_rows = n_molecules   # `grid_rows`: loadgen's name
        self.n_days = 0
        rng = np.random.default_rng(seed)
        p = 1.0 / np.arange(1, BITS + 1) ** BIT_SKEW
        # Popular bits are scattered over the fingerprint, as a hash
        # scatters them.
        cdf = np.cumsum(p / p.sum())
        bit_of = rng.permutation(BITS).astype(np.uint16)

        def draw_bits(k: int) -> np.ndarray:
            return bit_of[np.minimum(np.searchsorted(cdf, rng.random(k)),
                                     BITS - 1)]

        # Series: Zipf sizes until the library is full; members are
        # scattered over the row ids, as registration order scatters them.
        sizes = np.minimum(rng.zipf(SERIES_SKEW, n_molecules), SERIES_MAX)
        n_series = int(np.searchsorted(np.cumsum(sizes), n_molecules)) + 1
        series_of = np.repeat(np.arange(n_series, dtype=np.int64),
                              sizes[:n_series])[:n_molecules]
        series_of = series_of[rng.permutation(n_molecules)]
        mean, sd, lo, hi = SCAFFOLD_BITS
        s_len = np.clip(np.rint(rng.normal(mean, sd, n_series)),
                        lo, hi).astype(np.int64)
        s_off = np.concatenate([[0], np.cumsum(s_len)])
        s_bits = draw_bits(int(s_off[-1]))
        drop = rng.beta(*DROP_SHAPE, n_molecules)
        adds = rng.poisson(ADDS_PER_DROP * drop)

        rows, cols = [], []
        for m0 in range(0, n_molecules, BLOCK):
            m1 = min(m0 + BLOCK, n_molecules)
            ser = series_of[m0:m1]
            # A member keeps each scaffold bit with 1 - its drop share …
            k = s_len[ser]
            mol = np.repeat(np.arange(m0, m1, dtype=np.int64), k)
            at = np.arange(len(mol)) - np.repeat(np.cumsum(k) - k, k)
            bit = s_bits[np.repeat(s_off[ser], k) + at]
            kept = rng.random(len(mol)) >= np.repeat(drop[m0:m1], k)
            # … and adds a few bits of its own.
            a = adds[m0:m1]
            mol = np.concatenate(
                [mol[kept], np.repeat(np.arange(m0, m1, dtype=np.int64), a)])
            bit = np.concatenate([bit[kept], draw_bits(int(a.sum()))])
            key = np.unique(mol * BITS + bit)
            mol, bit = key // BITS, (key % BITS).astype(np.uint16)
            # Clip to MIN_BITS..MAX_BITS on-bits: the sparsest gain bits
            # spread over the fingerprint, the densest lose their highest.
            count = np.bincount(mol - m0, minlength=m1 - m0)
            short = np.flatnonzero(count < MIN_BITS) + m0
            if len(short):      # rare: a few molecules a million
                extra = []
                for m in short.tolist():
                    have = set(bit[mol == m].tolist())
                    b = m * 2654435761 % BITS
                    while len(have) < MIN_BITS:
                        if b not in have:
                            have.add(b)
                            extra.append(m * BITS + b)
                        b = (b + 509) % BITS
                key = np.unique(np.concatenate(
                    [key, np.asarray(extra, dtype=np.int64)]))
                mol, bit = key // BITS, (key % BITS).astype(np.uint16)
                count = np.bincount(mol - m0, minlength=m1 - m0)
            rank = np.arange(len(mol)) - np.repeat(
                np.cumsum(count) - count, count)
            ok = rank < MAX_BITS
            rows.append(mol[ok].astype(np.uint32))
            cols.append(bit[ok])
        mol = np.concatenate(rows)
        self.bits = np.concatenate(cols)
        del rows, cols
        self.popcount = np.bincount(mol, minlength=n_molecules) \
            .astype(np.int64)
        self.offsets = np.concatenate([[0], np.cumsum(self.popcount)])
        # The inverted index: a stable sort by bit keeps each list
        # ascending by molecule.
        order = np.argsort(self.bits, kind="stable")
        self.post = mol[order]
        self.post_offsets = np.concatenate(
            [[0], np.cumsum(np.bincount(self.bits, minlength=BITS))])

    def fingerprint(self, m: int) -> np.ndarray:
        return self.bits[self.offsets[m]:self.offsets[m + 1]]

    def block(self, m0: int, m1: int) -> tuple:
        """(row ids, columns) of the on-bits of molecules [m0, m1)."""
        o0, o1 = self.offsets[m0], self.offsets[m1]
        return (np.repeat(np.arange(m0, m1, dtype=np.uint64),
                          self.popcount[m0:m1]), self.bits[o0:o1])


def make(config: dict, shard_width: int) -> Library:
    return Library(config["data_seed"], config["grid_rows"])


def fingerprint(config: dict, shard_width: int) -> dict:
    """What a kept data directory must have been loaded with."""
    return {"dataset": "chem", "schema": SCHEMA,
            "data_seed": config["data_seed"],
            "molecules": config["grid_rows"], "bits": BITS,
            "shard_width": shard_width}


def bank_bytes(config: dict) -> int:
    """Bytes of the fingerprint field's dense device bank, from its
    shape: slots pad to the next power of two above molecules + 1 (one
    zero slot), one shard, 4,096 bits a slot."""
    slots = 1 << int(config["grid_rows"]).bit_length()
    return slots * (BITS // 8)


def popcnt_launch_bytes(config: dict) -> float:
    """Bytes one `popcnt_reduce_fusion` launch of an answer reads, in
    the mean. The device trace holds three ops of that name an answer
    (TPU v5e, this installation's XLA; `benches/sweep_variants.py
    --rows 2097152 --shards 1 --words 128` reads the sweep's from the
    compiled HLO): `topn_sweep_tanimoto` compiles to TWO fusions over a
    bank of one-lane rows, `|row and filter|` and `|row|`, each reading
    the whole bank, and `popcount_row` — the filter row's own popcount,
    512 bytes — compiles to a third of the same name. The reader counts
    launches by name and multiplies by one number, so that number is
    (2 banks + 1 row) / 3. A sweep body that reads the bank once makes
    it (1 bank + 1 row) / 2: this function changes with it."""
    return (2 * bank_bytes(config) + BITS // 8) / 3


# ------------------------------------------------------------------ loading


def _resident_sweeps(srv) -> int:
    return srv.get("/debug/vars")["counters"].get(
        "executor.topn_sweeps{path:resident}", 0)


PROBE = "mole_rule_probe"


def refuse_another_rule(srv) -> None:
    """Before a byte is loaded: two molecules in a scratch index whose
    similarity is exactly 70 % (7 of 10 bits), asked for at T = 70.
    Upstream drops a ratio equal to the threshold (the module's text); a
    server that keeps it answers one query in twenty-five of this
    deployment otherwise than the source does, and is refused here, in
    seconds, by its own answer."""
    srv.post_json(f"/index/{PROBE}", {})
    srv.post_json(f"/index/{PROBE}/field/{FIELD}",
                  {"options": {"maxColumns": BITS}})
    srv.request("POST", f"/index/{PROBE}/query", " ".join(
        [f"Set({c}, {FIELD}=0)" for c in range(10)]
        + [f"Set({c}, {FIELD}=1)" for c in range(7)]).encode(),
        "text/plain")
    got = srv.query(PROBE, pql(0, 0, 70))
    srv.request("DELETE", f"/index/{PROBE}")
    if got != [{"id": 0, "count": 10}]:
        raise BenchFailure(
            f"{pql(0, 0, 70)} over rows of 10 and 7 shared bits answers "
            f"{got}: this server keeps a similarity of exactly T, "
            "upstream's rule (fragment.go:1146-1150) drops it")


def load(srv, lib: Library, log=lambda m: None) -> None:
    """Schema + data through the public routes — the field with the
    fingerprint's width declared, the bits as import-roaring payloads of
    the one shard, a block of rows a body — then the source's query
    once: a server that does not answer it by a sweep of the resident
    bank cannot run the deployment (it would stream chunk banks through
    the device, minutes a query), and the run ends here instead."""
    refuse_another_rule(srv)
    srv.post_json(f"/index/{INDEX}", {})
    srv.post_json(f"/index/{INDEX}/field/{FIELD}",
                  {"options": {"maxColumns": BITS}})
    for m0 in range(0, lib.n, BLOCK):
        m1 = min(m0 + BLOCK, lib.n)
        rows, cols = lib.block(m0, m1)
        srv.request("POST", f"/index/{INDEX}/field/{FIELD}/import-roaring/0",
                    roaring_bytes(rows, cols, SHARD_WIDTH),
                    "application/octet-stream")
        log(f"loaded molecules {m1}/{lib.n}")
    before = _resident_sweeps(srv)
    pql, want = family_queries(lib)[0]
    got = srv.query(INDEX, pql)
    if _resident_sweeps(srv) != before + 1:
        raise BenchFailure(
            "executor.topn_sweeps{path:resident} did not move for "
            f"{pql}: this server does not sweep the library's bank "
            "resident")
    if not equal(got, want):
        raise BenchFailure(f"{pql} after the load: server {str(got)[:200]} "
                           f"reference {str(want)[:200]}")


# ------------------------------------------------------------------ queries


def similar(lib: Library, m: int, n: int, threshold: int) -> list:
    """TopN(fingerprint, Row(fingerprint=m), n, tanimotoThreshold) by
    upstream's rule (the module's text names the lines)."""
    on = lib.fingerprint(m)
    src = len(on)
    inter = np.zeros(lib.n, dtype=np.uint8)     # |A and B| <= 128
    for b in on.tolist():
        inter[lib.post[lib.post_offsets[b]:lib.post_offsets[b + 1]]] += 1
    cand = np.flatnonzero(inter)                # count == 0: skipped
    count = inter[cand].astype(np.int64)
    cnt = lib.popcount[cand]
    if threshold:
        min_t = float(src * threshold) / 100
        max_t = float(src * 100) / float(threshold)
        keep = ~((cnt.astype(np.float64) <= min_t)
                 | (cnt.astype(np.float64) >= max_t))
        tanimoto = np.ceil((count * 100).astype(np.float64)
                           / (cnt + src - count).astype(np.float64))
        keep &= ~(tanimoto <= float(threshold))
        cand, count = cand[keep], count[keep]
    order = np.lexsort((cand, -count))
    if n:
        order = order[:n]
    return [{"id": int(cand[o]), "count": int(count[o])} for o in order]


def pql(m: int, n: int, threshold: int) -> str:
    n_arg = f", n={n}" if n else ""
    return (f"TopN({FIELD}, Row({FIELD}={m}){n_arg}, "
            f"tanimotoThreshold={threshold})")


def family_queries(lib: Library) -> list:
    """The source's own query: (pql, expected). Posted at the end of the
    load and at the start of every warm-up."""
    m, n, t = SOURCE_QUERY
    m = min(m, lib.n - 1)
    return [(pql(m, n, t), similar(lib, m, n, t))]


class Draws:
    """What one request draws, from the client's own generator: the
    query molecule, uniform over the library (`row_skew` 0) or Zipf(s)
    over the row ids."""

    def __init__(self, shape: dict, rng, row_skew: float = 0.0):
        self.rng = rng
        self.n = shape["grid_rows"]
        self._cdf = None
        if row_skew > 0:
            p = 1.0 / np.arange(1, self.n + 1) ** row_skew
            self._cdf = np.cumsum(p / p.sum())

    def molecule(self) -> int:
        if self._cdf is None:
            return int(self.rng.integers(0, self.n))
        return int(min(np.searchsorted(self._cdf, self.rng.random()),
                       self.n - 1))


def _q_tanimoto(lib, d, threshold=70, n=50, **_):
    m = d.molecule()
    return pql(m, n, threshold), lambda: similar(lib, m, n, threshold)


# family name -> builder(library, draws, **pinned) -> (pql, reference thunk)
FAMILIES = {"tanimoto": _q_tanimoto}


def query(lib: Library, family: str, draws: Draws, **pinned) -> tuple:
    return FAMILIES[family](lib, draws, **pinned)


def equal(got, want) -> bool:
    """The comparison that decides one answer: exact equality of the
    decoded JSON result with the reference's pairs, in order (limit 0)."""
    return got == want
