"""The Star Schema Benchmark deployment on the host it was cut from:
`datasets/ssb.py`'s LINEORDER, loader, 13 queries, plain reference and
`least_bytes`, unchanged, behind two things of its own — as
`taxi_mesh.py` stands to `taxi.py`.

**A check before the load.** SF = 10 whole is 58 shards whose banks are
split over the four chips of one host (`p_brand1` alone is
`[1024, 60, 32768]` u32 = 7.5 GiB, a quarter of it a chip). A program
that prices a sharded bank as one array cannot serve it from resident
banks, and it says so itself: it publishes no per-device limits in `GET
/info`. `load` reads `/info` first and refuses it, in seconds and before
a byte is loaded (`taxi_mesh.py`'s rule and its two keys); then
`ssb.load` runs `ssb.refuse_no_aggregate` as it does on one chip. The
check is on the benchmark's side: no server setting, switch or
environment variable exists for it.

**The load, from a pool of connections.** `ssb.load` posts nineteen
`import-roaring` bodies a shard, one after the other, and waits for
each: about 19 s a shard on one chip (PR 32), most of it the server's
side — some 18 minutes at 58 shards. Here the same `ssb.load` runs over
a stand-in for the server (`_Pool`) that hands each import body to one
of `POOL` connections, chosen by the shard, and returns at once: a
shard's nineteen bodies stay on one connection, in order; the payloads
are built on the caller's thread meanwhile (numpy); every other request
waits until the connections are idle and goes to the server as it is.
What is posted, where and in which order within a shard is `ssb.load`'s.

The reference stays `ssb.answer`: the whole host's `[group, count, sum]`
table, a mask a block of 2^20 rows, so no temporary is larger than a
block whatever the table's size.
"""

import queue
import re
import threading

from datasets.ssb import (  # noqa: F401
    INDEX, Draws, answer, equal, family_queries, fingerprint, least_bytes,
    make, query)
from datasets import ssb
from datasets.taxi_mesh import LIMITS
from harness.server import BenchFailure, Client

POOL = 4            # connections that carry import bodies
AHEAD = 38          # bodies a connection may hold unsent: two shards'
_IMPORT = re.compile(r"/import-roaring/(\d+)")


class _Pool:
    """The harness's server as `ssb.load` sees it: an `import-roaring`
    POST is queued on connection `shard % POOL` and acknowledged at
    once; anything else drains the queues first. A body the server does
    not take with 200 fails the load at the next request."""

    def __init__(self, srv):
        self.srv = srv
        self.failed = []
        self.queues = [queue.Queue(AHEAD) for _ in range(POOL)]
        self.threads = [threading.Thread(target=self._carry, args=(q,),
                                         daemon=True) for q in self.queues]
        for t in self.threads:
            t.start()

    def _carry(self, q) -> None:
        client = Client(self.srv.port)
        while True:
            item = q.get()
            try:
                if item is None:
                    return
                if not self.failed:
                    method, path, body, ctype = item
                    status, data = client.request(method, path, body, ctype)
                    if status != 200:
                        self.failed.append(
                            f"{method} {path} -> {status}: {data[:300]!r}")
            except Exception as e:      # a dropped connection, a timeout
                self.failed.append(f"{item[1]}: {type(e).__name__}: {e}")
            finally:
                q.task_done()
                if item is None:
                    client.close()

    def drain(self) -> None:
        for q in self.queues:
            q.join()
        if self.failed:
            raise BenchFailure(self.failed[0])

    def close(self) -> None:
        for q in self.queues:
            q.put(None)
        for t in self.threads:
            t.join()

    def request(self, method, path, body=None, ctype="application/json"):
        shard = _IMPORT.search(path)
        if shard is None:
            self.drain()
            return self.srv.request(method, path, body, ctype)
        if self.failed:
            raise BenchFailure(self.failed[0])
        self.queues[int(shard.group(1)) % POOL].put(
            (method, path, body, ctype))
        return {}

    def get(self, path):
        self.drain()
        return self.srv.get(path)

    def post_json(self, path, obj):
        self.drain()
        return self.srv.post_json(path, obj)

    def query(self, index, pql):
        self.drain()
        return self.srv.query(index, pql)


def load(srv, lo, log=lambda m: None) -> None:
    limits = srv.get("/info").get("residentLimits") or {}
    missing = [k for k in LIMITS if k not in limits]
    if missing:
        raise BenchFailure(
            "the server's /info publishes no residentLimits "
            f"{missing}: this program prices a sharded bank as one array "
            "and cannot hold SF = 10's banks resident on a host")
    log(f"resident limits per device: {limits}")
    pool = _Pool(srv)
    try:
        ssb.load(pool, lo, log)
    finally:
        pool.close()
