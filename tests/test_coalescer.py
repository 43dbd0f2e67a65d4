"""Serving-path query coalescer (server/coalescer.py): threaded stress
against a live PilosaHTTPServer asserting result-equivalence vs the
direct path, per-request error isolation, deadline ejection, 429 at
queue capacity, and the new observability surface. Rides alongside
test_concurrency.py (in-process races) — here the races cross the HTTP
boundary, which is the layer the coalescer lives at."""

import json
import threading
import time
import urllib.error
import urllib.request

import numpy as np
import pytest

from pilosa_tpu.core.holder import Holder
from pilosa_tpu.server import API, serve
from pilosa_tpu.server.coalescer import QueryCoalescer
from pilosa_tpu.utils.stats import MemStatsClient

N_THREADS = 8
N_QUERIES = 6


def post(base, path, body, timeout=30):
    """(status, raw_bytes, headers) for a POST; 4xx captured, not raised."""
    data = body if isinstance(body, bytes) else json.dumps(body).encode()
    r = urllib.request.Request(base + path, data=data, method="POST")
    try:
        with urllib.request.urlopen(r, timeout=timeout) as resp:
            return resp.status, resp.read(), dict(resp.headers)
    except urllib.error.HTTPError as e:
        return e.code, e.read(), dict(e.headers)


def flushes_by_reason(stats):
    counters = stats.snapshot()["counters"]
    return {r: counters[f"coalescer.flushes{{reason:{r}}}"]
            for r in QueryCoalescer.FLUSH_REASONS}


def seed_data(holder):
    idx = holder.create_index("c")
    f = idx.create_field("f")
    rng = np.random.default_rng(7)
    rows = rng.integers(0, 8, 4000).astype(np.uint64)
    cols = rng.integers(0, 3 * 2**20, 4000).astype(np.uint64)
    f.import_bits(rows, cols)
    idx.add_existence(cols)


@pytest.fixture
def pair(tmp_path):
    """Two identically-seeded live servers: one coalesced, one direct.
    Yields (coalesced_base, direct_base, coalesced_api)."""
    servers, holders, coalescers = [], [], []
    bases = []
    for name, with_coal in (("coal", True), ("direct", False)):
        h = Holder(str(tmp_path / name))
        h.open()
        seed_data(h)
        api = API(h, stats=MemStatsClient())
        if with_coal:
            api.coalescer = QueryCoalescer(
                api.executor, window_s=0.002, max_batch=32,
                stats=api.stats)
            api.coalescer.start()
            coalescers.append(api.coalescer)
            capi = api
        srv = serve(api, "localhost", 0, background=True)
        servers.append(srv)
        holders.append(h)
        bases.append(f"http://localhost:{srv.server_address[1]}")
    yield bases[0], bases[1], capi
    for srv in servers:
        srv.shutdown()
        srv.server_close()
    for c in coalescers:
        c.stop()
    for h in holders:
        h.close()


QUERIES = ([f"Count(Row(f={r}))" for r in range(8)]
           + [f"Row(f={r})" for r in range(4)]
           + ["TopN(f, n=3)", "Count(Union(Row(f=0), Row(f=1)))",
              "Count(Intersect(Row(f=2), Row(f=3)))"])


def test_coalesced_byte_identical_to_direct_threaded(pair):
    """N client threads x M queries against the coalesced server; every
    response body must be byte-identical to the direct server's answer
    for the same query."""
    coal, direct, _api = pair
    want = {q: post(direct, "/index/c/query", q.encode()) for q in QUERIES}
    for q, (st, _, _) in want.items():
        assert st == 200, q
    errors = []
    barrier = threading.Barrier(N_THREADS)

    def worker(tid):
        try:
            barrier.wait()
            for i in range(N_QUERIES):
                q = QUERIES[(tid * N_QUERIES + i) % len(QUERIES)]
                st, body, _ = post(coal, "/index/c/query", q.encode())
                assert st == 200, (q, body)
                assert body == want[q][1], (q, body, want[q][1])
        except Exception as e:  # noqa: BLE001
            errors.append(e)

    threads = [threading.Thread(target=worker, args=(t,))
               for t in range(N_THREADS)]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    assert not errors, errors


def test_error_isolation_across_batchmates(pair):
    """Bad queries (unknown field) racing good ones: each bad request
    gets ITS 400; good batchmates still answer 200 with exact results."""
    coal, direct, _api = pair
    good = "Count(Row(f=1))"
    want = post(direct, "/index/c/query", good.encode())[1]
    errors = []
    barrier = threading.Barrier(N_THREADS)

    def worker(tid):
        try:
            barrier.wait()
            for i in range(N_QUERIES):
                if (tid + i) % 2:
                    st, body, _ = post(coal, "/index/c/query",
                                       b"Count(Row(nope=1))")
                    assert st == 400, (st, body)
                    assert b"error" in body
                else:
                    st, body, _ = post(coal, "/index/c/query",
                                       good.encode())
                    assert st == 200 and body == want, (st, body)
        except Exception as e:  # noqa: BLE001
            errors.append(e)

    threads = [threading.Thread(target=worker, args=(t,))
               for t in range(N_THREADS)]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    assert not errors, errors


def test_writes_flush_and_stay_exact(pair):
    """Write-containing queries ride the coalescer (immediate flush, no
    dedup) while readers hammer the same field; no lost writes."""
    coal, _direct, _api = pair
    errors = []
    barrier = threading.Barrier(4)

    def writer(tid):
        try:
            barrier.wait()
            for i in range(20):
                st, body, _ = post(
                    coal, "/index/c/query",
                    f"Set({4 * 2**20 + tid * 1000 + i}, f={20 + tid})"
                    .encode())
                assert st == 200, body
        except Exception as e:  # noqa: BLE001
            errors.append(e)

    def reader():
        try:
            barrier.wait()
            for _ in range(20):
                st, body, _ = post(coal, "/index/c/query",
                                   b"Count(Row(f=20))")
                assert st == 200, body
        except Exception as e:  # noqa: BLE001
            errors.append(e)

    threads = [threading.Thread(target=writer, args=(t,))
               for t in range(3)] + [threading.Thread(target=reader)]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    assert not errors, errors
    for tid in range(3):
        st, body, _ = post(coal, "/index/c/query",
                           f"Count(Row(f={20 + tid}))".encode())
        assert json.loads(body)["results"] == [20], (tid, body)


def test_dedup_identical_queries_one_execution(pair):
    """Identical read-only queries landing in one window execute once
    and fan out; a long window + barrier makes the batch deterministic."""
    coal, direct, api = pair
    api.coalescer.window_s = 0.25  # hold the window open for the burst
    try:
        want = post(direct, "/index/c/query", b"Count(Row(f=5))")[1]
        results, errors = [], []
        barrier = threading.Barrier(12)

        def worker():
            try:
                barrier.wait()
                results.append(post(coal, "/index/c/query",
                                    b"Count(Row(f=5))"))
            except Exception as e:  # noqa: BLE001
                errors.append(e)

        threads = [threading.Thread(target=worker) for _ in range(12)]
        for t in threads:
            t.start()
        for t in threads:
            t.join()
        assert not errors, errors
        assert all(st == 200 and body == want
                   for st, body, _ in results), results
        snap = api.stats.snapshot()
        assert snap["counters"].get("coalescer.deduped", 0) > 0
        assert snap["histograms"]["coalescer.batch_size"]["count"] >= 1
    finally:
        api.coalescer.window_s = 0.002


@pytest.mark.parametrize("recorded", [True, False])
def test_sequential_client_does_not_wait_out_the_window(pair, recorded,
                                                        monkeypatch):
    """One client on one keep-alive connection: each of its requests is
    the only one the server holds, so none waits for batch-mates — with
    a 50 ms window the median of twenty answers is under 25 ms (the
    slowest is a wall-clock reading under six test workers: one stall of
    the machine, not the window, and the counters below carry the claim
    for every one of the twenty), and the flush reason says why. The
    same with the timeline off: the handler
    then holds no record, the query path opens the request a second
    time, and the server still holds ONE (and lets it go)."""
    import http.client
    from pilosa_tpu.utils.timeline import TIMELINE
    coal, _direct, api = pair
    monkeypatch.setattr(TIMELINE, "enabled", recorded)
    api.coalescer.window_s = 0.05
    host, port = coal.removeprefix("http://").split(":")
    conn = http.client.HTTPConnection(host, int(port), timeout=30)

    def ask(q):
        t0 = time.perf_counter()
        conn.request("POST", "/index/c/query", body=q.encode())
        resp = conn.getresponse()
        body = resp.read()
        assert resp.status == 200, body
        return time.perf_counter() - t0

    try:
        queries = [f"Count(Row(f={r % 8}))" for r in range(20)]
        for q in set(queries):      # compile outside the timed posts
            ask(q)
        before = flushes_by_reason(api.stats)
        times = [ask(q) for q in queries]
        after = flushes_by_reason(api.stats)
    finally:
        conn.close()
        api.coalescer.window_s = 0.002
    assert sorted(times)[len(times) // 2] < 0.025, times
    moved = {r: after[r] - before[r] for r in after if after[r] != before[r]}
    assert moved == {"alone": 20}, moved
    # The request closes in the handler's finally block, after the reply
    # is on the socket: the client can get here first.
    for _ in range(400):
        if api.held.count() == 0:
            break
        time.sleep(0.005)
    assert api.held.count() == 0


class _GatedExecutor:
    """Delegating executor whose execute paths block on a release event
    — pins the dispatcher mid-batch so queue-capacity and deadline
    behavior become deterministic."""

    def __init__(self, inner):
        self._inner = inner
        self.started = threading.Event()
        self.release = threading.Event()

    def __getattr__(self, name):
        return getattr(self._inner, name)

    def _gate(self):
        self.started.set()
        assert self.release.wait(30), "gate never released"

    def execute_full(self, *a, **kw):
        self._gate()
        return self._inner.execute_full(*a, **kw)

    def execute_batch_shaped(self, *a, **kw):
        self._gate()
        return self._inner.execute_batch_shaped(*a, **kw)


@pytest.fixture
def gated(tmp_path):
    """Live server whose coalescer has a tiny queue + deadline and a
    gated executor. Yields (base, gate, api)."""
    h = Holder(str(tmp_path / "g"))
    h.open()
    seed_data(h)
    api = API(h, stats=MemStatsClient())
    gate = _GatedExecutor(api.executor)
    api.coalescer = QueryCoalescer(
        gate, window_s=0.0005, max_batch=8, max_queue=2,
        deadline_s=0.2, stats=api.stats)
    api.coalescer.start()
    srv = serve(api, "localhost", 0, background=True)
    yield f"http://localhost:{srv.server_address[1]}", gate, api
    gate.release.set()
    srv.shutdown()
    srv.server_close()
    api.coalescer.stop()
    h.close()


def _overload_and_eject(base, gate, api):
    """Pin the dispatcher in the gate, fill the queue, and see one
    request rejected (429) and the two queued ones ejected (408)."""
    results = {}

    def bg(name):
        def run():
            results[name] = post(base, "/index/c/query",
                                 b"Count(Row(f=1))")
        t = threading.Thread(target=run)
        t.start()
        return t

    # First request: dispatcher claims it and blocks inside the gate.
    t1 = bg("inflight")
    assert gate.started.wait(10), "dispatcher never started the batch"
    # Two more fill the bounded pending queue (max_queue=2)...
    t2, t3 = bg("q1"), bg("q2")
    deadline = time.monotonic() + 5
    while time.monotonic() < deadline:
        depth = api.stats.snapshot()["gauges"].get(
            "coalescer.queue_depth", 0)
        if depth >= 2:
            break
        time.sleep(0.01)
    # ...so the next submit is rejected up front: 429 + Retry-After.
    st, body, headers = post(base, "/index/c/query", b"Count(Row(f=1))")
    assert st == 429, (st, body)
    assert "Retry-After" in headers, headers
    assert b"capacity" in body
    # The two queued requests outlive their 200 ms queue deadline while
    # the dispatcher stays pinned: ejected with 408, never dispatched.
    t2.join(timeout=10)
    t3.join(timeout=10)
    assert results["q1"][0] == 408, results["q1"]
    assert results["q2"][0] == 408, results["q2"]
    snap = api.stats.snapshot()
    assert snap["counters"].get("coalescer.deadline_ejected", 0) >= 2
    assert snap["counters"].get("coalescer.rejected", 0) >= 1
    # Release the gate: the in-flight request completes normally.
    gate.release.set()
    t1.join(timeout=10)
    assert results["inflight"][0] == 200, results["inflight"]


def test_overload_429_and_deadline_ejection(gated):
    _overload_and_eject(*gated)


def test_held_count_returns_to_zero(gated):
    """The server's count of the query requests it holds opens in
    begin_request and closes in end_request whatever became of the
    request: answered, unparseable, rejected at capacity (429) or
    ejected by its deadline (408). A count that leaked would keep every
    later lone request waiting out the window; one that closed early
    would let a request with batch-mates flush without them."""
    base, gate, api = gated
    with urllib.request.urlopen(base + "/metrics") as resp:
        text = resp.read().decode()
    # Published from the start: a share of flushes is read over a
    # window in which no request may be alone.
    assert 'pilosa_coalescer_flushes_total{reason="alone"} 0' in text, \
        [ln for ln in text.splitlines() if "coalescer_flushes" in ln]
    assert api.held.count() == 0
    _overload_and_eject(base, gate, api)
    assert post(base, "/index/c/query", b"Count(Row(f=")[0] == 400
    assert post(base, "/index/c/query", b"Count(Row(nope=1))")[0] == 400
    for r in range(3):
        st, body, _ = post(base, "/index/c/query",
                           f"Count(Row(f={r}))".encode())
        assert st == 200, body
    # In-process callers hold a request too, and let it go on an error.
    assert api.query_coalesced("c", "Count(Row(f=1))")["results"]
    with pytest.raises(Exception):
        api.query_coalesced("c", "Count(Row(nope=1))")
    # A handler closes its request after the reply is on the socket.
    deadline = time.monotonic() + 5
    while api.held.count() and time.monotonic() < deadline:
        time.sleep(0.01)
    assert api.held.count() == 0
    snap = api.stats.snapshot()["counters"]
    assert snap["coalescer.flushes{reason:alone}"] >= 4, snap


def test_stats_and_metrics_surface(pair):
    """The acceptance-named stats reach both /debug/vars (expvar) and
    /metrics (Prometheus text)."""
    coal, _direct, api = pair
    barrier = threading.Barrier(6)

    def worker():
        barrier.wait()
        for _ in range(4):
            post(coal, "/index/c/query", b"Count(Row(f=1))")

    threads = [threading.Thread(target=worker) for _ in range(6)]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    with urllib.request.urlopen(coal + "/debug/vars") as resp:
        snap = json.loads(resp.read())
    assert "coalescer.queue_depth" in snap["gauges"]
    assert "coalescer.batch_size" in snap["histograms"]
    assert snap["counters"].get("coalescer.admitted", 0) >= 24
    assert any(k.startswith("coalescer.flush.")
               for k in snap["counters"]), snap["counters"]
    with urllib.request.urlopen(coal + "/metrics") as resp:
        text = resp.read().decode()
    assert "pilosa_coalescer_queue_depth" in text
    # occupancy is unitless: no _seconds suffix on the summary
    assert "pilosa_coalescer_batch_size_bucket{" in text
    assert "pilosa_coalescer_batch_size_seconds" not in text
    assert "pilosa_coalescer_flush_" in text


def test_graceful_stop_drains_and_degrades(pair):
    """stop() executes everything already admitted, and later requests
    fall back to the direct path (same answers, no errors)."""
    coal, direct, api = pair
    want = post(direct, "/index/c/query", b"Count(Row(f=2))")[1]
    st, body, _ = post(coal, "/index/c/query", b"Count(Row(f=2))")
    assert st == 200 and body == want
    api.coalescer.stop()
    st, body, _ = post(coal, "/index/c/query", b"Count(Row(f=2))")
    assert st == 200 and body == want


def test_single_request_degrades_to_direct_path(pair):
    """A lone request (batch of one) takes the execute_full path and
    matches the direct server exactly."""
    coal, direct, _api = pair
    for q in ("Count(Row(f=3))", "TopN(f, n=2)"):
        assert (post(coal, "/index/c/query", q.encode())[1]
                == post(direct, "/index/c/query", q.encode())[1]), q


def test_config_coalescer_section(tmp_path):
    """[coalescer] TOML table flattens onto the coalescer_* fields; env
    spelling stays flat."""
    from pilosa_tpu.utils.config import load_config

    p = tmp_path / "c.toml"
    p.write_text('bind = "localhost:1"\n'
                 "[coalescer]\n"
                 "enabled = false\n"
                 "window-ms = 3.5\n"
                 "max_batch = 16\n")
    cfg = load_config(str(p))
    assert cfg.coalescer_enabled is False
    assert cfg.coalescer_window_ms == 3.5
    assert cfg.coalescer_max_batch == 16
    assert cfg.coalescer_max_queue == 256  # untouched default
    with pytest.raises(ValueError, match="unknown config key"):
        p.write_text("[coalescer]\nnot_a_key = 1\n")
        load_config(str(p))


# ---------------------------------------------- pipelined error paths
#
# The RTT-hiding pipelined dispatcher (PR 11) splits a flush into a
# begin half on the dispatcher thread and a _ShapedInFlight drain on
# the finalizer thread. A drain that THROWS must propagate to exactly
# the in-flight batch's requests, must not wedge the depth-1 double
# buffer, and must not leak into the next batch — pinned here (this
# file also runs under PILOSA_TPU_LOCK_CHECK=1 in the check.sh
# lock-order lane, so the error paths hold the lock discipline too).


@pytest.fixture
def plex(tmp_path):
    """In-process executor over the seeded index (the pipelined paths
    under test live below the HTTP layer)."""
    from pilosa_tpu.executor import Executor
    h = Holder(str(tmp_path / "pl"))
    h.open()
    seed_data(h)
    ex = Executor(h)
    ex.result_cache.enabled = False
    yield ex
    h.close()


def _pl_burst(co, queries, timeout=60):
    """Submit every query from its own thread; returns ({i: result},
    {i: exception}) with no worker left hanging."""
    results, errors = {}, {}
    barrier = threading.Barrier(len(queries))

    def worker(i, q):
        try:
            barrier.wait()
            results[i] = co.submit("c", q)
        except Exception as e:  # noqa: BLE001 — the subject under test
            errors[i] = e

    threads = [threading.Thread(target=worker, args=(i, q))
               for i, q in enumerate(queries)]
    for t in threads:
        t.start()
    for t in threads:
        t.join(timeout=timeout)
    assert not any(t.is_alive() for t in threads), \
        "a submitter wedged — the pipeline lost its batch"
    return results, errors


_PL_QUERIES = [f"Count(Row(f={r % 8}))" if r % 2 else f"Row(f={r % 8})"
               for r in range(16)]


def test_pipelined_finalizer_exception_propagates_and_recovers(plex):
    """A finalizer-thread exception in the _ShapedInFlight drain lands
    on that batch's requests as per-request errors, the depth-1 buffer
    clears, and the very next burst serves correctly."""
    from pilosa_tpu.executor import Executor

    direct = {i: plex.execute_full("c", q)
              for i, q in enumerate(_PL_QUERIES)}
    orig_finish = Executor.execute_batch_shaped_finish
    state = {"boom": True}

    def failing_finish(self, sh):
        if state["boom"]:
            state["boom"] = False
            raise RuntimeError("injected drain failure")
        return orig_finish(self, sh)

    Executor.execute_batch_shaped_finish = failing_finish
    co = QueryCoalescer(plex, window_s=0.005, max_batch=8,
                        stats=MemStatsClient(), pipeline=True)
    co.start()
    try:
        results, errors = _pl_burst(co, _PL_QUERIES)
        assert co.pipelined_flushes >= 1
        assert errors, "the failing drain must surface somewhere"
        for i, e in errors.items():
            assert "injected drain failure" in str(e), (i, e)
        # Requests outside the failed batch are untouched — correct
        # results, not errors.
        for i, res in results.items():
            assert res == direct[i], (i, _PL_QUERIES[i])
        # The double buffer is clear (not wedged) ...
        with co._pl_cond:
            assert co._pl_pending is None
        # ... and the next burst is fully correct: the error did not
        # leak forward.
        results2, errors2 = _pl_burst(co, _PL_QUERIES)
        assert not errors2, errors2
        assert results2 == direct
        assert co.pipelined_flushes >= 2
    finally:
        # Restore FIRST: a stop() that raises (the wedge this test
        # exists to catch) must not leak the patch into later tests.
        Executor.execute_batch_shaped_finish = orig_finish
        co.stop()


def test_pipelined_drain_failure_respects_batch_boundaries(plex):
    """While batch K's drain fails on the finalizer, batch K+1 has
    already dispatched (the overlap the pipeline exists for): K+1's
    requests must still resolve correctly — errors stay inside K."""
    from pilosa_tpu.executor import Executor

    direct = {i: plex.execute_full("c", q)
              for i, q in enumerate(_PL_QUERIES)}
    orig_begin = Executor.execute_batch_shaped_begin
    orig_finish = Executor.execute_batch_shaped_finish
    second_begin = threading.Event()
    state = {"begins": 0, "doomed": None}
    lock = threading.Lock()

    def tagged_begin(self, reqs, profiles=None):
        sh = orig_begin(self, reqs, profiles=profiles)
        with lock:
            state["begins"] += 1
            if state["begins"] == 1:
                state["doomed"] = sh
            elif state["begins"] == 2:
                second_begin.set()
        return sh

    def gated_finish(self, sh):
        if sh is state["doomed"]:
            # Hold the drain until the NEXT batch is in flight, then
            # fail: the overlap window is provably open.
            second_begin.wait(timeout=30)
            raise RuntimeError("injected drain failure")
        return orig_finish(self, sh)

    Executor.execute_batch_shaped_begin = tagged_begin
    Executor.execute_batch_shaped_finish = gated_finish
    co = QueryCoalescer(plex, window_s=0.005, max_batch=4,
                        stats=MemStatsClient(), pipeline=True)
    co.start()
    try:
        results, errors = _pl_burst(co, _PL_QUERIES)
        assert second_begin.is_set(), \
            "test premise: a second batch dispatched during the drain"
        assert errors, "the doomed batch's requests must error"
        for i, e in errors.items():
            assert "injected drain failure" in str(e), (i, e)
        for i, res in results.items():
            assert res == direct[i], (i, _PL_QUERIES[i])
        with co._pl_cond:
            assert co._pl_pending is None
    finally:
        Executor.execute_batch_shaped_begin = orig_begin
        Executor.execute_batch_shaped_finish = orig_finish
        co.stop()


def test_pipelined_finalizer_base_exception_wrapped(plex):
    """A non-Exception BaseException from the drain must not kill the
    finalizer silently: items resolve with a CoalescerStopped wrapper
    and the loop keeps draining subsequent batches."""
    from pilosa_tpu.executor import Executor
    from pilosa_tpu.server.coalescer import CoalescerStopped

    orig_finish = Executor.execute_batch_shaped_finish
    state = {"boom": True}

    def failing_finish(self, sh):
        if state["boom"]:
            state["boom"] = False
            raise SystemExit("injected non-Exception failure")
        return orig_finish(self, sh)

    Executor.execute_batch_shaped_finish = failing_finish
    co = QueryCoalescer(plex, window_s=0.005, max_batch=8,
                        stats=MemStatsClient(), pipeline=True)
    co.start()
    try:
        results, errors = _pl_burst(co, _PL_QUERIES)
        assert errors
        for e in errors.values():
            assert isinstance(e, CoalescerStopped), e
        results2, errors2 = _pl_burst(co, _PL_QUERIES[:8])
        assert not errors2, errors2
        assert len(results2) == 8
    finally:
        Executor.execute_batch_shaped_finish = orig_finish
        co.stop()


def test_a_slow_flush_caps_the_next_ones_at_the_target(plex, monkeypatch):
    """A pipelined flush answers its members when the last is finalised,
    so the coalescer holds a flush to FLUSH_TARGET_S: after one whose
    wall time a member was long, the next flushes claim only as many
    members as fit the target (never fewer than two); once flushes are
    quick again the cap is max_batch's."""
    from pilosa_tpu.executor import Executor
    from pilosa_tpu.server import coalescer as co_mod

    monkeypatch.setattr(co_mod, "FLUSH_TARGET_S", 0.2)
    direct = {i: plex.execute_full("c", q)
              for i, q in enumerate(_PL_QUERIES)}
    orig_finish = Executor.execute_batch_shaped_finish
    slow = {"on": True}

    def slow_finish(self, sh):
        if slow["on"]:
            time.sleep(0.4)         # >= 0.05 s a member of a flush of 8
        return orig_finish(self, sh)

    monkeypatch.setattr(Executor, "execute_batch_shaped_finish", slow_finish)
    sizes = []
    orig_pipelined = QueryCoalescer._execute_pipelined

    def recording(self, batch, reason):
        sizes.append(len(batch))
        return orig_pipelined(self, batch, reason)

    monkeypatch.setattr(QueryCoalescer, "_execute_pipelined", recording)
    co = QueryCoalescer(plex, window_s=0.05, max_batch=8,
                        stats=MemStatsClient(), pipeline=True)
    co.start()
    try:
        results, errors = _pl_burst(co, _PL_QUERIES)
        assert not errors and results == direct
        # Those flushes knew no member time (the second was claimed
        # while the first drained); the next burst's are held to
        # 0.2 s / (>= 0.05 s a member) = at most four members.
        assert max(sizes) > 4
        sizes.clear()
        results, errors = _pl_burst(co, _PL_QUERIES)
        assert not errors and results == direct
        assert max(sizes) <= 4 and min(sizes) >= 2
        slow["on"] = False
        for _ in range(3):          # quick flushes lift the cap again
            results, errors = _pl_burst(co, _PL_QUERIES)
            assert not errors and results == direct
        assert co._member_s < 0.2 / 8
        sizes.clear()
        _pl_burst(co, _PL_QUERIES)
        assert max(sizes) > 4
    finally:
        co.stop()


# ------------------------------------------- a request that is alone
#
# The window is a wait for batch-mates. The server counts the query
# requests it holds (API.held); a queued request that is the only one
# does not wait, every other case does what it did before.


@pytest.mark.parametrize("case", ["alone", "two_held", "bare_submit",
                                  "write_alone"])
def test_window_is_skipped_only_for_a_request_that_is_alone(plex, case):
    from pilosa_tpu.server.api import HeldRequests

    stats = MemStatsClient()
    held = HeldRequests()
    co = QueryCoalescer(plex, window_s=0.25, max_batch=8, stats=stats)
    co.start()
    both_held = threading.Barrier(2)
    took, errors = {}, []

    def client(name, query, announce, delay=0.0, together=False):
        """What a handler does: hold the request, submit, let go."""
        try:
            if announce:
                held.open()
            if together:
                both_held.wait(10)
            time.sleep(delay)       # the second client is still "parsing"
            t0 = time.perf_counter()
            co.submit("c", query, held=held if announce else None)
            took[name] = time.perf_counter() - t0
        except Exception as e:  # noqa: BLE001
            errors.append(e)
        finally:
            if announce:
                held.close()

    plans = {
        "alone": [("a", "Count(Row(f=1))", True)],
        "two_held": [("a", "Count(Row(f=1))", True, 0.0, True),
                     ("b", "Count(Row(f=2))", True, 0.05, True)],
        "bare_submit": [("a", "Count(Row(f=1))", False)],
        "write_alone": [("a", f"Set({5 * 2**20}, f=30)", True)],
    }
    try:
        plex.execute_full("c", "Count(Row(f=1))")    # compile first
        threads = [threading.Thread(target=client, args=args)
                   for args in plans[case]]
        for t in threads:
            t.start()
        for t in threads:
            t.join(timeout=30)
        assert not any(t.is_alive() for t in threads)
        assert not errors, errors
    finally:
        co.stop()
    assert held.count() == 0
    moved = {r: n for r, n in flushes_by_reason(stats).items() if n}
    sizes = stats.snapshot()["histograms"]["coalescer.batch_size"]
    if case == "alone":
        assert moved == {"alone": 1}, moved
        assert took["a"] < 0.1, took
    elif case == "two_held":
        # The first waited for the one still on its way, and both rode
        # one flush.
        assert moved == {"window": 1}, moved
        assert sizes["count"] == 1 and sizes["sum"] == 2, sizes
        assert took["a"] >= 0.2 and took["b"] >= 0.15, took
    elif case == "bare_submit":
        # A caller that announces nothing may be one of many: it waits.
        assert moved == {"window": 1}, moved
        assert took["a"] >= 0.2, took
    else:
        assert moved == {"write": 1}, moved
        assert took["a"] < 0.1, took
