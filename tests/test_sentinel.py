"""SLO & regression-sentinel plane (utils/sentinel.py + the
server/CLI wiring + tools/doctor.py): objective parsing, windowed-delta
latency quantiles (a step change shows up in the window, not diluted
by lifetime counts), the multi-window burn-rate fire/clear state
machine on an injected clock (no wall-clock sleeps anywhere), the
history ring bounds + ledger registration, the /debug/history +
/debug/slo + /cluster/slo surfaces, the client.5xx end-to-end alert
path across every surface, the drain ordering/once pins, the
zero-new-fences acceptance bar, and the doctor bundle verdicts."""

import json
import time
import urllib.error
import urllib.request

import pytest

from pilosa_tpu.utils.memledger import MemoryLedger
from pilosa_tpu.utils.sentinel import (
    BURN_WINDOWS, CLEAR_FACTOR, SENTINEL, SentinelRecorder,
    parse_objective, quantile_from_deltas,
)
from pilosa_tpu.utils.stats import MemStatsClient, prometheus_text

SLO_BUCKETS = tuple(2.0 ** e for e in range(-14, 4))
EP_TAGS = ("endpoint:/index/{index}/query", "status:200")


@pytest.fixture(autouse=True)
def _reset_sentinel():
    """The recorder is process-wide (like roofline.ROOFLINE): every
    test starts clean and leaves defaults behind."""
    SENTINEL.reset()
    SENTINEL.configure(enabled=True, ring=720, decimate=10,
                       alert_ring=256, objectives={},
                       watermark_bytes=0)
    yield
    SENTINEL.reset()
    SENTINEL.configure(enabled=True, ring=720, decimate=10,
                       alert_ring=256, objectives={},
                       watermark_bytes=0)
    import time
    SENTINEL.clock = time.time


def _recorder(objectives=None, **kw):
    s = SentinelRecorder()
    s.configure(enabled=True, ring=kw.pop("ring", 720),
                decimate=kw.pop("decimate", 10),
                alert_ring=kw.pop("alert_ring", 64),
                objectives=objectives or {}, **kw)
    return s


def _observe(stats, seconds, n=1, status=200):
    red = stats.with_tags("endpoint:/index/{index}/query",
                          f"status:{status}")
    for _ in range(n):
        red.histogram("http_request_seconds", seconds,
                      buckets=SLO_BUCKETS)


def _histos(stats):
    return stats.snapshot()["histograms"]


# ------------------------------------------------------ objective parsing


def test_parse_objective():
    assert parse_objective("99.9% < 25ms") == \
        (pytest.approx(0.999), pytest.approx(0.025))
    assert parse_objective(" 95 % < 2 s ") == \
        (pytest.approx(0.95), 2.0)
    assert parse_objective("99% < 500us") == \
        (pytest.approx(0.99), pytest.approx(0.0005))
    for bad in ("99.9%", "< 25ms", "99.9 < 25ms", "99.9% < 25",
                "99.9% < 25m", "101% < 1s", "0% < 1s", "99% < 0ms"):
        with pytest.raises(ValueError):
            parse_objective(bad)


def test_quantile_from_deltas_interpolation():
    # Finite bounds only; the +Inf bucket is deltas' extra last entry.
    bounds = (0.001, 0.01, 0.1)
    # 10 obs in (0.001, 0.01]: p50 interpolates inside that bucket.
    q = quantile_from_deltas(bounds, (0, 10, 0, 0), 0.50)
    assert 0.001 < q <= 0.01
    # Observations in the +Inf bucket clamp to the last finite bound.
    assert quantile_from_deltas(bounds, (0, 0, 0, 5), 0.99) == 0.1
    assert quantile_from_deltas(bounds, (0, 0, 0, 0), 0.99) == 0.0


# -------------------------------------------------- windowed quantiles


def test_windowed_quantiles_see_step_change():
    """Satellite: latency quantiles derive from histogram DELTAS
    between consecutive samples, not lifetime counts — a latency step
    change shows in the next tick even after a long fast history."""
    sent = _recorder({"query": "99% < 25ms"})
    stats = MemStatsClient()
    t = 1000.0
    sent.sample({}, _histos(stats), now=t)
    # Long fast regime: 200 observations at ~5 ms over 20 ticks.
    for _ in range(20):
        _observe(stats, 0.005, n=10)
        t += 30.0
        sent.sample({}, _histos(stats), now=t)
    snap = sent.slo_snapshot()
    fast_p95 = snap["endpoints"][0]["rates"]["p95"]
    assert fast_p95 < 0.01
    # Step: ONE tick of 200 ms observations. A lifetime quantile over
    # 210 observations would still sit in the 5 ms buckets; the
    # windowed delta must land in the 200 ms regime.
    _observe(stats, 0.200, n=10)
    t += 30.0
    sent.sample({}, _histos(stats), now=t)
    snap = sent.slo_snapshot()
    rates = snap["endpoints"][0]["rates"]
    assert rates["p50"] > 0.1, rates
    assert rates["p95"] > 0.1, rates
    assert rates["qps"] == pytest.approx(10 / 30.0)
    # The derived rates are also history series (endpoint.query.*).
    hist = sent.history(series=["endpoint.query.p95"])
    pts = hist["series"]["endpoint.query.p95"]["points"]
    assert pts[-1][1] > 0.1 and pts[0][1] < 0.01


# ------------------------------------------------- burn-rate state machine


def test_burn_alert_fires_sticky_and_clears_with_hysteresis():
    """The multi-window multi-burn-rate state machine on an injected
    clock: a 50%-bad burst fires both window pairs, the alert stays
    sticky while burn hovers between clear and fire thresholds, and
    clears only when BOTH windows drop below threshold*CLEAR_FACTOR."""
    sent = _recorder({"query": "99.9% < 25ms"})
    stats = MemStatsClient()
    t = 1000.0
    sent.sample({}, _histos(stats), now=t)
    _observe(stats, 0.005, n=32)                  # healthy baseline
    t += 30.0
    sent.sample({}, _histos(stats), now=t)
    assert sent.active_alerts() == []
    _observe(stats, 0.005, n=32, status=500)      # the bad burst
    t += 30.0
    sent.sample({}, _histos(stats), now=t)
    keys = {a["key"] for a in sent.active_alerts()}
    assert keys == {"slo-burn:query:300s", "slo-burn:query:1800s"}
    snap = sent.slo_snapshot()
    ep = snap["endpoints"][0]
    assert len(ep["burn"]) == len(BURN_WINDOWS) == 2
    for b in ep["burn"]:
        assert b["active"]
        assert b["fastBurn"] > b["threshold"]
    assert ep["budgetConsumed"] > 1.0             # budget blown
    assert ep["budgetRemaining"] == 0.0
    # Recovery, but within the slow windows: cumulative counters mean
    # the old-window delta still contains the burst -> sticky, no
    # clear, no re-fire (fired count unchanged).
    fired = snap["alerts"]["fired"]
    _observe(stats, 0.005, n=32)
    t += 60.0
    sent.sample({}, _histos(stats), now=t)
    assert {a["key"] for a in sent.active_alerts()} == keys
    assert sent.slo_snapshot()["alerts"]["fired"] == fired == 2
    # Jump past the slowest window (6 h): every window's delta is now
    # bad-free -> burn 0 < threshold*CLEAR_FACTOR for both pairs.
    assert CLEAR_FACTOR == 0.5
    t += 22000.0
    _observe(stats, 0.005, n=32)
    sent.sample({}, _histos(stats), now=t)
    assert sent.active_alerts() == []
    snap = sent.slo_snapshot()
    assert snap["alerts"]["cleared"] == 2
    events = [(e["event"], e["key"]) for e in snap["alerts"]["ring"]]
    assert events.count(("fire", "slo-burn:query:300s")) == 1
    assert events.count(("clear", "slo-burn:query:300s")) == 1


def test_latency_violations_burn_budget_without_5xx():
    """The objective is availability AND latency: requests over the
    threshold bucket are bad even when every status is 200."""
    sent = _recorder({"query": "99% < 25ms"})
    stats = MemStatsClient()
    t = 0.0
    _observe(stats, 0.005, n=2)                   # baseline sample
    sent.sample({}, _histos(stats), now=t)
    _observe(stats, 0.200, n=10)                  # slow but 200
    t += 30.0
    sent.sample({}, _histos(stats), now=t)
    ep = sent.slo_snapshot()["endpoints"][0]
    assert ep["bad"] == 10
    assert ep["budgetConsumed"] > 1.0
    # thresholdBucket reports the bucket bound the 25 ms objective
    # actually snapped to (pow-2 buckets: 31.25 ms).
    assert ep["thresholdBucket"] == pytest.approx(0.03125)


def test_note_condition_edge_triggered():
    sent = _recorder()
    sent.note_condition("hbm.pressure", True, "over watermark",
                        kind="memory", now=1.0)
    sent.note_condition("hbm.pressure", True, "over watermark",
                        now=2.0)  # still true: no duplicate fire
    snap = sent.slo_snapshot()
    assert snap["alerts"]["fired"] == 1
    assert len(snap["alerts"]["ring"]) == 1
    sent.note_condition("hbm.pressure", False, now=3.0)
    sent.note_condition("hbm.pressure", False, now=4.0)
    snap = sent.slo_snapshot()
    assert snap["alerts"]["cleared"] == 1
    assert sent.active_alerts() == []


# ------------------------------------------------------ history ring


def test_history_ring_bounded_with_decimated_tier():
    sent = _recorder(ring=16, decimate=4)
    for i in range(100):
        sent.sample({"device_idle_ratio": i / 100.0}, None,
                    now=float(i))
    doc = sent.history()
    s = doc["series"]["device_idle_ratio"]
    assert len(s["points"]) == 16                # raw tier bounded
    assert s["points"][-1] == [99.0, 0.99]
    assert len(s["decimated"]) == 16             # 10:1 -> here 4:1
    assert s["decimate"] == 4
    # Decimated tier retains OLDER history than the raw tier spans.
    assert s["decimated"][0][0] < s["points"][0][0]
    # Timestamps strictly monotone in both tiers.
    for tier in (s["points"], s["decimated"]):
        ts = [p[0] for p in tier]
        assert ts == sorted(ts) and len(set(ts)) == len(ts)
    # series= filter and last= truncation.
    doc = sent.history(series=["nope"])
    assert doc["series"] == {}
    doc = sent.history(series=["device_idle_ratio"], last=3)
    assert len(doc["series"]["device_idle_ratio"]["points"]) == 3
    # Perfetto counter export: one ph:"C" event per returned point.
    evs = doc["traceEvents"]
    assert len(evs) == 3
    assert all(e["ph"] == "C" and e["name"] == "history:device_idle_ratio"
               for e in evs)
    assert evs[-1]["args"]["value"] == 0.99


def test_ring_nbytes_ledgered():
    """History ring bytes are ledger-provable: the `telemetry`
    category carries a sentinel_rings entry equal to ring_nbytes()."""
    sent = _recorder({"query": "99% < 25ms"})
    stats = MemStatsClient()
    _observe(stats, 0.005, n=8)
    for i in range(12):
        sent.sample({"device_idle_ratio": 0.5}, _histos(stats),
                    now=float(i))
    led = MemoryLedger()
    sent.register_memory(led)
    n = sent.ring_nbytes()
    assert n > 512
    assert led.totals()["telemetry"]["bytes"] == n
    entries = led.entries("telemetry")
    assert any(e.get("kind") == "sentinel" for e in entries)
    # Snapshot totals include it (the /debug/memory provability pin).
    snap = led.snapshot()
    assert snap["totalBytes"] == sum(
        c["bytes"] for c in snap["categories"].values())


def test_disabled_sentinel_is_inert():
    sent = _recorder()
    sent.configure(enabled=False)
    sent.sample({"device_idle_ratio": 0.5}, None, now=1.0)
    sent.note_condition("x", True, now=2.0)
    snap = sent.slo_snapshot()
    assert snap["samples"] == 0 and snap["alerts"]["fired"] == 0


# ------------------------------------------------------ /metrics + HELP


def test_publish_gauges_and_help_lines():
    """Satellite: publish() exports burn/budget/alert gauges, and
    prometheus_text emits exactly one # HELP immediately before
    exactly one # TYPE per family."""
    sent = _recorder({"query": "99.9% < 25ms"})
    stats = MemStatsClient()
    t = 0.0
    _observe(stats, 0.005, n=4)                   # baseline sample
    sent.sample({}, _histos(stats), now=t)
    _observe(stats, 0.005, n=32, status=500)
    t += 30.0
    sent.sample({}, _histos(stats), now=t)
    sent.publish(stats)
    prom = prometheus_text(stats)
    assert 'pilosa_slo_burn_rate{endpoint="query",window="300s"}' \
        in prom
    assert 'pilosa_slo_burn_rate{endpoint="query",window="21600s"}' \
        in prom
    assert 'pilosa_slo_error_budget_remaining{endpoint="query"} 0' \
        in prom
    assert "pilosa_sentinel_alerts_active 2" in prom
    assert "pilosa_sentinel_alerts_fired 2" in prom
    lines = prom.splitlines()
    helps = [l for l in lines if l.startswith("# HELP")]
    types = [l for l in lines if l.startswith("# TYPE")]
    assert len(helps) == len(types) > 0
    seen = set()
    for i, l in enumerate(lines):
        if not l.startswith("# TYPE "):
            continue
        fam = l.split()[2]
        assert fam not in seen          # one TYPE per family
        seen.add(fam)
        # HELP directly precedes its TYPE and names the same family.
        assert lines[i - 1].startswith(f"# HELP {fam} "), lines[i - 1]
    # Registered families get real help text, not the fallback.
    assert "# HELP pilosa_slo_burn_rate " in prom
    assert "pilosa-tpu metric pilosa_slo_burn_rate" not in prom


# ------------------------------------------------------ server wiring


def _get(base, path):
    with urllib.request.urlopen(base + path, timeout=30) as r:
        return json.loads(r.read())


def test_debug_history_and_slo_routes(live_server):
    base, api, _h = live_server
    clock = [5000.0]
    SENTINEL.configure(objectives={"query": "99.9% < 25ms"},
                       clock=lambda: clock[0])
    for _ in range(3):
        api.sample_sentinel()
        clock[0] += 30.0
    doc = _get(base, "/debug/history")
    assert doc["samples"] == 3 and "node" in doc
    assert len(doc["series"]) >= 3          # roofline/caches/hbm...
    for s in doc["series"].values():
        ts = [p[0] for p in s["points"]]
        assert ts == sorted(ts)
    names = set(doc["series"])
    assert {"hbm_live_bytes", "result_cache_hit_ratio"} <= names
    # The host-clock idle gauge left with the dispatch-gap analyzer.
    assert "device_idle_ratio" not in names
    # series= + last= narrow the document.
    doc = _get(base, "/debug/history?series=hbm_live_bytes&last=2")
    assert set(doc["series"]) == {"hbm_live_bytes"}
    assert len(doc["series"]["hbm_live_bytes"]["points"]) == 2
    # Unknown query params are rejected (the surface-wide contract).
    with pytest.raises(urllib.error.HTTPError) as ei:
        _get(base, "/debug/history?bogus=1")
    assert ei.value.code == 400
    doc = _get(base, "/debug/slo")
    assert doc["enabled"] and doc["samples"] == 3
    assert doc["objectives"]["query"]["thresholdS"] == 0.025
    assert doc["burnWindows"] == [dict(w) for w in BURN_WINDOWS]
    # Single-node /cluster/slo degrades to the local document.
    doc = _get(base, "/cluster/slo")
    assert doc["totalNodes"] == doc["respondedNodes"] == 1
    assert doc["totals"]["alertsActive"] == 0
    # /internal/health carries the compact slo stanza.
    doc = _get(base, "/internal/health")
    assert doc["slo"]["objectives"] == 1
    assert doc["slo"]["alertsActive"] == 0
    # /metrics carries uptime + build info (satellite).
    with urllib.request.urlopen(base + "/metrics", timeout=30) as r:
        met = r.read().decode()
    assert "pilosa_process_uptime_seconds" in met
    assert 'pilosa_build_info{' in met and 'version="' in met \
        and 'backend="' in met
    assert "pilosa_sentinel_series" in met


def test_sentinel_sampling_adds_no_device_fences(live_server,
                                                 monkeypatch):
    """Acceptance: the whole sentinel plane is host-side dict reads —
    sampling, history, slo and the metrics refresh never fence the
    device (GL003 by construction, pinned here)."""
    import pilosa_tpu.executor.executor as exmod
    base, api, _h = live_server
    SENTINEL.configure(objectives={"query": "99.9% < 25ms"},
                       clock=lambda: 1.0)
    fences = []
    monkeypatch.setattr(exmod, "_fence_device",
                        lambda out: fences.append(1) or 0.0)
    api.sample_sentinel()
    api.debug_history()
    api.debug_slo()
    api.cluster_slo()
    api.refresh_memory_gauges()
    assert fences == []


# ------------------------------------------------ cluster fire/clear e2e


def test_client_5xx_burst_fires_and_clears_across_surfaces(tmp_path):
    """The acceptance scenario end to end on a 2-node cluster with an
    injected clock: a client.5xx failpoint burst fires the burn-rate
    alert visibly in /debug/slo, /metrics, /internal/health and
    /cluster/slo; recovery past the slow window clears it with
    hysteresis. No wall-clock sleeps."""
    from pilosa_tpu.utils.failpoints import FAILPOINTS
    from tests.test_cluster import _seed_bits, req, run_cluster

    clock = [1000.0]
    # 100 s threshold sits past every finite pow-2 bucket, so the
    # objective degrades to availability-only (thresholdBucket +Inf):
    # the e2e pin is the 5xx path, and real wall-clock latency on a
    # loaded CI box must not be able to burn budget here (the latency
    # leg is pinned separately on synthetic histograms).
    SENTINEL.configure(objectives={"query": "99.9% < 100s"},
                       clock=lambda: clock[0])
    nodes = run_cluster(tmp_path, 2, replica_n=1)
    try:
        base = nodes[0].uri
        _seed_bits(base)
        api = nodes[0].api
        sent = [0]

        def settle():
            # _observe_slo runs in the handler's `finally`, AFTER the
            # response bytes hit the socket — the client can return
            # before the server thread records the observation. Wait
            # for every sent query to land in the histogram so a
            # straggler 5xx cannot leak past a sample into the
            # recovery window (which would keep the alert burning).
            def landed():
                return sum(
                    h["count"] for k, h in
                    api.stats.snapshot()["histograms"].items()
                    if k.startswith("http_request_seconds")
                    and "/index/{index}/query" in k)
            deadline = time.time() + 10.0
            while landed() < sent[0] and time.time() < deadline:
                time.sleep(0.005)
            assert landed() >= sent[0]

        for _ in range(8):   # warm jit/caches BEFORE the baseline
            sent[0] += 1
            req(base, "POST", "/index/ci/query", b"Count(Row(f=1))")

        def burst(n=32, expect_5xx=False):
            bad = 0
            for _ in range(n):
                sent[0] += 1
                try:
                    req(base, "POST", "/index/ci/query",
                        b"Count(Row(f=1))")
                except urllib.error.HTTPError as e:
                    assert e.code >= 500
                    bad += 1
            assert (bad > 0) == expect_5xx
            settle()
            clock[0] += 30.0
            api.sample_sentinel()

        settle()
        api.sample_sentinel()          # baseline sample
        clock[0] += 30.0
        burst()                        # healthy traffic
        doc = req(base, "GET", "/debug/slo")
        assert doc["alerts"]["active"] == []
        ep = next(e for e in doc["endpoints"] if "target" in e)
        assert ep["total"] >= 32 and ep["bad"] == 0
        assert ep["thresholdBucket"] == "+Inf"  # availability-only

        # Fail the partner node's client leg: fan-out queries now 500.
        port1 = nodes[1].uri.rsplit(":", 1)[1]
        FAILPOINTS.arm("client.5xx", f"partition(:{port1})")
        burst(expect_5xx=True)
        FAILPOINTS.disarm_all()

        doc = req(base, "GET", "/debug/slo")
        active = {a["key"] for a in doc["alerts"]["active"]}
        assert active == {"slo-burn:query:300s",
                          "slo-burn:query:1800s"}
        met = req(base, "GET", "/metrics", raw=True).decode()
        assert "pilosa_sentinel_alerts_active 2" in met
        assert 'pilosa_slo_burn_rate{endpoint="query",window="300s"}' \
            in met
        health = req(base, "GET", "/internal/health")
        assert health["slo"]["alertsActive"] == 2
        assert health["slo"]["worstBurn"] > 14.4
        cdoc = req(base, "GET", "/cluster/slo")
        assert cdoc["respondedNodes"] == 2
        assert cdoc["totals"]["alertsActive"] >= 2
        assert cdoc["totals"]["endpoints"]["query"]["bad"] > 0
        assert cdoc["totals"]["endpoints"]["query"][
            "budgetConsumed"] > 1.0
        chealth = req(base, "GET", "/cluster/health")
        assert chealth["totals"]["sloAlertsActive"] >= 2

        # Recovery: jump past the 6 h slow window; good traffic only.
        clock[0] += 22000.0
        burst()
        doc = req(base, "GET", "/debug/slo")
        assert doc["alerts"]["active"] == []
        assert doc["alerts"]["cleared"] == 2
        met = req(base, "GET", "/metrics", raw=True).decode()
        assert "pilosa_sentinel_alerts_active 0" in met
        # The fleet roll-up sums bad/total, so the burst stays visible
        # in the budget even after the alert clears.
        cdoc = req(base, "GET", "/cluster/slo")
        assert cdoc["totals"]["alertsActive"] == 0
    finally:
        FAILPOINTS.disarm_all()
        for nd in nodes:
            nd.stop()


# ------------------------------------------------------------- drain


def test_drain_telemetry_order_once_and_reentrant(tmp_holder):
    """Satellite: one drain dumps every ring exactly once, in plane
    order (watchdog -> profiler -> workload -> timeline -> roofline ->
    sentinel -> tracer); a second call is a no-op."""
    from pilosa_tpu.cli.main import drain_telemetry
    from pilosa_tpu.server.api import API
    from tests.test_memledger import _LogStub

    api = API(tmp_holder, stats=MemStatsClient())
    SENTINEL.configure(objectives={"query": "99% < 25ms"},
                       clock=lambda: 1.0)
    api.profiler.record_slow("i", "Count(Row(f=1))", 2.5)
    api.sample_sentinel()
    SENTINEL.note_condition("roofline.drift", True, "synthetic",
                            now=2.0)

    class _Tracer:
        stops = 0

        def stop(self):
            self.stops += 1

    api.tracer = _Tracer()
    log = _LogStub()
    drain_telemetry(api, watchdog=None, logger=log)
    sent_lines = [l for l in log.lines if l.startswith("sentinel:")]
    assert any("1 samples" in l for l in sent_lines)
    assert any("alert fire roofline.drift" in l for l in sent_lines)
    # Ordering: profiler's slow-query line precedes the sentinel dump.
    first_sent = next(i for i, l in enumerate(log.lines)
                      if l.startswith("sentinel:"))
    slow = next(i for i, l in enumerate(log.lines)
                if "Count(Row(f=1))" in l)
    assert slow < first_sent
    assert api.tracer.stops == 1
    # Re-entrant second drain: nothing dumps twice, tracer not
    # re-stopped.
    n = len(log.lines)
    drain_telemetry(api, watchdog=None, logger=log)
    assert len(log.lines) == n
    assert api.tracer.stops == 1


# ------------------------------------------------------------- doctor


def _load_doctor():
    import importlib.util
    import pathlib
    path = pathlib.Path(__file__).resolve().parents[1] / "tools" \
        / "doctor.py"
    spec = importlib.util.spec_from_file_location("_doctor", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def test_doctor_bundle_diff_and_baseline(live_server, tmp_path,
                                         capsys):
    """tools/doctor.py against a live server: the bundle captures
    every surface, self-diff is empty (exit 0), the baseline judge
    passes on a healthy unmodified tree, and an active alert flips the
    verdict to failing."""
    doctor = _load_doctor()
    base, api, _h = live_server
    SENTINEL.configure(objectives={"query": "99.9% < 25ms"},
                       clock=lambda: 7000.0)
    api.sample_sentinel()
    bundle = doctor.snapshot_bundle(base)
    assert [k for k, _ in doctor.SURFACES] == list(bundle["surfaces"])
    errs = {k: s["error"] for k, s in bundle["surfaces"].items()
            if "error" in s}
    assert errs == {}, errs
    p1 = tmp_path / "a.json"
    p1.write_text(json.dumps(bundle))
    assert doctor.main(["diff", str(p1), str(p1)]) == 0
    out = capsys.readouterr().out
    assert "0 difference(s)" in out
    # Structural diff pins: changed leaf, added key, volatile ignored.
    lines = doctor.diff_docs(
        doctor._normalize({"a": 1, "t": 5, "x": {"y": 2}}),
        doctor._normalize({"a": 2, "t": 9, "x": {"y": 2, "z": 3}}))
    assert any(l.startswith("~ a:") for l in lines)
    assert any(l.startswith("+ x.z") for l in lines)
    assert not any(" t" in l.split(":")[0] for l in lines)
    # Baseline judge on the healthy bundle: zero failing checks
    # (BASELINE.json's empty `published` skips, never passes).
    verdicts = doctor.judge_bundle(
        bundle, baseline={"published": {}})
    bad = [(c, s, d) for c, s, d in verdicts
           if s in ("FAIL", "REGRESSED")]
    assert bad == [], bad
    assert ("memory.sentinel-ledgered", "PASS") in \
        [(c, s) for c, s, _ in verdicts]
    assert any(c == "baseline.published" and s == "SKIP"
               for c, s, _ in verdicts)
    # Published numbers: regression detected beyond tolerance.
    bundle["metrics"] = {"qps": 50.0}
    verdicts = doctor.judge_bundle(
        bundle, baseline={"published": {"qps": 100.0}})
    assert any(c == "baseline.qps" and s == "REGRESSED"
               for c, s, _ in verdicts)
    # An active alert fails the bundle.
    SENTINEL.note_condition("hbm.pressure", True, "synthetic",
                            now=7100.0)
    bundle2 = doctor.snapshot_bundle(base)
    verdicts = doctor.judge_bundle(bundle2)
    assert any(c == "slo.no-active-alerts" and s == "FAIL"
               for c, s, _ in verdicts)


def test_doctor_records_unreachable_surface():
    doctor = _load_doctor()
    bundle = doctor.snapshot_bundle("http://localhost:1")  # refused
    assert all("error" in s for s in bundle["surfaces"].values())
    verdicts = doctor.judge_bundle(bundle)
    assert any(c == "surface:slo" and s == "FAIL"
               for c, s, _ in verdicts)


# ------------------------------------------------------------- config


def test_config_slo_and_sentinel_tables(tmp_path, monkeypatch):
    from pilosa_tpu.utils.config import Config, load_config
    cfg_path = tmp_path / "c.toml"
    cfg_path.write_text(
        '[slo]\n'
        'query = "99.9% < 25ms"\n'
        '"/batch/query" = "99% < 100ms"\n'
        '[sentinel]\n'
        'ring = 360\n'
        'decimate = 5\n')
    cfg = load_config(str(cfg_path))
    assert cfg.slo == {"query": "99.9% < 25ms",
                       "/batch/query": "99% < 100ms"}
    assert cfg.sentinel_ring == 360 and cfg.sentinel_decimate == 5
    assert cfg.sentinel_enabled
    # Env dict merge layers on top of the file.
    monkeypatch.setenv("PILOSA_TPU_SLO", "query=99% < 50ms")
    cfg = load_config(str(cfg_path))
    assert cfg.slo["query"] == "99% < 50ms"
    assert cfg.slo["/batch/query"] == "99% < 100ms"
    # validate() rejects malformed objectives and bad ring bounds.
    bad = Config()
    bad.slo = {"query": "fast please"}
    with pytest.raises(ValueError, match="objective"):
        bad.validate()
    bad = Config()
    bad.sentinel_ring = 1
    with pytest.raises(ValueError, match="sentinel ring"):
        bad.validate()
