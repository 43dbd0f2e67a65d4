"""Kernel cost & roofline attribution plane (ops/megakernel.plan_cost
+ utils/roofline.py + the executor/metrics wiring): exact hand-computed
byte arithmetic over the full opcode table, the zero-new-fences
acceptance bar on the unsampled path, the /metrics family and label
invariants, the predicted-vs-measured drift detector, and the recorder
bounds (LRU cohorts, memory-ledger registration)."""

import numpy as np
import pytest

from pilosa_tpu.core.holder import Holder
from pilosa_tpu.executor import Executor
from pilosa_tpu.executor import megakernel as megamod
from pilosa_tpu.ops import megakernel as mk
from pilosa_tpu.ops.bitset import SHARD_WIDTH
from pilosa_tpu.utils.memledger import MemoryLedger
from pilosa_tpu.utils.roofline import (
    DRIFT_MARGIN, ROOFLINE, RooflineRecorder,
)
from pilosa_tpu.utils.stats import MemStatsClient, prometheus_text


@pytest.fixture(autouse=True)
def _reset_roofline():
    """The recorder is process-wide (like timeline.TIMELINE): every
    test starts clean and leaves defaults behind."""
    ROOFLINE.reset()
    ROOFLINE.configure(enabled=True, gbps=0.0, ewma_alpha=0.25,
                       max_cohorts=256)
    ROOFLINE.note_sample_every(0)
    yield
    ROOFLINE.reset()
    ROOFLINE.configure(enabled=True, gbps=0.0, ewma_alpha=0.25,
                       max_cohorts=256)
    ROOFLINE.note_sample_every(0)


# --------------------------------------------------- plan_cost arithmetic


def _plan(*, n_slots, widths, instrs, n_instrs, n_regs, out_count,
          out_row, lane_count_widths=(), lane_row_widths=(),
          slots=None, xbanks=(), xslots=(), n_xslots=0):
    """Hand-built Plan: plan_cost reads only host-side fields, so dense
    banks can be empty stand-ins."""
    if slots is None:
        slots = tuple(np.array([i], np.int32) for i in range(n_slots))
    w = np.zeros(n_regs, np.int32)
    w[:len(widths)] = widths
    return mk.Plan(
        banks=tuple(None for _ in range(n_slots)), slots=slots,
        widths=w, instrs=np.asarray(instrs, np.int32),
        out_count=np.asarray(out_count, np.int32),
        out_row=np.asarray(out_row, np.int32),
        n_slots=n_slots, n_regs=n_regs, n_instrs=n_instrs,
        lane_count_widths=lane_count_widths,
        lane_row_widths=lane_row_widths,
        xbanks=xbanks, xslots=xslots, n_xslots=n_xslots)


def test_plan_cost_full_opcode_table_exact():
    """Every opcode priced by its verifier read set: ZERO writes only
    (1 row), COPY reads one (2), AND/OR/XOR/ANDNOT read two (3),
    THRESH is the accumulate opcode — dst is a READ operand too (4)."""
    S, W = 2, 8
    row = S * W * 4                                   # 64
    instrs = [
        (mk.OP_AND, 2, 0, 1), (mk.OP_OR, 3, 0, 1),
        (mk.OP_XOR, 4, 0, 1), (mk.OP_ANDNOT, 5, 2, 3),
        (mk.OP_ZERO, 6, 0, 0), (mk.OP_COPY, 2, 4, 0),
        (mk.OP_THRESH, 6, 2, 3),
        (mk.OP_ZERO, 7, 7, 7),                        # pad tail
    ]
    plan = _plan(n_slots=2, widths=[3, 8], instrs=instrs, n_instrs=7,
                 n_regs=8, out_count=[6, 7], out_row=[4],
                 lane_count_widths=(5,), lane_row_widths=(8,))
    cost = mk.plan_cost(plan, S, W)
    # Gather: per dense slot, live masked words read + one row written.
    assert cost["gatherBytes"] == (S * 3 * 4 + row) + (S * 8 * 4 + row)
    # Compute: 4 three-operand ops + ZERO(1) + COPY(2) + THRESH(4),
    # plus 1 real count lane (popcount row + S*4 out) and 1 real row
    # lane (2 rows).
    assert cost["computeBytes"] == (4 * 3 * row + 1 * row + 2 * row
                                    + 4 * row
                                    + (row + S * 4) + 2 * row)
    assert cost["expandBytes"] == 0
    # Pad: 1 slab register above the high-water mark (the spare), 1 pad
    # instruction, 1 pad count lane; row lanes have no padding.
    assert cost["padBytes"] == row + row + (row + S * 4)
    assert cost["totalBytes"] == (cost["gatherBytes"]
                                  + cost["computeBytes"]
                                  + cost["expandBytes"]
                                  + cost["padBytes"])
    assert cost["opcodeHist"] == {"and": 1, "or": 1, "xor": 1,
                                  "andnot": 1, "zero": 1, "copy": 1,
                                  "thresh": 1}   # REAL instrs only
    assert cost["nInstrs"] == 7
    # Ledger restatement: slab/live-slab/plan bytes as registered.
    assert cost["slabBytes"] == mk.slab_nbytes(8, S, W)
    assert cost["liveSlabBytes"] == mk.slab_nbytes(2, S, W)
    assert cost["planBytes"] == plan.plan_nbytes


def test_plan_cost_expand_scatter_exact():
    """OP_EXPAND traffic: per expand register the sparse bank's full
    (pos, starts) buffers + one scatter-written row; per instruction
    one row read + one written."""
    S, W = 2, 8
    row = S * W * 4
    pos = np.zeros(10, np.int32)                      # 40 bytes
    starts = np.zeros(5, np.int32)                    # 20 bytes
    instrs = [
        (mk.OP_EXPAND, 4, 1, 0), (mk.OP_EXPAND, 5, 2, 0),
        (mk.OP_AND, 6, 4, 5),
        (mk.OP_ZERO, 7, 7, 7),                        # pad tail
    ]
    plan = _plan(n_slots=1, widths=[4], instrs=instrs, n_instrs=3,
                 n_regs=8, out_count=[], out_row=[6],
                 lane_row_widths=(4,),
                 xbanks=((pos, starts),),
                 xslots=(np.array([0, 1], np.int32),), n_xslots=2)
    cost = mk.plan_cost(plan, S, W)
    assert cost["gatherBytes"] == S * 4 * 4 + row
    # 2 expand instrs * 2 rows + 2 expand regs * (pos + starts + row).
    assert cost["expandBytes"] == 2 * 2 * row \
        + 2 * (pos.nbytes + starts.nbytes + row)
    assert cost["computeBytes"] == 3 * row + 2 * row  # AND + row lane
    assert cost["padBytes"] == row + row              # spare + pad instr
    assert cost["liveSlabBytes"] == mk.slab_nbytes(3, S, W)  # slot+2x


def test_plan_cost_zero_reads_opaque_xbank_buffers():
    """Device-opaque (pos, starts) stubs without .nbytes price as 0
    instead of raising — attribution never kills a launch."""
    S, W = 1, 4

    class _Opaque:  # no nbytes, no shape
        pass

    plan = _plan(n_slots=0, widths=[], slots=(),
                 instrs=[(mk.OP_EXPAND, 1, 0, 0)], n_instrs=1,
                 n_regs=4, out_count=[], out_row=[1],
                 lane_row_widths=(4,),
                 xbanks=((_Opaque(), _Opaque()),),
                 xslots=(np.array([0], np.int32),), n_xslots=1)
    cost = mk.plan_cost(plan, S, W)
    row = S * W * 4
    assert cost["expandBytes"] == 2 * row + 1 * row   # buffers priced 0
    assert cost["totalBytes"] > 0


# ------------------------------------------------------ live mega wiring


N_ROWS = 8
MIXED = ([("i", f"Count(Row(f={r}))", None) for r in (1, 2, 3)]
         + [("i", f"Row(g={r})", None) for r in (4, 5)]
         + [("i", "Count(Intersect(Row(f=6), Row(g=7)))", None)])


@pytest.fixture
def ex(tmp_path):
    h = Holder(str(tmp_path))
    h.open()
    idx = h.create_index("i")
    f = idx.create_field("f")
    g = idx.create_field("g")
    rng = np.random.default_rng(23)
    rows = rng.integers(0, N_ROWS, 4000).astype(np.uint64)
    cols = rng.integers(0, 2 * SHARD_WIDTH, 4000).astype(np.uint64)
    f.import_bits(rows, cols)
    g.import_bits(rows[::2], cols[::2])
    idx.add_existence(cols)
    executor = Executor(h)
    executor.result_cache.enabled = False
    prev = megamod.MEGAKERNEL_ENABLED
    megamod.MEGAKERNEL_ENABLED = True
    yield executor
    megamod.MEGAKERNEL_ENABLED = prev
    h.close()


def test_zero_new_fences_on_unsampled_path(ex, monkeypatch):
    """Acceptance: the cost/roofline plane adds NO block_until_ready
    fences — bytes are recorded for every launch, bandwidth only when
    a profiler-sampled fence already fires."""
    import pilosa_tpu.executor.executor as exmod

    fences = []
    monkeypatch.setattr(exmod, "_fence_device",
                        lambda out: fences.append(1) or 0.0)
    ex.execute_batch_shaped(MIXED)
    assert fences == []
    assert ex.mega_launches == 1
    snap = ROOFLINE.snapshot()
    assert snap["launches"] == 1          # cost recorded fence-free
    assert snap["fencedLaunches"] == 0    # ...but no bandwidth sample
    assert snap["bytesByKind"]["gather"] > 0
    assert ex.launch_bytes_gather > 0 and ex.launch_bytes_compute > 0


def test_launch_cost_metrics_families(ex):
    """/metrics invariants: the byte splits export as one counter
    family split by kind=, opcodes as one family split by op= — never
    a family per kind/op (bounded label sets, test_stats.py rules)."""
    from pilosa_tpu.utils.profile import QueryProfile

    ex.stats = MemStatsClient()
    # The CPU backend has no peak on record, so the roofline is given
    # by config here (the gauges below exist only against a known one).
    ROOFLINE.configure(gbps=819.0)
    profs = [QueryProfile(i, q, sample_device=True)
             for i, q, _s in MIXED]
    ex.execute_batch_shaped(MIXED, profiles=profs)
    ROOFLINE.publish(ex.stats)
    prom = prometheus_text(ex.stats)
    for kind in ("gather", "compute", "pad"):
        assert f'pilosa_executor_launch_bytes_total{{kind="{kind}"}}' \
            in prom, prom
    assert 'pilosa_executor_opcode_total{op="' in prom
    assert prom.count("# TYPE pilosa_executor_launch_bytes_total") == 1
    assert prom.count("# TYPE pilosa_executor_opcode_total") == 1
    assert "pilosa_roofline_gbps" in prom
    assert "pilosa_roofline_fraction" in prom
    assert "pilosa_roofline_achieved_gbps" in prom
    # The executor's totals agree with the recorder's.
    snap = ROOFLINE.snapshot()
    assert snap["bytesByKind"]["gather"] == ex.launch_bytes_gather
    assert snap["opcodeTotals"] == ex.opcode_counts
    assert snap["fencedLaunches"] == 1
    assert snap["achievedGbps"] > 0


def test_cost_rides_profile_tree_and_slow_ring(ex):
    """Satellite: eval nodes of a megakernel launch carry launchBytes +
    opcodeHist, so the slow-query ring shows what a launch MOVED."""
    from pilosa_tpu.utils.profile import QueryProfile

    profs = [QueryProfile(i, q) for i, q, _s in MIXED]
    ex.execute_batch(MIXED, profiles=profs)
    assert ex.mega_launches == 1
    for p in profs:
        evals = [n for op in p.ops for n in op.children
                 if n.name.startswith("eval:")]
        assert evals, p.ops
        node = evals[0]
        assert node.attrs["launchBytes"] > 0
        assert isinstance(node.attrs["opcodeHist"], dict)
        assert sum(node.attrs["opcodeHist"].values()) > 0


# ------------------------------------------------------- drift detector


def _cost(total):
    return {"gatherBytes": total, "computeBytes": 0, "expandBytes": 0,
            "padBytes": 0, "totalBytes": total,
            "opcodeHist": {"and": 1}, "nInstrs": 1}


def test_drift_detector_flags_inverted_cohorts():
    """Predicted says A cheaper than B (margin 1.25 on both axes);
    measured fences say the opposite -> both cohorts flagged, the
    counter increments once per transition, re-agreement clears the
    gauge but not the counter."""
    rec = RooflineRecorder(ewma_alpha=1.0)
    rec.configure(enabled=True, gbps=100.0, ewma_alpha=1.0)
    rec.note_launch("A", _cost(100_000), predicted_bytes=100_000)
    rec.note_device("A", 100_000, 0.001)
    assert rec.snapshot()["driftFlags"] == 0   # nothing to compare yet
    # B predicted 2x A's bytes but measured 2.5x FASTER: inversion.
    assert 200_000 > 100_000 * DRIFT_MARGIN
    rec.note_launch("B", _cost(200_000), predicted_bytes=200_000)
    rec.note_device("B", 200_000, 0.0004)
    snap = rec.snapshot()
    assert snap["driftFlags"] == 2             # both sides flagged
    assert all(c["drift"] for c in snap["cohorts"])
    # Residuals rank drift-flagged cohorts first.
    assert snap["residuals"][0]["drift"]
    # Stats counter sees the transitions exactly once.
    stats = MemStatsClient()
    rec.publish(stats)
    rec.publish(stats)  # no new transitions -> no double count
    prom = prometheus_text(stats)
    assert "pilosa_roofline_drift_total 2" in prom, prom
    assert "pilosa_roofline_drift_flagged 2" in prom
    # Measured ordering swings back (alpha=1.0: EWMA = latest): B now
    # slower than A, agreeing with the prediction -> flags clear.
    rec.note_device("B", 200_000, 0.005)
    rec.note_device("A", 100_000, 0.001)
    snap = rec.snapshot()
    assert not any(c["drift"] for c in snap["cohorts"])
    assert snap["driftFlags"] == 2             # history, not state
    rec.publish(stats)
    assert "pilosa_roofline_drift_flagged 0" in prometheus_text(stats)


def test_cohort_lru_bound_and_ledger_registration():
    rec = RooflineRecorder(max_cohorts=2)
    for key in ("A", "B", "C"):
        rec.note_launch(key, _cost(1000))
    snap = rec.snapshot()
    assert len(snap["cohorts"]) == 2
    assert {c["cohort"] for c in snap["cohorts"]} == {"B", "C"}
    led = MemoryLedger()
    rec.register_memory(led)
    tel = led.totals()["telemetry"]
    assert tel["bytes"] == rec.state_nbytes() > 0


def test_device_seconds_estimate_scales_by_sample_rate():
    """Satellite 1: the sampled device-seconds sum is 1-in-N biased;
    the snapshot carries the rate and the scaled unbiased estimate,
    while achieved GB/s comes from per-fence pairs (unbiased as-is)."""
    rec = RooflineRecorder()
    rec.configure(enabled=True, gbps=10.0)
    rec.note_sample_every(4)
    rec.note_launch("A", _cost(10_000_000))
    rec.note_device("A", 10_000_000, 0.001)
    snap = rec.snapshot()
    assert snap["deviceSampleEvery"] == 4
    assert snap["deviceSecondsSampled"] == pytest.approx(0.001)
    assert snap["deviceSecondsEstimate"] == pytest.approx(0.004)
    assert snap["achievedGbps"] == pytest.approx(10.0)  # 10MB in 1ms
    assert snap["rooflineFraction"] == pytest.approx(1.0)


def test_unattributed_fences_counted():
    """Fused/unfused fences carry no plan IR: the surface states its
    own coverage instead of silently claiming all device time."""
    rec = RooflineRecorder()
    rec.note_unattributed_fence(0.002)
    rec.note_unattributed_fence(0.0)   # ignored: unusable
    snap = rec.snapshot()
    assert snap["unattributedFences"] == 1
    assert snap["unattributedDeviceSeconds"] == pytest.approx(0.002)


def test_disabled_recorder_records_nothing():
    rec = RooflineRecorder()
    rec.configure(enabled=False)
    rec.note_launch("A", _cost(1000), predicted_bytes=1000)
    assert rec.note_device("A", 1000, 0.001) is None
    rec.note_unattributed_fence(0.001)
    snap = rec.snapshot()
    assert snap["launches"] == 0 and snap["fencedLaunches"] == 0
    assert snap["unattributedFences"] == 0


def test_unknown_device_kind_publishes_no_fraction(ex):
    """A launch on a device with no peak on record (the CPU backend):
    the kind resolves to "no roofline" once, achieved GB/s still
    accumulates, and no fraction reaches /metrics or the timeline."""
    from pilosa_tpu.utils.profile import QueryProfile

    ex.stats = MemStatsClient()
    profs = [QueryProfile(i, q, sample_device=True)
             for i, q, _s in MIXED]
    ex.execute_batch_shaped(MIXED, profiles=profs)
    assert ROOFLINE.roofline_gbps() == (0.0, "cpu", True)
    assert not ROOFLINE.needs_resolve()
    bw = ROOFLINE.note_device("A", 10**9, 0.01)
    assert bw["gbps"] == pytest.approx(100.0) and bw["frac"] is None
    ROOFLINE.publish(ex.stats)
    prom = prometheus_text(ex.stats)
    assert "pilosa_roofline_achieved_gbps" in prom
    assert "pilosa_roofline_fraction" not in prom
    assert "pilosa_roofline_gbps" not in prom
    snap = ROOFLINE.snapshot()
    assert snap["rooflineGbps"] == 0.0 and snap["estimateOnly"] is True


def test_roofline_gbps_source_precedence():
    rec = RooflineRecorder()
    assert rec.roofline_gbps() == (0.0, "unresolved", True)
    assert rec.needs_resolve()
    rec.set_resolved(819.0, "cpu", True)
    assert rec.roofline_gbps() == (819.0, "cpu", True)
    assert not rec.needs_resolve()
    rec.configure(gbps=1640.0)         # config wins over resolution
    assert rec.roofline_gbps() == (1640.0, "config", False)
    assert not rec.needs_resolve()


# --------------------------------------------------- optimizer calibration


def test_optimizer_records_predicted_bytes(ex, monkeypatch):
    """Calibration feed: every optimized plan carries the density-
    predicted byte cost the drift detector compares against."""
    from pilosa_tpu.ops import plan_opt

    captured = []
    orig = plan_opt.optimize_plan

    def spy(plan, n_shards, w_mega):
        out_plan, stats = orig(plan, n_shards, w_mega)
        captured.append((out_plan, stats))
        return out_plan, stats

    monkeypatch.setattr(plan_opt, "optimize_plan", spy)
    monkeypatch.setattr(megamod, "PLAN_OPT_ENABLED", True)
    ex.execute_batch_shaped(MIXED)
    assert captured
    out_plan, stats = captured[0]
    assert stats.predicted_bytes > 0
    assert stats.as_dict()["predictedBytes"] == stats.predicted_bytes
    # The attached stats ride the plan into _launch's note_launch.
    assert out_plan.opt_stats is stats
    cohorts = ROOFLINE.snapshot()["cohorts"]
    assert cohorts and cohorts[0]["predictedBytesEwma"] == \
        pytest.approx(stats.predicted_bytes)


def test_predict_cost_bytes_density_weighting():
    """The host-side predictor prices reads by operand density: a
    dense-read AND costs more than the same AND over sparse operands,
    and every instruction pays its full row write."""
    from pilosa_tpu.ops.plan_opt import (
        SPARSE_DENSITY, predict_cost_bytes,
    )

    S, W = 2, 8
    row = S * W * 4
    rows = [(mk.OP_AND, 2, 0, 1)]
    dense = predict_cost_bytes(rows, {0: 1.0, 1: 1.0}, S, W)
    sparse = predict_cost_bytes(
        rows, {0: SPARSE_DENSITY, 1: SPARSE_DENSITY}, S, W)
    assert dense == int((1.0 + 1.0 + 1.0) * row)
    assert sparse == int((2 * SPARSE_DENSITY + 1.0) * row)
    assert sparse < dense


# ------------------------------------------------------------- shutdown


def test_dump_writes_printf_lines():
    rec = RooflineRecorder()
    rec.configure(enabled=True, gbps=100.0)
    rec.note_launch("A", _cost(1000), predicted_bytes=1000)
    rec.note_device("A", 1000, 0.001)

    lines = []

    class _Log:
        def printf(self, fmt, *args):
            lines.append(fmt % args if args else fmt)

    assert rec.dump(_Log()) >= 2
    assert all(ln.startswith("roofline:") for ln in lines)
    assert any("residual" in ln for ln in lines)
    # Nothing recorded -> nothing written (quiet shutdowns stay quiet).
    assert RooflineRecorder().dump(_Log()) == 0


def test_config_roofline_keys(tmp_path):
    from pilosa_tpu.utils.config import load_config
    p = tmp_path / "c.toml"
    p.write_text("[roofline]\nenabled = false\ngbps = 1640.0\n"
                 "ewma_alpha = 0.5\nmax_cohorts = 32\n")
    cfg = load_config(str(p))
    assert cfg.roofline_enabled is False
    assert cfg.roofline_gbps == 1640.0
    assert cfg.roofline_ewma_alpha == 0.5
    assert cfg.roofline_max_cohorts == 32
    with pytest.raises(ValueError):
        load_config(None, {"roofline_gbps": -1.0})
    with pytest.raises(ValueError):
        load_config(None, {"roofline_ewma_alpha": 0.0})
    with pytest.raises(ValueError):
        load_config(None, {"roofline_max_cohorts": 0})
