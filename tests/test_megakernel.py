"""Heterogeneous staged-query megakernel (ops/megakernel.py +
executor/megakernel.py) and RTT-hiding pipelined dispatch
(server/coalescer.py): a mixed-signature batch must collapse to
exactly ONE plan-buffer launch with per-query results bit-identical to
the unfused/unpipelined path, the kill switches must restore the
per-group / serial paths exactly, and a pipelined flush must run its
two halves on two threads inside one record. Launch counts are
asserted deterministically through the ``Executor._call_program``
funnel stub (the tests/test_fusion.py idiom)."""

import threading

import numpy as np
import pytest

from pilosa_tpu.core.field import FieldOptions
from pilosa_tpu.core.holder import Holder
from pilosa_tpu.executor import Executor
from pilosa_tpu.executor import megakernel as megamod
from pilosa_tpu.ops import megakernel as mk
from pilosa_tpu.ops.bitset import SHARD_WIDTH

N_ROWS = 16


@pytest.fixture
def ex(tmp_path):
    h = Holder(str(tmp_path))
    h.open()
    idx = h.create_index("i")
    f = idx.create_field("f")
    g = idx.create_field("g")
    rng = np.random.default_rng(23)
    rows = rng.integers(0, N_ROWS, 6000).astype(np.uint64)
    cols = rng.integers(0, 2 * SHARD_WIDTH, 6000).astype(np.uint64)
    f.import_bits(rows, cols)
    g.import_bits(rows[::2], cols[::2])
    # Negative min: BSI base-value offsets are in play, so the lowered
    # plane scans run against offset-encoded predicates like the
    # traced path does.
    idx.create_field("v", FieldOptions(type="int", min=-500, max=10000))
    vcols = rng.integers(0, 2 * SHARD_WIDTH, 900).astype(np.uint64)
    idx.field("v").import_values(
        vcols, rng.integers(-500, 10000, 900).astype(np.int64))
    idx.add_existence(cols)
    executor = Executor(h)
    # Exact launch counts are the subject; the result cache would
    # serve repeats and zero them out (cache-ON interplay is pinned in
    # tests/test_result_cache.py).
    executor.result_cache.enabled = False
    # The default is `auto` (TPU-only — the launch collapse loses on
    # CPU where launches are ~free); force it ON so the CPU test run
    # exercises the megakernel path.
    prev = megamod.MEGAKERNEL_ENABLED
    megamod.MEGAKERNEL_ENABLED = True
    yield executor
    megamod.MEGAKERNEL_ENABLED = prev
    h.close()


def count_dispatches(monkeypatch):
    calls = []
    orig = Executor._call_program

    def stub(self, fn, *args):
        calls.append(fn)
        return orig(self, fn, *args)

    monkeypatch.setattr(Executor, "_call_program", stub)
    return calls


MIXED = ([("i", f"Count(Row(f={r}))", None) for r in (1, 2, 3)]
         + [("i", f"Row(g={r})", None) for r in (4, 5)]
         + [("i", "Count(Intersect(Row(f=6), Row(g=7)))", None)]
         + [("i", "Count(Row(v > 300))", None)]
         + [("i", "Row(v < 9000)", None)])


def test_mixed_signatures_collapse_to_one_launch(ex, monkeypatch):
    direct = [ex.execute_full(i, q, shards=s) for i, q, s in MIXED]
    calls = count_dispatches(monkeypatch)
    jc0 = ex.jit_compiles
    shaped = ex.execute_batch_shaped(MIXED)
    assert shaped == direct
    assert len(calls) == 1, "a mixed batch must be ONE launch"
    assert ex.mega_launches == 1
    assert ex.mega_queries == len(MIXED)
    assert ex.mega_plan_entries > 0
    assert ex.mega_plan_bytes > 0
    # The per-group vmap path never ran.
    assert ex.fused_dispatches == 0
    assert ex.jit_compiles == jc0 + 1, "one interpreter compile"
    # Same composition again: same capacities -> cached program, one
    # more launch, zero new compiles.
    assert ex.execute_batch_shaped(MIXED) == direct
    assert len(calls) == 2
    assert ex.jit_compiles == jc0 + 1
    assert ex.mega_launches == 2


def test_kill_switch_restores_per_group_fusion(ex, monkeypatch):
    direct = [ex.execute_full(i, q, shards=s) for i, q, s in MIXED]
    monkeypatch.setattr(megamod, "MEGAKERNEL_ENABLED", False)
    calls = count_dispatches(monkeypatch)
    shaped = ex.execute_batch_shaped(MIXED)
    assert shaped == direct, "kill switch must not change results"
    assert ex.mega_launches == 0
    assert len(calls) == 5, "5 signature groups under the fallback"
    assert ex.fused_dispatches >= 1


OPS = [
    "Count(Row(f=1))",
    "Row(f=2)",
    "Count(Union(Row(f=1), Row(g=2), Row(f=3)))",
    "Count(Intersect(Row(f=4), Row(g=4)))",
    "Count(Difference(Row(f=5), Row(g=5)))",
    "Count(Xor(Row(f=6), Row(g=6)))",
    "Not(Row(f=7))",
    "Count(Not(Row(g=8)))",
    "Row(f=999)",                      # absent row -> zero-slot leaf
    "Count(Row(v > 300))",
    "Count(Row(v >= 300))",
    "Count(Row(v < 4000))",
    "Count(Row(v <= 4000))",
    "Count(Row(v == 1234))",
    "Count(Row(v != 1234))",
    "Count(Row(v == -800))",           # out of range -> zeros leaf
    "Count(Row(v != -800))",           # out of range -> not-null
    "Count(Row(-100 < v < 500))",      # between
    "Row(v > -499)",
    "Count(Intersect(Row(f=1), Row(v > 2000)))",
]


def test_every_opcode_bit_identical(ex, monkeypatch):
    """Every lowerable op family, mixed in one batch: AND/OR/XOR/
    ANDNOT folds, existence-Not, zero leaves, and the whole BSI
    comparison table (the host-value-specialized plane scans) must
    match the traced per-group programs bit for bit."""
    reqs = [("i", q, None) for q in OPS]
    direct = [ex.execute_full(i, q, shards=s) for i, q, s in reqs]
    calls = count_dispatches(monkeypatch)
    shaped = ex.execute_batch_shaped(reqs)
    assert shaped == direct
    assert len(calls) == 1
    assert ex.mega_queries == len(OPS)


def test_unlowerable_shift_falls_back_beside_megakernel(ex, monkeypatch):
    reqs = ([("i", f"Count(Row(f={r}))", None) for r in (1, 2)]
            + [("i", f"Row(g={r})", None) for r in (3, 4)]
            + [("i", "Count(Shift(Row(f=5), n=3))", None)])
    direct = [ex.execute_full(i, q, shards=s) for i, q, s in reqs]
    calls = count_dispatches(monkeypatch)
    shaped = ex.execute_batch_shaped(reqs)
    assert shaped == direct
    # One megakernel launch for the 4 lowerable evals + one solo
    # program for the Shift (no mega opcode for word carries).
    assert len(calls) == 2
    assert ex.mega_launches == 1
    assert ex.mega_queries == 4


def test_write_fences_megakernel_batches(ex, monkeypatch):
    (c0,) = ex.execute("i", "Count(Row(f=5))")
    r0 = ex.execute("i", "Row(g=5)")[0].columns().tolist()
    calls = count_dispatches(monkeypatch)
    free_col = 2 * SHARD_WIDTH - 7
    out = ex.execute_batch([
        ("i", "Count(Row(f=5))", None),
        ("i", "Row(g=5)", None),
        ("i", f"Set({free_col}, f=5)", None),
        ("i", "Count(Row(f=5))", None),
        ("i", "Row(g=5)", None),
    ])
    assert out[0][0][0] == c0, "head read sees pre-write state"
    assert out[1][0][0].columns().tolist() == r0
    assert out[2][0][0] is True
    assert out[3][0][0] == c0 + 1, "tail read observes the write"
    assert out[4][0][0].columns().tolist() == r0
    # Two mega launches (head pair, tail pair) split by the fence.
    assert len(calls) == 2
    assert ex.mega_launches == 2
    assert ex.mega_queries == 4


def test_single_signature_batches_keep_vmap_fusion(ex, monkeypatch):
    """A homogeneous batch is already one (vmapped) launch — the
    interpreter must not take it."""
    queries = [f"Count(Row(f={r}))" for r in range(8)]
    direct = [ex.execute("i", q)[0] for q in queries]
    calls = count_dispatches(monkeypatch)
    out = ex.execute_batch([("i", q, None) for q in queries])
    assert [r[0][0] for r in out] == direct
    assert len(calls) == 1
    assert ex.fused_dispatches == 1
    assert ex.mega_launches == 0


def test_slab_budget_falls_back_per_group(ex, monkeypatch):
    monkeypatch.setattr(megamod, "MEGA_MAX_BYTES", 1)
    direct = [ex.execute_full(i, q, shards=s) for i, q, s in MIXED]
    calls = count_dispatches(monkeypatch)
    assert ex.execute_batch_shaped(MIXED) == direct
    assert ex.mega_launches == 0
    assert len(calls) == 5


def test_profile_attribution_mega_fields(ex):
    from pilosa_tpu.utils.profile import QueryProfile
    # The Intersect contributes real plan instructions (a gather-only
    # launch legitimately has planEntries == 0).
    reqs = ([("i", f"Count(Row(f={r}))", None) for r in (1, 2)]
            + [("i", "Count(Intersect(Row(f=3), Row(g=3)))", None)])
    profs = [QueryProfile("i", q) for _, q, _ in reqs]
    ex.execute_batch(reqs, profiles=profs)
    seen = set()
    for p in profs:
        evals = [n for op in p.ops for n in op.children
                 if n.name.startswith("eval:")]
        assert evals, p.ops
        node = evals[0]
        assert node.attrs["megaBatch"] == 3
        assert node.attrs["planEntries"] > 0
        assert node.attrs["planBytes"] > 0
        assert node.attrs["jit"] in ("hit", "miss")
        seen.add(node.attrs["megaIndex"])
        assert p.fused_batch == 3
    assert seen == {0, 1, 2}, "each member gets its own launch lane"


def test_post_dispatch_failure_isolates_per_member(ex, monkeypatch):
    """An async device failure surfacing AFTER the launch (at the
    ?profile=true _fence_device inside attribution) must land on the
    cohort's members as per-request errors — the _FuseGroup.run
    isolation contract — and leave the executor serving."""
    from pilosa_tpu.executor import executor as exmod
    from pilosa_tpu.utils.profile import QueryProfile

    def boom(out):
        raise RuntimeError("simulated async device failure")

    monkeypatch.setattr(exmod, "_fence_device", boom)
    profs = [QueryProfile("i", "q", sample_device=True)
             for _ in range(2)]
    out = ex.execute_batch_shaped(
        [("i", "Count(Row(f=1))", None), ("i", "Row(g=2)", None)],
        profiles=profs)
    assert all(isinstance(r, Exception) for r in out), out
    monkeypatch.undo()
    assert ex.execute("i", "Count(Row(f=1))")[0] >= 0


def test_shared_operand_rows_share_one_slab_register(ex):
    """The Tanimoto shape: N Count(Intersect(Row(fp=Q), Row(fp=c)))
    probes share the query row Q — the lowering must gather it ONCE
    per launch, not once per referencing entry."""
    from pilosa_tpu.ops.megakernel import Lowering
    bank = object()
    low = Lowering()
    ir = (("slot", 0, 0), ("slot", 0, 1), ("fold", "and", 2))
    for c in (5, 6, 7):
        low.add_entry(ir, [bank], [3, c], [], 8, "count")
    plan = low.finish()
    # Slots: shared Q row (slot 3) once + three distinct candidates.
    assert sorted(plan.slots[0].tolist()) == [3, 5, 6, 7]


@pytest.mark.parametrize("width", [64, 48, 96])
def test_the_slab_reads_no_bank_through_a_gather(width):
    """Lowered (StableHLO) text, platform-independent: the slab's rows
    are read a slot a dynamic slice (`ops/bitset.pick_rows`), a bank
    narrower or wider than the launch among them — `bank[slots]` is a
    gather, which XLA's TPU backend serves from a copy of the WHOLE
    bank once a row is past 1 MiB, and a leaf's bank may be 2 GiB."""
    import jax
    import jax.numpy as jnp
    bank = jnp.zeros((16, 2, width), jnp.uint32)   # the slab is [8, ...]
    args = ((bank,), (jnp.zeros(4, jnp.int32),), jnp.zeros(8, jnp.int32),
            jnp.zeros((4, 4), jnp.int32), jnp.zeros(2, jnp.int32),
            jnp.zeros(2, jnp.int32))
    text = jax.jit(mk.build_program(2, 64, 8)).lower(*args).as_text()
    assert "stablehlo.dynamic_slice" in text
    assert f"tensor<16x2x{width}xui32>" in text
    for line in text.splitlines():
        if "stablehlo.gather" in line:
            assert f"(tensor<16x2x{width}xui32>" not in line, line[:200]


def test_error_isolation_beside_megakernel(ex, monkeypatch):
    calls = count_dispatches(monkeypatch)
    out = ex.execute_batch([
        ("i", "Count(Row(f=1))", None),
        ("i", "Count(Row(nosuch=1))", None),  # plan-time error
        ("i", "Row(g=2)", None),
    ])
    assert isinstance(out[1], Exception)
    assert out[0][0][0] == ex.execute("i", "Count(Row(f=1))")[0]
    assert out[2][0][0].columns().tolist() == \
        ex.execute("i", "Row(g=2)")[0].columns().tolist()
    assert ex.mega_queries == 2


# -------------------------------------------------------------------- mesh


@pytest.fixture
def mesh4():
    import jax
    from pilosa_tpu.parallel import MeshContext
    assert len(jax.devices()) >= 4
    return MeshContext(jax.devices()[:4])


def _mesh_ex(holder, mesh):
    executor = Executor(holder, mesh=mesh)
    executor.result_cache.enabled = False
    return executor


def test_mesh_cohort_single_launch_and_counters(ex, mesh4, monkeypatch):
    """A mixed batch on a mesh executor is ONE SPMD launch: the plan
    verifies against the MeshSpec, the mesh counters move, and every
    result matches the single-device executor bit for bit."""
    direct = [ex.execute_full(i, q, shards=s) for i, q, s in MIXED]
    mex = _mesh_ex(ex.holder, mesh4)
    calls = count_dispatches(monkeypatch)
    shaped = mex.execute_batch_shaped(MIXED)
    assert shaped == direct, "mesh cohort results differ"
    assert len(calls) == 1, "a mesh mixed batch must be ONE launch"
    assert mex.mesh_launches == 1
    assert mex.mega_launches == 1
    assert mex.plan_verify_passes >= 1, "mesh plan must be verified"
    assert mex.mesh_collective_bytes > 0
    # Same composition again: cached partitioned program, one more
    # mesh launch, no recompile.
    assert mex.execute_batch_shaped(MIXED) == direct
    assert mex.mesh_launches == 2


def test_mesh_kill_switch_bit_identical(ex, mesh4, monkeypatch):
    """PILOSA_TPU_MESH=0 (module attr MESH_ENABLED) restores the
    pre-mesh behavior exactly: no collector under the mesh, no mesh
    launches, identical bytes."""
    direct = [ex.execute_full(i, q, shards=s) for i, q, s in MIXED]
    mex = _mesh_ex(ex.holder, mesh4)
    monkeypatch.setattr(megamod, "MESH_ENABLED", False)
    shaped = mex.execute_batch_shaped(MIXED)
    assert shaped == direct, "kill switch must not change results"
    assert mex.mesh_launches == 0
    assert mex.mega_launches == 0


def test_mesh_count_reduce_path_zero_host_partials(ex, mesh4):
    """The acceptance's d2h claim: under the mesh epilogue a Count
    lane's device->host transfer is the FINAL uint32 answer (4 bytes),
    never the [S] per-shard partial vector — the in-kernel psum left
    nothing for the host to reduce. Asserted through the profiler's
    real d2h accounting (transfer_nbytes over the pending arrays)."""
    from pilosa_tpu.utils.profile import QueryProfile
    mex = _mesh_ex(ex.holder, mesh4)
    reqs = [("i", f"Count(Row(f={r}))", None) for r in (1, 2)] \
        + [("i", "Count(Intersect(Row(f=3), Row(g=3)))", None)]
    profs = [QueryProfile("i", q) for _, q, _ in reqs]
    out = mex.execute_batch(reqs, profiles=profs)
    assert not any(isinstance(r, Exception) for r in out), out
    assert mex.mesh_launches == 1
    for p in profs:
        assert p.d2h_bytes == 4, (
            f"count reduce path moved {p.d2h_bytes} host bytes — "
            f"expected the 4-byte final answer only")
    # The unmeshed path on the same queries moves the per-shard
    # partials (n_shards * 4 per lane) — the contrast that proves the
    # reduce moved on device.
    profs2 = [QueryProfile("i", q) for _, q, _ in reqs]
    ex.execute_batch(reqs, profiles=profs2)
    for p in profs2:
        assert p.d2h_bytes > 4


def test_mesh_burst_bit_identical(ex, mesh4):
    """The acceptance burst: a 64-thread mixed-signature burst through
    the pipelined coalescer on a mesh executor is byte-identical to
    the same burst with the mesh cohort path killed."""
    from pilosa_tpu.server.coalescer import QueryCoalescer
    from pilosa_tpu.utils.stats import MemStatsClient

    queries = _mixed_queries(64)
    direct = {i: ex.execute_full("i", q) for i, q in enumerate(queries)}

    def burst(executor):
        co = QueryCoalescer(executor, window_s=0.005, max_batch=8,
                            stats=MemStatsClient(), pipeline=True)
        co.start()
        results, errors = {}, []
        try:
            _burst(co, queries, results, errors)
        finally:
            co.stop()
        assert not errors, errors
        return results

    mex_on = _mesh_ex(ex.holder, mesh4)
    on = burst(mex_on)
    assert mex_on.mesh_launches >= 1, "burst must take the mesh path"

    megamod.MESH_ENABLED = False
    try:
        mex_off = _mesh_ex(ex.holder, mesh4)
        off = burst(mex_off)
        assert mex_off.mesh_launches == 0
    finally:
        megamod.MESH_ENABLED = True

    assert on == off == direct, \
        "mesh on/off burst responses must be byte-identical"


# --------------------------------------------------------------- pipelined


def _burst(co, queries, results, errors):
    barrier = threading.Barrier(len(queries))

    def worker(i, q):
        try:
            barrier.wait()
            results[i] = co.submit("i", q)
        except Exception as e:  # noqa: BLE001
            errors.append((q, e))

    threads = [threading.Thread(target=worker, args=(i, q))
               for i, q in enumerate(queries)]
    for t in threads:
        t.start()
    for t in threads:
        t.join(timeout=60)


def _mixed_queries(n):
    qs = []
    for k in range(n):
        r = k % N_ROWS
        qs.append([f"Count(Row(f={r}))", f"Row(g={r})",
                   f"Count(Intersect(Row(f={r}), Row(g={r})))",
                   f"Count(Union(Row(f={r}), Row(g={r})))"][k % 4])
    return qs


def test_pipelined_coalescer_bit_identical(ex):
    from pilosa_tpu.server.coalescer import QueryCoalescer
    from pilosa_tpu.utils.stats import MemStatsClient
    queries = _mixed_queries(48)
    direct = {i: ex.execute_full("i", q) for i, q in enumerate(queries)}
    co = QueryCoalescer(ex, window_s=0.005, max_batch=8,
                        stats=MemStatsClient(), pipeline=True)
    assert co.pipeline
    co.start()
    results, errors = {}, []
    try:
        _burst(co, queries, results, errors)
    finally:
        co.stop()
    assert not errors, errors
    assert results == direct, "pipelined responses differ from direct"
    assert co.pipelined_flushes >= 1
    assert ex.mega_launches >= 1


def test_pipeline_kill_switch_serial_path(ex):
    from pilosa_tpu.server.coalescer import QueryCoalescer
    from pilosa_tpu.utils.stats import MemStatsClient
    queries = _mixed_queries(24)
    direct = {i: ex.execute_full("i", q) for i, q in enumerate(queries)}
    co = QueryCoalescer(ex, window_s=0.005, max_batch=8,
                        stats=MemStatsClient(), pipeline=False)
    assert not co.pipeline
    co.start()
    results, errors = {}, []
    try:
        _burst(co, queries, results, errors)
    finally:
        co.stop()
    assert not errors, errors
    assert results == direct
    assert co.pipelined_flushes == 0


def test_pipelined_write_observes_sequencing(ex):
    """A write arriving among pipelined read flushes barriers: the
    post-write read must observe it (sequential semantics per item)."""
    from pilosa_tpu.server.coalescer import QueryCoalescer
    from pilosa_tpu.utils.stats import MemStatsClient
    co = QueryCoalescer(ex, window_s=0.002, max_batch=8,
                        stats=MemStatsClient(), pipeline=True)
    co.start()
    try:
        results, errors = {}, []
        _burst(co, _mixed_queries(16), results, errors)
        assert not errors, errors
        (c0,) = ex.execute("i", "Count(Row(f=3))")
        free_col = 2 * SHARD_WIDTH - 11
        assert co.submit("i", f"Set({free_col}, f=3)")["results"] == [True]
        assert co.submit("i", "Count(Row(f=3))")["results"] == [c0 + 1]
    finally:
        co.stop()


def test_pipelined_flushes_overlap_and_record_both_halves(ex, monkeypatch):
    """A pipelined burst actually overlaps (real coalescer, injected
    launch and shaping latency): ``pipelined_flushes`` fires, every
    query answers, and each pipelined flush is ONE record whose
    dispatch half ran on the dispatcher's thread and whose drain half
    on the finalizer's, with the hand-off between them accounted. No
    timing ratio is asserted: single-run wall-clock ratios are
    thread-scheduler noise on CPU (device idle is read from a
    profiler trace — tools/trace_gaps.py — not from host clocks)."""
    import time as time_mod

    from pilosa_tpu.server.coalescer import QueryCoalescer
    from pilosa_tpu.utils.stats import MemStatsClient
    from pilosa_tpu.utils.timeline import TIMELINE

    queries = _mixed_queries(32)
    # Warm every compiled variant so no burst pays tracing time.
    for q in queries:
        ex.execute_full("i", q)
    ex.execute_batch_shaped([("i", q, None) for q in queries[:8]])

    orig_call = Executor._call_program

    def rtt_call(self, fn, *args):
        def slow_fn(*a):
            time_mod.sleep(0.005)
            return fn(*a)
        return orig_call(self, slow_fn, *args)

    orig_shape = Executor.shape_response

    def slow_shape(self, *a, **k):
        time_mod.sleep(0.002)
        return orig_shape(self, *a, **k)

    monkeypatch.setattr(Executor, "_call_program", rtt_call)
    monkeypatch.setattr(Executor, "shape_response", slow_shape)

    def run(pipeline):
        TIMELINE.reset()
        co = QueryCoalescer(ex, window_s=0.002, max_batch=8,
                            stats=MemStatsClient(), pipeline=pipeline)
        co.start()
        results, errors = {}, []
        try:
            _burst(co, queries, results, errors)
        finally:
            co.stop()
        assert not errors, errors
        assert len(results) == len(queries)
        flushes = [r for r in TIMELINE.requests() if r.kind == "flush"]
        assert sum(1 for r in flushes for c in r.root.children
                   if c.name == "dispatch") >= 2
        return co.pipelined_flushes, flushes

    n, flushes = run(False)
    assert n == 0 and flushes
    assert not any(r.root.attrs["pipelined"] for r in flushes)
    n, flushes = run(True)
    TIMELINE.reset()
    piped = [r for r in flushes if r.root.attrs["pipelined"]]
    assert n >= 1 and len(piped) == n
    for r in piped:
        names = [c.name for c in r.root.children]
        assert "coalescer.handoff" in names
        k = names.index("coalescer.handoff")
        assert "dispatch" in names[:k] and "finish" in names[k:]
        # Two threads, two lanes: dispatcher before, finalizer after.
        before = {c.tid for c in r.root.children[:k]}
        after = {c.tid for c in r.root.children[k + 1:]}
        assert len(before) == 1 and len(after) == 1 and before != after


def test_a_device_that_cannot_initialise_is_an_error(monkeypatch):
    """`auto` asks the backend which platform it is; a backend that
    cannot start must raise, not read as "megakernel off" and let the
    server carry on somewhere else."""
    import jax

    def no_backend():
        raise RuntimeError("Unable to initialize backend 'tpu'")

    monkeypatch.setattr(jax, "devices", no_backend)
    monkeypatch.delenv("PILOSA_TPU_MEGAKERNEL", raising=False)
    with pytest.raises(RuntimeError, match="Unable to initialize"):
        megamod._default_enabled()
    # An explicit setting never asks the backend.
    monkeypatch.setenv("PILOSA_TPU_MEGAKERNEL", "0")
    assert megamod._default_enabled() is False


# ---------------------------------------------------------------------------
# ops/megakernel.plan_cost: a launch's HBM bytes priced from the verified
# IR's shapes (counts, no clock), and the executor counters it feeds.


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


def test_launch_cost_metrics_families(ex, monkeypatch):
    """/metrics invariants: the byte splits export as one counter
    family split by kind=, opcodes as one family split by op= — never
    a family per kind/op (bounded label sets, test_stats.py rules).
    The executor's counters are the launch's plan_cost, and what
    plan_cost calls pad waste is exactly what the launch registers
    with the memory ledger as fusion_pad padding."""
    from pilosa_tpu.utils.memledger import LEDGER
    from pilosa_tpu.utils.stats import MemStatsClient, prometheus_text

    costs, tracked = [], []
    orig_cost, orig_track = mk.plan_cost, LEDGER.track

    def cost_spy(plan, n_shards, w_mega, mesh=None):
        costs.append(orig_cost(plan, n_shards, w_mega, mesh=mesh))
        return costs[-1]

    def track_spy(obj, category, nbytes, padded_bytes=0, **meta):
        if category == "fusion_pad":
            tracked.append(int(padded_bytes))
        return orig_track(obj, category, nbytes, padded_bytes, **meta)

    monkeypatch.setattr(mk, "plan_cost", cost_spy)
    monkeypatch.setattr(LEDGER, "track", track_spy)
    ex.stats = MemStatsClient()
    ex.execute_batch_shaped(MIXED)
    assert ex.mega_launches == 1 and len(costs) == 1
    cost = costs[0]
    assert cost["gatherBytes"] > 0 and cost["computeBytes"] > 0
    assert ex.launch_bytes_gather == cost["gatherBytes"]
    assert ex.launch_bytes_compute == cost["computeBytes"]
    assert ex.opcode_counts == dict(cost["opcodeHist"])
    assert tracked == [cost["slabBytes"] - cost["liveSlabBytes"]
                       + cost["planBytes"]]
    prom = prometheus_text(ex.stats)
    for kind in ("gather", "compute", "pad"):
        assert f'pilosa_executor_launch_bytes_total{{kind="{kind}"}}' \
            in prom, prom
    assert 'pilosa_executor_opcode_total{op="' in prom
    assert prom.count("# TYPE pilosa_executor_launch_bytes_total") == 1
    assert prom.count("# TYPE pilosa_executor_opcode_total") == 1


def test_cost_rides_profile_tree_and_slow_ring(ex):
    """Eval nodes of a megakernel launch carry launchBytes +
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
