#!/usr/bin/env bash
# tools/check.sh — the single CI gate.
#
#   ruff  ->  mypy  ->  graftlint  ->  native -Werror build
#         ->  lock-order-checked concurrency tests  ->  tier-1 pytest
#
# ruff/mypy are OPTIONAL tools: the jax_graft image does not bake them
# in, so a missing binary is reported and skipped (configs live in
# pyproject.toml and apply wherever the tools exist, e.g. dev laptops).
# Everything else is mandatory and fails the gate.
#
# Usage: tools/check.sh [--fast|--san]
#   --fast  skip the full tier-1 pytest sweep (graftlint in --changed
#           diff mode + native + lock-check + graftlint's own tests
#           still run). The default path scans the full tree and
#           writes the graftlint.sarif artifact.
#   --san   the native sanitizer gate (docs/development.md "Native
#           correctness plane"): ASan + UBSan builds of the roaring
#           codec, fuzz-corpus replay + a deterministic fuzz run +
#           the native-touching test subset under each. ASan needs its
#           runtime preloaded (python is uninstrumented);
#           availability-gated on gcc shipping libasan. The TSan
#           target builds (make -C native SAN=tsan) but is not gated:
#           TSan startup is nondeterministically flaky on old kernels
#           (4.4) — run it manually where it works.

set -u -o pipefail
cd "$(dirname "$0")/.."

FAST=0
SAN=0
[ "${1:-}" = "--fast" ] && FAST=1
[ "${1:-}" = "--san" ] && SAN=1

if [ "$SAN" = 1 ]; then
    fail=0
    step() { printf '\n== %s\n' "$*"; }

    step "sanitizer builds (asan, ubsan, tsan)"
    make -C native SAN=asan || fail=1
    make -C native SAN=ubsan || fail=1
    make -C native SAN=tsan || fail=1

    NATIVE_TESTS="tests/test_native.py tests/test_roaring.py \
        tests/test_fuzz.py tests/test_differential.py"

    step "UBSan: corpus replay + fuzz + native test subset"
    # -fno-sanitize-recover: any UB aborts the process = a red run.
    (
        export PILOSA_TPU_NATIVE_SAN=ubsan
        python -m tools.roaring_fuzz --replay \
            && python -m tools.roaring_fuzz --seed 0 --iters 300 --no-save \
            && JAX_PLATFORMS=cpu python -m pytest $NATIVE_TESTS -q \
                -p no:cacheprovider
    ) || fail=1

    step "ASan: corpus replay + fuzz + native test subset"
    LIBASAN="$(gcc -print-file-name=libasan.so 2>/dev/null || true)"
    LIBSTDCXX="$(gcc -print-file-name=libstdc++.so 2>/dev/null || true)"
    if [ -f "$LIBASAN" ]; then
        # detect_leaks=0: CPython itself 'leaks' at interpreter exit;
        # the target is heap corruption / OOB in the parser, which
        # aborts regardless. Untrusted input is staged in exact-size
        # malloc buffers (native.py _StagedBytes) so redzones sit at
        # the precise boundary. libstdc++ rides in the preload too:
        # python links no C++ runtime, so without it ASan's
        # __cxa_throw interceptor never resolves and the first C++
        # exception jaxlib throws turns into an ASan CHECK abort.
        (
            export LD_PRELOAD="$LIBASAN $LIBSTDCXX"
            export ASAN_OPTIONS=detect_leaks=0
            export PILOSA_TPU_NATIVE_SAN=asan
            python -m tools.roaring_fuzz --replay \
                && python -m tools.roaring_fuzz --seed 0 --iters 300 \
                    --no-save \
                && JAX_PLATFORMS=cpu python -m pytest $NATIVE_TESTS -q \
                    -p no:cacheprovider
        ) || fail=1
    else
        echo "libasan.so not found via gcc — ASan leg skipped"
    fi

    step "result"
    if [ "$fail" = 0 ]; then
        echo "check.sh --san: ALL CLEAN"
    else
        echo "check.sh --san: FAILURES (see above)"
    fi
    exit $fail
fi

fail=0
step() { printf '\n== %s\n' "$*"; }

step "ruff (optional)"
if command -v ruff >/dev/null 2>&1; then
    ruff check pilosa_tpu tools tests || fail=1
elif python -c "import ruff" >/dev/null 2>&1; then
    python -m ruff check pilosa_tpu tools tests || fail=1
else
    echo "ruff not installed — skipped (config: pyproject.toml [tool.ruff])"
fi

step "mypy (optional)"
if python -c "import mypy" >/dev/null 2>&1; then
    python -m mypy pilosa_tpu || fail=1
elif command -v mypy >/dev/null 2>&1; then
    mypy pilosa_tpu || fail=1
else
    echo "mypy not installed — skipped (config: pyproject.toml [tool.mypy])"
fi

step "graftlint"
if [ "$FAST" = 1 ]; then
    # Diff mode: the WHOLE tree is still analyzed (cross-file rules
    # need whole-program context) but findings are reported only in
    # files changed since the merge-base with main — the pre-push loop.
    python -m tools.graftlint --changed || fail=1
else
    # Full default scan (pilosa_tpu tests benches tools) + the SARIF
    # artifact CI uploads. Baseline debt (tools/graftlint/baseline.json
    # — empty on the shipped tree) never fails the run; regenerating it
    # is an explicit, reviewed action:
    #     python -m tools.graftlint --write-baseline
    # and the diff of baseline.json is the review surface.
    python -m tools.graftlint --format sarif --output graftlint.sarif \
        || fail=1
fi

step "native build (-Wall -Wextra -Werror)"
make -C native clean all || fail=1

step "native static analysis (clang-tidy, fallback cppcheck)"
# Pinned check list: native/.clang-tidy. Availability-gated like
# ruff/mypy (exit 0 + a skip note when neither analyzer is installed);
# emits native_tidy.sarif alongside graftlint.sarif for CI upload.
python -m tools.native_tidy --output native_tidy.sarif || fail=1

step "plan-IR verifier self-sweep (tools/planverify)"
# The checked-IR contract, device-free: every plan the shipped
# megakernel lowering emits across the opcode/BSI table must pass
# verify_plan, and every mutation in the coverage set must be
# rejected. Emits planverify.sarif beside the other analyzers.
python -m tools.planverify --output planverify.sarif || fail=1

step "interleave gate (corpus replay + known-bad detection + digest stability)"
# The deterministic interleaving explorer (tools/interleave): the
# committed reproducer corpus replays red-on-known-bad /
# green-on-fixed, and every seeded known-bad scenario (the PR 8/10/14
# races, re-introduced as fixtures) is found within the default
# budget. Fast mode replays the corpus only; the default path adds the
# full sweep (good scenarios clean, known-bad caught) and pins
# exploration determinism (two --digest runs must agree), emitting
# interleave.sarif beside the other analyzers.
if [ "$FAST" = 1 ]; then
    JAX_PLATFORMS=cpu python -m tools.interleave --replay || fail=1
else
    (
        set -e
        JAX_PLATFORMS=cpu python -m tools.interleave --replay
        # DFS gate: good scenarios sweep clean, every known-bad race
        # is caught within its budget; the SARIF artifact comes from
        # this sweep.
        JAX_PLATFORMS=cpu python -m tools.interleave --no-save \
            --output interleave.sarif
        # Seeded random walk over the good scenarios ((seed, index)
        # reproducer contract).
        JAX_PLATFORMS=cpu python -m tools.interleave --seed 0 \
            --iters 100 --no-save
        d1=$(JAX_PLATFORMS=cpu python -m tools.interleave --digest \
            --no-save | tail -1)
        d2=$(JAX_PLATFORMS=cpu python -m tools.interleave --digest \
            --no-save | tail -1)
        [ -n "$d1" ] && [ "$d1" = "$d2" ] || {
            echo "interleave: digest UNSTABLE ($d1 vs $d2)"; exit 1; }
        echo "interleave: digest stable ($d1)"
    ) || fail=1
fi

if [ "$FAST" != 1 ]; then
    step "SARIF merge (graftlint + native_tidy + planverify + interleave -> check.sarif)"
    # One artifact for CI, one run object per tool (SARIF's own
    # composition model); availability-gated inputs may be absent.
    python -m tools.sarif_merge --output check.sarif \
        graftlint.sarif native_tidy.sarif planverify.sarif \
        interleave.sarif || fail=1
fi

step "profiler smoke (one profiled query, JAX_PLATFORMS=cpu)"
JAX_PLATFORMS=cpu python - <<'EOF' || fail=1
import tempfile
import numpy as np
from pilosa_tpu.core.holder import Holder
from pilosa_tpu.ops.bitset import SHARD_WIDTH
from pilosa_tpu.server.api import API
from pilosa_tpu.utils.stats import MemStatsClient, prometheus_text

with tempfile.TemporaryDirectory() as d:
    h = Holder(d); h.open()
    idx = h.create_index("smoke")
    cols = np.array([1, 2, SHARD_WIDTH + 3], np.uint64)
    for name in ("f", "g"):
        idx.create_field(name).import_bits(np.full(3, 1, np.uint64), cols)
    idx.add_existence(cols)
    api = API(h, stats=MemStatsClient())
    resp = api.query("smoke", "Count(Intersect(Row(f=1), Row(g=1)))",
                     profile=True)
    assert resp["results"] == [3], resp
    p = resp["profile"]
    # Well-formed tree: sampled, one op per call, an eval child with
    # jit + device-time + transfer-byte fields, closed totals.
    assert p["deviceSampled"] is True and p["durS"] > 0, p
    assert p["ops"] and p["ops"][0]["name"] == "Count", p
    def walk(n):
        yield n
        for c in n.get("children", []):
            yield from walk(c)
    evals = [n for op in p["ops"] for n in walk(op)
             if n["name"].startswith("eval:")]
    assert evals and evals[0]["jit"] in ("hit", "miss"), p
    assert "deviceS" in evals[0] and evals[0]["shards"] == 2, p
    assert p["ops"][0]["d2hBytes"] > 0, p
    assert "pilosa_executor_" in prometheus_text(api.stats)
    h.close()
print("profiler smoke OK")
EOF

step "fusion smoke (16 same-signature counts -> 1 fused dispatch)"
# Cache off: exact dispatch counts are the subject here — the result
# cache would serve the repeats and zero them out (its own smoke and
# tests/test_result_cache.py pin the cache-ON interplay).
PILOSA_TPU_RESULT_CACHE=0 JAX_PLATFORMS=cpu python - <<'EOF' || fail=1
import tempfile
import numpy as np
from pilosa_tpu.core.holder import Holder
from pilosa_tpu.executor import Executor
from pilosa_tpu.ops.bitset import SHARD_WIDTH

with tempfile.TemporaryDirectory() as d:
    h = Holder(d); h.open()
    idx = h.create_index("fuse")
    f = idx.create_field("f")
    rng = np.random.default_rng(5)
    rows = rng.integers(0, 16, 4000).astype(np.uint64)
    cols = rng.integers(0, 2 * SHARD_WIDTH, 4000).astype(np.uint64)
    f.import_bits(rows, cols)
    idx.add_existence(cols)
    ex = Executor(h)
    queries = [f"Count(Row(f={r}))" for r in range(16)]
    direct = [ex.execute("fuse", q)[0] for q in queries]
    out = ex.execute_batch([("fuse", q, None) for q in queries])
    assert [r[0][0] for r in out] == direct, "fused != unfused results"
    assert ex.fused_dispatches == 1, ex.fused_dispatches
    assert ex.fused_queries == 16, ex.fused_queries
    assert ex.jit_cache_size() > 0
    h.close()
print("fusion smoke OK")
EOF

step "megakernel smoke (32 mixed-signature queries -> 1 launch, kill-switch bit-identity)"
# Cache off for the same reason as the fusion smoke; megakernel forced
# ON (default is auto = TPU-only) so the CPU gate exercises the path;
# plan verification pinned ON (production default is auto) so every
# launch in the gate also passes the checked-IR contract.
PILOSA_TPU_RESULT_CACHE=0 PILOSA_TPU_MEGAKERNEL=1 \
    PILOSA_TPU_PLAN_VERIFY=on JAX_PLATFORMS=cpu \
    python - <<'EOF' || fail=1
import tempfile
import numpy as np
from pilosa_tpu.core.holder import Holder
from pilosa_tpu.executor import Executor
from pilosa_tpu.executor import megakernel as megamod
from pilosa_tpu.ops.bitset import SHARD_WIDTH

with tempfile.TemporaryDirectory() as d:
    h = Holder(d); h.open()
    idx = h.create_index("mega")
    f = idx.create_field("f"); g = idx.create_field("g")
    rng = np.random.default_rng(7)
    rows = rng.integers(0, 8, 4000).astype(np.uint64)
    cols = rng.integers(0, 2 * SHARD_WIDTH, 4000).astype(np.uint64)
    f.import_bits(rows, cols); g.import_bits(rows[::2], cols[::2])
    idx.add_existence(cols)
    ex = Executor(h)
    assert megamod.MEGAKERNEL_ENABLED, "env force must enable"
    # 32 queries over 4 distinct signatures: one mixed burst.
    reqs = []
    for k in range(32):
        r = k % 8
        reqs.append(("mega", [f"Count(Row(f={r}))", f"Row(g={r})",
                              f"Count(Intersect(Row(f={r}), Row(g={r})))",
                              f"Count(Union(Row(f={r}), Row(g={r})))"
                              ][(k // 8) % 4], None))
    calls = []
    orig = Executor._call_program
    def stub(self, fn, *args):
        calls.append(fn)
        return orig(self, fn, *args)
    Executor._call_program = stub
    on = ex.execute_batch_shaped(reqs)
    Executor._call_program = orig
    assert len(calls) == 1, f"mixed burst must be ONE launch, got {len(calls)}"
    assert ex.mega_launches == 1 and ex.mega_queries == 32, \
        (ex.mega_launches, ex.mega_queries)
    # The launch passed the plan-IR verification gate (checked IR).
    assert ex.plan_verify_passes == 1 and ex.plan_verify_rejects == 0, \
        (ex.plan_verify_passes, ex.plan_verify_rejects)
    # The PILOSA_TPU_MEGAKERNEL=0 + PILOSA_TPU_PIPELINE=0 regime:
    # per-group fusion, serial dispatch — responses must be
    # bit-identical.
    megamod.MEGAKERNEL_ENABLED = False
    off = ex.execute_batch_shaped(reqs)
    assert on == off, "megakernel responses differ from kill-switch path"
    assert ex.mega_launches == 1, "kill switch must stop launches"
    h.close()
print("megakernel smoke OK")
EOF

step "mesh smoke (4-device SPMD burst -> 1 mesh launch, collective reduce, kill-switch bit-identity)"
# The mesh cohort path on 4 forced host devices: one SPMD megakernel
# launch over mesh-sharded banks, the collective epilogue psums count
# lanes in-kernel (verify_plan's mesh rules gate the plan), and
# PILOSA_TPU_MESH=0 must restore the exact single-device path
# byte-for-byte.
XLA_FLAGS=--xla_force_host_platform_device_count=4 \
    PILOSA_TPU_RESULT_CACHE=0 PILOSA_TPU_MEGAKERNEL=1 \
    PILOSA_TPU_PLAN_VERIFY=on JAX_PLATFORMS=cpu \
    python - <<'EOF' || fail=1
import tempfile
import numpy as np
import jax
from pilosa_tpu.core.holder import Holder
from pilosa_tpu.executor import Executor
from pilosa_tpu.executor import megakernel as megamod
from pilosa_tpu.ops.bitset import SHARD_WIDTH
from pilosa_tpu.parallel import MeshContext

assert len(jax.devices()) == 4, jax.devices()
with tempfile.TemporaryDirectory() as d:
    h = Holder(d); h.open()
    idx = h.create_index("mesh")
    f = idx.create_field("f"); g = idx.create_field("g")
    rng = np.random.default_rng(7)
    rows = rng.integers(0, 8, 4000).astype(np.uint64)
    cols = rng.integers(0, 2 * SHARD_WIDTH, 4000).astype(np.uint64)
    f.import_bits(rows, cols); g.import_bits(rows[::2], cols[::2])
    idx.add_existence(cols)
    reqs = []
    for k in range(32):
        r = k % 8
        reqs.append(("mesh", [f"Count(Row(f={r}))", f"Row(g={r})",
                              f"Count(Intersect(Row(f={r}), Row(g={r})))",
                              f"Count(Union(Row(f={r}), Row(g={r})))"
                              ][(k // 8) % 4], None))
    mex = Executor(h, mesh=MeshContext(jax.devices()))
    on = mex.execute_batch_shaped(reqs)
    assert mex.mesh_launches == 1 and mex.mega_launches == 1, \
        (mex.mesh_launches, mex.mega_launches)
    # The mesh plan passed the verifier's mesh rules pre-launch.
    assert mex.plan_verify_passes == 1 and mex.plan_verify_rejects == 0, \
        (mex.plan_verify_passes, mex.plan_verify_rejects)
    assert mex.mesh_collective_bytes > 0
    # PILOSA_TPU_MESH=0 regime on the same sharded banks.
    megamod.MESH_ENABLED = False
    off = Executor(h, mesh=MeshContext(jax.devices())).execute_batch_shaped(reqs)
    megamod.MESH_ENABLED = True
    assert on == off, "mesh responses differ from kill-switch path"
    # No mesh at all (single-device megakernel) is also bit-identical.
    plain = Executor(h).execute_batch_shaped(reqs)
    assert on == plain, "mesh responses differ from single-device path"
    h.close()
print("mesh smoke OK")
EOF

step "plan-optimizer smoke (64 shared-subtree queries -> CSE hits, kill-switch bit-identity)"
# The PR 16 cost-based optimizer (ops/plan_opt.py): a shared-subtree
# burst must produce cross-request CSE hits with the optimized launch
# still passing the plan-IR verification gate, and PILOSA_TPU_PLAN_OPT
# off must keep the optimizer fully out of the path at byte-identical
# responses. Threshold queries ride along so the OP_THRESH lowering
# is in the gated plan.
PILOSA_TPU_RESULT_CACHE=0 PILOSA_TPU_MEGAKERNEL=1 \
    PILOSA_TPU_PLAN_VERIFY=on JAX_PLATFORMS=cpu \
    python - <<'EOF' || fail=1
import tempfile
import numpy as np
from pilosa_tpu.core.holder import Holder
from pilosa_tpu.executor import Executor
from pilosa_tpu.executor import megakernel as megamod
from pilosa_tpu.ops.bitset import SHARD_WIDTH

with tempfile.TemporaryDirectory() as d:
    h = Holder(d); h.open()
    idx = h.create_index("opt")
    f = idx.create_field("f"); g = idx.create_field("g")
    rng = np.random.default_rng(9)
    rows = rng.integers(0, 8, 4000).astype(np.uint64)
    cols = rng.integers(0, 2 * SHARD_WIDTH, 4000).astype(np.uint64)
    f.import_bits(rows, cols); g.import_bits(rows[::2], cols[::2])
    idx.add_existence(cols)
    ex = Executor(h)
    assert megamod.PLAN_OPT_ENABLED, "default must be on"
    # 64 queries, every one reusing the Intersect(f=r, g=r) subtree
    # (once commuted) plus a Threshold rider over the same rows.
    reqs = []
    for k in range(64):
        r = k % 8
        reqs.append(("opt", [
            f"Count(Intersect(Row(f={r}), Row(g={r})))",
            f"Intersect(Row(g={r}), Row(f={r}))",
            f"Count(Union(Intersect(Row(f={r}), Row(g={r})), Row(f={(r+1)%8})))",
            f"Count(Threshold(Row(f={r}), Row(g={r}), Row(f={(r+1)%8}), k=2))",
            ][(k // 8) % 4], None))
    on = ex.execute_batch_shaped(reqs)
    assert ex.mega_launches == 1 and ex.opt_plans == 1, \
        (ex.mega_launches, ex.opt_plans)
    assert ex.opt_cse_hits > 0, "shared-subtree burst must CSE"
    assert ex.opt_entries_eliminated > 0 and ex.opt_bytes_saved > 0, \
        (ex.opt_entries_eliminated, ex.opt_bytes_saved)
    # Optimized plan passed the verification gate (checked IR).
    assert ex.plan_verify_passes == 1 and ex.plan_verify_rejects == 0, \
        (ex.plan_verify_passes, ex.plan_verify_rejects)
    # PILOSA_TPU_PLAN_OPT=0 regime: raw Lowering plans, byte-identical.
    megamod.PLAN_OPT_ENABLED = False
    off = ex.execute_batch_shaped(reqs)
    assert on == off, "optimizer responses differ from kill-switch path"
    assert ex.opt_plans == 1, "kill switch must stop optimizer runs"
    h.close()
print("plan-optimizer smoke OK")
EOF

step "plan-fuzz gate (corpus replay + deterministic sweep + digest stability)"
# The plan-space differential oracle (tools/plan_fuzz): committed
# corpus replays clean, then a seeded sweep — every batch bit-exact
# across megakernel / vmap fusion / packed numpy, every captured plan
# verified, every mutation rejected. Fast mode replays the corpus
# only; the default path adds the 300-case sweep, a four-way sweep
# with the mesh collective leg (--mesh 4: every case also runs the
# SPMD cohort path over 4 forced host devices, bit-exact against the
# single-device interpreter) and pins generator determinism (two
# --digest runs must agree).
if [ "$FAST" = 1 ]; then
    JAX_PLATFORMS=cpu python -m tools.plan_fuzz --replay || fail=1
else
    (
        set -e
        JAX_PLATFORMS=cpu python -m tools.plan_fuzz --replay
        JAX_PLATFORMS=cpu python -m tools.plan_fuzz --seed 0 \
            --iters 300 --no-save
        XLA_FLAGS=--xla_force_host_platform_device_count=4 \
            JAX_PLATFORMS=cpu python -m tools.plan_fuzz --seed 1 \
            --iters 40 --mesh 4 --no-save
        d1=$(python -m tools.plan_fuzz --seed 0 --iters 300 --digest)
        d2=$(python -m tools.plan_fuzz --seed 0 --iters 300 --digest)
        [ -n "$d1" ] && [ "$d1" = "$d2" ] || {
            echo "plan_fuzz: digest UNSTABLE ($d1 vs $d2)"; exit 1; }
        echo "plan_fuzz: digest stable ($d1)"
    ) || fail=1
fi

step "pipelined-dispatch smoke (coalesced burst, pipeline on vs off)"
PILOSA_TPU_RESULT_CACHE=0 JAX_PLATFORMS=cpu python - <<'EOF' || fail=1
import tempfile, threading
import numpy as np
from pilosa_tpu.core.holder import Holder
from pilosa_tpu.executor import Executor
from pilosa_tpu.server.coalescer import QueryCoalescer
from pilosa_tpu.utils.stats import MemStatsClient
from pilosa_tpu.ops.bitset import SHARD_WIDTH

with tempfile.TemporaryDirectory() as d:
    h = Holder(d); h.open()
    idx = h.create_index("pl")
    f = idx.create_field("f")
    rng = np.random.default_rng(9)
    rows = rng.integers(0, 8, 4000).astype(np.uint64)
    cols = rng.integers(0, 2 * SHARD_WIDTH, 4000).astype(np.uint64)
    f.import_bits(rows, cols)
    idx.add_existence(cols)
    ex = Executor(h)
    queries = [f"Count(Row(f={r % 8}))" if r % 2 else f"Row(f={r % 8})"
               for r in range(32)]
    def burst(pipeline):
        co = QueryCoalescer(ex, window_s=0.005, max_batch=8,
                            stats=MemStatsClient(), pipeline=pipeline)
        co.start()
        results, errors = {}, []
        barrier = threading.Barrier(len(queries))
        def worker(i, q):
            try:
                barrier.wait()
                results[i] = co.submit("pl", q)
            except Exception as e:
                errors.append(e)
        ts = [threading.Thread(target=worker, args=(i, q))
              for i, q in enumerate(queries)]
        [t.start() for t in ts]; [t.join(timeout=60) for t in ts]
        co.stop()
        assert not errors, errors
        return results, co.pipelined_flushes
    on, pl_on = burst(True)
    off, pl_off = burst(False)
    assert pl_on >= 1 and pl_off == 0, (pl_on, pl_off)
    assert on == off, "pipelined responses differ from serial path"
    h.close()
print("pipelined-dispatch smoke OK")
EOF

step "result-cache smoke (32 identical queries -> >=30 hits, 1 fused dispatch)"
JAX_PLATFORMS=cpu python - <<'EOF' || fail=1
import tempfile
import numpy as np
from pilosa_tpu.core.holder import Holder
from pilosa_tpu.executor import Executor
from pilosa_tpu.ops.bitset import SHARD_WIDTH
from pilosa_tpu.utils.memledger import LEDGER

with tempfile.TemporaryDirectory() as d:
    h = Holder(d); h.open()
    idx = h.create_index("rc")
    f = idx.create_field("f")
    rng = np.random.default_rng(11)
    rows = rng.integers(0, 8, 4000).astype(np.uint64)
    cols = rng.integers(0, 2 * SHARD_WIDTH, 4000).astype(np.uint64)
    f.import_bits(rows, cols)
    idx.add_existence(cols)
    ex = Executor(h)
    assert ex.result_cache.enabled, "result cache must default ON"
    q = "Count(Row(f=1))"
    # 32 identical queries: a first coalesced pair (one fused launch
    # fills the generation-keyed cache), then 30 repeats served from
    # it — no staging, no compile, no dispatch.
    first = ex.execute_batch([("rc", q, None), ("rc", q, None)])
    got = [r[0][0] for r in first]
    got += [ex.execute_batch([("rc", q, None)])[0][0][0]
            for _ in range(30)]
    assert len(got) == 32 and len(set(got)) == 1, got
    snap = ex.result_cache.snapshot()
    assert snap["hits"] >= 30, snap
    assert ex.fused_dispatches == 1, ex.fused_dispatches
    # Cache memory is ledgered: /debug/memory's result_cache category
    # equals the cache's own byte gauge.
    cats = LEDGER.snapshot()["categories"]
    assert cats.get("result_cache", {}).get("bytes", 0) \
        == snap["bytes"] > 0, (cats, snap)
    # Bit-identical with the cache disabled (the
    # PILOSA_TPU_RESULT_CACHE=0 regime).
    ex.result_cache.enabled = False
    off = ex.execute_batch([("rc", q, None)])[0][0][0]
    assert off == got[0], (off, got[0])
    h.close()
print("result-cache smoke OK")
EOF

step "telemetry smoke (live /debug/memory + /cluster/health + doctor self-diff)"
JAX_PLATFORMS=cpu python - <<'EOF' || fail=1
import json
import pathlib
import tempfile
import urllib.request
import numpy as np
from pilosa_tpu.core.holder import Holder
from pilosa_tpu.ops.bitset import SHARD_WIDTH
from pilosa_tpu.server import API, serve
from pilosa_tpu.utils.memledger import LEDGER, MemoryWatchdog
from pilosa_tpu.utils.stats import MemStatsClient
from tools.doctor import main as doctor_main, snapshot_bundle

with tempfile.TemporaryDirectory() as d:
    h = Holder(d); h.open()
    idx = h.create_index("tel")
    cols = np.array([1, 2, SHARD_WIDTH + 3], np.uint64)
    idx.create_field("f").import_bits(np.full(3, 1, np.uint64), cols)
    idx.add_existence(cols)
    api = API(h, stats=MemStatsClient())
    wd = MemoryWatchdog(LEDGER, stats=api.stats, sample_every_s=60)
    api.watchdog = wd
    srv = serve(api, "localhost", 0, background=True)
    base = f"http://localhost:{srv.server_address[1]}"
    r = urllib.request.urlopen(base + "/index/tel/query",
                               data=b"Count(Row(f=1))").read()
    assert json.loads(r)["results"] == [3], r
    mem = json.loads(urllib.request.urlopen(base + "/debug/memory").read())
    assert mem["totalBytes"] > 0, mem
    assert mem["totalBytes"] == sum(
        c["bytes"] for c in mem["categories"].values()), mem
    assert mem["top"] and mem["top"][0]["bytes"] > 0, mem
    health = json.loads(
        urllib.request.urlopen(base + "/cluster/health").read())
    assert health["healthyNodes"] == health["totalNodes"] == 1, health
    node = health["nodes"][0]
    assert node["healthy"] is True, health
    assert node["memory"]["totalBytes"] == mem["totalBytes"], health
    wd.sample_once()  # the watchdog populates the /metrics gauges
    met = urllib.request.urlopen(base + "/metrics").read().decode()
    assert 'pilosa_memory_bytes{category="bank"}' in met
    assert "pilosa_memory_padding_bytes" in met
    # Doctor bundle: all surfaces captured, self-diff empty.
    bundle = snapshot_bundle(base)
    errs = [k for k, s in bundle["surfaces"].items() if "error" in s]
    assert not errs, errs
    p = pathlib.Path(d) / "bundle.json"
    p.write_text(json.dumps(bundle, default=str))
    assert doctor_main(["diff", str(p), str(p)]) == 0
    srv.shutdown(); srv.server_close(); h.close()
print("telemetry smoke OK")
EOF

step "hotspots smoke (repeated-query burst -> /debug/hotspots)"
# Cache off: the workload recorder/estimator under test prices repeats
# that STAGE; with the cache on, hits skip staging by design and the
# query window records only the first execution of each identity.
PILOSA_TPU_RESULT_CACHE=0 JAX_PLATFORMS=cpu python - <<'EOF' || fail=1
import json
import tempfile
import urllib.request
import numpy as np
from pilosa_tpu.core.holder import Holder
from pilosa_tpu.ops.bitset import SHARD_WIDTH
from pilosa_tpu.server import API, serve
from pilosa_tpu.server.coalescer import QueryCoalescer
from pilosa_tpu.utils.hotspots import WORKLOAD
from pilosa_tpu.utils.stats import MemStatsClient

WORKLOAD.reset()
with tempfile.TemporaryDirectory() as d:
    h = Holder(d); h.open()
    idx = h.create_index("hot")
    cols = np.array([1, 2, SHARD_WIDTH + 3], np.uint64)
    idx.create_field("f").import_bits(np.full(3, 1, np.uint64), cols)
    idx.add_existence(cols)
    api = API(h, stats=MemStatsClient())
    api.coalescer = QueryCoalescer(api.executor, window_s=0.0005,
                                   stats=api.stats)
    api.coalescer.start()
    srv = serve(api, "localhost", 0, background=True)
    base = f"http://localhost:{srv.server_address[1]}"
    # Burst of repeated queries: 32 requests over 4 distinct reads.
    for i in range(32):
        r = urllib.request.urlopen(
            base + "/index/hot/query",
            data=f"Count(Row(f={i % 4}))".encode()).read()
        assert json.loads(r)["results"] == [3 if i % 4 == 1 else 0], r
    doc = json.loads(urllib.request.urlopen(
        base + "/debug/hotspots").read())
    # Nonzero cross-request repeat ratio: 32 arrivals, 4 identities.
    assert doc["queriesWindow"]["ratio"] > 0.8, doc["queriesWindow"]
    assert doc["requestsWindow"]["ratio"] > 0.8, doc["requestsWindow"]
    # Provable totals: totals == tracked + evicted ...
    assert doc["totals"]["fragmentReads"] == \
        doc["tracked"]["fragmentReads"] + \
        doc["evicted"]["fragmentReads"], doc["totals"]
    # ... and consistent with the exported counter family.
    met = urllib.request.urlopen(base + "/metrics").read().decode()
    line = next(l for l in met.splitlines()
                if l.startswith("pilosa_fragment_reads_total"))
    assert int(line.rsplit(" ", 1)[1]) == \
        doc["totals"]["fragmentReads"], (line, doc["totals"])
    assert doc["opportunity"]["signatures"], "no cacheable signatures"
    assert doc["opportunity"]["totalEstSavedS"] > 0
    srv.shutdown(); srv.server_close(); api.coalescer.stop(); h.close()
print("hotspots smoke OK")
EOF

step "timeline smoke (32-query burst -> /debug/timeline trace-event JSON)"
# Cache off: the plan/dispatch/materialize stage slices under test
# only exist for requests that execute — cache hits produce a two-
# slice (queue, cache) timeline instead (pinned in
# tests/test_result_cache.py::test_timeline_cache_lane_slice_on_hit).
PILOSA_TPU_RESULT_CACHE=0 JAX_PLATFORMS=cpu python - <<'EOF' || fail=1
import json
import tempfile
import urllib.request
import numpy as np
from pilosa_tpu.core.holder import Holder
from pilosa_tpu.ops.bitset import SHARD_WIDTH
from pilosa_tpu.server import API, serve
from pilosa_tpu.server.coalescer import QueryCoalescer
from pilosa_tpu.utils.stats import MemStatsClient
from pilosa_tpu.utils.timeline import TIMELINE
from pilosa_tpu.utils.tracing import ContextTracer

TIMELINE.reset()
with tempfile.TemporaryDirectory() as d:
    h = Holder(d); h.open()
    idx = h.create_index("tls")
    cols = np.array([1, 2, SHARD_WIDTH + 3], np.uint64)
    idx.create_field("f").import_bits(np.full(3, 1, np.uint64), cols)
    idx.add_existence(cols)
    api = API(h, stats=MemStatsClient(), tracer=ContextTracer())
    api.executor.result_cache.enabled = False   # every query executes
    api.coalescer = QueryCoalescer(api.executor, window_s=0.0005,
                                   stats=api.stats)
    api.coalescer.start()
    srv = serve(api, "localhost", 0, background=True)
    base = f"http://localhost:{srv.server_address[1]}"
    # 32-query burst through the coalesced serving path.
    for i in range(32):
        r = urllib.request.urlopen(
            base + "/index/tls/query",
            data=f"Count(Row(f={i % 4}))".encode()).read()
        assert "results" in json.loads(r), r
    doc = json.loads(urllib.request.urlopen(
        base + "/debug/timeline?last=16").read())
    # Chrome trace-event shape: every event carries ph/ts/dur/pid/tid.
    assert doc["traceEvents"], "no trace events recorded"
    for ev in doc["traceEvents"]:
        for k in ("ph", "ts", "dur", "pid", "tid"):
            assert k in ev, (k, ev)
    names = {e["name"] for e in doc["traceEvents"] if e["ph"] == "X"}
    for want in ("request", "http.read", "pql.parse", "coalescer.wait",
                 "cache.lookup", "plan", "dispatch", "d2h", "finish",
                 "http.serialize", "http.write"):
        assert want in names, (want, names)
    s = doc["summary"]
    assert s["requests"] == 16, s
    assert s["stageMedianS"]["dispatch"] > 0, s
    assert s["byCall"]["Count"]["requests"] == 16, s
    # The cumulative stage histograms and the per-endpoint RED
    # histograms export; the host-clock idle gauge is gone.
    met = urllib.request.urlopen(base + "/metrics").read().decode()
    assert "# TYPE pilosa_request_stage_seconds histogram" in met
    assert 'pilosa_request_stage_seconds_count{stage="plan"}' in met
    assert "pilosa_request_unaccounted_seconds_sum" in met
    assert "device_idle_ratio" not in met
    assert "# TYPE pilosa_http_request_seconds histogram" in met
    assert 'endpoint="/index/{index}/query"' in met
    srv.shutdown(); srv.server_close(); api.coalescer.stop(); h.close()
print("timeline smoke OK")
EOF

step "hybrid-layout smoke (skewed corpus -> re-layout -> ledger delta + kill-switch identity)"
# Cache off inside the tool (exact-path differential); plan
# verification pinned ON so every sparse-expand launch also passes
# the checked-IR contract (the OP_EXPAND typing rule).
PILOSA_TPU_PLAN_VERIFY=on JAX_PLATFORMS=cpu \
    python -m tools.layout_smoke || fail=1

step "chaos smoke (3-proc cluster, failpoint-killed node mid-resize, bit-exact + availability + clean drain)"
# The resilience-plane gate (ISSUE 15): live mixed traffic against a
# real multi-process cluster while a seed-join resize runs with
# failpoint-delayed pulls, one node failpoint-killed and recovered
# inside the window, torn scatter-leg bodies injected afterwards.
# Asserts zero request errors, bit-exact results vs a single-node
# oracle, the kill/recovery visible in /cluster/timeline +
# /cluster/health, and a clean drain (the harness SIGTERMs every
# node and fails on unreaped children).
JAX_PLATFORMS=cpu python -m tools.chaos --smoke || fail=1

step "lock-order runtime check (PILOSA_TPU_LOCK_CHECK=1)"
PILOSA_TPU_LOCK_CHECK=1 JAX_PLATFORMS=cpu \
    python -m pytest tests/test_coalescer.py tests/test_concurrency.py \
    -q -m 'not slow' -p no:cacheprovider || fail=1

if [ "$FAST" = 1 ]; then
    step "graftlint self-tests (fast mode)"
    JAX_PLATFORMS=cpu python -m pytest tests/test_graftlint.py -q \
        -p no:cacheprovider || fail=1
else
    step "tier-1 pytest"
    JAX_PLATFORMS=cpu python -m pytest tests/ -q -m 'not slow' \
        --continue-on-collection-errors -p no:cacheprovider || fail=1
fi

step "result"
if [ "$fail" = 0 ]; then
    echo "check.sh: ALL CLEAN"
else
    echo "check.sh: FAILURES (see above)"
fi
exit $fail
