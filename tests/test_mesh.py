"""Mesh placement + mesh plan typing (parallel/mesh.py +
ops/megakernel mesh rules): ShardPlacement's pad/device_of math,
MeshContext's sharding specs and jit-cache key across replica shapes,
and the verify_plan mesh rules — shard-axis agreement, the
replica-axis no-op proof and per-lane collective typing — each
rejection branch pinned against a LIVE plan captured from the
lowering, so the rules are proven on the IR the executor actually
ships."""

import numpy as np
import pytest

from pilosa_tpu.core.holder import Holder
from pilosa_tpu.executor import Executor
from pilosa_tpu.executor import megakernel as megamod
from pilosa_tpu.ops import megakernel as mk
from pilosa_tpu.ops.bitset import SHARD_WIDTH
from pilosa_tpu.parallel import MeshContext
from pilosa_tpu.parallel.mesh import ShardPlacement


# ----------------------------------------------------------- placement


def test_pad_rounds_up_to_device_multiple():
    p = ShardPlacement(4)
    assert p.pad([0, 1, 2, 3]) == [0, 1, 2, 3]
    padded = p.pad([0, 1, 2, 3, 4, 5])
    assert len(padded) == 8
    assert padded[:6] == [0, 1, 2, 3, 4, 5]


def test_pad_ids_are_provably_absent():
    p = ShardPlacement(4)
    # Pad ids must sit above BOTH the requested shards and the floor
    # (every existing shard of the index) — otherwise padding aliases
    # a real shard the caller excluded and its bits leak into the
    # reduction.
    padded = p.pad([0, 2], floor=9)
    assert padded[:2] == [0, 2]
    assert all(s >= 9 for s in padded[2:])
    assert len(set(padded)) == len(padded)
    # Without a floor the pads clear the requested max.
    padded = p.pad([7, 3])
    assert all(s >= 8 for s in padded[2:])


def test_pad_empty_shard_list():
    assert ShardPlacement(2).pad([]) == [0, 1]


def test_device_of_block_assignment():
    p = ShardPlacement(4)
    shards = [10, 11, 12, 13, 14, 15, 16, 17]
    for pos, s in enumerate(shards):
        assert p.device_of(shards, s) == pos // 2
    assert p.blocks(8) == [slice(0, 2), slice(2, 4), slice(4, 6),
                           slice(6, 8)]
    # Six shards pad to eight: the blocks are of the padded list.
    assert [p.device_of(shards[:6], s) for s in shards[:6]] \
        == [0, 0, 1, 1, 2, 2]
    with pytest.raises(ValueError, match="pad the list first"):
        p.blocks(6)


def test_device_of_is_where_the_bank_sharding_puts_a_shard():
    """The truth is the placed array's: the device whose addressable
    shard holds a shard's column is the one `device_of` names."""
    import jax
    devices = jax.devices()[:4]
    mesh = MeshContext(devices)
    shards = [3, 4, 7, 9, 12, 13, 20, 21]
    host = np.zeros((2, len(shards), 8), np.uint32)
    host[0, :, 0] = shards            # mark each column with its shard
    placed = mesh.put_bank(host)
    seen = {}
    for sh in placed.addressable_shards:
        for s in np.asarray(sh.data)[0, :, 0].tolist():
            seen[s] = devices.index(sh.device)
    assert seen == {s: mesh.placement.device_of(shards, s) for s in shards}
    assert seen == {3: 0, 4: 0, 7: 1, 9: 1, 12: 2, 13: 2, 20: 3, 21: 3}


# -------------------------------------------------------- mesh context


@pytest.fixture
def mesh4():
    import jax
    assert len(jax.devices()) >= 4
    return MeshContext(jax.devices()[:4])


def test_mesh_axes_and_shardings(mesh4):
    from jax.sharding import PartitionSpec as P
    assert mesh4.n_shard_devices == 4
    assert mesh4.replicas == 1
    assert mesh4.mesh.axis_names == (MeshContext.SHARD_AXIS,)
    assert mesh4.bank_sharding().spec == P(None, "shards", None)
    assert mesh4.row_sharding().spec == P("shards", None)
    assert mesh4.replicated().spec == P()


def test_replica_axis_leads_and_banks_stay_replicated_over_it():
    import jax
    from jax.sharding import PartitionSpec as P
    if len(jax.devices()) < 8:
        pytest.skip("needs 8 devices")
    m = MeshContext(jax.devices()[:8], replicas=2)
    assert m.mesh.axis_names == (MeshContext.REPLICA_AXIS,
                                 MeshContext.SHARD_AXIS)
    assert m.replicas == 2
    assert m.n_shard_devices == 4
    # The bank spec names ONLY the shard axis: PartitionSpec None on
    # the replica axis is what replicates banks across replicas — the
    # structural half of the replica-axis no-op proof.
    assert m.bank_sharding().spec == P(None, "shards", None)
    assert MeshContext.REPLICA_AXIS not in (
        m.bank_sharding().spec + m.row_sharding().spec)


def test_replicas_must_divide_devices():
    import jax
    with pytest.raises(ValueError, match="not divisible"):
        MeshContext(jax.devices()[:4], replicas=3)


def test_cache_key_stable_and_shape_sensitive(mesh4):
    import jax
    devs = jax.devices()
    assert mesh4.cache_key() == MeshContext(devs[:4]).cache_key()
    assert mesh4.cache_key() != MeshContext(devs[:2]).cache_key()
    if len(devs) >= 8:
        # Same 8 devices, different replica shape -> different
        # partitioned program -> different key.
        assert (MeshContext(devs[:8]).cache_key()
                != MeshContext(devs[:8], replicas=2).cache_key())


def test_put_bank_splits_shard_axis(mesh4):
    bank = np.zeros((3, 4, 8), dtype=np.uint32)
    dev = mesh4.put_bank(bank)
    assert dev.sharding == mesh4.bank_sharding()
    # Each device holds one shard column, rows/words unsplit.
    shard_shape = dev.sharding.shard_shape(dev.shape)
    assert shard_shape == (3, 1, 8)


# ------------------------------------------- mesh plan rules (live IR)


@pytest.fixture
def live_plan(tmp_path, monkeypatch):
    """One (plan, n_shards, w_mega) captured from the shipped lowering
    on a mixed batch — count lanes and row lanes both present."""
    h = Holder(str(tmp_path))
    h.open()
    idx = h.create_index("i")
    f = idx.create_field("f")
    rng = np.random.default_rng(7)
    rows = rng.integers(0, 8, 3000).astype(np.uint64)
    cols = rng.integers(0, 4 * SHARD_WIDTH, 3000).astype(np.uint64)
    f.import_bits(rows, cols)
    idx.add_existence(cols)
    executor = Executor(h)
    executor.result_cache.enabled = False
    prev = megamod.MEGAKERNEL_ENABLED
    megamod.MEGAKERNEL_ENABLED = True

    captured = []
    orig = megamod._build

    def wrapped(cohort):
        plan, w_mega, lanes = orig(cohort)
        captured.append((plan, cohort[0].entries[0].n_shards, w_mega))
        return plan, w_mega, lanes

    monkeypatch.setattr(megamod, "_build", wrapped)
    executor.execute_batch_shaped(
        [("i", "Count(Row(f=1))", None), ("i", "Row(f=2)", None),
         ("i", "Count(Row(f=3))", None)])
    megamod.MEGAKERNEL_ENABLED = prev
    h.close()
    assert captured, "batch did not reach the megakernel lowering"
    return captured[0]


def _spec(plan, n_devices=2, **kw):
    epi = kw.pop("epilogue", mk.mesh_epilogue(plan))
    return mk.MeshSpec("shards", "replica", n_devices,
                       kw.pop("replicas", 1), epi)


def test_canonical_mesh_plan_verifies(live_plan):
    plan, n_shards, w_mega = live_plan
    spec = _spec(plan, n_devices=2)
    mk.verify_plan(plan, n_shards, w_mega, mesh=spec)
    # The epilogue types every REAL lane, pad lanes excluded.
    assert len(spec.epilogue.count_ops) == len(plan.lane_count_widths)
    assert len(spec.epilogue.row_ops) == len(plan.lane_row_widths)


def test_mesh_rejects_uneven_shard_split(live_plan):
    plan, n_shards, w_mega = live_plan
    with pytest.raises(mk.PlanVerifyError, match="split evenly"):
        mk.verify_plan(plan, n_shards, w_mega,
                       mesh=_spec(plan, n_devices=3))


def test_mesh_rejects_missing_epilogue(live_plan):
    plan, n_shards, w_mega = live_plan
    with pytest.raises(mk.PlanVerifyError, match="no collective"):
        mk.verify_plan(plan, n_shards, w_mega,
                       mesh=_spec(plan, epilogue=None))


def test_mesh_rejects_replica_axis_reduction(live_plan):
    # The replica-axis no-op proof: an epilogue that reduces over the
    # replica axis would count replicated banks replicas-x.
    plan, n_shards, w_mega = live_plan
    epi = mk.mesh_epilogue(plan)
    bad = mk.Epilogue(("shards", "replica"), epi.count_ops, epi.row_ops)
    with pytest.raises(mk.PlanVerifyError, match="axes"):
        mk.verify_plan(plan, n_shards, w_mega,
                       mesh=_spec(plan, epilogue=bad))


def test_mesh_rejects_axis_name_collision(live_plan):
    plan, n_shards, w_mega = live_plan
    spec = mk.MeshSpec("shards", "shards", 2, 1, mk.mesh_epilogue(plan))
    with pytest.raises(mk.PlanVerifyError, match="distinct"):
        mk.verify_plan(plan, n_shards, w_mega, mesh=spec)


def test_mesh_rejects_mistyped_lanes(live_plan):
    plan, n_shards, w_mega = live_plan
    epi = mk.mesh_epilogue(plan)
    if len(epi.count_ops):
        bad = mk.Epilogue(epi.axes,
                          [mk.EPI_NONE] * len(epi.count_ops),
                          epi.row_ops)
        with pytest.raises(mk.PlanVerifyError, match="psum"):
            mk.verify_plan(plan, n_shards, w_mega,
                           mesh=_spec(plan, epilogue=bad))
    if len(epi.row_ops):
        bad = mk.Epilogue(epi.axes, epi.count_ops,
                          [mk.EPI_PSUM] * len(epi.row_ops))
        with pytest.raises(mk.PlanVerifyError, match="all_gather"):
            mk.verify_plan(plan, n_shards, w_mega,
                           mesh=_spec(plan, epilogue=bad))


def test_mesh_rejects_lane_count_mismatch(live_plan):
    plan, n_shards, w_mega = live_plan
    epi = mk.mesh_epilogue(plan)
    bad = mk.Epilogue(epi.axes,
                      list(epi.count_ops) + [mk.EPI_PSUM], epi.row_ops)
    with pytest.raises(mk.PlanVerifyError, match="lanes"):
        mk.verify_plan(plan, n_shards, w_mega,
                       mesh=_spec(plan, epilogue=bad))


def test_plan_cost_mesh_attribution(live_plan):
    plan, n_shards, w_mega = live_plan
    base = mk.plan_cost(plan, n_shards, w_mega)
    spec = _spec(plan, n_devices=2)
    cost = mk.plan_cost(plan, n_shards, w_mega, mesh=spec)
    assert cost["meshDevices"] == 2
    # Per-device traffic: the same total HBM bytes split across chips
    # (ceil division — the roofline models the slowest device).
    assert cost["deviceBytes"] == -(-base["totalBytes"] // 2)
    nc = len(plan.lane_count_widths)
    nr = len(plan.lane_row_widths)
    assert cost["psumBytes"] == 2 * (2 - 1) * nc * 4
    assert cost["collectiveBytes"] == (cost["psumBytes"]
                                       + cost["allGatherBytes"])
    if nr:
        assert cost["allGatherBytes"] > 0
    # One device -> no wire traffic.
    assert mk.plan_cost(plan, n_shards, w_mega, mesh=_spec(
        plan, n_devices=1))["collectiveBytes"] == 0
