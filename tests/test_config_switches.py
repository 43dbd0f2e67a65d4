"""The inventory of `PILOSA_TPU_*` names the package reads: a literal
list that can only shrink, every name of it in docs/configuration.md,
and no environment variable that picks the TopN sweep's body."""

import os
import re

import pytest

from pilosa_tpu.executor import Executor

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

# A name that ends in `_` is a prefix: the bare one is utils/config's
# ENV_PREFIX (every config key is PILOSA_TPU_<KEY>), the others are the
# config tables' comments (`env uses PILOSA_TPU_WORKLOAD_*`).
SWITCHES = """
PILOSA_TPU_
PILOSA_TPU_ATTR_LOG_BYTES
PILOSA_TPU_ATTR_LOG_ENTRIES
PILOSA_TPU_BANK_BYTES
PILOSA_TPU_CACHE_RESULT_ENABLED
PILOSA_TPU_CLUSTER_
PILOSA_TPU_COALESCER_WINDOW_MS
PILOSA_TPU_FAILPOINTS
PILOSA_TPU_FAILPOINTS_HTTP
PILOSA_TPU_FUSION
PILOSA_TPU_GROUPBY_CHUNK_BYTES
PILOSA_TPU_HBM_BUDGET_BYTES
PILOSA_TPU_HOST_BLOCK_CACHE_BYTES
PILOSA_TPU_HYBRID_LAYOUT
PILOSA_TPU_JIT_CACHE_MAX
PILOSA_TPU_LAYOUT_
PILOSA_TPU_LAYOUT_EVICT
PILOSA_TPU_LOCK_CHECK
PILOSA_TPU_MEGAKERNEL
PILOSA_TPU_MEGA_BYTES
PILOSA_TPU_MESH
PILOSA_TPU_NATIVE_SAN
PILOSA_TPU_NO_NATIVE
PILOSA_TPU_OPTIMIZER_ENABLED
PILOSA_TPU_PBANK
PILOSA_TPU_PBANK_FIXED_SLOTS
PILOSA_TPU_PBANK_INFLIGHT
PILOSA_TPU_PBANK_MEMBERSHIP
PILOSA_TPU_PBANK_ROW_PAD
PILOSA_TPU_PBANK_SEGMENT
PILOSA_TPU_PBANK_SPARSE_BITS
PILOSA_TPU_PIPELINE
PILOSA_TPU_PLAN_OPT
PILOSA_TPU_PLAN_VERIFY
PILOSA_TPU_RANK_CACHE
PILOSA_TPU_RANK_PATCH_MAX
PILOSA_TPU_RESULT_CACHE
PILOSA_TPU_RESULT_CACHE_BYTES
PILOSA_TPU_SPARSE_UPLOAD
PILOSA_TPU_TELEMETRY_SAMPLE_EVERY_S
PILOSA_TPU_TIMELINE_
PILOSA_TPU_TOPN_BANK_BYTES
PILOSA_TPU_TOPN_CHUNK_ROWS
PILOSA_TPU_TOPN_SELFCHECK
PILOSA_TPU_WORKLOAD_
""".split()

NAME = re.compile(r"PILOSA_TPU_[A-Z_0-9]*")


def _names_in(path: str) -> set:
    with open(path, encoding="utf-8") as f:
        return set(NAME.findall(f.read()))


def _names_the_package_reads() -> set:
    found = set()
    for root, _, files in os.walk(os.path.join(REPO, "pilosa_tpu")):
        for name in files:
            if name.endswith(".py"):
                found |= _names_in(os.path.join(root, name))
    return found


def test_the_switch_list_is_pinned():
    """A new name fails here: add no switch where the code can observe
    what it needs (ROADMAP C3). A name that went is taken off the list."""
    assert sorted(_names_the_package_reads()) == SWITCHES
    assert len(SWITCHES) == 45


@pytest.mark.parametrize("name", SWITCHES)
def test_every_switch_is_documented(name):
    documented = _names_in(os.path.join(REPO, "docs", "configuration.md"))
    if name.endswith("_"):
        assert any(d.startswith(name) and d != name for d in documented)
    else:
        assert name in documented


class _EnvSpy:
    """os.environ, noting every key that is looked up."""

    def __init__(self, env):
        self.env, self.read = env, []

    def get(self, key, default=None):
        self.read.append(key)
        return self.env.get(key, default)

    def __getitem__(self, key):
        self.read.append(key)
        return self.env[key]

    def __contains__(self, key):
        self.read.append(key)
        return key in self.env

    def __getattr__(self, attr):
        return getattr(self.env, attr)


def test_no_environment_variable_picks_the_sweep_body(tmp_holder,
                                                      monkeypatch):
    """`_counts_fn` chooses its program from the call's arguments alone:
    building both reads no switch, and nothing but the arguments
    is in a jit key."""
    ex = Executor(tmp_holder)
    spy = _EnvSpy(os.environ)
    monkeypatch.setattr(os, "environ", spy)
    shape = (8, 2, 64)
    ex._counts_fn(True, shape)
    ex._counts_fn(False, shape)
    monkeypatch.undo()
    assert [k for k in spy.read if k.startswith("PILOSA_TPU_")] == []
    assert sorted(ex._jit_cache) == [
        f"topn:{f}:(8, 2, 64)" for f in (False, True)]
