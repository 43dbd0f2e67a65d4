"""The device-time measurement helpers (pilosa_tpu/utils/benchenv.py),
the HBM peak table they judge against (utils/roofline.resolve_roofline)
and bench.py's contract off the chip: one process, no CPU record."""

import os
import subprocess
import sys
from types import SimpleNamespace

import pytest

from pilosa_tpu.utils import benchenv
from pilosa_tpu.utils.roofline import UnknownDeviceKind, resolve_roofline

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _dev(kind):
    return SimpleNamespace(device_kind=kind, platform="tpu")


@pytest.mark.parametrize("kind", ["TPU v5 lite", "TPU v5e"])
def test_resolve_roofline_knows_the_v5e(kind):
    assert resolve_roofline(_dev(kind)) == (819.0, kind.lower())


@pytest.mark.parametrize("kind", ["cpu", "TPU v4", "TPU v6 lite", ""])
def test_resolve_roofline_unknown_kind_raises(kind):
    """A device that is not in the peak table is an error, never the
    v5e figure by default."""
    with pytest.raises(UnknownDeviceKind):
        resolve_roofline(_dev(kind))


def test_validated_chain_slope_refuses_an_unknown_device():
    with pytest.raises(UnknownDeviceKind):
        benchenv.validated_chain_slope(lambda k: 1.0 + k, 1e9,
                                       _dev("cpu"))


def test_chain_slope_recovers_the_per_iteration_time():
    """timed(k) = fixed overhead + k * sweep: the Theil-Sen slope is
    the sweep, whatever the overhead."""
    r = benchenv.chain_slope_gbps(lambda k: 0.070 + k * 0.002, 1e9,
                                  reps=1)
    assert r["per_iter_s"] == pytest.approx(0.002)
    assert r["gbps_median"] == pytest.approx(500.0)
    assert r["slope_pairs"] == 6 and r["slope_pairs_nonpositive"] == 0


def test_chain_slope_refuses_a_flat_chain():
    """No dependence on k means the sweeps were elided or the clock is
    noise: that is no measurement."""
    with pytest.raises(RuntimeError, match="non-positive"):
        benchenv.chain_slope_gbps(lambda k: 0.070, 1e9, reps=1)


def test_validated_chain_slope_marks_above_roofline_invalid():
    """1 GB per 0.5 ms = 2000 GB/s on an 819 GB/s part: recorded as
    invalid, not as a number."""
    r = benchenv.validated_chain_slope(lambda k: k * 0.0005, 1e9,
                                       _dev("TPU v5 lite"), reps=1)
    assert r["invalid"] is True and r["roofline_gbps_assumed"] == 819.0
    ok = benchenv.validated_chain_slope(lambda k: k * 0.002, 1e9,
                                        _dev("TPU v5 lite"), reps=1)
    assert "invalid" not in ok
    assert ok["roofline_frac"] == pytest.approx(500.0 / 819.0)


def test_bench_py_off_chip_exits_nonzero_with_no_record():
    """No TPU: bench.py fails and prints nothing on stdout — no
    cpu-fallback record, no CPU figure under the device metric."""
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    r = subprocess.run([sys.executable, os.path.join(REPO, "bench.py")],
                       env=env, capture_output=True, text=True,
                       timeout=120)
    assert r.returncode != 0
    assert r.stdout.strip() == ""
    assert "not a tpu" in r.stderr


def test_bench_py_is_one_process():
    """A parent that has touched jax holds the chip: bench.py starts
    no child, and has no child mode to be started in."""
    with open(os.path.join(REPO, "bench.py")) as f:
        src = f.read()
    assert "subprocess" not in src and "--tpu-child" not in src
    assert "cpu-fallback" not in src
